"""Multi-device brute-force kNN: shard the database, search locally, merge.

Ref pattern: the reference ships the comms layer + ``knn_merge_parts``
(neighbors/brute_force.cuh:80) and downstream MNMG kNN shards database rows
across ranks, searches each shard, and merges the per-rank top-k
(docs/source/using_comms.rst:1-40; SURVEY.md §2.12 item 4).

TPU-native: one ``shard_map`` over the mesh's data axis — each device scans
its shard with the fused tiled kernel, then the per-shard top-k merges with
the shared merge collective (comms/topk_merge.py): the pairwise k-selection
runs *inside* the collective's ppermute steps, so communication is O(q·k)
per step instead of an O(q·k·n_dev) allgather plus a replicated re-sort
(``merge_engine`` selects allgather | ring | ring_bf16 | auto).

Degraded-mode serving (docs/fault_tolerance.md): ``live_mask`` (typically
``ShardHealth.live_mask``) neutralizes dead shards' candidates to the
merge-padding sentinels (+inf distances / -1 ids — exactly what
``topk_merge`` ranks last) so a lost host yields the exact top-k over the
SURVIVING shards plus a per-query ``coverage`` fraction, never an
exception.

Online serving (docs/serving.md): the serve runtime calls this entry
point per micro-batch; :func:`shard_database` pre-places the database
once so the hot path never re-transfers it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_tpu.comms.topk_merge import (
    merge_dispatch_stats,
    pipeline_chunk_bounds,
    resolve_merge_engine,
    resolve_pipeline_chunks,
)
from raft_tpu.core.error import expects
from raft_tpu.neighbors.brute_force import _TILE_DB, _tiled_knn_l2
from raft_tpu.parallel.degraded import (
    check_live_mask,
    live_args,
    live_specs,
    local_alive,
    neutralize_dead,  # noqa: F401  (re-exported via raft_tpu.parallel)
    replicated,
    scan_merge_dispatch,
)
from raft_tpu.util.pow2 import ceildiv


def shard_database(mesh: Mesh, db, axis: str = "data") -> jax.Array:
    """Pre-place database rows sharded over ``mesh[axis]`` (the layout
    :func:`sharded_knn` consumes).

    One-time placement for serving hot paths: the serve runtime
    (``raft_tpu.serve``) calls :func:`sharded_knn` once per micro-batch,
    and a host→device transfer of the database per request would dwarf
    the search itself. Row count must divide the axis size (pad
    upstream; same contract as :func:`sharded_knn`)."""
    db = jnp.asarray(db)
    expects(db.ndim == 2, "db must be (n, d), got %s", db.shape)
    expects(db.shape[0] % mesh.shape[axis] == 0,
            "db rows must divide the mesh axis (pad first)")
    return jax.device_put(db, NamedSharding(mesh, P(axis, None)))


def sharded_knn(
    mesh: Mesh,
    db,
    queries,
    k: int,
    axis: str = "data",
    sqrt: bool = False,
    tile_db: int = _TILE_DB,
    merge_engine: str = "auto",
    live_mask=None,
    pipeline_chunks: int = 0,
):
    """Exact L2 kNN with the database row-sharded over ``mesh[axis]``.

    ``db`` rows must be divisible by the axis size (pad upstream if not;
    static shapes). Returns replicated ``(distances (q,k), indices (q,k))``
    with global row ids. ``merge_engine`` picks the top-k merge collective
    (see comms/topk_merge.py): "allgather", "ring", "ring_bf16",
    "pipelined", "pipelined_bf16" or "auto". The pipelined engines chunk
    each shard's row scan into ``pipeline_chunks`` runs of whole
    ``tile_db`` scan tiles (0 = the resolve_pipeline_chunks default; a
    shard of one tile runs unchunked) and overlap each finished chunk's
    ring exchange with the next chunk's scan — bit-identical results
    (docs/sharded_search.md §pipeline); "auto" here never picks them
    (the brute-force scan has no probe structure to key the heuristic
    on — opt in explicitly).

    ``live_mask`` (bool (n_dev,), e.g. ``ShardHealth.live_mask``) enables
    degraded serving: dead shards contribute nothing, the result is the
    exact top-k over the surviving shards' rows (tail slots pad with
    +inf/-1 when k exceeds surviving capacity), and a third output
    ``coverage`` (float32 (q,)) reports the fraction of database rows
    searched per query. With every shard live the (distances, indices)
    are bit-identical to the ``live_mask=None`` path.
    """
    db = jnp.asarray(db)
    if getattr(db, "sharding", None) != NamedSharding(mesh, P(axis, None)):
        db = shard_database(mesh, db, axis)   # declared placement, not an
    queries = replicated(mesh, queries)       # implicit dispatch transfer
    n_dev = mesh.shape[axis]
    n, d = db.shape
    expects(n % n_dev == 0, "db rows must divide the mesh axis (pad first)")
    shard = n // n_dev
    kk = min(k, shard)
    tile = min(tile_db, shard)
    engine = resolve_merge_engine(merge_engine, queries.shape[0], k, n_dev)
    # Chunks are runs of whole scan tiles, so every chunk's distance
    # matmuls have the unchunked scan's shapes: a matmul's summation order
    # follows its shape, and a chunk of another width would differ from
    # the allgather result in the last bit.
    n_tiles = ceildiv(shard, tile)
    chunks = tuple(
        (lo * tile, min(hi * tile, shard))
        for lo, hi in pipeline_chunk_bounds(
            n_tiles, resolve_pipeline_chunks(engine, n_tiles, n_dev,
                                             requested=pipeline_chunks)))
    # Host-side dispatch accounting for the metrics scrape (engine +
    # estimated exchange bytes; obs.registry.MergeDispatchCollector).
    # A chunked dispatch records ONE logical merge whose estimate sums
    # the per-chunk exchanges (comms/topk_merge.py).
    merge_dispatch_stats.record(
        engine, queries.shape[0], k, kk, n_dev,
        chunk_kks=([min(k, hi - lo) for lo, hi in chunks]
                   if len(chunks) > 1 else None))
    live = (None if live_mask is None
            else check_live_mask(live_mask, n_dev, mesh))
    return _sharded_knn_jit(db, queries, live, mesh=mesh, axis=axis, k=k,
                            kk=kk, sqrt=sqrt, tile=tile, shard=shard,
                            engine=engine, chunks=chunks)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "k", "kk", "sqrt", "tile", "shard",
                     "engine", "chunks"))
def _sharded_knn_jit(db, queries, live, *, mesh, axis, k, kk, sqrt, tile,
                     shard, engine, chunks=((0, 0),)):
    # jit around shard_map is load-bearing: an un-jitted shard_map runs in
    # the eager SPMD interpreter (~10x slower, measured on the CPU mesh).
    # ``live=None`` traces the exact pre-fault-tolerance program (two
    # outputs, no liveness operand) — the all-live fast path stays
    # bit-identical and pays nothing.
    has_live = live is not None

    def local_search(db_local, q, *rest):
        # db_local: (shard, d) — this device's rows; q replicated.
        alive = local_alive(rest[0], axis) if has_live else None

        def scan_range(lo, hi, kk_c):
            # One run of row tiles (a ragged last tile pads to the full
            # tile, as in the unchunked scan); with the pipelined engines
            # each chunk's ring exchange overlaps the next chunk's scan
            # (scan_merge_dispatch).
            d_c, i_c = _tiled_knn_l2(q, db_local[lo:hi], kk_c, sqrt, tile,
                                     True)
            return d_c, i_c + (lax.axis_index(axis) * shard + lo)

        out_d, out_i = scan_merge_dispatch(
            scan_range, chunks,
            chunk_width=lambda lo, hi: min(kk, hi - lo),
            full_kk=kk, engine=engine, k=k, axis=axis, select_min=True,
            alive=alive)
        if not has_live:
            return out_d, out_i
        # Equal rows per shard → covered fraction is the live-shard
        # fraction, reported per query (the IVF paths refine this by
        # actually-probed rows).
        cov = jnp.mean(rest[0].astype(jnp.float32))
        return out_d, out_i, jnp.full((q.shape[0],), cov, jnp.float32)

    extra_in, extra_out = live_specs(has_live)
    fn = jax.shard_map(
        local_search, mesh=mesh,
        in_specs=(P(axis, None), P(None, None)) + extra_in,
        out_specs=(P(None, None), P(None, None)) + extra_out,
        check_vma=False)
    return fn(db, queries, *live_args(live))
