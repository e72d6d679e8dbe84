"""Multi-device IVF-Flat / IVF-PQ: shard the lists, search locally, merge.

Ref pattern: the reference ships the comms layer + ``knn_merge_parts``
(neighbors/brute_force.cuh:80) and downstream MNMG ANN shards database rows
across ranks against a *shared* cluster model, searches each rank's shard,
and merges the per-rank top-k (docs/source/using_comms.rst:1-40; SURVEY.md
§2.12 item 4).

TPU-native: one coarse model (balanced-kmeans centers, and for PQ the
rotation + codebooks) is trained once and replicated; every device holds
the capacity-padded list tensors of *its row shard only* (lists are
per-shard slices of the same global clusters, so the union of all shards'
list l is exactly the single-device list l). Search runs as a jitted
``shard_map``: each device probes the shared centers, scans its local
lists, and the shared merge collective (comms/topk_merge.py) combines the
per-device top-k inside its ppermute steps — O(n_queries·k) per step
(``merge_engine``: allgather | ring | ring_bf16 | auto), never the lists
themselves.
Search results are identical to the single-device index built from the
same model, because the probed candidate set is the same by construction.

Both search entry points accept a ``live_mask`` for degraded-mode serving
(docs/fault_tolerance.md): dead shards' candidates neutralize to the merge
padding sentinels and a per-query ``coverage`` fraction (live probed rows /
total probed rows) is returned alongside the results.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_tpu.cluster import kmeans_balanced
from raft_tpu.cluster.kmeans_types import KMeansBalancedParams
from raft_tpu.comms.topk_merge import (
    PIPELINED_ENGINES,
    merge_dispatch_stats,
    pipeline_chunk_bounds,
    resolve_merge_engine,
    resolve_pipeline_chunks,
)
from raft_tpu.core.error import expects
from raft_tpu.core.mdarray import validate_idx_dtype
from raft_tpu.core.sentinels import PAD_ID, worst_value
from raft_tpu.distance.distance_types import DistanceType
from raft_tpu.neighbors import ivf_flat as _flat
from raft_tpu.neighbors import ivf_pq as _pq
from raft_tpu.ops import pallas_interpret
from raft_tpu.parallel.degraded import (
    check_live_mask,
    live_args,
    live_specs,
    local_alive,
    probed_coverage,
    replicated,
    scan_merge_dispatch,
)
from raft_tpu.parallel.routing import (
    ListPlacement,
    RoutePlan,
    assign_lists,
    build_placement,
    empty_plan,
    plan_route,
    route_shapes,
    routing_stats,
)
from raft_tpu.util.atomic_io import DEFAULT_IO, FileIO, atomic_savez
from raft_tpu.util.pow2 import ceildiv, next_pow2


@dataclass
class ShardedIvfFlat:
    """IVF-Flat with list tensors row-sharded over a mesh axis; the coarse
    centers are replicated (the shared cluster model of the MNMG pattern)."""

    metric: DistanceType
    centers: jax.Array      # (n_lists, dim) replicated
    data: jax.Array         # (n_dev, n_lists, cap, dim) sharded on axis 0
    indices: jax.Array      # (n_dev, n_lists, cap) global ids
    list_sizes: jax.Array   # (n_dev, n_lists) int32
    axis: str = "data"
    # Monotonic content version, bumped by every mutation (extend /
    # delete / upsert; compaction publishes a successor at epoch + 1) —
    # the serving layer's cache-invalidation key (serve/cache.py).
    # Process-local: not serialized (a reload re-validates caches by
    # construction).
    epoch: int = 0
    # Tombstone mask sharded like the list tensors (raft_tpu/lifecycle);
    # None traces the mask-free program, set masks are traced operands
    # (deleting more rows never retraces). See ivf_flat.Index.deleted.
    deleted: Optional[jax.Array] = None   # (n_dev, n_lists, cap) bool
    n_deleted: int = 0
    # Next auto-assigned id — see ivf_flat.Index._next_id.
    _next_id: Optional[int] = None
    # placement="list" (ISSUE 15): host-side map of which shard owns
    # (and optionally replicates) each whole IVF list; None = the
    # historical row-sharded placement. See parallel/routing.py.
    placement_map: Optional[ListPlacement] = None
    # Host mirror of the per-list row counts ((epoch, np (n_lists,)))
    # the router prices coverage with; refreshed per epoch via an
    # explicit device_get. Not serialized.
    _route_sizes: Optional[tuple] = None

    @property
    def placement(self) -> str:
        return "list" if self.placement_map is not None else "row"

    @property
    def size(self) -> int:
        # placement="list": count each list's PRIMARY copy only —
        # replica slots hold the same rows again and would double-count
        # (n_deleted follows the same primary-only convention).
        if self.placement_map is not None:
            return int(_routed_sizes_h(self).sum())
        return int(jnp.sum(self.list_sizes))

    @property
    def live_size(self) -> int:
        """Rows that answer queries: ``size`` minus tombstoned slots."""
        return self.size - self.n_deleted


@dataclass
class ShardedIvfPq:
    """IVF-PQ with packed code tensors row-sharded over a mesh axis; the
    coarse centers, rotation and codebooks are replicated."""

    metric: DistanceType
    codebook_kind: "_pq.CodebookGen"
    centers: jax.Array
    rotation_matrix: jax.Array
    pq_centers: jax.Array
    pq_codes: jax.Array     # (n_dev, n_lists, cap, nbytes) sharded on axis 0
    indices: jax.Array      # (n_dev, n_lists, cap)
    list_sizes: jax.Array   # (n_dev, n_lists)
    pq_bits: int = 8
    pq_dim: int = 0
    axis: str = "data"
    # Monotonic content version, bumped by every mutation (extend /
    # delete / upsert; compaction publishes a successor at epoch + 1) —
    # the serving layer's cache-invalidation key (serve/cache.py).
    # Process-local: not serialized (a reload re-validates caches by
    # construction).
    epoch: int = 0
    # Lazy per-shard compressed-scan operands (transposed codes sharded
    # over the mesh axis + replicated absolute tables); rebuilt after
    # extend/delete/load. Not serialized. See _sharded_scan_operands.
    _scan_cache: Optional[tuple] = None
    # Tombstone mask sharded like the code tensors (raft_tpu/lifecycle);
    # the compressed tier folds it into the cached invalid operand.
    deleted: Optional[jax.Array] = None   # (n_dev, n_lists, cap) bool
    n_deleted: int = 0
    # Next auto-assigned id — see ivf_flat.Index._next_id.
    _next_id: Optional[int] = None
    # placement="list" (ISSUE 15) — see ShardedIvfFlat.placement_map.
    placement_map: Optional[ListPlacement] = None
    _route_sizes: Optional[tuple] = None
    # Lazy slot-gathered center tables of the routed PQ bodies
    # ((crot_slot, crot_p_slot, books_slot)); rebuilt after migration /
    # replication / load. Not serialized. See _routed_pq_operands.
    _route_ops: Optional[tuple] = None

    @property
    def placement(self) -> str:
        return "list" if self.placement_map is not None else "row"

    @property
    def rot_dim(self) -> int:
        return self.rotation_matrix.shape[0]

    @property
    def size(self) -> int:
        # Primary copies only under placement="list" — see
        # ShardedIvfFlat.size.
        if self.placement_map is not None:
            return int(_routed_sizes_h(self).sum())
        return int(jnp.sum(self.list_sizes))

    @property
    def live_size(self) -> int:
        """Rows that answer queries: ``size`` minus tombstoned slots."""
        return self.size - self.n_deleted


def _shard_pack(mesh: Mesh, axis: str, rows, labels_h, ids, n_lists: int):
    """Pack each row shard's lists at one common capacity and place the
    stacked tensors sharded over ``mesh[axis]``."""
    n_dev = mesh.shape[axis]
    n = rows.shape[0]
    shard = n // n_dev
    counts = np.zeros((n_dev, n_lists), np.int64)
    for s in range(n_dev):
        counts[s] = np.bincount(labels_h[s * shard:(s + 1) * shard],
                                minlength=n_lists)
    cap = next_pow2(int(counts.max()))

    def inputs(s):
        rs = slice(s * shard, (s + 1) * shard)
        return rows[rs], labels_h[rs], ids[rs]

    return _place_packs(
        mesh, axis, inputs,
        lambda x, lab, i: _flat._pack_lists(x, lab, i, n_lists,
                                            min_cap=cap))


def _place_packs(mesh: Mesh, axis: str, inputs, pack):
    """Pack every shard on a device that holds it and assemble the
    per-shard tuples into arrays sharded over ``mesh[axis]``.

    ``inputs(s)`` selects shard ``s``'s rows; they move to the shard's
    first device and ``pack(*inputs)`` runs there, and the other devices
    holding shard ``s`` get copies. The source device thus holds only
    its own shard's padded lists: a 4M-row corpus's packs, built or
    stacked on one device, do not fit one v5e's HBM."""
    n_dev = mesh.shape[axis]
    sharding = NamedSharding(mesh, P(axis))
    where = sharding.addressable_devices_indices_map((n_dev,))
    parts = {}
    for s in range(n_dev):
        devs = [d for d, index in where.items()
                if (index[0].start or 0) == s]
        if not devs:
            continue
        packed = pack(*jax.device_put(inputs(s), devs[0]))
        for dev in devs:
            parts[dev] = [jax.device_put(a, dev)[None] for a in packed]
        del packed
    devs = list(where)
    return tuple(
        jax.make_array_from_single_device_arrays(
            (n_dev,) + parts[devs[0]][i].shape[1:], sharding,
            [parts[d][i] for d in devs])
        for i in range(len(parts[devs[0]])))


def _list_pack(mesh: Mesh, axis: str, rows, labels_h, ids, n_lists: int,
               centers=None) -> tuple:
    """placement="list" packer: affinity-aware size-balanced bin
    packing assigns WHOLE lists to shards
    (parallel/routing.assign_lists over the post-build list sizes, with
    the coarse centroids as the affinity signal so centroid-neighbor
    lists — the ones a query co-probes — co-locate), then each shard
    packs its owned lists into local slots at one common capacity.
    Returns ``(data, idx, sizes, placement)`` with the tensors stacked
    (n_dev, n_slots, cap[, dim]) over ``mesh[axis]`` — slot
    ``n_slots − 1`` is empty on every shard (the router's padding
    target)."""
    n_dev = mesh.shape[axis]
    counts = np.bincount(labels_h, minlength=n_lists)
    centers_h = (None if centers is None
                 else np.asarray(jax.device_get(centers)))
    pm = build_placement(assign_lists(counts, n_dev, centers=centers_h),
                        n_dev)
    cap = next_pow2(max(int(counts.max()), 1))
    # Remap global list labels to (owner, local slot); pack per shard.
    owner_r = pm.owner[labels_h]
    slot_r = pm.slot[labels_h]

    def inputs(s):
        sel = np.flatnonzero(owner_r == s)
        return rows[sel], slot_r[sel], ids[sel]

    return _place_packs(
        mesh, axis, inputs,
        lambda x, slot, i: _flat._pack_lists(x, slot, i, pm.n_slots,
                                             min_cap=cap)) + (pm,)


def sharded_ivf_flat_build(
    mesh: Mesh, params: "_flat.IndexParams", dataset, axis: str = "data",
    centers: Optional[jax.Array] = None, train_distributed: bool = False,
    placement: str = "row",
) -> ShardedIvfFlat:
    """Build with rows sharded over ``mesh[axis]`` (ref: the MNMG
    shard-then-merge recipe, using_comms.rst). ``centers`` injects a
    pre-trained coarse model (otherwise trained like ivf_flat.build);
    ``train_distributed`` trains them with the sharded balancing EM
    instead (for datasets beyond one device's HBM — quality of the flat
    distributed EM trails the hierarchical single-device trainer
    slightly). Row count must divide the axis size (pad upstream).

    ``placement`` selects the shard layout (docs/sharded_search.md):
    "row" (default) slices every list across every shard — the MNMG
    recipe; "list" assigns WHOLE lists to shards (size-balanced bin
    packing, coarse quantizer replicated) and search routes each query
    only to the shards owning its probed lists (ISSUE 15) — results are
    bit-identical between the two placements."""
    expects(placement in ("row", "list"),
            "placement must be 'row' or 'list', got %r", placement)
    X = _flat._as_float(_flat.as_array(dataset))
    n, dim = X.shape
    n_dev = mesh.shape[axis]
    expects(placement == "list" or n % n_dev == 0,
            "rows must divide the mesh axis (pad first)")

    if centers is None:
        if train_distributed:
            from raft_tpu.parallel.kmeans import sharded_kmeans_balanced_fit

            centers = sharded_kmeans_balanced_fit(
                mesh, X, params.n_lists, n_iters=params.kmeans_n_iters,
                axis=axis)
        else:
            centers = _flat._train_centers(params, X)

    labels = kmeans_balanced.predict(
        KMeansBalancedParams(metric=params.metric), centers, X)
    labels_h = np.asarray(labels)
    ids = jnp.arange(n, dtype=validate_idx_dtype(params.idx_dtype))
    if placement == "list":
        data, idx, sizes, pm = _list_pack(mesh, axis, X, labels_h, ids,
                                          params.n_lists, centers=centers)
        return ShardedIvfFlat(metric=params.metric, centers=centers,
                              data=data, indices=idx, list_sizes=sizes,
                              axis=axis, placement_map=pm)
    data, idx, sizes = _shard_pack(mesh, axis, X, labels_h, ids,
                                   params.n_lists)
    return ShardedIvfFlat(metric=params.metric, centers=centers, data=data,
                          indices=idx, list_sizes=sizes, axis=axis)


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "k", "n_probes",
                              "inner_is_l2", "sqrt", "use_cells", "qrows",
                              "interpret", "engine", "chunks"))
def _sharded_flat_search_jit(data, indices, sizes, centers, Q, live=None,
                             tomb=None, *,
                             mesh, axis, k, n_probes, inner_is_l2, sqrt,
                             use_cells, qrows, interpret, engine,
                             chunks=((0, 0),)):
    # jit around shard_map is load-bearing: un-jitted shard_map runs in the
    # eager SPMD interpreter (~10x slower, measured on the CPU mesh).
    # ``live=None`` traces the pre-fault-tolerance two-output program —
    # the all-live path stays bit-identical and pays nothing.  ``tomb``
    # (the sharded tombstone mask, raft_tpu/lifecycle) follows the same
    # contract: None keeps the mask-free trace; a set mask is a traced
    # per-shard operand, so further deletes never retrace.
    has_live = live is not None
    has_tomb = tomb is not None

    def body(data_l, idx_l, sz_l, centers_r, q, *rest):
        data_l, idx_l, sz_l = data_l[0], idx_l[0], sz_l[0]
        rest = list(rest)
        alive_mask = rest.pop(0) if has_live else None
        tomb_l = rest.pop(0)[0] if has_tomb else None
        alive = local_alive(alive_mask, axis) if has_live else None
        cap = data_l.shape[1]
        # Per-device top-k is bounded by this shard's slot capacity.
        kk = min(k, data_l.shape[0] * cap)
        norms = (None if use_cells else
                 (jnp.sum(data_l * data_l, axis=2)
                  if inner_is_l2 else None))
        probe_ids = _flat._coarse_probe(q, centers_r, n_probes,
                                        inner_is_l2)

        def scan_range(lo, hi, kk_c):
            # One probe-column scan at candidate width kk_c — the shared
            # producer of the eager chain (all probes at once) and the
            # pipelined chunks (a column slice per chunk;
            # scan_merge_dispatch overlaps each chunk's exchange with
            # the next chunk's scan, bit-identical).
            pids = probe_ids[:, lo:hi]
            if use_cells:
                # The PRODUCTION single-chip engine runs per shard (the
                # reference's MNMG decomposition shards the production
                # kernel and merges, brute_force.cuh:80 knn_merge_parts)
                # — packed-cells Pallas scan, no probe drops, fully
                # traced. sqrt is deferred to after the collective merge.
                return _flat._cells_scan_probes(
                    q, pids, data_l, idx_l, sz_l, kk_c, inner_is_l2,
                    qrows, False, interpret, deleted=tomb_l)
            return _flat._probe_scan(q, data_l, norms, idx_l, sz_l, kk_c,
                                     inner_is_l2, False, probe_ids=pids,
                                     deleted=tomb_l)

        out_d, out_i = scan_merge_dispatch(
            scan_range, chunks,
            chunk_width=lambda lo, hi: min(k, (hi - lo) * cap),
            full_kk=kk, engine=engine, k=k, axis=axis,
            select_min=inner_is_l2, alive=alive)
        if inner_is_l2 and sqrt:
            out_d = jnp.sqrt(out_d)
        if not has_live:
            return out_d, out_i
        # Coverage over the probed lists (every engine probes the same
        # coarse top-n_probes — the model is replicated).
        cov = probed_coverage(probe_ids, sz_l, alive, axis)
        return out_d, out_i, cov

    extra_in, extra_out = live_specs(has_live)
    if has_tomb:
        extra_in = extra_in + (P(axis),)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P()) + extra_in,
        out_specs=(P(), P()) + extra_out, check_vma=False)
    args = live_args(live) + ((tomb,) if has_tomb else ())
    return fn(data, indices, sizes, centers, Q, *args)


def sharded_ivf_flat_search(
    mesh: Mesh, params: "_flat.SearchParams", index: ShardedIvfFlat,
    queries, k: int, merge_engine: str = "auto", live_mask=None,
    pipeline_chunks: int = 0, _plan=None, valid_rows=None,
    suspect_mask=None, plan_cb=None,
):
    """Search the sharded index; returns replicated global-id results,
    identical to the single-device index built from the same centers.

    Engine dispatch mirrors the single-chip :func:`ivf_flat.search`: the
    packed-cells Pallas engine runs per shard whenever it is eligible
    there (k ≤ cells cap, per-list block within VMEM, TPU backend with
    enough probe load — or an explicit engine="bucketed"), so multi-chip
    search QPS tracks the single-chip production engine instead of the
    per-query scan tier (VERDICT r4 Missing #1). ``merge_engine``
    selects the top-k merge collective (comms/topk_merge.py):
    "allgather" | "ring" | "ring_bf16" | "pipelined" | "pipelined_bf16"
    | "auto". The pipelined engines chunk the per-shard scan over probe
    lists ("auto" picks them at n_probes >= 16 on 4+ shards) and
    overlap each chunk's exchange with the next chunk's scan —
    bit-identical results; ``pipeline_chunks`` overrides the chunk
    count (0 = auto; docs/sharded_search.md §pipeline).

    ``live_mask`` (bool (n_dev,), e.g. ``ShardHealth.live_mask``)
    enables degraded serving (docs/fault_tolerance.md): dead shards'
    candidates are neutralized before the merge, the result is exact
    over the surviving shards' probed lists, and a third output
    ``coverage`` (float32 (q,)) reports the per-query fraction of
    probed candidate rows searched. All-live results are bit-identical
    to the ``live_mask=None`` path.

    ``placement="list"`` indexes serve the ROUTED path instead
    (docs/sharded_search.md §placement): a host-side router maps each
    query's probed lists to the owning shards, each shard scans only
    its locally-probed lists for its routed queries, and the merge's
    exchange accounting covers the participating shards only — results
    stay bit-identical to this row-sharded path.  Under a ``live_mask``
    liveness becomes a routing input: dead shards receive no queries,
    live replicas keep hot lists served, and ``coverage`` prices the
    lists with no live owner.  ``suspect_mask`` makes latency one too
    (routed only): a suspect primary with a healthy replica serves
    through the replica (parallel/routing.plan_route).  ``plan_cb`` is
    called with each router-built RoutePlan — how the Searcher learns
    the dispatch's participants for latency attribution and hedging.
    ``_plan`` injects a pre-built RoutePlan (the
    :func:`sharded_routed_warmup` vehicle)."""
    Q = replicated(mesh, _flat._as_float(_flat.as_array(queries)))
    # Model tensors place replicated ONCE (write-back): the un-placed
    # single-device centers would otherwise re-transfer at every jit
    # dispatch, implicitly.
    index.centers = replicated(mesh, index.centers)
    expects(Q.shape[1] == index.centers.shape[1], "query dim mismatch")
    if index.placement == "list":
        return _routed_flat_search(mesh, params, index, Q, k,
                                   merge_engine, live_mask,
                                   pipeline_chunks, plan=_plan,
                                   valid_rows=valid_rows,
                                   suspect_mask=suspect_mask,
                                   plan_cb=plan_cb)
    n_probes = min(params.n_probes, index.centers.shape[0])
    # Clamp by the GLOBAL capacity (n_dev shards merge their top-k), the
    # same contract as the single-device search's capacity clamp.
    k = min(k, index.indices.shape[0] * index.indices.shape[1]
            * index.indices.shape[2])
    inner_is_l2 = index.metric != DistanceType.InnerProduct
    sqrt = index.metric in (DistanceType.L2SqrtExpanded,
                            DistanceType.L2SqrtUnexpanded)
    # Same gate as the single-chip dispatch (shared helper — a re-spelled
    # copy would drift), with the per-SHARD list capacity.
    use_cells = _flat._cells_eligible(
        params.engine, k, params.bucket_cap, index.indices.shape[2],
        index.centers.shape[1], Q.shape[0], n_probes,
        index.indices.shape[1])
    live = (None if live_mask is None
            else check_live_mask(live_mask, mesh.shape[index.axis], mesh))
    n_dev = mesh.shape[index.axis]
    engine = resolve_merge_engine(merge_engine, Q.shape[0], k, n_dev,
                                  n_probes=n_probes)
    cap = index.indices.shape[2]
    chunks = tuple(pipeline_chunk_bounds(
        n_probes, resolve_pipeline_chunks(engine, n_probes, n_dev,
                                          requested=pipeline_chunks)))
    # Host-side dispatch accounting for the metrics scrape (engine +
    # estimated exchange bytes; obs.registry.MergeDispatchCollector).
    # A chunked dispatch records ONE logical merge whose estimate sums
    # the per-chunk exchanges (comms/topk_merge.py).
    merge_dispatch_stats.record(
        engine, Q.shape[0], k,
        min(k, index.indices.shape[1] * cap), n_dev,
        idx_bytes=index.indices.dtype.itemsize,
        chunk_kks=([min(k, (hi - lo) * cap) for lo, hi in chunks]
                   if len(chunks) > 1 else None))
    return _sharded_flat_search_jit(
        index.data, index.indices, index.list_sizes, index.centers, Q,
        live, index.deleted, mesh=mesh, axis=index.axis, k=k, n_probes=n_probes,
        inner_is_l2=inner_is_l2, sqrt=sqrt, use_cells=use_cells,
        qrows=min(_flat._CELL_QROWS, max(8, Q.shape[0])),
        interpret=pallas_interpret(),
        engine=engine, chunks=chunks)


# ---------------------------------------------------------------------------
# Routed search over the list-owned placement (ISSUE 15): a host-side
# router (parallel/routing.py) maps each query's probed lists to the
# owning shards; each shard scans ONLY its locally-probed lists for its
# routed queries, scatters the group's candidates back to the global
# query rows (non-routed queries contribute merge-padding sentinels —
# the sparse-participant merge), and the existing merge collectives
# (incl. the pipelined scan→merge overlap, chunked over the LOCAL probe
# axis) combine the shards.  Results are bit-identical to the
# row-sharded placement and to single-host search over the same build.


@functools.partial(jax.jit, static_argnames=("n_probes", "inner_is_l2"))
def _routed_probe_flat(Q, centers, *, n_probes, inner_is_l2):
    """The routed flat path's coarse probe — the IDENTICAL computation
    the in-shard-map row bodies run (shared helper), jitted standalone
    so the router can read the assignments back."""
    return _flat._coarse_probe(Q, centers, n_probes, inner_is_l2)


@functools.partial(jax.jit, static_argnames=("n_probes", "is_ip"))
def _routed_probe_pq(Q, centers, *, n_probes, is_ip):
    return _pq._select_clusters((Q, centers), n_probes, is_ip)


def _routed_sizes_h(index) -> np.ndarray:
    """Host mirror of the per-list row counts (primary copies), cached
    per epoch — what the router prices coverage with.  One EXPLICIT
    ``jax.device_get`` per mutation epoch, not per dispatch."""
    pm = index.placement_map
    if index._route_sizes is None or index._route_sizes[0] != index.epoch:
        sizes = np.asarray(jax.device_get(index.list_sizes))
        index._route_sizes = (index.epoch,
                              sizes[pm.owner, pm.slot].astype(np.int64))
    return index._route_sizes[1]


def _routed_plan(mesh, index, Q, probe_fn, live_mask,
                 valid_rows=None, suspect_mask=None) -> RoutePlan:
    """Route one batch: probe on device, read the assignments back (the
    routed path's one declared device→host boundary — the router is
    host-side by design), plan in numpy, record the routing telemetry.
    ``valid_rows`` marks the real rows of a shape-bucketed batch (the
    scheduler's zero padding routes nowhere and stays out of the
    telemetry); ``suspect_mask`` steers hot lists off slow-but-live
    shards (plan_route)."""
    n_dev = mesh.shape[index.axis]
    live = None
    if live_mask is not None:
        # Host-side validation only — liveness is a ROUTING input here,
        # never a collective operand (dead shards receive no queries).
        check_live_mask(live_mask, n_dev)
        live = np.asarray(live_mask).astype(bool)
    suspect = (None if suspect_mask is None
               else np.asarray(suspect_mask).astype(bool))
    # analyze: host-sync-ok (routed dispatch: the router reads the probe
    # assignments back by design; one declared device_get per batch)
    probe_h = np.asarray(jax.device_get(probe_fn(Q, index.centers)))
    plan = plan_route(
        probe_h, index.placement_map, live_mask=live,
        list_sizes=_routed_sizes_h(index) if live is not None else None,
        n_valid=valid_rows, suspect_mask=suspect)
    routing_stats.record(
        plan, index.placement_map,
        probe_ids=probe_h if valid_rows is None else probe_h[:valid_rows])
    return plan


def routed_primary_mask(mesh: Mesh, index) -> Optional[jax.Array]:
    """Per-slot "is a primary copy" mask ((n_dev, n_slots) bool,
    sharded like the list tensors), or None for row placement / an
    unreplicated placement: lifecycle delete counts newly-tombstoned
    slots against it so a row deleted from a replicated list counts
    ONCE (both copies still get masked — they must stay
    bit-identical).  Cached on the index (the mask only changes with
    the placement, which always publishes a new index)."""
    pm = index.placement_map
    if pm is None or not (pm.replica_owner >= 0).any():
        return None
    cached = index.__dict__.get("_route_primary")
    if cached is None:
        s2l = np.maximum(  # analyze: host-sync-ok (host routing table, built once per placement)
            pm.slot_to_list, 0)
        shard_col = np.arange(  # analyze: host-sync-ok (host routing table)
            pm.n_dev, dtype=np.int32)[:, None]
        primary = ((pm.slot_to_list >= 0)  # analyze: host-sync-ok (host routing table)
                   & (pm.owner[s2l] == shard_col))  # analyze: host-sync-ok (host routing table)
        cached = jax.device_put(jnp.asarray(primary),
                                NamedSharding(mesh, P(index.axis)))
        index.__dict__["_route_primary"] = cached
    return cached


def _routed_operands(mesh, index, plan: RoutePlan):
    """The plan's device operands, explicitly placed sharded over the
    mesh axis (a declared boundary transfer — the sanitizer lane's
    guard rejects the implicit kind)."""
    sharding = NamedSharding(mesh, P(index.axis))
    return (jax.device_put(plan.q_rows, sharding),
            jax.device_put(plan.probe_slots, sharding))


def _scatter_back(d_g, i_g, rows_l, n_q: int, select_min: bool):
    """Scatter one shard's routed-group candidates back to their
    global query rows (shared by every routed body): non-routed
    queries keep the merge-padding sentinels — the sparse-participant
    contribution — and padded group rows (row == n_q) drop out of
    range (JAX OOB-scatter semantics)."""
    worst = worst_value(select_min, d_g.dtype)
    full_d = jnp.full((n_q, d_g.shape[1]), worst, d_g.dtype)
    full_i = jnp.full((n_q, i_g.shape[1]), PAD_ID, i_g.dtype)
    return (full_d.at[rows_l].set(d_g, mode="drop"),
            full_i.at[rows_l].set(i_g, mode="drop"))


def _routed_prelude(mesh, index, Q, k: int, merge_engine, live_mask,
                    pipeline_chunks: int, probe_fn, plan,
                    valid_rows=None, suspect_mask=None, plan_cb=None):
    """The shared route→resolve→account prelude of both routed entry
    points (one definition so participant accounting and chunk-width
    resolution cannot drift between the flat and PQ paths): clamp k,
    build (or accept) the plan, resolve the engine + pipeline chunks
    over the plan's LOCAL probe width, and record the one logical
    merge for the participating shards — telemetry skipped for
    injected (warmup) plans, which also bypass ``plan_cb`` (the
    Searcher's participation feed covers real dispatches only).
    Returns ``(k, plan, engine, chunks)``."""
    n_dev = mesh.shape[index.axis]
    cap = index.indices.shape[2]
    k = min(k, index.placement_map.n_lists * cap)
    warm = plan is not None
    if not warm:
        plan = _routed_plan(mesh, index, Q, probe_fn, live_mask,
                            valid_rows=valid_rows,
                            suspect_mask=suspect_mask)
        if plan_cb is not None:
            plan_cb(plan)
    engine = resolve_merge_engine(merge_engine, Q.shape[0], k, n_dev,
                                  n_probes=plan.pb)
    chunks = tuple(pipeline_chunk_bounds(
        plan.pb, resolve_pipeline_chunks(engine, plan.pb, n_dev,
                                         requested=pipeline_chunks)))
    if not warm:
        # One logical merge, accounted for the PARTICIPATING shards
        # only — the routed exchange estimate scales with locality.
        merge_dispatch_stats.record(
            engine, Q.shape[0], k, min(k, plan.pb * cap), n_dev,
            idx_bytes=index.indices.dtype.itemsize,
            chunk_kks=([min(k, (hi - lo) * cap) for lo, hi in chunks]
                       if len(chunks) > 1 else None),
            participants=plan.participants)
    return k, plan, engine, chunks


def _routed_result(out, plan, live_mask, n_q: int):
    """The shared routed epilogue: splice the host-computed coverage
    in when liveness was consulted (the routed program itself is
    liveness-free)."""
    if live_mask is None:
        return out
    cov = plan.coverage if plan.coverage is not None \
        else np.ones(n_q, np.float32)
    return out[0], out[1], cov


def _pad_candidates(out_d, out_i, k: int, select_min: bool):
    """Pad a merged candidate set narrower than ``k`` (the routed width
    is min(k, pb·cap·n_dev)) back up to the k-wide result contract with
    the merge sentinels — exactly what the row-sharded path returns
    beyond the probed candidates."""
    if out_d.shape[1] >= k:
        return out_d, out_i
    pad = k - out_d.shape[1]
    out_d = jnp.pad(out_d, ((0, 0), (0, pad)),
                    constant_values=worst_value(select_min))
    out_i = jnp.pad(out_i, ((0, 0), (0, pad)), constant_values=PAD_ID)
    return out_d, out_i


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "k", "inner_is_l2", "sqrt",
                              "use_cells", "qrows", "interpret", "engine",
                              "chunks"))
def _routed_flat_search_jit(data, indices, sizes, Q, q_rows, probe_slots,
                            tomb=None, *, mesh, axis, k, inner_is_l2,
                            sqrt, use_cells, qrows, interpret, engine,
                            chunks=((0, 0),)):
    """Routed IVF-Flat search body: each shard gathers its routed query
    group, scans its locally-probed slots (both flat tiers), scatters
    the group's candidates back to global query rows (sentinels
    elsewhere — the sparse-participant contribution), and the merge
    collective combines the shards.  The only batch-dependent shapes
    are the plan's pow2 (qg, pb) buckets."""
    has_tomb = tomb is not None
    n_q = Q.shape[0]

    def body(data_l, idx_l, sz_l, q, rows_l, slots_l, *rest):
        data_l, idx_l, sz_l = data_l[0], idx_l[0], sz_l[0]
        rows_l, slots_l = rows_l[0], slots_l[0]
        tomb_l = rest[0][0] if has_tomb else None
        cap = data_l.shape[1]
        pb = slots_l.shape[1]
        kk = min(k, pb * cap)
        # Padded group rows (row == n_q) gather an arbitrary real query
        # and compute garbage — dropped at the scatter below.
        q_l = q[jnp.minimum(rows_l, n_q - 1)]
        norms = (None if use_cells else
                 (jnp.sum(data_l * data_l, axis=2)
                  if inner_is_l2 else None))

        def scan_range(lo, hi, kk_c):
            pids = slots_l[:, lo:hi]
            if use_cells:
                d_g, i_g = _flat._cells_scan_probes(
                    q_l, pids, data_l, idx_l, sz_l, kk_c, inner_is_l2,
                    qrows, False, interpret, deleted=tomb_l)
            else:
                d_g, i_g = _flat._probe_scan(
                    q_l, data_l, norms, idx_l, sz_l, kk_c, inner_is_l2,
                    False, probe_ids=pids, deleted=tomb_l)
            return _scatter_back(d_g, i_g, rows_l, n_q, inner_is_l2)

        out_d, out_i = scan_merge_dispatch(
            scan_range, chunks,
            chunk_width=lambda lo, hi: min(k, (hi - lo) * cap),
            full_kk=kk, engine=engine, k=k, axis=axis,
            select_min=inner_is_l2, alive=None)
        out_d, out_i = _pad_candidates(out_d, out_i, k, inner_is_l2)
        if inner_is_l2 and sqrt:
            out_d = jnp.sqrt(out_d)
        return out_d, out_i

    extra = (P(axis),) if has_tomb else ()
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P(axis), P(axis))
        + extra,
        out_specs=(P(), P()), check_vma=False)
    args = (tomb,) if has_tomb else ()
    return fn(data, indices, sizes, Q, q_rows, probe_slots, *args)


def _routed_flat_search(mesh, params, index, Q, k: int, merge_engine,
                        live_mask, pipeline_chunks: int, plan=None,
                        valid_rows=None, suspect_mask=None,
                        plan_cb=None):
    """Route → dispatch → sparse merge for the list-owned IVF-Flat.
    ``plan`` injects a pre-built (typically all-padding) RoutePlan —
    the warmup vehicle (:func:`sharded_routed_warmup`); telemetry is
    recorded only for real (router-built) plans."""
    n_probes = min(params.n_probes, index.centers.shape[0])
    inner_is_l2 = index.metric != DistanceType.InnerProduct
    sqrt = index.metric in (DistanceType.L2SqrtExpanded,
                            DistanceType.L2SqrtUnexpanded)
    k, plan, engine, chunks = _routed_prelude(
        mesh, index, Q, k, merge_engine, live_mask, pipeline_chunks,
        functools.partial(_routed_probe_flat, n_probes=n_probes,
                          inner_is_l2=inner_is_l2), plan,
        valid_rows=valid_rows, suspect_mask=suspect_mask,
        plan_cb=plan_cb)
    use_cells = _flat._cells_eligible(
        params.engine, k, params.bucket_cap, index.indices.shape[2],
        index.centers.shape[1], plan.qg, plan.pb,
        index.indices.shape[1])
    q_rows, probe_slots = _routed_operands(mesh, index, plan)
    out = _routed_flat_search_jit(
        index.data, index.indices, index.list_sizes, Q, q_rows,
        probe_slots, index.deleted, mesh=mesh, axis=index.axis, k=k,
        inner_is_l2=inner_is_l2, sqrt=sqrt, use_cells=use_cells,
        qrows=min(_flat._CELL_QROWS, max(8, plan.qg)),
        interpret=pallas_interpret(), engine=engine,
        chunks=chunks)
    return _routed_result(out, plan, live_mask, Q.shape[0])


def _routed_pq_operands(mesh, index: ShardedIvfPq) -> tuple:
    """Slot-gathered center tables of the routed PQ bodies, cached on
    the index: the probe operands are LOCAL slot ids, so every
    per-probed-list lookup (rotated centers for the LUT residuals, the
    permuted rotated centers of the compressed kernel, per-cluster
    codebooks) needs a per-shard (n_slots, ...) table gathered through
    ``slot_to_list`` — empty slots borrow list 0 (their size is 0, so
    only sentinels survive).  Rebuilt after migration / replication /
    load; dropped with ``_scan_cache``."""
    if index._route_ops is None:
        from raft_tpu.ops.pq_scan import permute_subspaces
        pm = index.placement_map
        sharding = NamedSharding(mesh, P(index.axis))
        s2l = jnp.asarray(
            np.maximum(pm.slot_to_list, 0))  # analyze: host-sync-ok (host routing table, built once per placement)
        centers_rot = jnp.matmul(index.centers, index.rotation_matrix.T,
                                 precision=lax.Precision.HIGHEST)
        crot_slot = jax.device_put(centers_rot[s2l], sharding)
        crot_p = permute_subspaces(centers_rot, index.pq_dim,
                                   index.pq_bits)
        crot_p_slot = jax.device_put(crot_p[s2l], sharding)
        books_slot = None
        if index.codebook_kind == _pq.CodebookGen.PER_CLUSTER:
            books_slot = jax.device_put(index.pq_centers[s2l], sharding)
        index._route_ops = (crot_slot, crot_p_slot, books_slot)
    return index._route_ops


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "k", "is_ip", "per_cluster",
                              "pq_dim", "pq_bits", "sqrt", "lut_dtype",
                              "internal_dtype", "engine", "chunks"))
def _routed_pq_lut_jit(codes, indices, sizes, crot_slot, books, rot, Q,
                       q_rows, probe_slots, tomb=None, *, mesh, axis, k,
                       is_ip, per_cluster, pq_dim, pq_bits, sqrt,
                       lut_dtype, internal_dtype=jnp.float32,
                       engine="allgather", chunks=((0, 0),)):
    """Routed LUT-tier IVF-PQ search body (the routed analog of
    ``_sharded_pq_search_jit``): probe operands are local slots, so the
    rotated-center (and per-cluster codebook) lookups go through the
    slot-gathered tables of :func:`_routed_pq_operands`."""
    has_tomb = tomb is not None
    n_q = Q.shape[0]

    def body(codes_l, idx_l, sz_l, crot_l, books_o, rot_r, q, rows_l,
             slots_l, *rest):
        codes_l, idx_l, sz_l = codes_l[0], idx_l[0], sz_l[0]
        crot_l, rows_l, slots_l = crot_l[0], rows_l[0], slots_l[0]
        books_l = books_o[0] if per_cluster else books_o
        tomb_l = rest[0][0] if has_tomb else None
        cap = codes_l.shape[1]
        pb = slots_l.shape[1]
        kk = min(k, pb * cap)
        q_l = q[jnp.minimum(rows_l, n_q - 1)]
        rotq = jnp.matmul(q_l, rot_r.T, precision=lax.Precision.HIGHEST)

        def scan_range(lo, hi, kk_c):
            d_g, i_g = _pq._pq_probe_scan(
                rotq, slots_l[:, lo:hi], codes_l, idx_l, sz_l, kk_c,
                is_ip, per_cluster, lut_dtype, pq_dim, pq_bits,
                internal_dtype, pq_centers=books_l, centers_rot=crot_l,
                deleted=tomb_l)
            return _scatter_back(d_g, i_g, rows_l, n_q, not is_ip)

        out_d, out_i = scan_merge_dispatch(
            scan_range, chunks,
            chunk_width=lambda lo, hi: min(k, (hi - lo) * cap),
            full_kk=kk, engine=engine, k=k, axis=axis,
            select_min=not is_ip, alive=None)
        out_d, out_i = _pad_candidates(out_d, out_i, k, not is_ip)
        if sqrt:
            out_d = jnp.sqrt(jnp.maximum(out_d, 0.0))
        return out_d, out_i

    books_spec = P(axis) if per_cluster else P()
    extra = (P(axis),) if has_tomb else ()
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), books_spec, P(),
                  P(), P(axis), P(axis)) + extra,
        out_specs=(P(), P()), check_vma=False)
    args = (tomb,) if has_tomb else ()
    return fn(codes, indices, sizes, crot_slot, books, rot, Q, q_rows,
              probe_slots, *args)


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "k", "is_ip", "pq_dim",
                              "pq_bits", "sqrt", "qrows", "interpret",
                              "engine", "chunks"))
def _routed_pq_compressed_jit(codesT, invalid, indices, crot_p_slot,
                              abs_lo, abs_hi, rot, Q, q_rows,
                              probe_slots, *, mesh, axis, k, is_ip,
                              pq_dim, pq_bits, sqrt, qrows, interpret,
                              engine, chunks=((0, 0),)):
    """Routed compressed-tier IVF-PQ search body: each shard runs the
    production Pallas gather-decode scan over its routed query group's
    locally-probed slots (the permuted rotated centers slot-gathered),
    scatters back, and merges sparsely."""
    n_q = Q.shape[0]

    def body(codesT_l, inv_l, idx_l, crot_l, lo_r, hi_r, rot_r, q,
             rows_l, slots_l):
        codesT_l, inv_l, idx_l = codesT_l[0], inv_l[0], idx_l[0]
        crot_l, rows_l, slots_l = crot_l[0], rows_l[0], slots_l[0]
        from raft_tpu.ops.pq_scan import permute_subspaces

        cap = idx_l.shape[1]
        pb = slots_l.shape[1]
        kk = min(k, pb * cap)
        q_l = q[jnp.minimum(rows_l, n_q - 1)]
        rotq_p = permute_subspaces(
            jnp.matmul(q_l, rot_r.T, precision=lax.Precision.HIGHEST),
            pq_dim, pq_bits)

        def scan_range(lo, hi, kk_c):
            d_g, i_g = _pq._compressed_scan_probes(
                rotq_p, slots_l[:, lo:hi], codesT_l, lo_r, hi_r, inv_l,
                idx_l, crot_l, kk_c, is_ip, pq_dim, pq_bits, qrows,
                interpret)
            return _scatter_back(d_g, i_g, rows_l, n_q, not is_ip)

        out_d, out_i = scan_merge_dispatch(
            scan_range, chunks,
            chunk_width=lambda lo, hi: min(k, (hi - lo) * cap),
            full_kk=kk, engine=engine, k=k, axis=axis,
            select_min=not is_ip, alive=None)
        out_d, out_i = _pad_candidates(out_d, out_i, k, not is_ip)
        if sqrt:
            out_d = jnp.sqrt(jnp.maximum(out_d, 0.0))
        return out_d, out_i

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(), P(), P(),
                  P(), P(axis), P(axis)),
        out_specs=(P(), P()), check_vma=False)
    return fn(codesT, invalid, indices, crot_p_slot, abs_lo, abs_hi,
              rot, Q, q_rows, probe_slots)


def _routed_pq_search(mesh, params, index, Q, k: int, merge_engine,
                      live_mask, pipeline_chunks: int, plan=None,
                      valid_rows=None, suspect_mask=None, plan_cb=None):
    """Route → dispatch → sparse merge for the list-owned IVF-PQ (both
    tiers; tier dispatch mirrors the row-sharded entry with the routed
    group/probe widths)."""
    lut_dtype, internal_dtype = _pq.validate_search_dtypes(params)
    n_probes = min(params.n_probes, index.centers.shape[0])
    is_ip = index.metric == DistanceType.InnerProduct
    sqrt = index.metric == DistanceType.L2SqrtExpanded
    k, plan, engine, chunks = _routed_prelude(
        mesh, index, Q, k, merge_engine, live_mask, pipeline_chunks,
        functools.partial(_routed_probe_pq, n_probes=n_probes,
                          is_ip=is_ip), plan, valid_rows=valid_rows,
        suspect_mask=suspect_mask, plan_cb=plan_cb)
    q_rows, probe_slots = _routed_operands(mesh, index, plan)
    default_dtypes = (lut_dtype == jnp.float32
                      and internal_dtype == jnp.float32)
    use_compressed = _pq._compressed_tier_ok(
        params.engine, _pq._compressed_supported(index), default_dtypes,
        k, index.pq_codes.shape[2], index.pq_codes.shape[3],
        index.rot_dim, plan.qg, plan.pb, index.indices.shape[1])
    crot_slot, crot_p_slot, books_slot = _routed_pq_operands(mesh, index)
    if use_compressed:
        codesT, invalid, abs_lo, abs_hi, _ = \
            _sharded_scan_operands(mesh, index)
        out = _routed_pq_compressed_jit(
            codesT, invalid, index.indices, crot_p_slot, abs_lo, abs_hi,
            index.rotation_matrix, Q, q_rows, probe_slots, mesh=mesh,
            axis=index.axis, k=k, is_ip=is_ip, pq_dim=index.pq_dim,
            pq_bits=index.pq_bits, sqrt=sqrt,
            qrows=min(_pq._CELL_QROWS, max(8, plan.qg)),
            interpret=pallas_interpret(), engine=engine,
            chunks=chunks)
    else:
        per_cluster = index.codebook_kind == _pq.CodebookGen.PER_CLUSTER
        books = books_slot if per_cluster else index.pq_centers
        out = _routed_pq_lut_jit(
            index.pq_codes, index.indices, index.list_sizes, crot_slot,
            books, index.rotation_matrix, Q, q_rows, probe_slots,
            index.deleted, mesh=mesh, axis=index.axis, k=k, is_ip=is_ip,
            per_cluster=per_cluster, pq_dim=index.pq_dim,
            pq_bits=index.pq_bits, sqrt=sqrt, lut_dtype=lut_dtype,
            internal_dtype=internal_dtype, engine=engine, chunks=chunks)
    return _routed_result(out, plan, live_mask, Q.shape[0])


def sharded_routed_warmup(mesh: Mesh, params, index, n_queries: int,
                          k: int, merge_engine: str = "auto") -> int:
    """Pre-compile the routed dispatch's CLOSED (qg, pb) shape grid for
    one (n_queries, k) bucket shape, so steady-state routed serving
    never compiles (the routing analog of ``serve.bucketing.warmup`` —
    which calls this per grid shape for routed searchers).  Dispatches
    one all-padding plan per shape (values never enter the trace);
    returns the number of shapes dispatched."""
    pm = index.placement_map
    expects(pm is not None, "routed warmup needs a placement='list' index")
    n_probes = min(params.n_probes, index.centers.shape[0])
    dummy = np.zeros((n_queries, index.centers.shape[1]), np.float32)
    is_flat = isinstance(index, ShardedIvfFlat)
    shapes = route_shapes(n_queries, n_probes)
    for qg, pb in shapes:
        plan = empty_plan(pm, n_queries, qg, pb)
        if is_flat:
            sharded_ivf_flat_search(mesh, params, index, dummy, k,
                                    merge_engine=merge_engine, _plan=plan)
        else:
            sharded_ivf_pq_search(mesh, params, index, dummy, k,
                                  merge_engine=merge_engine, _plan=plan)
    return len(shapes)


def sharded_ivf_pq_build(
    mesh: Mesh, params: "_pq.IndexParams", dataset, axis: str = "data",
    model: Optional["_pq.Index"] = None, placement: str = "row",
) -> ShardedIvfPq:
    """Build an IVF-PQ with codes sharded over ``mesh[axis]``. The coarse
    centers / rotation / codebooks come from ``model`` (an empty Index from
    ivf_pq.build with add_data_on_build=False) or are trained here the
    same way; every shard encodes its rows against the shared model.
    ``placement="list"`` assigns whole lists to shards for routed search
    (see :func:`sharded_ivf_flat_build`)."""
    expects(placement in ("row", "list"),
            "placement must be 'row' or 'list', got %r", placement)
    X = _pq._as_float(_pq.as_array(dataset))
    n, dim = X.shape
    n_dev = mesh.shape[axis]
    expects(placement == "list" or n % n_dev == 0,
            "rows must divide the mesh axis (pad first)")

    if model is None:
        import dataclasses

        model = _pq.build(dataclasses.replace(params, add_data_on_build=False),
                          X)

    labels, codes = _pq.encode_rows(model, X)

    ids = jnp.arange(n, dtype=model.indices.dtype)
    if placement == "list":
        packed, idx, sizes, pm = _list_pack(
            mesh, axis, codes, np.asarray(labels), ids, model.n_lists,
            centers=model.centers)
        return ShardedIvfPq(
            metric=model.metric, codebook_kind=model.codebook_kind,
            centers=model.centers, rotation_matrix=model.rotation_matrix,
            pq_centers=model.pq_centers, pq_codes=packed.astype(jnp.uint8),
            indices=idx, list_sizes=sizes, pq_bits=model.pq_bits,
            pq_dim=model.pq_dim, axis=axis, placement_map=pm)
    packed, idx, sizes = _shard_pack(mesh, axis, codes, np.asarray(labels),
                                     ids, model.n_lists)
    return ShardedIvfPq(
        metric=model.metric, codebook_kind=model.codebook_kind,
        centers=model.centers, rotation_matrix=model.rotation_matrix,
        pq_centers=model.pq_centers, pq_codes=packed.astype(jnp.uint8),
        indices=idx, list_sizes=sizes, pq_bits=model.pq_bits,
        pq_dim=model.pq_dim, axis=axis)


def _sharded_scan_operands(mesh: Mesh, index: ShardedIvfPq) -> tuple:
    """Per-shard operands of the compressed-domain Pallas scan, cached on
    the sharded index (the multi-device analog of
    ``Index.compressed_scan_operands``): ``(codesT, invalid, lo, hi,
    crot_p)`` — transposed packed codes and slot masks sharded over
    ``mesh[axis]``; the shared codeword tables and the permuted rotated
    centers come from the REPLICATED model (they do not depend on which
    rows a shard holds), so they replicate like the centers."""
    if index._scan_cache is None:
        from raft_tpu.ops.pq_scan import (_SC, book_tables,
                                          permute_subspaces)
        sharding = NamedSharding(mesh, P(index.axis))
        cap = index.pq_codes.shape[2]
        capp = ceildiv(cap, _SC) * _SC
        codesT = jnp.swapaxes(index.pq_codes, 2, 3)  # (n_dev, L, nbytes, cap)
        if capp != cap:
            codesT = jnp.pad(codesT,
                             ((0, 0), (0, 0), (0, 0), (0, capp - cap)))
        codesT = jax.device_put(codesT, sharding)
        invalid = (jnp.arange(capp, dtype=jnp.int32)[None, None, :]
                   >= index.list_sizes[:, :, None])
        if index.deleted is not None:
            # Tombstones ride the existing invalid operand (same shape,
            # so a delete never changes the compiled program; delete()
            # drops _scan_cache and the rebuild lands here).
            invalid |= jnp.pad(index.deleted,
                               ((0, 0), (0, 0), (0, capp - cap)))
        invalid = jax.device_put(invalid, sharding)
        centers_rot = jnp.matmul(index.centers, index.rotation_matrix.T,
                                 precision=lax.Precision.HIGHEST)
        crot_p = replicated(
            mesh, permute_subspaces(centers_rot, index.pq_dim,
                                    index.pq_bits))
        lo, hi = book_tables(index.pq_centers, index.pq_bits)
        index._scan_cache = (codesT, invalid, replicated(mesh, lo),
                             replicated(mesh, hi), crot_p)
    return index._scan_cache


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "k", "n_probes", "is_ip",
                              "pq_dim", "pq_bits", "sqrt", "qrows",
                              "interpret", "engine", "chunks"))
def _sharded_pq_compressed_jit(codesT, invalid, indices, centers, rot,
                               abs_lo, abs_hi, crot_p, Q, live=None, *,
                               mesh, axis, k, n_probes, is_ip, pq_dim,
                               pq_bits, sqrt, qrows, interpret, engine,
                               chunks=((0, 0),)):
    """Sharded compressed-domain search: each shard runs the PRODUCTION
    single-chip pipeline (``ivf_pq._compressed_search`` — packed query
    cells + the Pallas gather-decode MXU scan) over its own code shard,
    then the per-shard top-k merges inside the merge collective (the
    knn_merge_parts decomposition, brute_force.cuh:80; VERDICT r4
    Missing #1 — the sharded path previously ran the 139–254 QPS-class
    LUT scan tier). The pipelined engines chunk the scan over probe
    columns and overlap each chunk's exchange with the next chunk's
    Pallas scan (comms.topk_merge_pipelined — bit-identical)."""
    has_live = live is not None
    pipelined = engine in PIPELINED_ENGINES and len(chunks) > 1

    def body(codesT_l, inv_l, idx_l, centers_r, rot_r, lo_r, hi_r,
             crot_r, q, *rest):
        codesT_l, inv_l, idx_l = codesT_l[0], inv_l[0], idx_l[0]
        alive = local_alive(rest[0], axis) if has_live else None
        cap = idx_l.shape[1]
        kk = min(k, idx_l.shape[0] * cap)
        if pipelined:
            # The chunked producer probes/rotates ONCE outside the
            # chunk loop (the eager branch keeps the historical
            # one-call _compressed_search trace).
            from raft_tpu.ops.pq_scan import permute_subspaces

            probe_ids = _pq._select_clusters((q, centers_r), n_probes,
                                             is_ip)
            rotq_p = permute_subspaces(
                jnp.matmul(q, rot_r.T, precision=lax.Precision.HIGHEST),
                pq_dim, pq_bits)

            def scan_range(lo, hi, kk_c):
                return _pq._compressed_scan_probes(
                    rotq_p, probe_ids[:, lo:hi], codesT_l, lo_r, hi_r,
                    inv_l, idx_l, crot_r, kk_c, is_ip, pq_dim, pq_bits,
                    qrows, interpret)
        else:
            def scan_range(lo, hi, kk_c):
                return _pq._compressed_search(
                    q, centers_r, rot_r, codesT_l, lo_r, hi_r, inv_l,
                    idx_l, crot_r, n_probes, kk_c, is_ip, pq_dim,
                    pq_bits, qrows, interpret)

        out_d, out_i = scan_merge_dispatch(
            scan_range, chunks,
            chunk_width=lambda lo, hi: min(k, (hi - lo) * cap),
            full_kk=kk, engine=engine, k=k, axis=axis,
            select_min=not is_ip, alive=alive)
        if sqrt:
            out_d = jnp.sqrt(jnp.maximum(out_d, 0.0))
        if not has_live:
            return out_d, out_i
        # Coverage over the probed lists: sizes recovered from the slot
        # validity mask (sz = #valid slots per list); the probe set is
        # the replicated coarse model's, reproduced exactly.
        sz_l = jnp.sum((~inv_l).astype(jnp.int32), axis=1)
        probe_ids = _pq._select_clusters((q, centers_r), n_probes, is_ip)
        cov = probed_coverage(probe_ids, sz_l, alive, axis)
        return out_d, out_i, cov

    extra_in, extra_out = live_specs(has_live)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P(), P(), P(), P(),
                  P()) + extra_in,
        out_specs=(P(), P()) + extra_out, check_vma=False)
    return fn(codesT, invalid, indices, centers, rot, abs_lo, abs_hi,
              crot_p, Q, *live_args(live))


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "k", "n_probes", "is_ip",
                              "per_cluster", "pq_dim", "pq_bits", "sqrt",
                              "lut_dtype", "internal_dtype", "engine",
                              "chunks"))
def _sharded_pq_search_jit(codes, indices, sizes, centers, rot, books, Q,
                           live=None, tomb=None, *, mesh, axis, k,
                           n_probes, is_ip, per_cluster, pq_dim, pq_bits,
                           sqrt, lut_dtype,
                           internal_dtype=jnp.float32, engine="allgather",
                           chunks=((0, 0),)):
    has_live = live is not None
    has_tomb = tomb is not None

    def body(codes_l, idx_l, sz_l, centers_r, rot_r, books_r, q, *rest):
        codes_l, idx_l, sz_l = codes_l[0], idx_l[0], sz_l[0]
        rest = list(rest)
        alive_mask = rest.pop(0) if has_live else None
        tomb_l = rest.pop(0)[0] if has_tomb else None
        alive = local_alive(alive_mask, axis) if has_live else None
        probe_ids = _pq._select_clusters((q, centers_r), n_probes, is_ip)
        rotq = jnp.matmul(q, rot_r.T, precision=lax.Precision.HIGHEST)
        centers_rot = jnp.matmul(centers_r, rot_r.T,
                                 precision=lax.Precision.HIGHEST)
        cap = codes_l.shape[1]
        kk = min(k, codes_l.shape[0] * cap)

        def scan_range(lo, hi, kk_c):
            # LUT probe scan over one probe-column range
            # (scan_merge_dispatch chunks it under the pipelined
            # engines — bit-identical).
            return _pq._pq_probe_scan(
                rotq, probe_ids[:, lo:hi], codes_l, idx_l, sz_l, kk_c,
                is_ip, per_cluster, lut_dtype, pq_dim, pq_bits,
                internal_dtype, pq_centers=books_r,
                centers_rot=centers_rot, deleted=tomb_l)

        out_d, out_i = scan_merge_dispatch(
            scan_range, chunks,
            chunk_width=lambda lo, hi: min(k, (hi - lo) * cap),
            full_kk=kk, engine=engine, k=k, axis=axis,
            select_min=not is_ip, alive=alive)
        if sqrt:
            out_d = jnp.sqrt(jnp.maximum(out_d, 0.0))
        if not has_live:
            return out_d, out_i
        cov = probed_coverage(probe_ids, sz_l, alive, axis)
        return out_d, out_i, cov

    extra_in, extra_out = live_specs(has_live)
    if has_tomb:
        extra_in = extra_in + (P(axis),)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P(), P(), P())
        + extra_in,
        out_specs=(P(), P()) + extra_out, check_vma=False)
    args = live_args(live) + ((tomb,) if has_tomb else ())
    return fn(codes, indices, sizes, centers, rot, books, Q, *args)


def sharded_ivf_pq_search(
    mesh: Mesh, params: "_pq.SearchParams", index: ShardedIvfPq,
    queries, k: int, merge_engine: str = "auto", live_mask=None,
    pipeline_chunks: int = 0, _plan=None, valid_rows=None,
    suspect_mask=None, plan_cb=None,
):
    """Search the sharded PQ index; returns replicated global-id results.

    Engine dispatch mirrors the single-chip :func:`ivf_pq.search`: the
    compressed-domain Pallas scan runs per shard whenever eligible
    (per-subspace books, byte-aligned fields, default score dtypes, k
    within the cells queue, per-list blocks within VMEM, TPU backend
    with enough probe load or explicit engine="bucketed"); otherwise
    the LUT scan tier runs per shard. Either way the per-shard top-k
    merges through the merge collective selected by ``merge_engine``
    (comms/topk_merge.py); the pipelined engines ("auto" at
    n_probes >= 16 on 4+ shards, or explicit "pipelined" /
    "pipelined_bf16") chunk the scan over probe lists and overlap each
    chunk's exchange with the next chunk's scan — bit-identical
    results; ``pipeline_chunks`` overrides the chunk count (0 = auto;
    docs/sharded_search.md §pipeline).

    ``live_mask`` (bool (n_dev,), e.g. ``ShardHealth.live_mask``)
    enables degraded serving on BOTH tiers (docs/fault_tolerance.md):
    exact-over-survivors results plus a third ``coverage`` (float32
    (q,)) output — the per-query fraction of probed candidate rows
    searched. All-live results are bit-identical to ``live_mask=None``.

    ``placement="list"`` indexes serve the ROUTED path — see
    :func:`sharded_ivf_flat_search`; bit-identical results, sparse
    participation."""
    Q = replicated(mesh, _pq._as_float(_pq.as_array(queries)))
    # Replicated model tensors placed once (write-back) — see the flat
    # entry point; without it every dispatch re-transfers implicitly.
    index.centers = replicated(mesh, index.centers)
    index.rotation_matrix = replicated(mesh, index.rotation_matrix)
    index.pq_centers = replicated(mesh, index.pq_centers)
    expects(Q.shape[1] == index.centers.shape[1], "query dim mismatch")
    if index.placement == "list":
        return _routed_pq_search(mesh, params, index, Q, k, merge_engine,
                                 live_mask, pipeline_chunks, plan=_plan,
                                 valid_rows=valid_rows,
                                 suspect_mask=suspect_mask,
                                 plan_cb=plan_cb)
    lut_dtype, internal_dtype = _pq.validate_search_dtypes(params)
    n_probes = min(params.n_probes, index.centers.shape[0])
    k = min(k, index.indices.shape[0] * index.indices.shape[1]
            * index.indices.shape[2])
    is_ip = index.metric == DistanceType.InnerProduct
    sqrt = index.metric == DistanceType.L2SqrtExpanded

    n_dev = mesh.shape[index.axis]
    engine = resolve_merge_engine(merge_engine, Q.shape[0], k, n_dev,
                                  n_probes=n_probes)
    cap = index.indices.shape[2]
    chunks = tuple(pipeline_chunk_bounds(
        n_probes, resolve_pipeline_chunks(engine, n_probes, n_dev,
                                          requested=pipeline_chunks)))
    # Host-side dispatch accounting — see sharded_ivf_flat_search.
    merge_dispatch_stats.record(
        engine, Q.shape[0], k,
        min(k, index.indices.shape[1] * cap), n_dev,
        idx_bytes=index.indices.dtype.itemsize,
        chunk_kks=([min(k, (hi - lo) * cap) for lo, hi in chunks]
                   if len(chunks) > 1 else None))
    live = (None if live_mask is None
            else check_live_mask(live_mask, mesh.shape[index.axis], mesh))
    n_lists = index.indices.shape[1]
    default_dtypes = (lut_dtype == jnp.float32
                      and internal_dtype == jnp.float32)
    # Same gate as the single-chip dispatch (shared scalar core — a
    # re-spelled copy would drift), with the per-SHARD cap/nbytes.
    use_compressed = _pq._compressed_tier_ok(
        params.engine, _pq._compressed_supported(index), default_dtypes,
        k, index.pq_codes.shape[2], index.pq_codes.shape[3],
        index.rot_dim, Q.shape[0], n_probes, n_lists)
    if use_compressed:
        codesT, invalid, abs_lo, abs_hi, crot_p = \
            _sharded_scan_operands(mesh, index)
        return _sharded_pq_compressed_jit(
            codesT, invalid, index.indices, index.centers,
            index.rotation_matrix, abs_lo, abs_hi, crot_p, Q, live,
            mesh=mesh, axis=index.axis, k=k, n_probes=n_probes,
            is_ip=is_ip, pq_dim=index.pq_dim, pq_bits=index.pq_bits,
            sqrt=sqrt,
            qrows=min(_pq._CELL_QROWS, max(8, Q.shape[0])),
            interpret=pallas_interpret(), engine=engine,
            chunks=chunks)
    return _sharded_pq_search_jit(
        index.pq_codes, index.indices, index.list_sizes, index.centers,
        index.rotation_matrix, index.pq_centers, Q, live, index.deleted,
        mesh=mesh, axis=index.axis, k=k, n_probes=n_probes, is_ip=is_ip,
        per_cluster=index.codebook_kind == _pq.CodebookGen.PER_CLUSTER,
        pq_dim=index.pq_dim, pq_bits=index.pq_bits,
        sqrt=sqrt, lut_dtype=lut_dtype, internal_dtype=internal_dtype,
        engine=engine, chunks=chunks)


# ---------------------------------------------------------------------------
# Sharded lifecycle: extend + save/load (ref: the MNMG pattern persists and
# grows per-rank state with the same versioned serializers as the
# single-device index, detail/ivf_pq_serialize.cuh:38-100).

def _sharded_scatter_append_impl(store, ids, sizes, payload, new_ids,
                                 labels):
    """vmapped O(n_new) append over the shard axis; under the donating
    jit each shard's buffer is updated in place (see
    ivf_flat._scatter_append_core); the _cow twin preserves the inputs
    for mutations racing live reader threads."""
    st, id_, sz, _ = jax.vmap(_flat._scatter_append_core)(
        store, ids, sizes, payload, new_ids, labels)
    return st, id_, sz


_sharded_scatter_append = functools.partial(
    jax.jit, donate_argnums=(0, 1))(_sharded_scatter_append_impl)
_sharded_scatter_append_cow = jax.jit(_sharded_scatter_append_impl)


def _routed_extend_deal(pm: ListPlacement, payload, new_ids, labels):
    """Deal extend rows to shards by LIST OWNERSHIP (placement="list"):
    row r appends on owner[label_r] at the list's local slot, plus a
    second copy on the replica shard when the list is replicated.
    Shards receive unequal counts, so the per-shard batches pad to the
    max with slot label ``n_slots`` — out of range, so the scatter
    drops the padding (JAX's documented OOB-scatter semantics, the same
    drop `_repack` relies on)."""
    if payload.shape[0] == 0:
        # Empty batch: an all-padding deal (a gather from a 0-row
        # payload would raise) — the scatter drops everything, matching
        # the row placement's zero-row no-op-with-epoch-bump behavior.
        return (jnp.zeros((pm.n_dev, 1) + tuple(payload.shape[1:]),
                          payload.dtype),
                jnp.full((pm.n_dev, 1), PAD_ID, new_ids.dtype),
                jnp.full((pm.n_dev, 1), pm.n_slots, jnp.int32))
    # analyze: host-sync-ok (mutation path: the routed deal groups rows
    # by owner shard on host, like the row path's capacity readback)
    labels_h = np.asarray(jax.device_get(labels)).astype(np.int64)
    owner = pm.owner[labels_h]
    slot = pm.slot[labels_h]
    rep_o = pm.replica_owner[labels_h]
    rep_s = pm.replica_slot[labels_h]
    rows, slots = [], []
    for s in range(pm.n_dev):
        pri = np.flatnonzero(owner == s)
        rep = np.flatnonzero(rep_o == s)
        rows.append(np.concatenate([pri, rep]))
        slots.append(np.concatenate([slot[pri], rep_s[rep]]))
    m = max(max(r.size for r in rows), 1)
    rows_m = np.zeros((pm.n_dev, m), np.int64)
    slots_m = np.full((pm.n_dev, m), pm.n_slots, np.int32)
    for s in range(pm.n_dev):
        rows_m[s, :rows[s].size] = rows[s]
        slots_m[s, :slots[s].size] = slots[s]
    rows_d = jnp.asarray(rows_m)
    return (jnp.asarray(payload)[rows_d], jnp.asarray(new_ids)[rows_d],
            jnp.asarray(slots_m))


def _sharded_extend(mesh, index, store_name: str, payload, new_ids, labels,
                    donate: bool = True, default_base=None):
    """Shared grow+append for both sharded index kinds. ``payload`` is the
    per-row storage payload (vectors / packed code rows), already encoded;
    rows are dealt to shards contiguously (n_new % n_dev == 0, the build
    contract). ``donate=False`` selects the copy-on-write scatter;
    ``default_base`` is _resolve_new_ids' host-computed auto-id base, so
    the id tracker advances without a device readback on that path."""
    axis = index.axis
    n_dev = mesh.shape[axis]
    store = getattr(index, store_name)
    n_new = payload.shape[0]
    if index.placement == "list":
        # Routed deal: each row goes to its list's OWNER shard (and to
        # the replica shard when the list is replicated — both copies
        # must stay bit-identical); the per-shard batches pad to a
        # common width with the out-of-range drop label.
        pl, ni, lb = _routed_extend_deal(index.placement_map, payload,
                                         new_ids, labels)
    else:
        expects(n_new % n_dev == 0,
                "rows must divide the mesh axis (pad first)")
        m = n_new // n_dev
        pl = payload.reshape(n_dev, m, payload.shape[1])
        ni = new_ids.reshape(n_dev, m)
        lb = labels.reshape(n_dev, m).astype(jnp.int32)

    # Common-capacity growth across shards (one scalar readback —
    # _grown_cap's max reduces over the stacked (n_dev, n_lists) sizes).
    # Out-of-range drop labels (the routed deal's padding) fall out of
    # the bincount, so they never inflate a slot's growth need.
    counts = jax.vmap(
        lambda l: jnp.bincount(l, length=store.shape[1]))(lb)
    cap = store.shape[2]
    new_cap = _flat._grown_cap(index.list_sizes, counts, cap,
                               conservative=False)
    sharding = NamedSharding(mesh, P(axis))
    if new_cap > cap:
        store = jax.device_put(
            jnp.pad(store, ((0, 0), (0, 0), (0, new_cap - cap))
                    + ((0, 0),) * (store.ndim - 3)), sharding)
        index.indices = jax.device_put(
            jnp.pad(index.indices, ((0, 0), (0, 0), (0, new_cap - cap)),
                    constant_values=PAD_ID), sharding)
        if index.deleted is not None:
            # Grow the tombstone mask alongside: fresh slots are live.
            index.deleted = jax.device_put(
                _flat._pad_deleted(index.deleted, new_cap), sharding)
    scatter = (_sharded_scatter_append if donate
               else _sharded_scatter_append_cow)
    st, id_, sz = scatter(
        store, index.indices, index.list_sizes, pl, ni, lb)
    setattr(index, store_name, st)
    index.indices, index.list_sizes = id_, sz
    _flat._track_next_id(index, new_ids, default_base, n_new)
    if hasattr(index, "_scan_cache"):
        index._scan_cache = None  # codes/occupancy changed
    index.epoch += 1              # invalidates serving-layer result caches
    return index


def _resolve_new_ids(index, n_new: int, new_indices):
    """Default ids allocate from ``max(existing id) + 1`` (tracked on the
    index — same contract as the single-device extend; the old
    ``sum(list_sizes)`` base collided with user-supplied ids after an
    explicit-id extend, and with live ids once delete shrinks the live
    count). Returns ``(ids, default_base)`` — base is None for
    explicit ids (the tracker then advances off their device max)."""
    if new_indices is None:
        base = _flat._auto_id_base(index)
        return (jnp.arange(base, base + n_new,
                           dtype=index.indices.dtype), base)
    return jnp.asarray(new_indices).astype(index.indices.dtype), None


def sharded_ivf_flat_extend(mesh: Mesh, index: ShardedIvfFlat, new_vectors,
                            new_indices=None, *,
                            donate: bool = True) -> ShardedIvfFlat:
    """Append rows to the sharded index in place at O(n_new) per shard
    (ref: ivf_flat::extend + the MNMG shard recipe). New rows are dealt
    contiguously across shards and scatter into each shard's free list
    slots; the shared coarse model is unchanged. ``donate=False``
    preserves the old shard buffers (copy-on-write) for mutations
    racing live reader threads (see ivf_flat.extend)."""
    X = _flat._as_float(_flat.as_array(new_vectors))
    expects(X.shape[1] == index.centers.shape[1], "dim mismatch")
    new_indices, default_base = _resolve_new_ids(index, X.shape[0],
                                                 new_indices)
    labels = kmeans_balanced.predict(
        KMeansBalancedParams(metric=index.metric), index.centers, X)
    return _sharded_extend(mesh, index, "data", X, new_indices, labels,
                           donate=donate, default_base=default_base)


def sharded_ivf_pq_extend(mesh: Mesh, index: ShardedIvfPq, new_vectors,
                          new_indices=None, *,
                          donate: bool = True) -> ShardedIvfPq:
    """Encode + append rows to the sharded PQ index in place (ref:
    ivf_pq::extend against the replicated model). ``donate=False``
    selects the copy-on-write scatter (see ivf_flat.extend)."""
    X = _pq._as_float(_pq.as_array(new_vectors))
    expects(X.shape[1] == index.centers.shape[1], "dim mismatch")
    new_indices, default_base = _resolve_new_ids(index, X.shape[0],
                                                 new_indices)
    labels, codes = _pq.encode_rows(index, X)
    return _sharded_extend(mesh, index, "pq_codes", codes, new_indices,
                           labels, donate=donate,
                           default_base=default_base)


# ---------------------------------------------------------------------------
# List migration + replication (placement="list" only): background
# passes that move/copy WHOLE lists between shards — the load-balancer
# half of the routed placement.  Both build a copy-on-write successor
# at epoch + 1 (the caller publishes by swapping one reference, the
# Compactor contract), never touching the input index; results are
# bit-identical across the move because list contents are unchanged.


def _rebuild_list_tensors(mesh, index, pm: "ListPlacement"):
    """Host repack of the shard tensors under a new placement map: each
    global list's cap-padded block moves from its old (owner, slot) to
    its new one (replica copies written alongside).  A background-pass
    host round-trip by design, like ``_compact_sharded``."""
    old = index.placement_map
    is_pq = isinstance(index, ShardedIvfPq)
    store = index.pq_codes if is_pq else index.data
    store_h = np.asarray(  # analyze: host-sync-ok (background migration pass)
        jax.device_get(store))
    idx_h = np.asarray(  # analyze: host-sync-ok (background migration pass)
        jax.device_get(index.indices))
    sz_h = np.asarray(  # analyze: host-sync-ok (background migration pass)
        jax.device_get(index.list_sizes))
    del_h = (np.asarray(  # analyze: host-sync-ok (background migration pass)
        jax.device_get(index.deleted))
             if index.deleted is not None else None)
    cap = idx_h.shape[2]
    n_dev = old.n_dev
    new_store = np.zeros((n_dev, pm.n_slots, cap) + store_h.shape[3:],
                         store_h.dtype)
    new_idx = np.full((n_dev, pm.n_slots, cap), PAD_ID, idx_h.dtype)
    new_sz = np.zeros((n_dev, pm.n_slots), sz_h.dtype)
    new_del = (np.zeros((n_dev, pm.n_slots, cap), bool)
               if del_h is not None else None)
    for g in range(pm.n_lists):
        src = (old.owner[g], old.slot[g])
        for dst in ((pm.owner[g], pm.slot[g]),
                    (pm.replica_owner[g], pm.replica_slot[g])):
            if dst[0] < 0:
                continue
            new_store[dst] = store_h[src]
            new_idx[dst] = idx_h[src]
            new_sz[dst] = sz_h[src]
            if new_del is not None:
                new_del[dst] = del_h[src]
    sharding = NamedSharding(mesh, P(index.axis))
    # n_deleted counts PRIMARY copies only (replica slots carry the
    # same tombstones again — one logical deletion each).
    n_del = (int(new_del[pm.owner, pm.slot].sum())
             if new_del is not None else 0)
    fields = dict(
        indices=jax.device_put(jnp.asarray(new_idx), sharding),
        list_sizes=jax.device_put(jnp.asarray(new_sz), sharding),
        deleted=(None if new_del is None
                 else jax.device_put(jnp.asarray(new_del), sharding)),
        n_deleted=n_del,
        placement_map=pm, epoch=index.epoch + 1, _route_sizes=None)
    st = jax.device_put(jnp.asarray(new_store), sharding)
    if is_pq:
        fields.update(pq_codes=st, _scan_cache=None, _route_ops=None)
    else:
        fields.update(data=st)
    import dataclasses as _dc

    return _dc.replace(index, **fields)


def _with_replicas(pm: ListPlacement, list_ids, sizes, live
                   ) -> ListPlacement:
    """A new placement with ``list_ids`` replicated onto a second
    shard each: per list the least row-loaded LIVE shard that is not
    the owner (deterministic); free local slots are used when
    available, else the slot count grows one pow2 step (a documented
    one-time retrace, like ``shrink_capacity``).  Lists already
    replicated keep their copy."""
    rep_o = pm.replica_owner.copy()
    rep_s = pm.replica_slot.copy()
    loads = np.zeros(pm.n_dev, np.int64)
    np.add.at(loads, pm.owner, sizes)
    used = {(s, j) for s in range(pm.n_dev)
            for j in np.flatnonzero(pm.slot_to_list[s] >= 0)}
    n_slots = pm.n_slots
    for g in np.asarray(list_ids, np.int64).reshape(-1):
        if rep_o[g] >= 0:
            continue                       # already replicated
        candidates = [s for s in range(pm.n_dev)
                      if s != pm.owner[g] and live[s]]
        expects(bool(candidates),
                "no live non-owner shard to replicate list %s onto", g)
        tgt = min(candidates, key=lambda s: (loads[s], s))
        # First free slot below the always-empty padding slot; grow a
        # pow2 step when the shard is full.
        free = [j for j in range(n_slots - 1) if (tgt, j) not in used]
        if not free:
            n_slots = next_pow2(n_slots + 1)
            free = [j for j in range(n_slots - 1) if (tgt, j) not in used]
        rep_o[g], rep_s[g] = tgt, free[0]
        used.add((tgt, free[0]))
        loads[tgt] += sizes[g]
    return build_placement(pm.owner, pm.n_dev, min_slots=n_slots,
                           replica_owner=rep_o, replica_slot=rep_s)


def sharded_migrate_lists(mesh: Mesh, index, new_owner,
                          live_mask=None) -> tuple:
    """Move whole lists to a new owner assignment (e.g. from
    :func:`raft_tpu.parallel.routing.assign_lists` over observed probe
    loads — the Compactor's ``balance_placement`` pass calls this).
    Keeps the predecessor's slot-count shape class when the new
    assignment fits (no retrace of warmed routed traces).  Lists that
    were replicated STAY replicated: their second copy is re-placed
    against the new owners (on a live non-owner shard; a migration
    must not silently strip the fault-tolerance an operator paid
    for).  Returns ``(successor, n_migrated)``."""
    pm = index.placement_map
    expects(pm is not None, "list migration needs placement='list'")
    new_owner = np.asarray(new_owner, np.int32).reshape(-1)
    expects(new_owner.shape[0] == pm.n_lists,
            "owner assignment must cover all %s lists", pm.n_lists)
    n_migrated = int((new_owner != pm.owner).sum())
    new_pm = build_placement(new_owner, pm.n_dev, min_slots=pm.n_slots)
    replicated_lists = np.flatnonzero(pm.replica_owner >= 0)
    if replicated_lists.size:
        live = (np.ones(pm.n_dev, bool) if live_mask is None
                else np.asarray(live_mask).astype(bool))
        new_pm = _with_replicas(new_pm, replicated_lists,
                                _routed_sizes_h(index), live)
    return _rebuild_list_tensors(mesh, index, new_pm), n_migrated


def sharded_replicate_lists(mesh: Mesh, index, list_ids,
                            live_mask=None) -> "object":
    """Replicate hot lists onto a second shard for read scaling: the
    router splits each replicated list's probe load across the live
    copies, and a dead primary keeps serving through the replica
    (``ShardHealth``-aware selection — dead-shard coverage loss becomes
    a routing decision).  Placement policy: :func:`_with_replicas`.
    Returns the copy-on-write successor."""
    pm = index.placement_map
    expects(pm is not None, "list replication needs placement='list'")
    live = (np.ones(pm.n_dev, bool) if live_mask is None
            else np.asarray(live_mask).astype(bool))
    new_pm = _with_replicas(pm, list_ids, _routed_sizes_h(index), live)
    return _rebuild_list_tensors(mesh, index, new_pm)


SHARDED_SERIALIZATION_VERSION = 1


def _manifest_path(basename: str) -> str:
    return f"{basename}.manifest.npz"


def sharded_ivf_save(basename: str, index, *, retry=None,
                     file_io: FileIO = DEFAULT_IO) -> None:
    """Persist a sharded index CRASH-SAFELY: one ``<base>.model.npz``
    with the replicated model + metadata, ``<base>.shard{i}.npz`` per
    shard — the per-rank layout of the reference's MNMG serializers
    (detail/ivf_pq_serialize.cuh:38) — and a ``<base>.manifest.npz``
    written LAST.  Works for ShardedIvfFlat and ShardedIvfPq.

    Every file goes to disk via tmp+fsync+rename (util/atomic_io.py),
    and the manifest (file list + sizes + CRC32s + the index epoch) is
    the publish point: a kill at ANY byte of the save leaves either the
    complete previous snapshot or a manifest that fails verification —
    ``sharded_ivf_load`` can never half-load a torn file set.  ``retry``
    (a :class:`~raft_tpu.core.retry.RetryPolicy`) retries each file
    write on transient ``OSError``; ``file_io`` is the chaos seam
    (``ChaosMonkey.wrap_write`` / ``wrap_rename``).

    Multi-process meshes: each process writes its own shards; process 0
    writes the model and the manifest with CRCs for its LOCAL files and
    ``-1`` (unverifiable, existence-checked only) for remote shards —
    the single-process layout gets full CRC coverage."""
    from raft_tpu.core.retry import with_retry

    def write(path, payload):
        fn = lambda: atomic_savez(path, file_io, **payload)  # noqa: E731
        meta = with_retry(fn, retry) if retry is not None else fn()
        return meta

    is_pq = isinstance(index, ShardedIvfPq)
    model = dict(
        version=np.int64(SHARDED_SERIALIZATION_VERSION),
        kind=np.str_("pq" if is_pq else "flat"),
        metric=np.int64(index.metric.value),
        axis=np.str_(index.axis),
        n_shards=np.int64(index.indices.shape[0]),
        centers=np.asarray(index.centers),
    )
    if is_pq:
        model.update(
            codebook_kind=np.int64(index.codebook_kind.value),
            rotation_matrix=np.asarray(index.rotation_matrix),
            pq_centers=np.asarray(index.pq_centers),
            pq_bits=np.int64(index.pq_bits),
            pq_dim=np.int64(index.pq_dim),
        )
    if index.placement_map is not None:
        # placement="list": the host routing table is model state (the
        # shard files already hold the per-slot tensors). Optional keys
        # keep row-placement files byte-compatible with v1.
        pm = index.placement_map
        model.update(
            placement_owner=pm.owner, placement_slot=pm.slot,
            placement_replica_owner=pm.replica_owner,
            placement_replica_slot=pm.replica_slot,
            placement_n_slots=np.int64(pm.n_slots),
        )
    # The replicated model is identical on every process — only process 0
    # writes it, or N processes would race on the same file path.
    import os as _os

    written = {}                       # file name -> (crc, size)
    if jax.process_index() == 0:
        meta = write(f"{basename}.model.npz", model)
        written[_os.path.basename(f"{basename}.model.npz")] = \
            (meta["crc"], meta["size"])
    store = index.pq_codes if is_pq else index.data

    # Each process writes only the shards it can address: on a
    # multi-process (jax.distributed) mesh the global arrays are not
    # fully addressable and np.asarray(whole_array) would raise. Files
    # are keyed by the shard's global position along the leading
    # (device) axis, so the union of all processes' files is the
    # complete index and the single-process layout is unchanged.
    def by_start(arr):
        out = {}
        for sh in arr.addressable_shards:
            if sh.replica_id != 0:
                # On a multi-axis mesh the shard tensors are replicated
                # over the non-data axes; only one replica writes each
                # shard file (same-path race as the model.npz gate).
                continue
            start = sh.index[0].start or 0
            data = np.asarray(sh.data)
            # One leading-axis row per device under P(axis); a process
            # with several local devices contributes several entries.
            for off in range(data.shape[0]):
                out[start + off] = data[off]
        return out

    stores, ids, sizes = (by_start(a) for a in
                          (store, index.indices, index.list_sizes))
    # Tombstones are index content (see ivf_flat.save): written per
    # shard only when any slot is tombstoned, keeping mask-free files
    # byte-compatible with the v1 layout.
    dels = by_start(index.deleted) if index.n_deleted else None
    for s, payload in stores.items():
        extra = {} if dels is None else {"deleted": dels[s]}
        path = f"{basename}.shard{s}.npz"
        meta = write(path, dict(store=payload, indices=ids[s],
                                list_sizes=sizes[s], **extra))
        written[_os.path.basename(path)] = (meta["crc"], meta["size"])
    if jax.process_index() == 0:
        # Manifest LAST — the snapshot's commit point.  Every expected
        # file is listed (existence-checked at load); files written by
        # THIS process additionally carry their CRC32 + size.
        n_shards = int(index.indices.shape[0])
        names = [_os.path.basename(f"{basename}.model.npz")] + [
            _os.path.basename(f"{basename}.shard{s}.npz")
            for s in range(n_shards)]
        crcs = np.array([written.get(n, (-1, -1))[0] for n in names],
                        np.int64)
        lens = np.array([written.get(n, (-1, -1))[1] for n in names],
                        np.int64)
        write(_manifest_path(basename), dict(
            version=np.int64(SHARDED_SERIALIZATION_VERSION),
            n_shards=np.int64(n_shards),
            epoch=np.int64(index.epoch),
            files=np.array(names), crc=crcs, size=lens))


def verify_sharded_manifest(basename: str) -> Optional[int]:
    """Verify a snapshot's manifest against the files on disk; returns
    the manifest's saved epoch, or None when no manifest exists (a
    legacy pre-manifest save — loadable, but without torn-set
    detection beyond file existence).  Raises loudly on ANY mismatch
    (missing file, size drift, CRC drift): a torn snapshot must fail
    here, before a single tensor is placed — never half-load."""
    import os as _os

    mpath = _manifest_path(basename)
    if not _os.path.exists(mpath):
        return None
    with np.load(mpath) as m:
        version = int(m["version"])
        expects(version == SHARDED_SERIALIZATION_VERSION,
                f"sharded manifest version mismatch: {version}")
        names = [str(n) for n in m["files"]]
        crcs = m["crc"].astype(np.int64)
        lens = m["size"].astype(np.int64)
        epoch = int(m["epoch"])
    base_dir = _os.path.dirname(basename)
    from raft_tpu.util.atomic_io import crc32 as _crc32

    for name, crc, size in zip(names, crcs, lens):
        path = _os.path.join(base_dir, name)
        expects(_os.path.exists(path),
                "torn snapshot %r: manifest lists %r but the file is "
                "missing (kill mid-save?)", basename, name)
        if crc < 0:
            continue                   # written by another process
        data = open(path, "rb").read()
        expects(len(data) == int(size),
                "torn snapshot %r: %r is %s bytes, manifest says %s",
                basename, name, len(data), int(size))
        expects(_crc32(data) == int(crc),
                "torn snapshot %r: %r fails its manifest CRC — file "
                "content does not match what the save committed",
                basename, name)
    return epoch


def sharded_ivf_load(mesh: Mesh, basename: str, *, retry=None):
    """Load a sharded index saved by :func:`sharded_ivf_save`, re-placing
    the shard tensors over ``mesh`` (the shard count must match the mesh
    axis size, like rank-count-pinned MNMG deserialization).

    When the save left a manifest, the WHOLE file set is verified
    (existence + size + CRC32) before any tensor is placed — a torn
    snapshot raises here instead of half-loading.  Legacy manifest-less
    saves still load, with an up-front existence check for every shard
    file.  ``retry`` retries each file read on transient ``OSError``."""
    from raft_tpu.core.retry import with_retry

    def load_npz(path):
        fn = lambda: np.load(path)  # noqa: E731
        return with_retry(fn, retry) if retry is not None else fn()

    verify_sharded_manifest(basename)
    with load_npz(f"{basename}.model.npz") as m:
        version = int(m["version"])
        expects(version == SHARDED_SERIALIZATION_VERSION,
                f"sharded serialization version mismatch: {version}")
        kind = str(m["kind"])
        axis = str(m["axis"])
        n_shards = int(m["n_shards"])
        expects(mesh.shape[axis] == n_shards,
                f"index has {n_shards} shards but mesh[{axis!r}] = "
                f"{mesh.shape[axis]}")
        model = {k: m[k] for k in m.files}
    # Legacy manifest-less saves: fail fast on a missing shard file up
    # front instead of deep inside the placement callback.
    import os as _os
    for s in range(n_shards):
        expects(_os.path.exists(f"{basename}.shard{s}.npz"),
                "sharded snapshot %r is missing shard file %d/%d "
                "(torn save?)", basename, s, n_shards)
    sharding = NamedSharding(mesh, P(axis))
    with load_npz(f"{basename}.shard0.npz") as z0:
        keys = ["store", "indices", "list_sizes"]
        if "deleted" in z0.files:
            keys.append("deleted")
        shapes = {k: (z0[k].shape, z0[k].dtype) for k in keys}
    # int64 ids require x64 — without the guard the device placement
    # silently truncates (same contract as ivf_flat.load / ivf_pq.load).
    validate_idx_dtype(shapes["indices"][1])

    # Each process materializes only the shards addressable on its own
    # devices (the callback receives the global index of one shard) —
    # the multi-process-safe inverse of sharded_ivf_save. Shard files
    # are read once each and closed (all three keys per open).
    shard_cache: dict = {}

    def shard_arrays(s: int):
        if s not in shard_cache:
            with load_npz(f"{basename}.shard{s}.npz") as z:
                shard_cache[s] = {k: z[k] for k in keys}
        return shard_cache[s]

    def placed(key):
        shape, dtype = shapes[key]

        def cb(index):
            rows = range(*index[0].indices(n_shards))
            parts = []
            for s in rows:
                a = shard_arrays(s)[key]
                # Every shard must match shard0's dtype — an astype here
                # would silently truncate e.g. int64 ids from a mixed
                # re-save down to shard0's int32 (the exact corruption
                # validate_idx_dtype guards against).
                expects(a.dtype == dtype,
                        f"shard {s} {key} dtype {a.dtype} != shard0's "
                        f"{dtype}")
                parts.append(a)
            return np.stack(parts)

        return jax.make_array_from_callback((n_shards,) + shape,
                                            sharding, cb)

    store = placed("store")
    ids = placed("indices")
    sizes = placed("list_sizes")
    centers = jnp.asarray(model["centers"])
    pm = None
    if "placement_owner" in model:
        pm = build_placement(
            model["placement_owner"], n_shards,
            min_slots=int(model["placement_n_slots"]),
            replica_owner=model["placement_replica_owner"],
            replica_slot=model["placement_replica_slot"])
        # Slots are re-dealt deterministically (ascending list id per
        # owner — every placement producer uses the same deal); verify
        # against the saved slots so a drifted deal can never silently
        # route probes into the wrong local slot.
        expects(bool(np.array_equal(pm.slot, model["placement_slot"])),
                "saved placement slots do not match the deterministic "
                "re-deal — file corrupt or writer/reader version skew")
    deleted, n_del = None, 0
    if "deleted" in keys:
        deleted = placed("deleted")
        # Global tombstone count summed on host per shard file (every
        # process can read the shared files; a jnp.sum over the placed
        # global array would not be multi-process addressable).  For a
        # replicated list placement, count PRIMARY slots only — the
        # replica copy carries the same tombstones again, and the
        # convention everywhere else (delete / migrate / size) is one
        # logical deletion per row.
        if pm is not None:
            for g in range(pm.n_lists):
                n_del += int(shard_arrays(
                    int(pm.owner[g]))["deleted"][pm.slot[g]].sum())
        else:
            for s in range(n_shards):
                n_del += int(shard_arrays(s)["deleted"].sum())
    shard_cache.clear()
    if kind == "pq":
        return ShardedIvfPq(
            metric=DistanceType(int(model["metric"])),
            codebook_kind=_pq.CodebookGen(int(model["codebook_kind"])),
            centers=centers,
            rotation_matrix=jnp.asarray(model["rotation_matrix"]),
            pq_centers=jnp.asarray(model["pq_centers"]),
            pq_codes=store, indices=ids, list_sizes=sizes,
            pq_bits=int(model["pq_bits"]), pq_dim=int(model["pq_dim"]),
            axis=axis, deleted=deleted, n_deleted=n_del,
            placement_map=pm)
    return ShardedIvfFlat(
        metric=DistanceType(int(model["metric"])), centers=centers,
        data=store, indices=ids, list_sizes=sizes, axis=axis,
        deleted=deleted, n_deleted=n_del, placement_map=pm)
