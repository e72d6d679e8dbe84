"""Exact brute-force k-nearest-neighbor search.

Ref: cpp/include/raft/neighbors/brute_force.cuh (``knn``, ``fused_l2_knn``,
``knn_merge_parts``) with detail in
spatial/knn/detail/knn_brute_force.cuh:51 (``tiled_brute_force_knn`` —
memory-aware tile sizing :71, pairwise tile :143, per-tile select_k
:176,216) and :254 (``brute_force_knn_impl`` — metric dispatch, multi-part
databases round-robined over the stream pool, merged with
``knn_merge_parts``).

TPU-native re-design. The three reference paths (fused-L2 kernel for small
dims, haversine kernel, generic tiled pairwise+select_k) become one shape:
a ``lax.scan`` over database tiles that computes the distance tile on the
MXU and folds it into a running top-k carry (concatenate + ``lax.top_k``).
The fused-L2 specialization falls out naturally — the gram tile + norms
epilogue is fused by XLA with the top-k update, so the (n_queries, n_db)
matrix never materializes — which is exactly what fused_l2_knn.cuh does
with registers. Multi-part databases are searched per part and merged with
:func:`knn_merge_parts`; XLA overlaps the parts' compute the way the
reference round-robins pool streams.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu.comms.topk_merge import merge_parts
from raft_tpu.core.error import expects
from raft_tpu.core.mdarray import as_array, validate_idx_dtype
from raft_tpu.core.sentinels import PAD_ID, worst_value
from raft_tpu.distance.distance_types import (
    DistanceType, resolve_metric, value_form_select_min)
from raft_tpu.distance.pairwise import distance as pairwise_distance_fn
from raft_tpu.matrix.select_k import select_k
from raft_tpu.ops import pallas_interpret
from raft_tpu.util.pow2 import ceildiv
from raft_tpu.core.nvtx import traced

# Database-tile length for the scan: large enough to keep the MXU busy,
# small enough that the (n_queries, tile) distance block plus the (n_queries,
# tile + k) merge buffer stays VMEM/HBM friendly. The reference picks its
# tile from free device memory (knn_brute_force.cuh:71); on TPU a fixed
# power-of-two works with XLA's static shapes.
_TILE_DB = 8192

# The Pallas fused kernel (ops/fused_knn.py) wins over the XLA scan once the
# database is large enough that the per-tile top_k sort dominates (measured
# 1.2x at 10k rows, 3x at 100k-1M rows on v5e); tiny databases stay on the
# XLA path. Mirrors the reference's own fused-vs-tiled dispatch
# (brute_force_knn_impl, knn_brute_force.cuh:362: fused kernel only for
# small D, L2/IP metrics).
_PALLAS_MIN_DB = 8192


def _use_pallas(n: int, d: int, k: int) -> bool:
    from raft_tpu.ops.fused_knn import fused_knn_supported

    return (jax.default_backend() == "tpu" and n >= _PALLAS_MIN_DB
            and k <= 128 and fused_knn_supported(1, n, d, k))


def _as_float(x) -> jax.Array:
    x = as_array(x)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(jnp.float32)
    return x


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _tiled_knn_l2(queries, db, k: int, sqrt: bool, tile_db: int, inner_is_l2: bool):
    """Fused tiled L2/IP kNN: per-tile gram on the MXU + running top-k merge.

    Ref: tiled_brute_force_knn (knn_brute_force.cuh:51-233) and the fused
    small-dim kernel (fused_l2_knn.cuh). ``inner_is_l2=False`` searches by
    max inner product instead (select-max polarity).
    """
    m, d = queries.shape
    n = db.shape[0]
    qn = jnp.sum(queries * queries, axis=1) if inner_is_l2 else None

    nb = ceildiv(n, tile_db)
    pad = nb * tile_db - n
    if pad:
        dbp = jnp.concatenate([db, jnp.zeros((pad, d), db.dtype)], axis=0)
        valid = jnp.concatenate(
            [jnp.zeros((n,), jnp.bool_), jnp.ones((pad,), jnp.bool_)]
        )
    else:
        dbp = db
        valid = jnp.zeros((n,), jnp.bool_)
    tiles = dbp.reshape(nb, tile_db, d)
    bad = valid.reshape(nb, tile_db)

    worst = worst_value(select_min=inner_is_l2)

    def body(carry, tile):
        best_d, best_i, base = carry
        yt, badt = tile
        g = jnp.matmul(queries, yt.T, precision=lax.Precision.HIGHEST)
        if inner_is_l2:
            ynt = jnp.sum(yt * yt, axis=1)
            dt = jnp.maximum(qn[:, None] + ynt[None, :] - 2.0 * g, 0.0)
        else:
            dt = g
        dt = jnp.where(badt[None, :], worst, dt)
        ids = (base + jnp.arange(tile_db, dtype=jnp.int32))[None, :].repeat(m, 0)
        # Merge the tile into the running top-k (candidate concat + top_k —
        # the role of the warp-select merge in the reference kernel).
        cat_d = jnp.concatenate([best_d, dt], axis=1)
        cat_i = jnp.concatenate([best_i, ids], axis=1)
        keys = -cat_d if inner_is_l2 else cat_d
        _, pos = lax.top_k(keys, k)
        best_d = jnp.take_along_axis(cat_d, pos, axis=1)
        best_i = jnp.take_along_axis(cat_i, pos, axis=1)
        return (best_d, best_i, base + tile_db), None

    init = (
        jnp.full((m, k), worst, queries.dtype),
        jnp.full((m, k), PAD_ID, jnp.int32),
        jnp.int32(0),
    )
    (best_d, best_i, _), _ = lax.scan(body, init, (tiles, bad))
    if inner_is_l2 and sqrt:
        best_d = jnp.sqrt(best_d)
    return best_d, best_i


@traced
def tiled_brute_force_knn(
    queries,
    db,
    k: int,
    metric: DistanceType = DistanceType.L2Expanded,
    metric_arg: float = 2.0,
    tile_db: int = _TILE_DB,
    method: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """General tiled kNN for any metric (ref: tiled_brute_force_knn,
    knn_brute_force.cuh:51). ``method`` selects the L2/IP engine: "auto"
    (shape/backend heuristic), "xla" (scan + top_k) or "pallas" (fused
    Pallas kernel, ops/fused_knn.py). Returns ``(distances (m,k),
    indices (m,k))``."""
    queries = _as_float(queries)
    db = _as_float(db)
    expects(queries.shape[1] == db.shape[1], "dim mismatch")
    expects(method in ("auto", "xla", "pallas"),
            f"unknown method {method!r} (auto|xla|pallas)")
    k = min(k, db.shape[0])

    if metric in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
                  DistanceType.L2Unexpanded, DistanceType.L2SqrtUnexpanded,
                  DistanceType.InnerProduct):
        is_l2 = metric != DistanceType.InnerProduct
        sqrt = metric in (DistanceType.L2SqrtExpanded,
                          DistanceType.L2SqrtUnexpanded)
        use_pallas = (method == "pallas" or
                      (method == "auto"
                       and _use_pallas(db.shape[0], db.shape[1], k)))
        if use_pallas:
            from raft_tpu.ops.fused_knn import fused_knn

            return fused_knn(queries, db, k,
                             metric="l2" if is_l2 else "ip", sqrt=sqrt,
                             interpret=pallas_interpret())
        return _tiled_knn_l2(queries, db, k, sqrt,
                             min(tile_db, max(db.shape[0], 1)), is_l2)

    # Generic path: metric-tile + select_k per tile block, scanned.
    n = db.shape[0]
    if n <= tile_db:
        dmat = pairwise_distance_fn(queries, db, metric=metric, metric_arg=metric_arg)
        return select_k(dmat, k, select_min=value_form_select_min(metric))
    # Host loop over tiles with running merge (build-time friendly; the
    # per-tile pairwise itself is jit-compiled).
    best_d = best_i = None
    for start in range(0, n, tile_db):
        tile = db[start : start + tile_db]
        dt = pairwise_distance_fn(queries, tile, metric=metric, metric_arg=metric_arg)
        sd, si = select_k(dt, min(k, tile.shape[0]), select_min=value_form_select_min(metric))
        si = si + start
        if best_d is None:
            best_d, best_i = sd, si
        else:
            cat_d = jnp.concatenate([best_d, sd], axis=1)
            cat_i = jnp.concatenate([best_i, si], axis=1)
            best_d, pos = select_k(cat_d, k, select_min=value_form_select_min(metric))
            best_i = jnp.take_along_axis(cat_i, pos, axis=1)
    return best_d, best_i


@traced
def knn_merge_parts(
    in_keys,
    in_values,
    n_samples: Optional[int] = None,
    select_min: bool = True,
    translations: Optional[Sequence[int]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Merge per-part kNN results into a global top-k.

    Ref: raft::neighbors::brute_force::knn_merge_parts
    (neighbors/brute_force.cuh:80, detail/knn_merge_parts.cuh warp-select
    merge). ``in_keys``/``in_values`` are (n_parts, n_queries, k);
    ``translations`` offsets each part's local ids into the global id space.

    Returns ``(keys (n_queries, k), values (n_queries, k))``.

    The merge runs the same pairwise-merge core as the multi-device
    merge collectives (comms/topk_merge.py ``merge_parts``), with ties
    keyed by concatenated position so the result matches the historical
    concat+select_k output bit-for-bit.
    """
    keys = as_array(in_keys)
    vals = as_array(in_values)
    return merge_parts(keys, vals, select_min=select_min,
                       translations=translations)


@traced
def knn(
    index: Union[jax.Array, Sequence[jax.Array]],
    queries,
    k: int,
    metric: Union[str, DistanceType] = DistanceType.L2Expanded,
    metric_arg: float = 2.0,
    global_id_offset: int = 0,
    handle=None,
    method: str = "auto",
    idx_dtype=jnp.int32,
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN over one or several database parts.

    Ref: raft::neighbors::brute_force::knn (neighbors/brute_force.cuh;
    detail brute_force_knn_impl knn_brute_force.cuh:254) and pylibraft
    ``brute_force.knn`` (neighbors/brute_force.pyx). Multi-part indexes are
    searched independently and merged (the reference round-robins parts over
    pool streams; XLA overlaps them through async dispatch).

    ``idx_dtype`` selects the neighbor-id dtype: int32 (default, like the
    reference's internal uint32 kernels) or int64 (the reference runtime
    surface, brute_force_knn_int64_t_float.cu — requires jax_enable_x64).
    Per-part positions stay int32 internally; the widening happens before
    global id offsets are applied, so multi-part id spaces past 2³¹ rows
    are representable.

    Returns ``(distances (n_queries, k), indices (n_queries, k))``.
    """
    metric = resolve_metric(metric)
    idx_dtype = validate_idx_dtype(idx_dtype)
    parts: List[jax.Array]
    if isinstance(index, (list, tuple)):
        parts = [as_array(p) for p in index]
    else:
        parts = [as_array(index)]
    expects(len(parts) >= 1, "index must contain at least one part")

    if len(parts) == 1:
        d, i = tiled_brute_force_knn(queries, parts[0], k, metric, metric_arg,
                                     method=method)
        i = i.astype(idx_dtype)
        if global_id_offset:
            i = i + jnp.asarray(global_id_offset, idx_dtype)
        return d, i

    all_d, all_i, offsets = [], [], []
    base = global_id_offset
    for p in parts:
        pd, pi = tiled_brute_force_knn(queries, p, min(k, p.shape[0]), metric,
                                       metric_arg, method=method)
        pi = pi.astype(idx_dtype)
        kk = pd.shape[1]
        if kk < k:  # pad small parts so merge shapes agree
            worst = worst_value(value_form_select_min(metric))
            pd = jnp.concatenate(
                [pd, jnp.full((pd.shape[0], k - kk), worst, pd.dtype)], axis=1)
            # translations re-offset merged ids by ``base``; pre-subtract
            # it so pad slots come out as the shared PAD_ID.
            pi = jnp.concatenate(
                [pi, jnp.full((pi.shape[0], k - kk), PAD_ID - base,
                              pi.dtype)], axis=1)
        all_d.append(pd)
        all_i.append(pi)
        offsets.append(base)
        base += p.shape[0]
    keys = jnp.stack(all_d)
    vals = jnp.stack(all_i)
    return knn_merge_parts(keys, vals, select_min=value_form_select_min(metric),
                           translations=offsets)


@traced
def fused_l2_knn(index, queries, k: int, sqrt: bool = False):
    """L2-only fused kNN (ref: raft::neighbors::brute_force::fused_l2_knn,
    neighbors/brute_force.cuh → fused_l2_knn.cuh)."""
    metric = DistanceType.L2SqrtExpanded if sqrt else DistanceType.L2Expanded
    return knn(index, queries, k, metric=metric)
