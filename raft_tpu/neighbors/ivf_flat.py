"""IVF-Flat: inverted-file index over raw vectors.

Ref: cpp/include/raft/neighbors/ivf_flat.cuh with types/params at
neighbors/ivf_flat_types.hpp:44-78 (``index_params{n_lists=1024,
kmeans_n_iters=20, kmeans_trainset_fraction=0.5, adaptive_centers,
conservative_memory_allocation}``, ``search_params{n_probes=20}``), build at
detail/ivf_flat_build.cuh:299 (subsample → kmeans_balanced::fit → extend
fills interleaved lists) and search at detail/ivf_flat_search.cuh
(coarse top-n_probes over centers, ``interleaved_scan_kernel``:669, select_k
merge).

TPU-native re-design. The reference stores each list as pointer-chased
interleaved groups of 32 rows (``kIndexGroupSize``, ivf_flat_types.hpp:42)
— a SIMT memory-coalescing idiom. Under XLA's static-shape model the lists
become one dense **capacity-padded tensor** ``data (n_lists, cap, dim)``
with a per-slot validity mask derived from ``list_sizes`` — balanced k-means
(the same trainer the reference uses) keeps the padding overhead small. The
probe scan is a ``lax.scan`` over probe ranks: each step gathers one probed
list per query, scores it on the MXU (einsum + norms epilogue), and folds a
running top-k — the role of ``interleaved_scan_kernel`` + warp-select.

``extend`` re-packs with capacity doubling, mirroring the amortized
reallocation of ``conservative_memory_allocation=false``
(ivf_flat_types.hpp:65-73).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu.core.error import expects
from raft_tpu.core.logger import logger
from raft_tpu.core.mdarray import as_array, validate_idx_dtype
from raft_tpu.cluster.kmeans_types import KMeansBalancedParams
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.distance.distance_types import DistanceType, is_min_close, resolve_metric
from raft_tpu.matrix.select_k import select_k
from raft_tpu.ops import pallas_interpret
from raft_tpu.random.rng_state import RngState
from raft_tpu.util.pow2 import ceildiv, next_pow2, round_up_safe
from raft_tpu.core.nvtx import traced


@dataclass
class IndexParams:
    """Ref: ivf_flat::index_params (neighbors/ivf_flat_types.hpp:44-78);
    field names and defaults preserved."""

    n_lists: int = 1024
    metric: DistanceType = DistanceType.L2Expanded
    metric_arg: float = 2.0
    add_data_on_build: bool = True
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    adaptive_centers: bool = False
    conservative_memory_allocation: bool = False
    # Neighbor-id dtype: int32 (default) or int64 (the reference's IdxT
    # runtime surface; requires jax_enable_x64). TPU extension knob — the
    # reference fixes IdxT per instantiation unit instead.
    idx_dtype: object = jnp.int32


@dataclass
class SearchParams:
    """Ref: ivf_flat::search_params (neighbors/ivf_flat_types.hpp:74-78).

    TPU extension fields (not in the reference struct, which tunes the
    analogous decomposition inside the kernel launch instead):

    ``engine``: "auto" | "scan" | "bucketed". "scan" is the per-query
    gather path (exact probe coverage). "bucketed" inverts the probe map
    into per-list MXU work (the query-grouping of calc_chunk_indices,
    detail/ivf_pq_search.cuh:267, turned into dense tiles). Since round
    4 it resolves to the PACKED-CELLS tier whenever k ≤ 256 (the
    two-lane-group k-pass queue — the reference warpsort's
    kMaxCapacity, select_warpsort.cuh:100) and one list's data block
    fits the VMEM budget AND ``bucket_cap`` is 0: fixed-width query
    cells (hot lists own several), no (query, probe) pair ever dropped,
    no capacity measurement, fully traceable under jit. An explicit
    ``bucket_cap`` keeps the legacy bucket-table engine below (its
    documented capacity/drop semantics; a well-packed hand-tuned table
    can win at uniform probe loads). "auto" picks cells on TPU when the
    probe load q·n_probes/n_lists is high enough to fill tiles.

    Only when the cells tier is unavailable (k > 256 or oversized list
    blocks) does "bucketed" fall back to the legacy bucket-table engine,
    where ``bucket_cap`` applies: a list probed by more than
    ``bucket_cap`` queries drops the excess pairs best-centroid-rank-
    kept per list; "auto" then sizes the capacity from the measured
    best-half-rank contention (one jitted scalar device read), bounded
    at 8× the mean probe load and floored at the rank-0 contention (a
    query's single best probe never drops), falling back to "scan" when
    the capacity would exceed the bucket memory budget.

    ``bucket_cap``: legacy-tier per-list query-slot capacity; 0 = the
    measured sizing above (memoized on the index per query-batch shape;
    ``extend`` invalidates the memo). Under an outer ``jit`` the
    legacy-tier measurement is impossible: auto falls back to "scan" and
    explicit "bucketed" requires an explicit bucket_cap there.
    """

    n_probes: int = 20
    engine: str = "auto"
    bucket_cap: int = 0


@dataclass
class Index:
    """Trained IVF-Flat index (ref: ivf_flat::index,
    neighbors/ivf_flat_types.hpp:86-230).

    data/indices are capacity-padded: slot j of list l is valid iff
    ``j < list_sizes[l]``.
    """

    metric: DistanceType
    centers: jax.Array          # (n_lists, dim)
    data: jax.Array             # (n_lists, cap, dim)
    indices: jax.Array          # (n_lists, cap) int32/int64 global row ids
    list_sizes: jax.Array       # (n_lists,) int32
    adaptive_centers: bool = False
    conservative_memory_allocation: bool = False
    # Monotonic content version, bumped by every mutation (extend /
    # delete / upsert; compaction publishes a successor index at
    # epoch + 1) — the serving layer's cache-invalidation key
    # (serve/cache.py), same contract as the sharded indexes
    # (parallel/ivf.py). Process-local: not serialized (a reload
    # re-validates caches by construction).
    epoch: int = 0
    # Tombstone mask (raft_tpu/lifecycle): slot j of list l is deleted
    # iff ``deleted[l, j]``. None (the common case) traces the
    # pre-lifecycle mask-free program; once set, the mask is a TRACED
    # OPERAND of every scan engine — deleting more rows re-uses the
    # compiled masked trace (the live_mask contract). Serialized only
    # when any slot is tombstoned.
    deleted: Optional[jax.Array] = None   # (n_lists, cap) bool
    # Host-side count of tombstoned slots (drives compaction triggers).
    n_deleted: int = 0
    # Next auto-assigned id (max(existing id) + 1), maintained by every
    # extend; None = derive lazily from the stored ids (loaded index).
    # ``index.size`` is NOT a valid id source: it collides after an
    # explicit-id extend and after delete shrinks the live count.
    _next_id: Optional[int] = None

    def __post_init__(self):
        # Cross-tensor shape consistency at construction: a corrupted or
        # hand-assembled index fails HERE, not with silently wrong
        # neighbors at search time (shapes are static even under jit).
        expects(self.data.shape[0] == self.indices.shape[0]
                == self.list_sizes.shape[0] == self.centers.shape[0],
                "n_lists mismatch across index tensors")
        expects(self.data.shape[1] == self.indices.shape[1],
                "list capacity mismatch between data and indices")
        expects(self.data.shape[2] == self.centers.shape[1],
                "dim mismatch between data and centers")

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def capacity(self) -> int:
        """Static total slot capacity (n_lists * per-list cap)."""
        return self.indices.shape[0] * self.indices.shape[1]

    @property
    def size(self) -> int:
        return int(jnp.sum(self.list_sizes))

    @property
    def live_size(self) -> int:
        """Rows that answer queries: ``size`` minus tombstoned slots."""
        return self.size - self.n_deleted

    def reset_search_cache(self) -> None:
        """Drop the memoized auto-engine bucket capacity (measured from
        the first query batch of each shape — see SearchParams). Call
        when the query distribution shifts within a batch shape, e.g. a
        later batch concentrating much harder on a few centroids than
        the batch the capacity was measured on."""
        self.__dict__.pop("_auto_cap_cache", None)


def _as_float(x) -> jax.Array:
    x = as_array(x)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(jnp.float32)
    return x


def _pack_lists(
    X: jax.Array, labels: jax.Array, ids: jax.Array, n_lists: int,
    min_cap: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Scatter rows into (n_lists, cap, dim) padded storage.

    The role of ``build_index_kernel`` (detail/ivf_flat_build.cuh) without
    the interleaved-group layout: rows are sorted by list, positions within
    each list computed from offset prefix sums, then scattered.
    """
    labels = labels.astype(jnp.int32)
    counts = jnp.bincount(labels, length=n_lists)
    cap = int(max(int(jnp.max(counts)), 1, min_cap))
    # Build-time one-shot: the bulk-fill caller passes a next_pow2
    # min_cap so steady-state capacity classes stay bucketed; only
    # conservative_memory_allocation opts into exact-fit shapes (and
    # pays a rebuild-grade compile when capacity moves, documented).
    data, idx = _fill_lists(X, labels, ids, counts, n_lists=n_lists,
                            cap=cap)
    return data, idx, counts.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_lists", "cap"))
def _fill_lists(X, labels, ids, counts, *, n_lists: int, cap: int):
    """The padded (n_lists, cap, dim) storage of :func:`_pack_lists` as
    one program: the scatter fills the zero storage in place (eager ops
    would hold the zeros and the filled copy at once) on the device that
    holds ``X``."""
    n, d = X.shape
    order = jnp.argsort(labels, stable=True)
    sorted_labels = labels[order]
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)])[:-1]
    pos = (jnp.arange(n, dtype=jnp.int32)
           - offsets[sorted_labels].astype(jnp.int32))
    data = jnp.zeros((n_lists, cap, d), X.dtype)
    idx = jnp.full((n_lists, cap), -1, ids.dtype)
    return (data.at[sorted_labels, pos].set(X[order]),
            idx.at[sorted_labels, pos].set(ids[order]))


def _train_centers(params, Xf: jax.Array) -> jax.Array:
    """Subsample ``kmeans_trainset_fraction`` of the rows and train the
    coarse centers (ref: the trainset subsample + kmeans_balanced::fit step
    of detail/ivf_flat_build.cuh:299). Shared by the single-device and
    sharded builds so both train the identical coarse model."""
    n = Xf.shape[0]
    frac = min(max(params.kmeans_trainset_fraction, 0.0), 1.0)
    n_train = max(params.n_lists, int(n * frac)) if frac < 1.0 else n
    stride = max(1, n // n_train)
    trainset = Xf[::stride][:n_train]
    kb = KMeansBalancedParams(
        n_iters=params.kmeans_n_iters,
        metric=params.metric,
        rng_state=RngState(seed=0),
    )
    return kmeans_balanced.fit(kb, trainset, params.n_lists)


def _coarse_probe(Q: jax.Array, centers: jax.Array, n_probes: int,
                  inner_is_l2: bool) -> jax.Array:
    """Top-n_probes coarse quantizer (ref: the select_clusters-analog in
    detail/ivf_flat_search.cuh) — shared by search and the sharded path so
    both probe the identical candidate set."""
    if inner_is_l2:
        cn = jnp.sum(centers * centers, axis=1)
        cd = (jnp.sum(Q * Q, axis=1)[:, None] + cn[None, :]
              - 2.0 * jnp.matmul(Q, centers.T,
                                 precision=lax.Precision.HIGHEST))
        _, probe_ids = select_k(cd, n_probes, select_min=True)
    else:
        cd = jnp.matmul(Q, centers.T, precision=lax.Precision.HIGHEST)
        _, probe_ids = select_k(cd, n_probes, select_min=False)
    return probe_ids


@traced
def build(params: IndexParams, dataset, handle=None) -> Index:
    """Train centers (balanced k-means on a subsample) and fill the lists.

    Ref: ivf_flat::build (neighbors/ivf_flat.cuh →
    detail/ivf_flat_build.cuh:299): subsample ``kmeans_trainset_fraction`` of
    the rows, ``kmeans_balanced::fit``, then ``extend`` with the full set.
    """
    X = as_array(dataset)
    expects(X.ndim == 2, "dataset must be (n_rows, dim)")
    n = X.shape[0]
    expects(n >= params.n_lists, "need at least n_lists rows")
    Xf = _as_float(X)

    centers = _train_centers(params, Xf)

    idx_dtype = validate_idx_dtype(params.idx_dtype)
    index = Index(
        metric=params.metric,
        centers=centers,
        data=jnp.zeros((params.n_lists, 1, X.shape[1]), X.dtype),
        indices=jnp.full((params.n_lists, 1), -1, idx_dtype),
        list_sizes=jnp.zeros((params.n_lists,), jnp.int32),
        adaptive_centers=params.adaptive_centers,
        conservative_memory_allocation=params.conservative_memory_allocation,
    )
    if params.add_data_on_build:
        index = extend(index, X, jnp.arange(n, dtype=idx_dtype))
    return index


def _scatter_append_core(store, ids, list_sizes, new_rows, new_ids, labels):
    """Traceable core of the O(n_new) append: sort the *new* rows by list,
    in-list position = ``list_sizes[label] + rank``, then one scatter.
    Also used vmapped over the shard axis by parallel/ivf.py."""
    n_lists = store.shape[0]
    n_new = new_rows.shape[0]
    labels = labels.astype(jnp.int32)
    counts = jnp.bincount(labels, length=n_lists)
    order = jnp.argsort(labels, stable=True)
    sl = labels[order]
    offsets = jnp.cumsum(counts) - counts
    rank = jnp.arange(n_new, dtype=jnp.int32) - offsets[sl].astype(jnp.int32)
    pos = list_sizes[sl] + rank
    store = store.at[sl, pos].set(new_rows[order].astype(store.dtype))
    ids = ids.at[sl, pos].set(new_ids[order])
    return store, ids, list_sizes + counts.astype(jnp.int32), counts


def _scatter_append_impl(store, ids, list_sizes, new_rows, new_ids, labels,
                         adaptive: bool = False, centers=None):
    """O(n_new) append into capacity-padded lists.

    Ref: the per-list append of ivf_flat::extend
    (detail/ivf_flat_build.cuh:159) — new rows land at each list's current
    fill offset. Under :data:`_scatter_append` ``store``/``ids`` are
    donated so XLA aliases the output onto the existing buffers — no
    full-index gather or copy appears anywhere in the program;
    :data:`_scatter_append_cow` is the copy-on-write twin for mutations
    racing live readers (a donated buffer a dispatched search still
    holds raises "buffer has been deleted or donated"). Shared by
    ivf_flat (payload = vectors) and ivf_pq (payload = packed code rows).
    """
    store, ids, new_sizes, counts = _scatter_append_core(
        store, ids, list_sizes, new_rows, new_ids, labels)
    labels = labels.astype(jnp.int32)
    if adaptive:
        # Running-mean drift (ivf_flat_types.hpp:53-58): with the center
        # equal to the mean of its members before the append, the
        # size-weighted update keeps it the mean after — no pass over the
        # existing rows needed.
        sums = jax.ops.segment_sum(new_rows.astype(centers.dtype), labels,
                                   num_segments=store.shape[0])
        tot = jnp.maximum(new_sizes.astype(centers.dtype), 1.0)
        upd = (centers * list_sizes.astype(centers.dtype)[:, None] + sums) \
            / tot[:, None]
        centers = jnp.where((counts > 0)[:, None], upd, centers)
    return store, ids, new_sizes, centers


_scatter_append = functools.partial(
    jax.jit, donate_argnums=(0, 1), static_argnums=(6,))(
        _scatter_append_impl)
_scatter_append_cow = functools.partial(
    jax.jit, static_argnums=(6,))(_scatter_append_impl)


def _grown_cap(list_sizes, counts, cap: int, conservative: bool):
    """Post-append capacity: unchanged when everything fits, else the
    next power of two (amortized doubling, ivf_flat_types.hpp:65-73) or
    the exact requirement under conservative allocation. One scalar
    device→host read."""
    need = int(jnp.max(list_sizes + counts))
    if need <= cap:
        return cap
    return max(need, 1) if conservative else next_pow2(need)


def _append_in_place(store, ids, list_sizes, payload, new_ids, labels,
                     conservative: bool, adaptive: bool = False,
                     centers=None, donate: bool = True):
    """Grow-if-needed + scatter-append, shared by ivf_flat (payload
    = vectors) and ivf_pq (payload = packed code rows). Returns
    ``(store, ids, sizes, centers)``. ``donate=False`` selects the
    copy-on-write scatter (see _scatter_append_impl)."""
    counts = jnp.bincount(labels.astype(jnp.int32), length=store.shape[0])
    cap = store.shape[1]
    new_cap = _grown_cap(list_sizes, counts, cap, conservative)
    if new_cap > cap:
        # Amortized growth: pad in place — existing rows keep their slots.
        store = jnp.pad(store, ((0, 0), (0, new_cap - cap), (0, 0)))
        ids = jnp.pad(ids, ((0, 0), (0, new_cap - cap)), constant_values=-1)
    scatter = _scatter_append if donate else _scatter_append_cow
    return scatter(store, ids, list_sizes,
                   payload.astype(store.dtype), new_ids, labels,
                   adaptive, centers)


def _auto_id_base(index) -> int:
    """First free auto-assigned id: ``max(existing id) + 1``, tracked on
    the index (``_next_id``) and derived from the stored ids when the
    tracker is unset (a loaded index). ``index.size`` is NOT a valid
    base — it collides with user-supplied ids after an explicit-id
    extend, and with live ids once delete shrinks the live count.
    Shared by the single-host and sharded extends."""
    nid = getattr(index, "_next_id", None)
    if nid is not None:
        return nid
    # Padding/invalid slots carry -1, real ids are >= 0, so the global
    # max is the largest live-or-tombstoned id; empty index -> -1 -> 0.
    return int(jnp.max(index.indices)) + 1


def _track_next_id(index, new_indices, default_base=None,
                   n_new: int = 0) -> None:
    """Advance the auto-id tracker after an extend: default-numbered
    appends advance it arithmetically (no device read); explicit ids
    advance it past their max (one scalar readback, like the capacity
    check)."""
    cur = _auto_id_base(index)
    if default_base is not None:
        index._next_id = max(cur, default_base + n_new)
    else:
        index._next_id = max(cur, int(jnp.max(new_indices)) + 1)


def _pad_deleted(deleted, new_cap: int):
    """Grow the tombstone mask alongside a capacity-grown list tensor:
    fresh slots are live by construction."""
    if deleted is None or deleted.shape[-1] == new_cap:
        return deleted
    pad = ((0, 0),) * (deleted.ndim - 1) + ((0, new_cap - deleted.shape[-1]),)
    return jnp.pad(deleted, pad)


@traced
def extend(index: Index, new_vectors, new_indices=None, *,
           donate: bool = True) -> Index:
    """Append vectors to the index, in place, at O(n_new) amortized cost.

    Ref: ivf_flat::extend (detail/ivf_flat_build.cuh:159; list growth
    policy ivf_flat_types.hpp:65-73). New rows scatter into each list's
    free slots (the storage buffers are donated to the scatter, so no
    copy of the existing rows is made); only when a list overflows its
    capacity does storage grow — by padding to the doubled capacity,
    which moves no existing row. The passed ``index`` is mutated and
    returned; arrays previously read off it (``index.data`` etc.) must
    be re-read after the call. ``donate=False`` keeps the old storage
    buffers valid (full copy-on-write scatter) — required when reader
    threads may hold a dispatched search against them (the serving
    facade passes it; docs/index_lifecycle.md). When
    ``adaptive_centers`` is set, centers drift to the running mean of
    their members (ivf_flat_types.hpp:53-58).

    Tombstoned slots are NOT reclaimed here — extend appends at each
    list's fill offset; reclamation is the compactor's job
    (raft_tpu/lifecycle/compact.py).
    """
    X = as_array(new_vectors)
    expects(X.ndim == 2 and X.shape[1] == index.dim, "dim mismatch")
    n_new = X.shape[0]
    if n_new == 0:
        return index
    default_base = None
    if new_indices is None:
        default_base = _auto_id_base(index)
        new_indices = jnp.arange(default_base, default_base + n_new,
                                 dtype=index.indices.dtype)
    else:
        new_indices = as_array(new_indices).astype(index.indices.dtype)

    labels = kmeans_balanced.predict(
        KMeansBalancedParams(metric=index.metric), index.centers, _as_float(X)
    )

    old_n = index.size
    if not old_n:
        # Bulk path (build-time fill of an empty index): one pack.
        min_cap = 0
        if not index.conservative_memory_allocation:
            counts = jnp.bincount(labels, length=index.n_lists)
            min_cap = next_pow2(int(jnp.max(counts)))
        data, ids, sizes = _pack_lists(X.astype(index.data.dtype), labels,
                                       new_indices, index.n_lists, min_cap)
        centers = index.centers
        if index.adaptive_centers:
            sums = jax.ops.segment_sum(_as_float(X), labels,
                                       num_segments=index.n_lists)
            cnt = jnp.maximum(sizes.astype(centers.dtype), 1.0)
            centers = jnp.where((sizes > 0)[:, None],
                                sums / cnt[:, None], centers)
        index.data, index.indices, index.list_sizes = data, ids, sizes
        index.centers = centers
        # Fresh fill: no tombstones — but an enable_tombstones
        # pre-attachment survives (as an all-live mask at the new
        # capacity), or the masked-trace warmup guarantee would
        # silently void on the first bulk extend.
        index.deleted = (None if index.deleted is None
                         else jnp.zeros(ids.shape, bool))
        index.n_deleted = 0
        _track_next_id(index, new_indices, default_base, n_new)
        index.epoch += 1      # serving caches must not outlive old contents
        index.reset_search_cache()
        return index

    data, ids, sizes, centers = _append_in_place(
        index.data, index.indices, index.list_sizes, X, new_indices,
        labels, index.conservative_memory_allocation,
        index.adaptive_centers,
        index.centers if index.adaptive_centers else None, donate=donate)
    index.data, index.indices, index.list_sizes = data, ids, sizes
    index.deleted = _pad_deleted(index.deleted, data.shape[1])
    if index.adaptive_centers:
        index.centers = centers
    _track_next_id(index, new_indices, default_base, n_new)
    index.epoch += 1          # serving caches must not outlive old contents
    index.reset_search_cache()  # occupancy changed
    return index


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _probe_scan(
    queries, data, data_sq_norms, indices, list_sizes, k: int, inner_is_l2: bool,
    sqrt: bool, probe_ids=None, deleted=None,
):
    """Scan probed lists, fold a running top-k.

    Ref: interleaved_scan_kernel (detail/ivf_flat_search.cuh:669) + the
    select_k merge (:944). One scan step handles probe-rank j for every
    query at once: gather list j's block, score on the MXU, merge.

    ``deleted`` is the optional per-slot tombstone mask
    (raft_tpu/lifecycle): tombstoned slots neutralize to the shared
    worst-value sentinel exactly like below-fill padding — a traced
    operand, so deleting more rows never retraces.
    """
    from raft_tpu.core.sentinels import worst_value

    q, d = queries.shape
    cap = data.shape[1]
    qn = jnp.sum(queries * queries, axis=1) if inner_is_l2 else None
    worst = worst_value(inner_is_l2)
    slot = jnp.arange(cap, dtype=jnp.int32)[None, :]

    def body(carry, probe_col):
        best_d, best_i = carry
        lists = probe_col                       # (q,) list id per query
        block = data[lists]                     # (q, cap, d)
        ids = indices[lists]                    # (q, cap)
        invalid = slot >= list_sizes[lists][:, None]
        if deleted is not None:
            invalid |= deleted[lists]
        g = jnp.einsum("qd,qcd->qc", queries, block,
                       precision=lax.Precision.HIGHEST)
        if inner_is_l2:
            dn = data_sq_norms[lists]           # (q, cap)
            dt = jnp.maximum(qn[:, None] + dn - 2.0 * g, 0.0)
        else:
            dt = g
        dt = jnp.where(invalid, worst, dt)
        cat_d = jnp.concatenate([best_d, dt], axis=1)
        cat_i = jnp.concatenate([best_i, ids], axis=1)
        keys = -cat_d if inner_is_l2 else cat_d
        _, pos = lax.top_k(keys, k)
        return (jnp.take_along_axis(cat_d, pos, axis=1),
                jnp.take_along_axis(cat_i, pos, axis=1)), None

    init = (jnp.full((q, k), worst, queries.dtype),
            jnp.full((q, k), -1, indices.dtype))
    (best_d, best_i), _ = lax.scan(body, init, probe_ids.T)
    if inner_is_l2 and sqrt:
        best_d = jnp.sqrt(best_d)
    return best_d, best_i


def _chunked_over_queries(fn, Q, probe_ids, per_q_bytes: int,
                          budget: int = 64 * 1024 * 1024):
    """Run ``fn(Q_chunk, probe_ids_chunk) -> (d, i)`` over query chunks
    sized so the per-chunk probe workspace stays under ``budget`` bytes —
    shared by both scan engines (their per-probe gather is
    O(q_chunk · per_q_bytes))."""
    nq = Q.shape[0]
    chunk = max(1, min(nq, budget // max(per_q_bytes, 1)))
    if nq <= chunk:
        return fn(Q, probe_ids)
    # Pad the ragged tail up to the shared chunk shape so every chunk hits
    # one XLA compilation (a distinct tail shape would compile a second
    # program); padded rows are sliced off after.
    pad = (-nq) % chunk
    if pad:
        Q = jnp.concatenate([Q, jnp.broadcast_to(Q[:1], (pad, Q.shape[1]))])
        probe_ids = jnp.concatenate(
            [probe_ids, jnp.broadcast_to(probe_ids[:1],
                                         (pad, probe_ids.shape[1]))])
    outs = [fn(Q[s:s + chunk], probe_ids[s:s + chunk])
            for s in range(0, Q.shape[0], chunk)]
    return (jnp.concatenate([o[0] for o in outs], axis=0)[:nq],
            jnp.concatenate([o[1] for o in outs], axis=0)[:nq])


# Per-engine-dispatch memory budget for the bucketed query-gather table
# (n_lists, bucket_cap, dim) f32 — beyond it, auto falls back to scan.
_BUCKET_TABLE_BYTES = 512 * 1024 * 1024


def _auto_cap_cache(index) -> dict:
    """Per-index memo for the auto-engine's measured bucket capacity
    (plain instance attribute — Index is not a pytree). Cleared by
    extend(), which changes list occupancy."""
    return index.__dict__.setdefault("_auto_cap_cache", {})


@functools.partial(jax.jit, static_argnums=(1,))
def _front_rank_contention(probe_ids, n_lists: int):
    """Per-list contention of (query, probe) pairs: returns
    ``(best_half_max, rank0_max)`` — the max count over lists of pairs
    whose centroid rank is in each query's best half, and of rank-0
    (best-probe) pairs alone. A bucket capacity ≥ best_half_max makes the
    bucketed engine drop only rank ≥ n_probes/2 probes; ≥ rank0_max is
    the hard floor below which a query could lose its single best probe
    (see SearchParams)."""
    half = max(1, probe_ids.shape[1] - probe_ids.shape[1] // 2)
    front = probe_ids[:, :half]
    return jnp.stack([
        jnp.max(jnp.bincount(front.reshape(-1), length=n_lists)),
        jnp.max(jnp.bincount(probe_ids[:, 0], length=n_lists)),
    ])


def _pick_engine(engine: str, n_queries: int, n_probes: int, n_lists: int,
                 k: int, bucket_cap: int, dim: int, probe_ids,
                 allow_bucketed: bool = True, cap_cache=None):
    """Resolve SearchParams.engine/"auto" and the bucket capacity — shared
    by ivf_flat.search and ivf_pq.search. Bucketed wins when the mean probe
    load per list fills MXU tiles; tiny loads leave the batched kernel
    mostly padding.

    Auto-sized bucket capacity is measured from the probe map (one jitted
    scalar device→host read): the capacity covers every pair whose centroid
    rank is in the query's best half — bounded at 8× the mean probe load
    under hot-list skew (floored at the rank-0 contention, so a query's
    single best probe never drops; between the floor and the best-half
    need, deeper-rank probes of hot lists may drop). If even the bounded
    capacity would blow the bucket-table memory budget, auto falls back
    to the exact scan engine instead of truncating hot lists. An explicit
    ``bucket_cap`` skips the measurement and accepts the documented drop
    behavior at that capacity.

    ``cap_cache`` (a dict owned by the Index) memoizes the measured
    capacity per (n_queries, n_probes) so a steady-state query loop pays
    the synchronizing scalar readback once, not per call — the role of the
    reference's per-index ``get_max_batch_size`` heuristic
    (detail/ivf_pq_search.cuh:1517). The memo assumes batches drawn from
    a stationary query distribution: the capacity is measured on the
    first batch of a shape (rounded up to a power of two, which absorbs
    ~2× contention drift), so a later same-shape batch that concentrates
    much harder on one centroid can overflow it and drop lower-ranked
    probes of the hot list. Callers whose distribution shifts should pass
    an explicit ``bucket_cap`` or call ``index.reset_search_cache()``;
    extend() invalidates the memo when occupancy changes.
    """
    expects(engine in ("auto", "scan", "bucketed"),
            f"unknown engine {engine!r} (auto|scan|bucketed)")
    cap_q = bucket_cap
    cap_clamp = max(8, _BUCKET_TABLE_BYTES // max(n_lists * dim * 4, 1))
    mean_load = max(1, (n_queries * n_probes) // n_lists)
    # Under an outer jit trace the probe map is abstract — no data-dependent
    # capacity can exist, so auto degrades to the exact scan engine and
    # jitted callers opt into bucketed with an explicit (static) bucket_cap.
    tracing = isinstance(probe_ids, jax.core.Tracer)

    def measured_cap():
        key = (n_queries, n_probes)
        if cap_cache is not None and key in cap_cache:
            return cap_cache[key]
        front, rank0 = (int(v) for v in
                        np.asarray(_front_rank_contention(probe_ids,
                                                          n_lists)))
        # Next power of two: batches with slightly different contention
        # land on the same compiled bucket shapes.
        cap = next_pow2(max(front, 4 * mean_load, 8))
        # Skew bound: a drop-free capacity beyond 8x the mean probe load
        # means a few hot lists would dictate everyone's bucket width (a
        # heavily clustered query batch measured 4-5x slower than the
        # tuned capacity at 1M for no recall gain). Cap there — but never
        # below the rank-0 contention: a query's single best probe must
        # never drop, whatever the skew. Beyond the bound, deeper-rank
        # probes of hot lists may drop (the documented overflow policy).
        bound = max(next_pow2(8 * mean_load), next_pow2(max(rank0, 1)))
        if cap > bound:
            logger.debug(
                "auto bucket cap %d exceeds skew bound %d (8x mean load, "
                "floored at rank-0 contention %d) - capping; deep-rank "
                "probes of contended lists may drop", cap, bound, rank0)
            cap = bound
        cap = min(n_queries, cap)
        if cap_cache is not None:
            cap_cache[key] = cap
        return cap

    if engine == "auto":
        load = n_queries * n_probes / n_lists
        if (allow_bucketed and jax.default_backend() == "tpu"
                and load >= 8 and k <= 128):
            if cap_q == 0:
                if tracing:
                    engine = "scan"
                else:
                    cap_q = measured_cap()
                    engine = "bucketed" if cap_q <= cap_clamp else "scan"
            else:
                engine = "bucketed"
        else:
            engine = "scan"
    elif engine == "bucketed" and cap_q == 0:
        expects(not tracing,
                "engine='bucketed' with bucket_cap=0 measures the probe "
                "map and cannot run under jit; pass an explicit bucket_cap")
        cap_q = measured_cap()
        if cap_q > cap_clamp:
            # The explicit-bucketed user insists on this engine; the
            # memory clamp can then cut below the rank-0 floor the
            # measured sizing guarantees — say so (auto falls back to
            # scan instead).
            logger.warning(
                "bucketed capacity clamped %d -> %d by the bucket-table "
                "memory budget; under heavy skew queries may lose "
                "best-rank probes (use engine='auto' or 'scan' for the "
                "drop-safe behavior)", cap_q, cap_clamp)
            cap_q = cap_clamp
    # Debug log at the dispatch decision, like the reference's
    # RAFT_LOG_DEBUG at perf-relevant branches (SURVEY.md §5).
    logger.debug(
        "ivf search dispatch: engine=%s q=%d probes=%d lists=%d k=%d cap_q=%d",
        engine, n_queries, n_probes, n_lists, k, cap_q)
    return engine, cap_q


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9, 10))
def _bucketed_probe_scan(
    queries, data, indices, list_sizes, probe_ids,
    k: int, inner_is_l2: bool, sqrt: bool, bucket_cap: int,
    interpret: bool = False, qsplit: bool = False, deleted=None,
):
    """Probe scan with the probe map inverted to per-list query buckets.

    Ref: the reference groups (query, probe) work by cluster via
    calc_chunk_indices (detail/ivf_pq_search.cuh:267) so each block scans
    one list for a chunk of queries. TPU re-tiling of the same idea: a
    stable sort of the flattened (probe_rank-major) pairs by list id yields,
    per list, the queries probing it ordered best-rank-first; the first
    ``bucket_cap`` fill a dense (n_lists, bucket_cap) bucket table. One
    batched Pallas fused-kNN launch then scores every bucket against its
    own list as a real (bucket_cap, d)×(d, cap) MXU matmul — instead of the
    scan path's per-query row gather + batched matvec — and each pair's
    top-k is routed back through the sort permutation for the final
    per-query merge (select_k over n_probes·k candidates).
    """
    from raft_tpu.ops.fused_knn import fused_batch_knn

    q, d = queries.shape
    n_lists, cap, _ = data.shape

    bucket, route = _invert_probe_map(probe_ids, n_lists, bucket_cap)

    # --- batched per-list kNN on the MXU
    qsel = jnp.maximum(bucket, 0)
    Qb = queries[qsel]                                         # (L, cap_q, d)
    invalid = jnp.arange(cap, dtype=jnp.int32)[None, :] >= list_sizes[:, None]
    if deleted is not None:
        invalid |= deleted           # tombstones mask exactly like padding
    bd_, bi_ = fused_batch_knn(
        Qb, data, invalid, k,
        metric="l2" if inner_is_l2 else "ip",
        bf16=data.dtype == jnp.bfloat16, qsplit=qsplit,
        interpret=interpret)
    gi = indices[jnp.arange(n_lists, dtype=jnp.int32)[:, None, None],
                 jnp.maximum(bi_, 0)]                          # (L, cap_q, kk)
    gi = jnp.where(bi_ < 0, -1, gi)

    worst = jnp.inf if inner_is_l2 else -jnp.inf
    cd, ci = _route_candidates(bd_, gi, route, q, probe_ids.shape[1],
                               bucket_cap, worst)
    # indices= payload: select_k then maps its k>n padding slots to the -1
    # sentinel instead of emitting out-of-range positions.
    best_d, best_i = select_k(cd, k, select_min=inner_is_l2, indices=ci)
    if inner_is_l2 and sqrt:
        best_d = jnp.sqrt(best_d)
    return best_d, best_i


def _invert_probe_map(probe_ids, n_lists: int, bucket_cap: int):
    """Invert (query → probed lists) into per-list query buckets,
    rank-major so bucket overflow drops the farthest-centroid probes
    first (the calc_chunk_indices re-tiling — see _bucketed_probe_scan).
    Returns ``(bucket (n_lists, cap_q), route)`` where ``route`` carries
    what :func:`_route_candidates` needs to send per-pair results back to
    their queries."""
    q, p = probe_ids.shape
    sorted_lists, sorted_query, pos, order = _sorted_probe_pairs(
        probe_ids, n_lists)
    keep = pos < bucket_cap
    slot = jnp.where(keep, sorted_lists * bucket_cap + pos,
                     n_lists * bucket_cap)                     # OOB → drop
    bucket = (jnp.full((n_lists * bucket_cap,), -1, jnp.int32)
              .at[slot].set(sorted_query, mode="drop")
              .reshape(n_lists, bucket_cap))
    return bucket, (sorted_lists, pos, keep, order)


def _sorted_probe_pairs(probe_ids, n_lists: int):
    """Shared prefix of both probe-map inverters: flatten (query, probe)
    pairs probe-rank-major, stable-sort by list id, and compute each
    pair's rank within its list. Returns ``(sorted_lists, sorted_query,
    pos, order)``."""
    q, p = probe_ids.shape
    flat_lists = probe_ids.T.reshape(-1)                       # (p·q,)
    flat_query = jnp.tile(jnp.arange(q, dtype=jnp.int32), p)
    order = jnp.argsort(flat_lists, stable=True)
    sorted_lists = flat_lists[order].astype(jnp.int32)
    sorted_query = flat_query[order]
    starts = jnp.searchsorted(sorted_lists,
                              jnp.arange(n_lists, dtype=jnp.int32))
    pos = jnp.arange(q * p, dtype=jnp.int32) - starts[sorted_lists]
    return sorted_lists, sorted_query, pos, order


def _invert_probe_map_cells(probe_ids, n_lists: int, qrows: int):
    """Invert (query → probed lists) into PACKED fixed-width query cells:
    list l owns ``ceil(load_l / qrows)`` consecutive cells of ``qrows``
    query slots each, so no (query, probe) pair is ever dropped and cell
    rows are ≥ half full on average — vs the per-list bucket table whose
    rows are mostly padding at skewed loads (the round-4 packing that
    recovers the ~85% wasted kernel rows). Returns ``(cell_list
    (max_cells,) int32 — the list each cell scans, -1 = unused, for the
    kernel's scalar-prefetched block index map; bucket (max_cells,
    qrows) query ids (-1 pad); route)`` where ``route`` feeds
    :func:`_route_candidates_cells`. max_cells is static:
    q·p // qrows + n_lists (one partial cell per list at worst)."""
    q, p = probe_ids.shape
    max_cells = (q * p) // qrows + n_lists
    sorted_lists, sorted_query, pos, order = _sorted_probe_pairs(
        probe_ids, n_lists)
    loads = jnp.bincount(sorted_lists, length=n_lists)
    n_cells = (loads + qrows - 1) // qrows
    base_cell = jnp.cumsum(n_cells) - n_cells                  # exclusive
    cell = base_cell[sorted_lists].astype(jnp.int32) + pos // qrows
    slot = pos % qrows
    bucket = (jnp.full((max_cells * qrows,), -1, jnp.int32)
              .at[cell * qrows + slot].set(sorted_query)
              .reshape(max_cells, qrows))
    cell_list = (jnp.full((max_cells,), -1, jnp.int32)
                 .at[cell].set(sorted_lists))
    return cell_list, bucket, (cell, slot, order)


def _route_candidates_cells(bd_, payload, route, q: int, p: int):
    """Send each packed cell slot's top-kk candidates back to its query:
    (q, p·kk) distance/payload candidate rows for the final select_k (the
    cells analog of :func:`_route_candidates`; nothing is dropped, so
    there is no keep mask)."""
    cell, slot, order = route
    kk = bd_.shape[2]
    cd = bd_[cell, slot]                                       # (p·q, kk)
    ci = payload[cell, slot]
    inv = jnp.argsort(order)
    cd = cd[inv].reshape(p, q, kk).transpose(1, 0, 2).reshape(q, p * kk)
    ci = ci[inv].reshape(p, q, kk).transpose(1, 0, 2).reshape(q, p * kk)
    return cd, ci


def _select_cells_ids(bd_, bi_, cell_list, indices, route, q: int,
                      p: int, k: int, scope: str):
    """The per-query merge of a cells kernel's output: route each cell
    slot's top-kk candidates back to its query, keep the best k, and only
    then look up their ids. Candidates travel as flat slot positions
    (``list · cap + slot``, row-major into ``indices``), so the id table
    is read for the q·k winners rather than for all max_cells·qrows·kk
    candidates. select_k ranks by value and breaks ties by position,
    never by the payload, so the answer is the one an id-carrying merge
    gives. ``bd_`` / ``bi_`` are the kernel's min-order distances and
    local slots (-1: no candidate); ``scope`` prefixes the stages' named
    scopes. Returns min-order ``(q, k)`` distances and ids (-1: none)."""
    n_lists, cap = indices.shape
    expects(n_lists * cap < 2 ** 31, "slot positions must fit in int32")
    with jax.named_scope(scope + ".route_select"):
        pos = jnp.where(bi_ < 0, -1,
                        jnp.maximum(cell_list, 0)[:, None, None] * cap + bi_)
        cd, cpos = _route_candidates_cells(bd_, pos, route, q, p)
        best_d, best_pos = select_k(cd, k, select_min=True, indices=cpos)
    with jax.named_scope(scope + ".id_gather"):
        # Two-axis indexing: a flat view of the table would make XLA
        # relayout all of it on the TPU for every batch.
        safe = jnp.maximum(best_pos, 0)
        best_i = jnp.where(best_pos < 0, -1, indices[safe // cap, safe % cap])
    return best_d, best_i


def _route_candidates(bd_, gi, route, q: int, p: int, bucket_cap: int,
                      worst):
    """Send each (list, slot) pair's top-kk candidates back to its query:
    (q, p·kk) distance/id candidate rows ready for the final select_k."""
    sorted_lists, pos, keep, order = route
    kk = bd_.shape[2]
    ppos = jnp.minimum(pos, bucket_cap - 1)
    cd = bd_[sorted_lists, ppos]                               # (p·q, kk)
    ci = gi[sorted_lists, ppos]
    cd = jnp.where(keep[:, None], cd, worst)
    ci = jnp.where(keep[:, None], ci, -1)
    inv = jnp.argsort(order)
    cd = cd[inv].reshape(p, q, kk).transpose(1, 0, 2).reshape(q, p * kk)
    ci = ci[inv].reshape(p, q, kk).transpose(1, 0, 2).reshape(q, p * kk)
    return cd, ci


# Query-slot width of one packed cell (see _invert_probe_map_cells), the
# VMEM the cells kernel may ask for (one list's block double-buffered
# plus its selection tiles, ops/fused_knn._cells_vmem_bytes), and the
# widest top-k queue the cells kernels carry (two 128-lane groups — the
# reference warpsort's kMaxCapacity=256, select_warpsort.cuh:100).
_CELL_QROWS = 64
_CELL_VMEM_BYTES = 96 * 1024 * 1024      # of the v5e's 128 MiB of VMEM
_CELLS_MAX_K = 256


def _cells_eligible(engine: str, k: int, bucket_cap: int, cap: int,
                    dim: int, n_queries: int, n_probes: int,
                    n_lists: int) -> bool:
    """Single definition of the packed-cells tier dispatch gate, shared
    by :func:`search` and the sharded search (parallel/ivf.py) so the
    two paths cannot drift: engine allows it, k within the cells queue,
    no explicit bucket_cap (which keeps the legacy bucket-table engine),
    the kernel's VMEM for one list within the budget (f32 accounting —
    the kernel's L2 epilogue upcasts bf16 storage), and for "auto" a TPU
    backend with enough probe load to fill the tiles."""
    if not (engine in ("auto", "bucketed") and k <= _CELLS_MAX_K
            and bucket_cap == 0):
        return False
    from raft_tpu.ops.fused_knn import _cells_vmem_bytes

    if _cells_vmem_bytes(_CELL_QROWS, round_up_safe(cap, 128),
                         round_up_safe(dim, 128)) > _CELL_VMEM_BYTES:
        return False
    if engine == "bucketed":
        return True
    load = n_queries * n_probes / max(n_lists, 1)
    return jax.default_backend() == "tpu" and load >= 8


def _cells_scan_probes(Q, probe_ids, data, indices, list_sizes, k: int,
                       inner_is_l2: bool, qrows: int, qsplit: bool,
                       interpret: bool = False, deleted=None):
    """Scan the GIVEN probed lists with the packed-cells Pallas engine:
    cells inversion, fused scan, routing and the per-query merge —
    returns best-first ``(q, k)`` candidates in true metric values (ip
    un-negated), no sqrt. The probe-chunkable core shared by
    :func:`_cells_search` and the sharded fused scan→merge pipeline
    (parallel/ivf.py feeds it one probe-column chunk at a time so each
    chunk's merge collective overlaps the next chunk's scan)."""
    from raft_tpu.ops.fused_knn import fused_cells_knn

    q = Q.shape[0]
    n_lists, cap, _ = data.shape
    # Each stage is a named scope, so a profiler trace names the device
    # operations it ran (metadata only: the program is the same).
    with jax.named_scope("ivf_flat.cells_invert"):
        cell_list, bucket, route = _invert_probe_map_cells(
            probe_ids, n_lists, qrows)
        Qc = Q[jnp.maximum(bucket, 0)]             # (max_cells, qrows, d)
        invalid = (jnp.arange(cap, dtype=jnp.int32)[None, :]
                   >= list_sizes[:, None])
        if deleted is not None:
            invalid |= deleted       # tombstones mask exactly like padding
    with jax.named_scope("ivf_flat.cells_scan"):
        bd_, bi_ = fused_cells_knn(cell_list, Qc, data, invalid, k,
                                   l2=inner_is_l2,
                                   bf16=data.dtype == jnp.bfloat16,
                                   qsplit=qsplit, interpret=interpret)
    best_d, best_i = _select_cells_ids(bd_, bi_, cell_list, indices, route,
                                       q, probe_ids.shape[1], k, "ivf_flat")
    # The kernel reports min-selection order (ip scores negated).
    if not inner_is_l2:
        best_d = -best_d
    return best_d, best_i


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9, 10, 11))
def _cells_search(Q, centers, data, indices, list_sizes, n_probes: int,
                  k: int, inner_is_l2: bool, sqrt: bool, qrows: int,
                  qsplit: bool, interpret: bool = False, deleted=None):
    """IVF-Flat search over packed query cells as ONE jitted program —
    coarse probe, cells inversion, fused Pallas scan, routing and the
    final merge (the round-4 engine treatment applied to IVF-Flat: no
    bucket-capacity measurement, no probe drops, no eager glue)."""
    with jax.named_scope("ivf_flat.coarse_probe"):
        probe_ids = _coarse_probe(Q, centers, n_probes, inner_is_l2)
    best_d, best_i = _cells_scan_probes(Q, probe_ids, data, indices,
                                        list_sizes, k, inner_is_l2, qrows,
                                        qsplit, interpret, deleted)
    if inner_is_l2 and sqrt:
        best_d = jnp.sqrt(best_d)
    return best_d, best_i


@traced
def search(
    params: SearchParams, index: Index, queries, k: int,
    handle=None,
) -> Tuple[jax.Array, jax.Array]:
    """Search the index: coarse top-n_probes over centers, then scan probed
    lists. Ref: ivf_flat::search (detail/ivf_flat_search.cuh; pylibraft
    neighbors/ivf_flat.pyx search). Returns ``(distances, neighbors)``.
    """
    Q = _as_float(queries)
    expects(Q.ndim == 2 and Q.shape[1] == index.dim, "query dim mismatch")
    n_probes = min(params.n_probes, index.n_lists)
    # Clamp by static capacity so search stays traceable (jit/scan over
    # query batches); below-capacity emptiness is handled by the per-slot
    # validity mask in _probe_scan (inf distance / -1 id), matching the
    # reference's fewer-than-k semantics.
    k = min(k, max(index.capacity, 1))

    metric = index.metric
    inner_is_l2 = metric != DistanceType.InnerProduct
    sqrt = metric in (DistanceType.L2SqrtExpanded, DistanceType.L2SqrtUnexpanded)

    if index.data.dtype in (jnp.dtype(jnp.uint8), jnp.dtype(jnp.int8)):
        # 8-bit integer storage (the reference's ivf_flat<int8/uint8>
        # instantiations, ivf_flat_search.cuh:456): 8-bit values are
        # exact in bf16, so the scoring rides the bf16 MXU path at half
        # the f32 staging bandwidth; norms accumulate in f32 below, and
        # the bucketed kernel keeps f32 *query* precision via the split
        # hi/lo matmul (qsplit) so real-valued queries are not rounded.
        dataf = index.data.astype(jnp.bfloat16)
        qsplit = True
    else:
        dataf = _as_float(index.data)
        qsplit = False

    # Packed-cells tier dispatch, BEFORE the bucket-capacity machinery
    # (the round-4 engine: no measured capacity, no probe drops, one
    # jitted pipeline — see _cells_search). An explicit bucket_cap keeps
    # the legacy bucket-table engine (its documented capacity/drop
    # semantics); at uniform probe loads a well-packed hand-tuned bucket
    # table can still win (123K vs 87K QPS at the 100K bench shape),
    # while cells wins at skewed/heavy loads and under jit.
    if _cells_eligible(params.engine, k, params.bucket_cap,
                       dataf.shape[1], index.dim, Q.shape[0], n_probes,
                       index.n_lists):
        return _cells_search(
            Q, index.centers, dataf, index.indices, index.list_sizes,
            n_probes, k, inner_is_l2, sqrt,
            min(_CELL_QROWS, max(8, Q.shape[0])), qsplit,
            pallas_interpret(), deleted=index.deleted)

    # Coarse quantizer: distances to centers + top-n_probes
    # (ref: select_clusters-analog in ivf_flat_search; the cells path
    # above probes inside its own jitted pipeline).
    probe_ids = _coarse_probe(Q, index.centers, n_probes, inner_is_l2)

    engine, cap_q = _pick_engine(params.engine, Q.shape[0], n_probes,
                                 index.n_lists, k, params.bucket_cap,
                                 index.dim, probe_ids,
                                 cap_cache=_auto_cap_cache(index))
    if engine == "bucketed":
        return _bucketed_probe_scan(
            Q, dataf, index.indices, index.list_sizes, probe_ids,
            k, inner_is_l2, sqrt, cap_q,
            pallas_interpret(), qsplit,
            deleted=index.deleted)

    if inner_is_l2:
        # f32-accumulated norms without materializing a full f32 copy of
        # (possibly bf16-cast 8-bit) storage: the upcast fuses into the
        # reduction.
        norms = jnp.einsum("lcd,lcd->lc", dataf, dataf,
                           preferred_element_type=jnp.float32)
    else:
        norms = None
    # The scan engine's per-probe gather is (q_chunk, cap, dim) — chunk the
    # query axis so the workspace stays bounded at large cap (at cap=2048,
    # d=128, 1000 unchunked queries would stage ~1 GB per probe step).
    return _chunked_over_queries(
        lambda q_, p_: _probe_scan(q_, dataf, norms, index.indices,
                                   index.list_sizes, k, inner_is_l2, sqrt,
                                   probe_ids=p_, deleted=index.deleted),
        Q, probe_ids, dataf.shape[1] * index.dim * 4)


# ---------------------------------------------------------------------------
# Serialization (ref: detail/ivf_flat_serialize.cuh:34, serialization_version=3;
# payloads as .npy inside an .npz, matching the reference's mdspan-as-npy
# convention, core/detail/mdspan_numpy_serializer.hpp).

SERIALIZATION_VERSION = 3


@traced
def save(filename: str, index: Index, retry=None) -> None:
    """Ref: ivf_flat::serialize / pylibraft save (neighbors/ivf_flat.pyx).

    The npz write runs under :func:`raft_tpu.core.retry.with_retry`
    (``retry`` overrides :data:`~raft_tpu.core.retry.DEFAULT_IO_RETRY`):
    index checkpoints land on network filesystems where transient
    ``OSError`` blips are routine and a deterministic backoff re-attempt
    is the correct response."""
    from raft_tpu.core.retry import DEFAULT_IO_RETRY, with_retry

    payload = dict(
        version=np.int64(SERIALIZATION_VERSION),
        metric=np.int64(index.metric.value),
        adaptive_centers=np.bool_(index.adaptive_centers),
        conservative=np.bool_(index.conservative_memory_allocation),
        centers=np.asarray(index.centers),
        data=np.asarray(index.data),
        indices=np.asarray(index.indices),
        list_sizes=np.asarray(index.list_sizes),
    )
    if index.n_deleted:
        # Tombstones are index CONTENT (resurrecting deleted rows on a
        # reload would be corruption); the key is written only when any
        # slot is tombstoned, so mask-free files keep the v3 layout.
        payload["deleted"] = np.asarray(index.deleted)
    with_retry(lambda: np.savez(filename, **payload),
               retry or DEFAULT_IO_RETRY)


@traced
def load(filename: str, retry=None) -> Index:
    """Ref: ivf_flat::deserialize / pylibraft load. IO retried like
    :func:`save` (the np.load + array reads are one retriable unit)."""
    from raft_tpu.core.retry import DEFAULT_IO_RETRY, with_retry

    if not filename.endswith(".npz"):
        filename = filename + ".npz"

    def read():
        with np.load(filename) as z:
            return {k: z[k] for k in z.files}

    z = with_retry(read, retry or DEFAULT_IO_RETRY)
    version = int(z["version"])
    expects(version == SERIALIZATION_VERSION,
            "serialization version mismatch: %s", version)
    # Guard the deserialize path the same way build() guards its
    # idx_dtype knob: int64 ids without x64 enabled would otherwise be
    # silently truncated to int32 by jnp.asarray.
    validate_idx_dtype(z["indices"].dtype)
    deleted = z.get("deleted")
    return Index(
        metric=DistanceType(int(z["metric"])),
        centers=jnp.asarray(z["centers"]),
        data=jnp.asarray(z["data"]),
        indices=jnp.asarray(z["indices"]),
        list_sizes=jnp.asarray(z["list_sizes"]),
        adaptive_centers=bool(z["adaptive_centers"]),
        conservative_memory_allocation=bool(z["conservative"]),
        deleted=None if deleted is None else jnp.asarray(deleted),
        n_deleted=0 if deleted is None else int(deleted.sum()),
    )
