"""Batched top-k selection — the performance linchpin of every k-NN path.

Ref: cpp/include/raft/matrix/select_k.cuh with the dispatch heuristic at
matrix/detail/select_k.cuh:67-87 choosing between a warp-level bitonic sort
("warpsort", select_warpsort.cuh) for k ≤ 256 and a multi-pass MSB radix
filter (select_radix.cuh) for large batch×len×k.

TPU-native re-design: the warp bitonic network and radix passes are CUDA
register/smem idioms with no TPU analog. Three engines:

* ``jax.lax.top_k`` (XLA's sort-based top-k) — fastest at small k and
  short rows; the ``kAuto`` default there;
* ``kStream`` — the large-len path (the select_radix role): a Pallas
  sweep extracts each 512-chunk's 8 smallest in VMEM (n → n/64
  candidates at memory-floor HBM traffic, no sort network), a small
  ``top_k`` ranks the candidates, and an exactness audit falls back to a
  full ``top_k`` inside ``lax.cond`` on pathological skew (sorted input,
  mass ties) — so the result is always exactly ``lax.top_k``'s,
  including tie order. ``kAuto`` dispatches here for k ≥ 64 and
  len ≥ 65536 on TPU (measured 4.3× over ``top_k`` at batch=64,
  len=131072, k=128; 1.5–30× across the probed region);
* ``kTwoPhase`` (explicit opt-in): per-chunk ``top_k`` then a final
  merge ``top_k`` — kept for shapes/backends where it may win.

``select_min`` is handled by key negation (floats) / complement (ints) so a
single largest-k kernel serves both polarities, like the reference's
``Comparator`` template parameter.
"""

from __future__ import annotations

import enum
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.core.error import expects
from raft_tpu.core.mdarray import as_array
from raft_tpu.core.sentinels import PAD_ID, dummy_key_val, worst_value
from raft_tpu.ops import pallas_interpret
from raft_tpu.util.pow2 import ceildiv, round_up_safe
from raft_tpu.core.nvtx import traced


class SelectMethod(enum.Enum):
    """Algorithm choice (ref: detail::SelectAlgo in select_k.cuh)."""

    kAuto = 0
    kTopK = 1       # direct lax.top_k (analog of warpsort path)
    kTwoPhase = 2   # chunked candidate compression (analog of radix path)
    kStream = 3     # Pallas streaming k-pass select (large-len path)


# Chunk length for the two-phase path: big enough to amortize sort overhead,
# small enough that n_chunks*k candidates stay tiny vs len.
_CHUNK = 16384


def _to_descending_keys(v: jax.Array, select_min: bool) -> jax.Array:
    """Map values so that 'largest key' == 'selected value'."""
    if not select_min:
        return v
    if jnp.issubdtype(v.dtype, jnp.floating):
        return -v
    return ~v if jnp.issubdtype(v.dtype, jnp.signedinteger) else jnp.iinfo(v.dtype).max - v


def _dummy_key_val(dtype, select_min: bool):
    """Sentinel for padding (ref: select_warpsort 'dummy' = worst value;
    the shared definition lives in core/sentinels.py)."""
    return dummy_key_val(dtype, select_min)


def _direct_top_k(values, k, select_min):
    keys = _to_descending_keys(values, select_min)
    _, idx = jax.lax.top_k(keys, k)
    sel = jnp.take_along_axis(values, idx, axis=-1)
    return sel, idx.astype(jnp.int32)


def _two_phase_top_k(values, k, select_min, chunk=_CHUNK):
    batch, n = values.shape
    n_chunks = ceildiv(n, chunk)
    pad = n_chunks * chunk - n
    dummy = _dummy_key_val(values.dtype, select_min)
    if pad:
        values_p = jnp.concatenate(
            [values, jnp.full((batch, pad), dummy, values.dtype)], axis=1
        )
    else:
        values_p = values
    tiles = values_p.reshape(batch, n_chunks, chunk)
    keys = _to_descending_keys(tiles, select_min)
    kc = min(k, chunk)
    _, idx_local = jax.lax.top_k(keys, kc)  # (batch, n_chunks, kc)
    base = (jnp.arange(n_chunks, dtype=jnp.int32) * chunk)[None, :, None]
    idx_global = (idx_local.astype(jnp.int32) + base).reshape(batch, n_chunks * kc)
    cand = jnp.take_along_axis(values_p, idx_global, axis=1)
    ckeys = _to_descending_keys(cand, select_min)
    _, pos = jax.lax.top_k(ckeys, k)
    sel = jnp.take_along_axis(cand, pos, axis=1)
    idx = jnp.take_along_axis(idx_global, pos, axis=1)
    return sel, idx


# Streaming engine geometry: each grid cell loads a _BT-lane tile holding
# _NSUB sub-chunks of _SUB lanes, extracts the _M smallest of every
# sub-chunk in parallel, and writes exactly one dense 128-lane candidate
# block (_NSUB · _M == 128 — no padded lanes, and lane stores stay
# 128-aligned as Mosaic requires).
_SUB = 512
_M = 8
_NSUB = 128 // _M
_BT = _SUB * _NSUB
_I32MAX = jnp.iinfo(jnp.int32).max
# Audit-failure budget for the per-row fallback: up to this many
# pathological rows re-run top_k individually before the whole batch does.
_PATCH_ROWS = 8


def extract_m_rows(work, ids, m: int, out_v, out_i, lane_base=0):
    """M-pass streaming extract — the work-compression primitive of the
    kStream select, single-sourced here and reused by the fused select
    epilogue of the compressed PQ scan (ops/pq_scan.py).

    Pulls the ``m`` smallest (value, id) pairs of each row of ``work``
    (f32, min-order; ties to the lowest id, matching ``lax.top_k``'s
    stable order) and places pass ``t``'s extract at lane
    ``lane_base + t`` of ``(out_v, out_i)`` — so callers compact many
    sub-chunks' extracts into one dense candidate block by varying
    ``lane_base`` (static or traced). Returns ``(residual work, out_v,
    out_i)``; extracted entries are knocked out of the residual with the
    worst value. Rows with fewer than ``m`` finite entries repeat
    ``(inf, min surviving id)`` for the tail passes — the same starved
    signature the k-pass select emits, masked to the -1 sentinel by
    every consumer's ``isinf`` epilogue."""
    col_out = jax.lax.broadcasted_iota(jnp.int32, out_v.shape, 1)

    def body_t(t, carry):
        w, vd, vi = carry
        cur = jnp.min(w, axis=1, keepdims=True)
        hit = w == cur
        sel = jnp.min(jnp.where(hit, ids, _I32MAX), axis=1,
                      keepdims=True)
        w = jnp.where(ids == sel, worst_value(True), w)
        put = col_out == lane_base + t
        vd = jnp.where(put, cur, vd)
        vi = jnp.where(put, sel, vi)
        return w, vd, vi

    return jax.lax.fori_loop(0, m, body_t, (work, out_v, out_i))


def _mextract_kernel(v_ref, outv_ref, outi_ref, *, n: int):
    """One (batch-block, tile) grid cell: for each of the tile's _NSUB
    sub-chunks, extract its _M smallest (value, index) pairs — ascending,
    ties to the lowest index, matching ``lax.top_k``'s stable order —
    entirely in VMEM (:func:`extract_m_rows`). Sub-chunk s's extracts
    land at lanes [s·_M, (s+1)·_M) of the dense 128-lane candidate
    block, so the tile's data is touched once and every output lane is
    real (memory-floor HBM traffic; no sort network runs anywhere). All
    ops stay 2-D — Mosaic cannot fold a (bq, _NSUB, _M) register tile
    into lanes."""
    j = pl.program_id(1)
    bq = v_ref.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, _SUB), 1)

    def body_sub(sub, carry):
        vd, vi = carry
        w = v_ref[:, pl.ds(sub * _SUB, _SUB)].astype(jnp.float32)
        ids = j * _BT + sub * _SUB + col
        w = jnp.where(ids < n, w, worst_value(True))
        _, vd, vi = extract_m_rows(w, ids, _M, vd, vi,
                                   lane_base=sub * _M)
        return vd, vi

    vd0 = jnp.full((bq, 128), worst_value(True), jnp.float32)
    vi0 = jnp.full((bq, 128), PAD_ID, jnp.int32)
    vd, vi = jax.lax.fori_loop(0, _NSUB, body_sub, (vd0, vi0))
    outv_ref[:] = vd
    outi_ref[:] = vi


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _stream_select_min(values, k: int, interpret: bool = False):
    """Streaming min-k over f32 keys: (batch, n) → ascending (batch, k)
    values + positional indices, exact.

    The TPU re-design of the reference's multi-pass radix filter
    (matrix/detail/select_radix.cuh): a Pallas sweep extracts each
    512-chunk's 8 smallest in VMEM (the work-compression pass — n →
    n/64 candidates at memory-floor HBM traffic), one small ``top_k``
    ranks the candidates, and an exactness audit catches the only way
    compression can lose an element: a chunk whose 8th-smallest still
    beats the candidate k-th. Audit hits are repaired per row: up to
    ``_PATCH_ROWS`` offending rows re-run ``top_k`` on just themselves
    (gather → top_k → scatter); only beyond that does the whole batch
    fall back — so a single pathological row (sorted, constant, NaN)
    costs ``_PATCH_ROWS/batch`` of a full top_k, not the batch. All
    branches are compiled, one executes (lax.cond). k ≤ 256 (the
    reference warpsort cap, select_warpsort.cuh:100).
    """
    batch, n = values.shape
    bq = min(round_up_safe(batch, 8), 64)
    bp = round_up_safe(batch, bq)
    np_ = round_up_safe(n, _BT)
    if bp != batch or np_ != n:
        values = jnp.pad(values, ((0, bp - batch), (0, np_ - n)),
                         constant_values=jnp.inf)
    nt = np_ // _BT                      # tiles per row
    nc = nt * _NSUB                      # sub-chunks per row

    kernel = functools.partial(_mextract_kernel, n=n)
    cand_v, cand_i = pl.pallas_call(
        kernel,
        grid=(bp // bq, nt),
        in_specs=[pl.BlockSpec((bq, _BT), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM)],
        out_specs=[
            pl.BlockSpec((bq, 128), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bq, 128), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, nc * _M), jnp.float32),
            jax.ShapeDtypeStruct((bp, nc * _M), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(values)
    cand_v = cand_v[:batch]
    cand_i = cand_i[:batch]

    neg, pos = jax.lax.top_k(-cand_v, k)
    best_v = -neg
    best_i = jnp.take_along_axis(cand_i, pos, axis=1)

    # Exactness audit, PER ROW: chunk slots are ascending, so slot _M-1
    # is each chunk's worst extract; if any still ties-or-beats the
    # row's candidate k-th, that chunk may hide a better element (<=
    # keeps tie order identical to lax.top_k's lowest-index rule).
    chunk_worst = cand_v.reshape(batch, nc, _M)[:, :, _M - 1]
    row_exact = jnp.all(chunk_worst > best_v[:, k - 1:k], axis=1)
    n_bad = jnp.sum(~row_exact)

    # A few pathological rows (sorted / constant / NaN-heavy) re-run the
    # full top_k only on themselves (gather -> top_k -> scatter); padding
    # slots of the fixed-size gather point at row 0, whose recompute is
    # exact and therefore safe to scatter back. Only when more than
    # _PATCH_ROWS rows trip does the whole batch fall back (round-3
    # behavior; ADVICE r3 asked for the bounded per-row cost).
    patch_rows = min(_PATCH_ROWS, batch)

    def fast(_):
        return best_v, best_i

    def patch(_):
        bad_idx = jnp.nonzero(~row_exact, size=patch_rows, fill_value=0)[0]
        sub = values[:batch][bad_idx]               # (patch_rows, n)
        nv, ni = jax.lax.top_k(-sub, k)
        return (best_v.at[bad_idx].set(-nv),
                best_i.at[bad_idx].set(ni.astype(jnp.int32)))

    def slow(_):
        nv, ni = jax.lax.top_k(-values[:batch], k)
        return -nv, ni.astype(jnp.int32)

    return jax.lax.cond(
        n_bad == 0, fast,
        lambda _: jax.lax.cond(n_bad <= patch_rows, patch, slow, None),
        None)


def _stream_top_k(values, k, select_min):
    """kStream engine: negate keys for max-selection, stream-select, gather
    original values at the selected positions. With k < n (the dispatch
    precondition) the selected indices are always real positions: padding
    keys are +inf and lose every min-comparison, and rows whose candidate
    set degenerates (mass ±inf) trip the audit into the exact fallback."""
    keys = values.astype(jnp.float32)
    if not select_min:
        keys = -keys
    _, idx = _stream_select_min(keys, k, interpret=pallas_interpret())
    return jnp.take_along_axis(values, idx, axis=-1), idx


def _stream_supported(batch: int, n: int, k: int, dtype) -> bool:
    """kAuto crossover (measured on v5e): the streaming extractor wins on
    long rows at large k, where XLA's top_k pays a full k-insertion sort
    per row (probed 1.5–30×, e.g. 4.3× at batch=64, len=131072, k=128);
    at small k XLA's partial sort is already cheap and keeps winning.
    Needs n/64 candidates ≥ 2k for audit headroom."""
    return (jax.default_backend() == "tpu" and 64 <= k <= 256
            and n >= 65536 and n >= 128 * k and batch >= 8
            and jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                     jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float16)))


@traced
def select_k(
    values,
    k: int,
    select_min: bool = True,
    indices: Optional[jax.Array] = None,
    method: SelectMethod = SelectMethod.kAuto,
) -> Tuple[jax.Array, jax.Array]:
    """Select the k smallest (or largest) entries per row with their indices.

    Ref: raft::matrix::select_k (matrix/select_k.cuh). ``indices``, when
    given, is a payload id matrix gathered through the selection (the
    reference's in_idx argument); otherwise positional indices are returned.

    Returns ``(values_out (batch,k), indices_out (batch,k) int32)`` sorted
    best-first.
    """
    v = as_array(values)
    squeeze = v.ndim == 1
    if squeeze:
        v = v[None, :]
    batch, n = v.shape
    if k >= n:
        # Degenerate: full sort (top_k over the mapped keys — argsort of the
        # negated keys would overflow for extreme integer values).
        sel, idx = _direct_top_k(v, n, select_min)
        if k > n:
            dummy = _dummy_key_val(v.dtype, select_min)
            sel = jnp.concatenate(
                [sel, jnp.full((batch, k - n), dummy, v.dtype)], axis=1
            )
            idx = jnp.concatenate(
                [idx, jnp.full((batch, k - n), n, jnp.int32)], axis=1
            )
    else:
        if method == SelectMethod.kStream:
            # Explicit engine request: validate rather than silently
            # degrade (integer keys would round through f32; too few
            # candidates would crash in the merge top_k).
            expects(k <= 256,
                    "kStream supports k <= 256 (the warpsort cap)")
            expects(v.dtype in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float16)),
                    "kStream requires f32/bf16/f16 values (integer and "
                    "f64 keys are not exact in its f32 pipeline)")
            expects(round_up_safe(n, _BT) // _SUB * _M >= k,
                    f"kStream needs len/64 candidates >= k (len={n}, "
                    f"k={k}); use kTopK")
        if method == SelectMethod.kTwoPhase:
            sel, idx = _two_phase_top_k(v, k, select_min)
        elif method == SelectMethod.kStream or (
                method == SelectMethod.kAuto
                and _stream_supported(batch, n, k, v.dtype)):
            sel, idx = _stream_top_k(v, k, select_min)
        else:
            sel, idx = _direct_top_k(v, k, select_min)
    if indices is not None:
        payload = as_array(indices)
        if payload.ndim == 1:
            payload = payload[None, :]
        # Padding slots (positional index == n, only when k > n) map to the
        # sentinel -1, not to a real payload id.
        pad = idx >= payload.shape[1]
        safe = jnp.minimum(idx, payload.shape[1] - 1)
        gathered = jnp.take_along_axis(payload, safe, axis=1)
        idx = jnp.where(pad, jnp.asarray(PAD_ID, gathered.dtype), gathered)
    if squeeze:
        return sel[0], idx[0]
    return sel, idx
