"""Fused brute-force kNN Pallas kernel: distance tile + running top-k.

Ref: cpp/src spatial/knn/detail/fused_l2_knn.cuh (tiled distance + in-kernel
warp-select top-k in one launch) and detail/knn_brute_force.cuh:51
(tiled_brute_force_knn). The CUDA design keeps the distance tile in
registers/smem and folds it into per-warp top-k queues so the
(n_queries, n_db) matrix never reaches global memory.

TPU-native re-design: a Pallas kernel over a (query_blocks, db_tiles) grid.
The db-tile axis is sequential ("arbitrary" dimension semantics), so the
output block — the running top-k for the current query block — stays
resident in VMEM across the whole db sweep and is written back to HBM once.
Per grid cell:

* the (BQ, D) query block and (BD, D) db tile multiply on the MXU
  (optionally in bfloat16 with f32 accumulation — exact for integer-valued
  data such as SIFT descriptors, the analog of the reference's int8
  fast path, ivf_flat_search.cuh:456);
* the L2 epilogue (norms) runs on the VPU in f32;
* a k-pass selection extracts the tile's k smallest (value, index) pairs —
  the VPU-friendly analog of the warp bitonic queue (util/bitonic_sort.cuh);
* a second k-pass merge folds them into the resident best-k, mirroring the
  warp-select merge step of knn_merge_parts.

Selection is always "min of work"; inner-product search negates the gram
tile (the reference flips its Comparator template argument instead).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.util.pow2 import round_up_safe

_LANES = 128
_I32MAX = jnp.iinfo(jnp.int32).max


def _distance_tile(q, y, l2: bool, bf16: bool, qsplit: bool):
    """The shared distance-tile core of all three fused-kNN kernels:
    MXU gram (optionally bf16, optionally with the split hi/lo query
    matmul that keeps f32 query precision on the bf16 path) + clamped
    expanded-L2 epilogue, or negated inner products (min-select order).
    Precision-sensitive — keep it single-sourced."""
    dims = (((1,), (1,)), ((), ()))
    # bf16 operands take one MXU pass with f32 accumulation. The precision
    # is pinned: a process-wide jax_default_matmul_precision of "highest"
    # would otherwise ask Mosaic for a bf16 dot it refuses to lower.
    one_pass = jax.lax.Precision.DEFAULT
    if bf16 and qsplit:
        yc = y.astype(jnp.bfloat16)
        qh = q.astype(jnp.bfloat16)
        ql = (q - qh.astype(jnp.float32)).astype(jnp.bfloat16)
        g = (jax.lax.dot_general(qh, yc, dimension_numbers=dims,
                                 preferred_element_type=jnp.float32,
                                 precision=one_pass)
             + jax.lax.dot_general(ql, yc, dimension_numbers=dims,
                                   preferred_element_type=jnp.float32,
                                   precision=one_pass))
    else:
        if bf16:
            qc, yc = q.astype(jnp.bfloat16), y.astype(jnp.bfloat16)
        else:
            qc, yc = q, y
        g = jax.lax.dot_general(
            qc, yc, dimension_numbers=dims,
            preferred_element_type=jnp.float32,
            precision=one_pass if bf16 else jax.lax.Precision.HIGHEST)
    if not l2:
        return -g
    yf = y.astype(jnp.float32)  # norms in f32 even for bf16-stored db
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    yn = jnp.sum(yf * yf, axis=1)[None, :]
    return jnp.maximum(qn + yn - 2.0 * g, 0.0)


def _kpass_select(work, ids, k: int, kp: int):
    """Extract the k smallest entries of each row of ``work`` (ascending),
    tie-broken by lowest id — the register-queue role of warp_sort_immediate
    (matrix/detail/select_warpsort.cuh:100)."""
    bq = work.shape[0]
    colk = jax.lax.broadcasted_iota(jnp.int32, (bq, kp), 1)

    def body(t, carry):
        w, td, ti = carry
        cur = jnp.min(w, axis=1, keepdims=True)
        hit = w == cur
        sel = jnp.min(jnp.where(hit, ids, _I32MAX), axis=1, keepdims=True)
        w = jnp.where(ids == sel, jnp.inf, w)
        put = colk == t
        td = jnp.where(put, cur, td)
        ti = jnp.where(put, sel, ti)
        return w, td, ti

    td0 = jnp.full((bq, kp), jnp.inf, jnp.float32)
    ti0 = jnp.full((bq, kp), -1, jnp.int32)
    _, td, ti = jax.lax.fori_loop(0, k, body, (work, td0, ti0))
    return td, ti


def _kpass_merge(ad, ai, bd_, bi, k: int, kp: int):
    """Merge two ascending top-k row sets into one (position tie-break)."""
    bq = ad.shape[0]
    colk = jax.lax.broadcasted_iota(jnp.int32, (bq, kp), 1)
    catd = jnp.concatenate([ad, bd_], axis=1)
    cati = jnp.concatenate([ai, bi], axis=1)
    col2 = jax.lax.broadcasted_iota(jnp.int32, catd.shape, 1)

    def body(t, carry):
        cd, nd, ni = carry
        cur = jnp.min(cd, axis=1, keepdims=True)
        pos = jnp.min(jnp.where(cd == cur, col2, _I32MAX), axis=1, keepdims=True)
        chosen = col2 == pos
        # dtype pinned: under x64, integer jnp.sum otherwise promotes to
        # int64 and breaks the fori_loop carry type.
        selid = jnp.sum(jnp.where(chosen, cati, 0), axis=1, keepdims=True,
                        dtype=jnp.int32)
        cd = jnp.where(chosen, jnp.inf, cd)
        put = colk == t
        nd = jnp.where(put, cur, nd)
        ni = jnp.where(put, selid, ni)
        return cd, nd, ni

    nd0 = jnp.full((bq, kp), jnp.inf, jnp.float32)
    ni0 = jnp.full((bq, kp), -1, jnp.int32)
    _, nd, ni = jax.lax.fori_loop(0, k, body, (catd, nd0, ni0))
    return nd, ni


def _fused_knn_kernel(q_ref, db_ref, outd_ref, outi_ref, *,
                      k: int, kp: int, bd: int, n: int, l2: bool, bf16: bool,
                      qsplit: bool):
    j = pl.program_id(1)
    single_tile = pl.num_programs(1) == 1

    if not single_tile:
        @pl.when(j == 0)
        def _():
            outd_ref[:] = jnp.full(outd_ref.shape, jnp.inf, jnp.float32)
            outi_ref[:] = jnp.full(outi_ref.shape, -1, jnp.int32)

    work = _distance_tile(q_ref[:], db_ref[:], l2, bf16, qsplit)
    ids = j * bd + jax.lax.broadcasted_iota(jnp.int32, work.shape, 1)
    work = jnp.where(ids < n, work, jnp.inf)

    td, ti = _kpass_select(work, ids, k, kp)
    if single_tile:
        # One db tile: the merge into the all-inf carry is an identity.
        nd, ni = td, ti
    else:
        nd, ni = _kpass_merge(outd_ref[:], outi_ref[:], td, ti, k, kp)
    outd_ref[:] = nd
    outi_ref[:] = ni


@functools.partial(
    jax.jit,
    static_argnames=("k", "l2", "sqrt", "bq", "bd", "bf16", "qsplit",
                     "interpret"))
def _fused_knn(queries, db, k: int, l2: bool, sqrt: bool,
               bq: int, bd: int, bf16: bool, qsplit: bool,
               interpret: bool):
    m, d = queries.shape
    n = db.shape[0]
    kp = round_up_safe(max(k, 1), _LANES)
    mp = round_up_safe(m, bq)
    np_ = round_up_safe(n, bd)
    dp = round_up_safe(d, _LANES)
    if mp != m or dp != d:
        queries = jnp.pad(queries, ((0, mp - m), (0, dp - d)))
    if np_ != n or dp != d:
        db = jnp.pad(db, ((0, np_ - n), (0, dp - d)))
    nb = np_ // bd

    kernel = functools.partial(
        _fused_knn_kernel, k=k, kp=kp, bd=bd, n=n, l2=l2, bf16=bf16,
        qsplit=qsplit)
    outd, outi = pl.pallas_call(
        kernel,
        grid=(mp // bq, nb),
        in_specs=[
            pl.BlockSpec((bq, dp), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bd, dp), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bq, kp), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bq, kp), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, kp), jnp.float32),
            jax.ShapeDtypeStruct((mp, kp), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(queries, db)

    outd = outd[:m, :k]
    outi = outi[:m, :k]
    if l2:
        if sqrt:
            outd = jnp.sqrt(outd)
    else:
        outd = -outd  # undo the min-selection negation: true inner products
    return outd, outi


def _batch_knn_kernel(q_ref, db_ref, bad_ref, outd_ref, outi_ref, *,
                      k: int, kp: int, bd: int, l2: bool, bf16: bool,
                      qsplit: bool):
    """One (batch, db-tile) grid cell of the batched independent kNN: same
    distance-tile + k-pass selection as ``_fused_knn_kernel``, but each
    batch element b searches only its own database slab, with per-slot
    invalidity provided by ``bad_ref`` (capacity padding mask). The running
    top-k stays VMEM-resident across the db-tile axis."""
    j = pl.program_id(1)
    single_tile = pl.num_programs(1) == 1

    if not single_tile:
        @pl.when(j == 0)
        def _():
            outd_ref[:] = jnp.full(outd_ref.shape, jnp.inf, jnp.float32)
            outi_ref[:] = jnp.full(outi_ref.shape, -1, jnp.int32)

    work = _distance_tile(q_ref[0], db_ref[0], l2, bf16, qsplit)
    ids = j * bd + jax.lax.broadcasted_iota(jnp.int32, work.shape, 1)
    work = jnp.where(bad_ref[0], jnp.inf, work)  # (1, bd) broadcasts

    td, ti = _kpass_select(work, ids, k, kp)
    if single_tile:
        # One db tile (the common bucketed-IVF case: cap ≤ bd): merging
        # into the all-inf initial carry is an identity — skip the k-pass
        # merge, which otherwise costs as much as the select itself.
        nd, ni = td, ti
    else:
        nd, ni = _kpass_merge(outd_ref[0], outi_ref[0], td, ti, k, kp)
    # Starved selection (fewer than k valid rows in this list): selected
    # slots whose value is inf are masked-invalid or already-consumed
    # columns carrying stale real ids — report the -1 sentinel like the
    # scan engine's fewer-than-k semantics.
    ni = jnp.where(jnp.isinf(nd), -1, ni)
    outd_ref[0] = nd
    outi_ref[0] = ni


@functools.partial(
    jax.jit,
    static_argnames=("k", "l2", "sqrt", "bd", "bf16", "qsplit",
                     "interpret"))
def _fused_batch_knn(queries, db, bad, k: int, l2: bool, sqrt: bool,
                     bd: int, bf16: bool, qsplit: bool, interpret: bool):
    B, m, d = queries.shape
    n = db.shape[1]
    kp = round_up_safe(max(k, 1), _LANES)
    mp = round_up_safe(m, 8)
    np_ = round_up_safe(n, bd)
    dp = round_up_safe(d, _LANES)
    if mp != m or dp != d:
        queries = jnp.pad(queries, ((0, 0), (0, mp - m), (0, dp - d)))
    if np_ != n or dp != d:
        db = jnp.pad(db, ((0, 0), (0, np_ - n), (0, dp - d)))
    if np_ != n:
        bad = jnp.pad(bad, ((0, 0), (0, np_ - n)), constant_values=True)
    # (B, 1, n): a middle unit axis keeps the block's trailing two dims
    # (1, bd) legal for the mosaic lowering (second-to-last == array dim).
    bad = bad[:, None, :]
    nb = np_ // bd

    kernel = functools.partial(
        _batch_knn_kernel, k=k, kp=kp, bd=bd, l2=l2, bf16=bf16,
        qsplit=qsplit)
    outd, outi = pl.pallas_call(
        kernel,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, mp, dp), lambda b, j: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bd, dp), lambda b, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bd), lambda b, j: (b, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, mp, kp), lambda b, j: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, mp, kp), lambda b, j: (b, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, mp, kp), jnp.float32),
            jax.ShapeDtypeStruct((B, mp, kp), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(queries, db, bad)

    outd = outd[:, :m, :k]
    outi = outi[:, :m, :k]
    if l2:
        if sqrt:
            outd = jnp.sqrt(outd)
    else:
        outd = -outd
    return outd, outi


def fused_batch_knn(queries, db, invalid, k: int, *, metric: str = "l2",
                    sqrt: bool = False, bd: int = 0, bf16: bool = False,
                    qsplit: bool = False,
                    interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Batched independent fused kNN: element b searches ``queries[b]``
    (m, d) against ``db[b]`` (n, d) with per-slot mask ``invalid[b]`` (n,)
    bool. The engine of the IVF-Flat bucketed probe scan (one batch element
    per probed list; ref: interleaved_scan_kernel's one-block-per-(query,
    probe) decomposition, detail/ivf_flat_search.cuh:669, re-tiled for the
    MXU). A bf16 ``db`` is accepted as-is when ``bf16=True`` (the IVF-PQ
    reconstruction cache) — norms/accumulation stay f32. ``qsplit``
    keeps f32 query precision on the bf16 path via a split hi/lo double
    matmul (for exactly-representable quantized storage, where query
    rounding would be the only error source).
    Returns (distances (B, m, k), local indices (B, m, k))."""
    queries = jnp.asarray(queries, jnp.float32)
    db = jnp.asarray(db)
    if not (bf16 and db.dtype == jnp.bfloat16):
        db = db.astype(jnp.float32)
    k = int(min(k, db.shape[1]))
    n = db.shape[1]
    if bd == 0:
        bd = min(2048, round_up_safe(n, _LANES))
    dp = round_up_safe(queries.shape[2], _LANES)
    while bd > 256 and bd * dp * 4 > 4 * 1024 * 1024:
        bd //= 2
    # Halving can land off the lane grid (e.g. 1920 -> 960 -> 480): keep the
    # db-tile BlockSpec lane-aligned or Mosaic may fail to lower it.
    bd = max(_LANES, bd // _LANES * _LANES)
    bd = min(bd, round_up_safe(n, _LANES))
    return _fused_batch_knn(queries, db, invalid, k, metric == "l2", sqrt,
                            bd, bf16, qsplit, interpret)


def _cells_knn_kernel(cell_ref, q_ref, db_ref, bad_ref, outd_ref, outi_ref,
                      *, k: int, kp: int, l2: bool, bf16: bool,
                      qsplit: bool):
    """One grid cell = one packed query cell scoring one list (the
    round-4 packed-cells layout: the scalar-prefetched ``cell_ref`` maps
    cell → list for the db/mask block index maps; -1 marks an unused
    tail cell, skipped entirely). Same distance tile + k-pass selection
    as ``_batch_knn_kernel``, but cell rows are ≥ half full at skewed
    probe loads instead of mostly padding."""
    b = pl.program_id(0)
    used = cell_ref[b] >= 0

    @pl.when(jnp.logical_not(used))
    def _():
        outd_ref[0] = jnp.full(outd_ref.shape[1:], jnp.inf, jnp.float32)
        outi_ref[0] = jnp.full(outi_ref.shape[1:], -1, jnp.int32)

    @pl.when(used)
    def _():
        work = _distance_tile(q_ref[0], db_ref[0], l2, bf16, qsplit)
        ids = jax.lax.broadcasted_iota(jnp.int32, work.shape, 1)
        work = jnp.where(bad_ref[0], jnp.inf, work)  # (1, cap) broadcasts
        nd, ni = _kpass_select(work, ids, k, kp)
        ni = jnp.where(jnp.isinf(nd), -1, ni)
        outd_ref[0] = nd
        outi_ref[0] = ni


@functools.partial(
    jax.jit,
    static_argnames=("k", "l2", "bf16", "qsplit", "interpret"))
def fused_cells_knn(cell_list, queries, db, invalid, k: int, *,
                    l2: bool = True, bf16: bool = False,
                    qsplit: bool = False, interpret: bool = False
                    ) -> Tuple[jax.Array, jax.Array]:
    """Packed-cells batched kNN: cell c scores ``queries[c]`` (qrows, d)
    against list ``cell_list[c]``'s rows ``db[cell_list[c]]`` (cap, d)
    with per-slot mask ``invalid``. The IVF-Flat analog of the
    compressed PQ scan's cell layout (see ivf_flat._invert_probe_map_cells);
    min-selection order for both metrics (ip scores are negated).
    Returns (distances (max_cells, qrows, k), local slot ids)."""
    max_cells, qrows, d = queries.shape
    n_lists, cap, _ = db.shape
    kp = round_up_safe(max(k, 1), _LANES)
    qr = round_up_safe(qrows, 8)
    capp = round_up_safe(cap, _LANES)
    dp = round_up_safe(d, _LANES)
    if qr != qrows or dp != d:
        queries = jnp.pad(queries, ((0, 0), (0, qr - qrows), (0, dp - d)))
    if capp != cap or dp != d:
        db = jnp.pad(db, ((0, 0), (0, capp - cap), (0, dp - d)))
    if capp != cap:
        invalid = jnp.pad(invalid, ((0, 0), (0, capp - cap)),
                          constant_values=True)

    kernel = functools.partial(
        _cells_knn_kernel, k=k, kp=kp, l2=l2, bf16=bf16, qsplit=qsplit)
    vmem = _cells_vmem_bytes(qr, capp, dp)

    def by_list(b, cl):
        return (jnp.maximum(cl[b], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(max_cells,),
        in_specs=[
            pl.BlockSpec((1, qr, dp), lambda b, cl: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, capp, dp), by_list,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, capp), by_list,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, qr, kp), lambda b, cl: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, qr, kp), lambda b, cl: (b, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
    )
    outd, outi = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((max_cells, qr, kp), jnp.float32),
            jax.ShapeDtypeStruct((max_cells, qr, kp), jnp.int32),
        ],
        compiler_params=(pltpu.CompilerParams(vmem_limit_bytes=vmem)
                         if vmem > _SCOPED_VMEM_DEFAULT else None),
        interpret=interpret,
    )(cell_list, queries, db, invalid[:, None, :])
    return outd[:, :qrows, :k], outi[:, :qrows, :k]


# Mosaic's default scoped-VMEM limit per kernel (v5e); past it the cells
# kernel asks for what it needs (budgeted by ivf_flat._CELL_VMEM_BYTES).
_SCOPED_VMEM_DEFAULT = 16 * 1024 * 1024


def _cells_vmem_bytes(qr: int, capp: int, dp: int) -> int:
    """Scoped VMEM the cells kernel needs: one list's (cap, d) f32 block
    and its mask row, each double-buffered, plus the (qrows, cap) score
    tile and the ids and selection temporaries the k-pass keeps beside
    it, and 4 MiB for the query and output blocks."""
    return (2 * capp * (dp + 8) * 4 + 6 * qr * capp * 4
            + 4 * 1024 * 1024)


def fused_knn_supported(m: int, n: int, d: int, k: int) -> bool:
    """Shapes the kernel handles well: k within one lane group of the
    top-k queue (the reference warpsort caps k at 256,
    select_warpsort.cuh:100) and a db tile that fits VMEM."""
    return k <= 256 and d <= 1024 and n >= 1 and m >= 1


def fused_knn(queries, db, k: int, *, metric: str = "l2", sqrt: bool = False,
              bq: int = 256, bd: int = 0, bf16: bool = False,
              qsplit: bool = False,
              interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Fused exact kNN. ``metric`` is "l2" (squared L2, optionally sqrt'd)
    or "ip" (max inner product). ``bd=0`` picks the db tile from the db
    size (measured on v5e: 1024 below ~32k rows, 2048 above). Returns
    (distances (m,k), indices (m,k)).
    """
    queries = jnp.asarray(queries)
    db = jnp.asarray(db)
    if queries.dtype != jnp.float32:
        queries = queries.astype(jnp.float32)
    if db.dtype != jnp.float32:
        db = db.astype(jnp.float32)
    k = int(min(k, db.shape[0]))
    if bd == 0:
        bd = 1024 if db.shape[0] <= 32768 else 2048
    # Keep the double-buffered db block within a VMEM budget as the feature
    # dim grows (the role of the reference's free-memory-based tile sizing,
    # knn_brute_force.cuh:71).
    dp = round_up_safe(queries.shape[1], _LANES)
    while bd > 256 and bd * dp * 4 > 4 * 1024 * 1024:
        bd //= 2
    bd = min(bd, round_up_safe(db.shape[0], _LANES))
    bq = min(bq, round_up_safe(queries.shape[0], 8))
    return _fused_knn(queries, db, k, metric == "l2", sqrt, bq, bd, bf16,
                      qsplit, interpret)
