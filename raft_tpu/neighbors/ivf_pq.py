"""IVF-PQ: product-quantized inverted-file index.

Ref: cpp/include/raft/neighbors/ivf_pq.cuh with types at
neighbors/ivf_pq_types.hpp (``codebook_gen`` :43, ``pq_bits`` 4–8 :68,
``pq_dim`` :81, random rotation :97, ``search_params.lut_dtype /
internal_distance_dtype`` :122-131, bit-packed interleaved ``list_spec``
:172-209), build at detail/ivf_pq_build.cuh:1074 (trainset → balanced
kmeans → residuals → ``train_per_subset``:393 / ``train_per_cluster``:473 →
``extend``:873 → ``process_and_fill_codes``:724) and search at
detail/ivf_pq_search.cuh:1551 (``select_clusters``:133 gemm+select_k, query
rotation gemm, ``compute_similarity_kernel``:611 — smem LUT built per
(query, probe), packed-code scan with LUT gathers — then select_k:1413 and
postprocessing :373/:401).

TPU-native re-design:

* codebooks are trained with a **vmapped vector-quantization EM** — all
  ``pq_dim`` subspace codebooks (or all ``n_lists`` per-cluster codebooks)
  train simultaneously as one batched program on the MXU, replacing the
  reference's per-subspace kernel launches;
* codes are stored **bit-packed** (⌈pq_dim·pq_bits/8⌉ bytes per row, the
  memory layout parity of the reference's ``list_spec``,
  ivf_pq_types.hpp:172-209) in the same capacity-padded list tensor layout
  as IVF-Flat; pack/unpack are branch-free vectorized bitfield ops over
  static per-subspace byte/shift tables, so the scan engine unpacks one
  probed list tile at a time on the VPU;
* the search LUT scan is a ``lax.scan`` over probe ranks: each step builds
  the (q, pq_dim, 2^bits) LUT for the probed cluster (batched matmul
  epilogue of the residual), scores the probed list with a batched
  ``take_along_axis`` gather over the code axis, and folds a running
  top-k — the role of ``compute_similarity_kernel`` + warp select.
"""

from __future__ import annotations

import enum
import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu.core.error import expects
from raft_tpu.core.mdarray import as_array, validate_idx_dtype
from raft_tpu.cluster.kmeans_types import KMeansBalancedParams
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.distance.distance_types import DistanceType
from raft_tpu.matrix.select_k import select_k
from raft_tpu.ops import pallas_interpret
from raft_tpu.neighbors.ivf_flat import (
    _CELL_QROWS,       # single definition of the cells packing width —
    _CELLS_MAX_K,      # a drifted local copy would mismatch the kernels
    _append_in_place,
    _auto_cap_cache,
    _auto_id_base,
    _bucketed_probe_scan,
    _chunked_over_queries,
    _invert_probe_map,
    _invert_probe_map_cells,
    _pack_lists,
    _pad_deleted,
    _pick_engine,
    _route_candidates,
    _select_cells_ids,
    _track_next_id,
)
from raft_tpu.random.rng_state import RngState
from raft_tpu.util.pow2 import ceildiv, next_pow2
from raft_tpu.core.nvtx import traced


class CodebookGen(enum.Enum):
    """Ref: ivf_pq::codebook_gen (ivf_pq_types.hpp:43)."""

    PER_SUBSPACE = 0
    PER_CLUSTER = 1


# ---------------------------------------------------------------------------
# Bit-packed code storage (ref: the bit-compressed interleaved list_spec,
# ivf_pq_types.hpp:172-209 — here a flat byte stream per row, with the
# per-subspace byte offset/shift tables resolved at trace time).


def packed_row_bytes(pq_dim: int, pq_bits: int) -> int:
    return ceildiv(pq_dim * pq_bits, 8)


def _bitfield_tables(pq_dim: int, pq_bits: int):
    """Static (byte_idx, shift) of each subspace's b-bit field within the
    row byte stream; every field spans at most two bytes (pq_bits ≤ 8)."""
    bitpos = np.arange(pq_dim, dtype=np.int64) * pq_bits
    return (jnp.asarray(bitpos // 8, jnp.int32),
            jnp.asarray(bitpos % 8, jnp.int32))


def pack_codes(codes: jax.Array, pq_bits: int) -> jax.Array:
    """(…, pq_dim) code ids → (…, packed_row_bytes) uint8. Fields never
    overlap, so the two byte-projections of each field scatter-add without
    carries (add ≡ or)."""
    pq_dim = codes.shape[-1]
    nbytes = packed_row_bytes(pq_dim, pq_bits)
    byte_idx, shift = _bitfield_tables(pq_dim, pq_bits)
    u = codes.astype(jnp.int32) << shift                  # ≤ 16 bits
    lead = codes.shape[:-1]
    out = jnp.zeros(lead + (nbytes + 1,), jnp.int32)
    out = out.at[..., byte_idx].add(u & 0xFF)
    out = out.at[..., byte_idx + 1].add(u >> 8)
    return out[..., :nbytes].astype(jnp.uint8)


def unpack_codes(packed: jax.Array, pq_dim: int, pq_bits: int) -> jax.Array:
    """(…, packed_row_bytes) uint8 → (…, pq_dim) int32 code ids."""
    byte_idx, shift = _bitfield_tables(pq_dim, pq_bits)
    p = packed.astype(jnp.int32)
    pad = jnp.zeros(packed.shape[:-1] + (1,), jnp.int32)
    p = jnp.concatenate([p, pad], axis=-1)
    u16 = p[..., byte_idx] | (p[..., byte_idx + 1] << 8)
    return (u16 >> shift) & ((1 << pq_bits) - 1)


@dataclass
class IndexParams:
    """Ref: ivf_pq::index_params (ivf_pq_types.hpp:50-100); names/defaults
    preserved. ``pq_dim=0`` auto-selects dim/2 rounded to a multiple of 8
    like the reference's heuristic (calculate_pq_dim, ivf_pq_build.cuh)."""

    n_lists: int = 1024
    metric: DistanceType = DistanceType.L2Expanded
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    pq_bits: int = 8
    pq_dim: int = 0
    codebook_kind: CodebookGen = CodebookGen.PER_SUBSPACE
    force_random_rotation: bool = False
    # TPU extension (no 23.04 analog; the 23.04 surface stops at
    # force_random_rotation): rounds of OPQ-style alternation between
    # codebook training and the orthogonal-Procrustes rotation update.
    # 0 = off (reference behavior). Helps anisotropic residual clouds;
    # see build() step 3b.
    opq_iters: int = 0
    add_data_on_build: bool = True
    conservative_memory_allocation: bool = False
    # TPU extension: build() keeps a REFERENCE to the dataset on the
    # index (no copy — the caller's array is kept alive) so
    # SearchParams.min_recall can refine internally. False releases it
    # with the caller's last reference — the index then holds packed
    # codes only (the PQ compression story), and recall-class requests
    # need an explicit search_refined(dataset=...).
    retain_dataset: bool = True
    # Neighbor-id dtype: int32 (default) or int64 (reference IdxT parity;
    # requires jax_enable_x64). See ivf_flat.IndexParams.idx_dtype.
    idx_dtype: object = jnp.int32


@dataclass
class SearchParams:
    """Ref: ivf_pq::search_params (ivf_pq_types.hpp:110-135). ``lut_dtype``
    / ``internal_distance_dtype`` accept jnp dtypes (fp32/bf16/fp16, plus
    ``uint8`` for lut_dtype — an affine per-(query, subspace) quantized LUT,
    the analog of the reference's fp_8bit, ivf_pq_search.cuh:70);
    lower-precision LUTs trade recall for VMEM footprint exactly like the
    reference's fp8/fp16 LUT options. ``internal_distance_dtype`` is the
    dtype scores are accumulated and top-k-carried in on the LUT scan
    path (bf16/f16 halve the score-tensor bandwidth; returned distances
    are always f32); unsupported dtypes raise."""

    n_probes: int = 20
    lut_dtype: object = jnp.float32
    internal_distance_dtype: object = jnp.float32
    # TPU extension (see ivf_flat.SearchParams): "bucketed" scores probed
    # lists as MXU matmuls against the bf16 reconstruction cache
    # (Index.reconstructed) instead of LUT gathers; "scan" is the LUT path.
    engine: str = "auto"
    bucket_cap: int = 0
    # TPU extension (ISSUE 14): quantize the compressed-tier codeword
    # tables to int8 with per-row symmetric scales (the fp_8bit recipe
    # applied to the VMEM-resident codebook, ops/pq_scan.book_tables) —
    # half the resident table bytes; the kernel dequantizes per cell.
    # Recall-bounded, not exact: each table component moves by at most
    # max|row|/254, the same order as the bf16 scoring noise
    # (docs/serving.md records the measured impact). Single-chip
    # compressed tier only; ignored by the other tiers.
    compressed_lut_int8: bool = False
    # TPU extension: requested recall class. Plain 8-bit PQ saturates
    # near 0.8 recall@10 on structureless query regimes (0.791 uniform,
    # 0.839 SIFT-u8 at 1M, BENCH_r05);
    # a request above _REFINE_RECALL_CLASS makes search() run the
    # reference's over-retrieve + exact-refine recipe internally
    # (neighbors/refine.cuh pairing) against the dataset retained on the
    # index (Index._source; build() keeps a reference when ids are the
    # default row numbering). None = never refine (reference behavior).
    min_recall: Optional[float] = None


def validate_search_dtypes(params: "SearchParams"):
    """Validate the LUT/score dtype knobs (ref: the smem_lut_dtype /
    score_t dispatch, ivf_pq_types.hpp:122-131) — shared by the
    single-device and sharded search entries. Returns the two dtypes."""
    internal_dtype = jnp.dtype(params.internal_distance_dtype)
    expects(internal_dtype in (jnp.dtype(jnp.float32),
                               jnp.dtype(jnp.bfloat16),
                               jnp.dtype(jnp.float16)),
            "internal_distance_dtype must be float32, bfloat16 or float16 "
            f"(got {internal_dtype}); ref ivf_pq_types.hpp:122-131")
    lut_dtype = jnp.dtype(params.lut_dtype)
    expects(lut_dtype in
            (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16),
             jnp.dtype(jnp.float16), jnp.dtype(jnp.uint8)),
            f"lut_dtype must be f32/bf16/f16/u8 (got {params.lut_dtype})")
    return lut_dtype, internal_dtype


@dataclass
class Index:
    """Trained IVF-PQ index (ref: ivf_pq::index, ivf_pq_types.hpp:285-530).

    ``pq_centers`` layout: PER_SUBSPACE (pq_dim, 2^bits, pq_len);
    PER_CLUSTER (n_lists, 2^bits, pq_len).
    """

    metric: DistanceType
    codebook_kind: CodebookGen
    centers: jax.Array            # (n_lists, dim)
    rotation_matrix: jax.Array    # (rot_dim, dim)
    pq_centers: jax.Array
    pq_codes: jax.Array           # (n_lists, cap, packed_row_bytes) uint8
    indices: jax.Array            # (n_lists, cap) int32
    list_sizes: jax.Array         # (n_lists,) int32
    pq_bits: int = 8
    pq_dim: int = 0
    conservative_memory_allocation: bool = False
    # Monotonic content version, bumped by every extend — the serving
    # layer's cache-invalidation key (serve/cache.py), same contract as
    # the sharded indexes (parallel/ivf.py). Process-local: not
    # serialized (a reload re-validates caches by construction).
    epoch: int = 0
    # Lazy bf16 reconstruction cache (n_lists, cap, rot_dim) backing the
    # recon-tier bucketed search engine; see reconstructed(). Not
    # serialized.
    _recon: Optional[jax.Array] = None
    # Lazy compressed-scan operands (transposed codes + per-list absolute
    # codeword tables); see compressed_scan_operands(). Not serialized.
    _scan_ops: Optional[tuple] = None
    # int8-table variant of _scan_ops (SearchParams.compressed_lut_int8);
    # cached separately so flipping the flag never rebuilds the other.
    _scan_ops_i8: Optional[tuple] = None
    # Reference to the dataset the index was built over, kept only while
    # the stored ids are the default global row numbering (build/extend
    # with default indices). Enables SearchParams.min_recall's internal
    # exact-refine without a separate API; a reference, not a copy — the
    # caller's array is simply kept alive. Not serialized (load() leaves
    # it None; attach via refine-capable search_refined instead).
    _source: Optional[jax.Array] = None
    # Tombstone mask (raft_tpu/lifecycle): slot j of list l is deleted
    # iff ``deleted[l, j]`` — a traced operand of every scan tier (the
    # compressed tier folds it into the cached ``invalid`` operand), so
    # deleting more rows never retraces. Serialized only when any slot
    # is tombstoned.
    deleted: Optional[jax.Array] = None   # (n_lists, cap) bool
    # Host-side count of tombstoned slots (drives compaction triggers).
    n_deleted: int = 0
    # Next auto-assigned id — see ivf_flat.Index._next_id.
    _next_id: Optional[int] = None

    def __post_init__(self):
        # pq_dim is load-bearing (codes are bit-packed, so it is no longer
        # derivable from pq_codes.shape) — fail at construction, not at the
        # first pq_len division. The cross-tensor checks make a corrupted
        # file fail HERE instead of searching silently wrong.
        expects(self.pq_dim > 0, "Index requires pq_dim > 0")
        expects(self.pq_codes.shape[0] == self.indices.shape[0]
                == self.list_sizes.shape[0] == self.centers.shape[0],
                "n_lists mismatch across index tensors")
        expects(self.pq_codes.shape[1] == self.indices.shape[1],
                "list capacity mismatch between pq_codes and indices")
        expects(self.pq_codes.shape[2]
                == packed_row_bytes(self.pq_dim, self.pq_bits),
                "pq_codes row bytes inconsistent with pq_dim/pq_bits")

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def rot_dim(self) -> int:
        return self.rotation_matrix.shape[0]

    @property
    def pq_len(self) -> int:
        return self.rot_dim // self.pq_dim

    @property
    def pq_book_size(self) -> int:
        return 1 << self.pq_bits

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
        """Drop the memoized query-distribution measurements: the
        auto-engine bucket capacity and the refine recipe's probe
        concentration (both measured from the first query batch of each
        shape). The bf16 reconstruction cache is kept — it depends only
        on the stored codes, not on the query distribution (extend()
        invalidates both)."""
        self.__dict__.pop("_auto_cap_cache", None)
        self.__dict__.pop("_conc_cache", None)

    def compressed_scan_operands(self, int8_lut: bool = False) -> tuple:
        """Cached operands of the compressed-domain Pallas scan
        (ops/pq_scan.py): ``(codesT, lo, hi, invalid, crot_p)`` — the
        transposed packed codes (= codes size, pre-padded to the
        kernel's group width so no per-search copy of the index is
        made), the SHARED codeword tables (rot_dim·max(B,128) f32,
        ~130 KB — the per-list center component moved to the query side,
        see ops/pq_scan.book_tables), the padded slot-validity mask,
        and the permuted rotated centers the query shift needs. Rebuilt
        lazily after extend(); PER_SUBSPACE + pq_bits∈{4,8} only.
        ``int8_lut`` (SearchParams.compressed_lut_int8) returns the
        int8-quantized tables instead, with their per-row scale array
        appended: ``(codesT, lo8, hi8, invalid, crot_p, scale)``. The
        heavy base operands (codesT/invalid/crot_p — codes-sized) are
        built once and SHARED by reference between the two variants;
        only the ~130 KB tables differ per cache slot."""
        from raft_tpu.ops.pq_scan import book_tables

        if int8_lut:
            if self._scan_ops_i8 is None:
                codesT, _, _, invalid, crot_p = \
                    self.compressed_scan_operands()
                lo, hi, scale = book_tables(self.pq_centers, self.pq_bits,
                                            int8=True)
                ops = (codesT, lo, hi, invalid, crot_p, scale)
                if isinstance(codesT, jax.core.Tracer):
                    return ops
                object.__setattr__(self, "_scan_ops_i8", ops)
            return self._scan_ops_i8
        if self._scan_ops is None:
            from raft_tpu.ops.pq_scan import _SC, permute_subspaces
            cap = self.pq_codes.shape[1]
            capp = ceildiv(cap, _SC) * _SC
            codesT = jnp.swapaxes(self.pq_codes, 1, 2)
            if capp != cap:
                codesT = jnp.pad(codesT, ((0, 0), (0, 0), (0, capp - cap)))
            invalid = (jnp.arange(capp, dtype=jnp.int32)[None, :]
                       >= self.list_sizes[:, None])
            if self.deleted is not None:
                # Tombstones ride the existing invalid operand — same
                # shape, so a delete never changes the compiled program
                # (delete() drops _scan_ops; the rebuild lands here).
                invalid |= jnp.pad(self.deleted,
                                   ((0, 0), (0, capp - cap)))
            centers_rot = jnp.matmul(self.centers, self.rotation_matrix.T,
                                     precision=lax.Precision.HIGHEST)
            crot_p = permute_subspaces(centers_rot, self.pq_dim,
                                       self.pq_bits)
            lo, hi = book_tables(self.pq_centers, self.pq_bits)
            ops = (codesT, lo, hi, invalid, crot_p)
            if isinstance(codesT, jax.core.Tracer):
                return ops
            object.__setattr__(self, "_scan_ops", ops)
        return self._scan_ops

    def reconstructed(self) -> jax.Array:
        """Absolute reconstruction of every stored vector in rotated space,
        bf16: ``recon[l, c] = R·center_l + codeword(codes[l, c])``.

        ADC scoring (the LUT of compute_similarity_kernel,
        ivf_pq_search.cuh:611) is exactly ``‖R·q − recon‖²`` because the
        rotation is orthonormal and the subspaces are disjoint — so search
        can run as a plain fused L2 kNN over this cache on the MXU instead
        of LUT gathers (the decision point flagged in SURVEY.md §7). bf16
        storage adds ~0.4% noise on top of the PQ quantization itself.
        Cached on first use; n_lists·cap·rot_dim·2 bytes of *padded*
        capacity (plus a transient f32 intermediate ~2× that during
        construction) — this trades PQ's compression back for speed, so
        engine="auto" only engages it below _RECON_AUTO_BYTES; larger
        indexes need an explicit engine="bucketed" (or stay on "scan").

        Call this eagerly once before wrapping ``search`` in jit/scan:
        under a trace the cache cannot persist, and inside a ``lax.scan``
        body XLA will re-run the decode every iteration.
        """
        if self._recon is None:
            n_lists, cap, _ = self.pq_codes.shape
            J = self.pq_dim
            B, L = self.pq_book_size, self.pq_len
            per_cluster = self.codebook_kind == CodebookGen.PER_CLUSTER
            flat_books = self.pq_centers.reshape(-1)
            centers_rot = jnp.matmul(self.centers, self.rotation_matrix.T,
                                     precision=lax.Precision.HIGHEST)

            chunk = max(1, min(n_lists, (1 << 25) // max(cap, 1)))
            if n_lists % chunk:
                chunk = 1 << (chunk.bit_length() - 1)
                while n_lists % chunk and chunk > 1:
                    chunk //= 2
            nc = n_lists // chunk
            if per_cluster:
                # each chunk needs its own books — gather flat per chunk
                books_c = self.pq_centers.reshape(nc, chunk * B * L)
                recon = lax.map(
                    lambda args: _decode_lists_block(
                        args[0], args[1], args[2], J, B, L, self.pq_bits,
                        True),
                    (self.pq_codes.reshape(nc, chunk, cap, -1),
                     centers_rot.reshape(nc, chunk, -1), books_c),
                ).reshape(n_lists, cap, J * L)
            else:
                recon = lax.map(
                    lambda args: _decode_lists_block(
                        args[0], args[1], flat_books, J, B, L,
                        self.pq_bits, False),
                    (self.pq_codes.reshape(nc, chunk, cap, -1),
                     centers_rot.reshape(nc, chunk, -1)),
                ).reshape(n_lists, cap, J * L)
            if isinstance(recon, jax.core.Tracer):
                # Called under jit: recompute per trace — never persist a
                # tracer on the index (it would poison later eager calls).
                return recon
            object.__setattr__(self, "_recon", recon)
        return self._recon


def _decode_lists_block(codes_c, crot_c, books_flat, J: int, B: int,
                        L: int, pq_bits: int, per_cluster: bool):
    """Decode a block of lists' packed codes to absolute bf16
    reconstructions — the single definition of the flat-gather codeword
    lookup (a naive per-subspace take_along_axis emits (…, L) arrays
    whose tiny trailing dim the TPU layout pads to 128 lanes — a 64×
    allocation blowup at pq_len=2, observed 64 GiB at SIFT-1M). Shared
    by Index.reconstructed and the on-the-fly _bucketed_decode_scan.
    ``books_flat`` is the global flat table (PER_SUBSPACE) or this
    block's own flat books (PER_CLUSTER)."""
    lc, cap = codes_c.shape[0], codes_c.shape[1]
    lp = jnp.arange(L, dtype=jnp.int32)
    codes2 = unpack_codes(codes_c, J, pq_bits).reshape(lc * cap, J)
    if per_cluster:
        base = jnp.repeat(jnp.arange(lc, dtype=jnp.int32) * (B * L),
                          cap)[:, None, None]
    else:
        base = (jnp.arange(J, dtype=jnp.int32) * B * L)[None, :, None]
    idx = base + codes2[:, :, None] * L + lp[None, None, :]
    cw = books_flat[idx.reshape(lc * cap, J * L)]
    cw = cw.reshape(lc, cap, J * L) + crot_c[:, None, :]
    return cw.astype(jnp.bfloat16)


@functools.partial(jax.jit,
                   static_argnums=(7, 8, 9, 10, 11, 12, 13))
def _bucketed_decode_scan(
    rotq, pq_codes, pq_centers, centers_rot, indices, list_sizes,
    probe_ids, k: int, is_ip: bool, per_cluster: bool, bucket_cap: int,
    pq_dim: int, pq_bits: int, interpret: bool = False, deleted=None,
):
    """Bucketed PQ search that decodes codes to bf16 tiles on the fly —
    no persistent reconstruction cache, so PQ keeps its compression while
    scoring rides the MXU (the in-kernel smem-LUT decode role of
    compute_similarity_kernel, ivf_pq_search.cuh:611, re-tiled: invert
    the probe map, then a lax.scan over list blocks decodes each block's
    codes — the flat-gather formulation of Index.reconstructed — and
    scores its query bucket with the fused batched kNN kernel). Peak
    extra memory is one (block, cap, rot_dim) bf16 tile instead of the
    full decompressed index.

    This is the beyond-_RECON_AUTO_BYTES tier: each search pays a full
    decode gather, so it runs ~2× the LUT scan's QPS (254 vs 139 at 1M
    measured) but far below the recon-cached engine (12K) — use it when
    the decompressed index genuinely cannot be resident."""
    from raft_tpu.ops.fused_knn import fused_batch_knn

    q, rot_dim = rotq.shape
    n_lists, cap, _ = pq_codes.shape
    J = pq_dim
    B = 1 << pq_bits
    L = rot_dim // J

    bucket, route = _invert_probe_map(probe_ids, n_lists, bucket_cap)
    qsel = jnp.maximum(bucket, 0)
    Qb = rotq[qsel]                                   # (n_lists, cap_q, d)
    invalid = (jnp.arange(cap, dtype=jnp.int32)[None, :]
               >= list_sizes[:, None])
    if deleted is not None:
        invalid |= deleted           # tombstones mask exactly like padding

    # Block size: bound the decoded bf16 tile (+ the unpack intermediate)
    # to ~32 MB and keep it a divisor of n_lists for a clean scan.
    block = max(1, min(n_lists, (1 << 24) // max(cap * rot_dim, 1)))
    block = 1 << (block.bit_length() - 1)
    while n_lists % block and block > 1:
        block //= 2
    nb = n_lists // block
    flat_books = pq_centers.reshape(-1)
    if per_cluster:
        books_blk = pq_centers.reshape(nb, block * B * L)

    def body(_, blk):
        if per_cluster:
            codes_b, crot_b, Qb_b, inv_b, fb = blk
        else:
            codes_b, crot_b, Qb_b, inv_b = blk
            fb = flat_books
        recon = _decode_lists_block(codes_b, crot_b, fb, J, B, L, pq_bits,
                                    per_cluster)
        bd_, bi_ = fused_batch_knn(Qb_b, recon, inv_b, k,
                                   metric="ip" if is_ip else "l2",
                                   bf16=True, interpret=interpret)
        return None, (bd_, bi_)

    xs = (pq_codes.reshape(nb, block, cap, -1),
          centers_rot.reshape(nb, block, rot_dim),
          Qb.reshape(nb, block, bucket_cap, rot_dim),
          invalid.reshape(nb, block, cap))
    if per_cluster:
        xs = xs + (books_blk,)
    _, (bd_, bi_) = lax.scan(body, None, xs)
    kk = bd_.shape[3]
    bd_ = bd_.reshape(n_lists, bucket_cap, kk)
    bi_ = bi_.reshape(n_lists, bucket_cap, kk)
    gi = indices[jnp.arange(n_lists, dtype=jnp.int32)[:, None, None],
                 jnp.maximum(bi_, 0)]
    gi = jnp.where(bi_ < 0, -1, gi)

    worst = -jnp.inf if is_ip else jnp.inf
    cd, ci = _route_candidates(bd_, gi, route, q, probe_ids.shape[1],
                               bucket_cap, worst)
    return select_k(cd, k, select_min=not is_ip, indices=ci)


def _compressed_eligible(params: "SearchParams", index: Index,
                         n_probes: int, k_pool: int, n_queries: int,
                         default_dtypes: bool) -> bool:
    """Single definition of the compressed-tier dispatch gate, shared by
    :func:`search` and :func:`search_refined` (two re-spelled copies
    would drift): supported config, no user recon cache, default score
    dtypes, queue width within the kernel's cap, per-list Pallas blocks
    within the VMEM budget, and — for engine="auto" — a TPU backend with
    enough probe load to beat the scan engine."""
    return (index._recon is None and _compressed_tier_ok(
        params.engine, _compressed_supported(index), default_dtypes,
        k_pool, index.pq_codes.shape[1], index.pq_codes.shape[2],
        index.rot_dim, n_queries, n_probes, index.n_lists))


def _compressed_tier_ok(engine: str, supported: bool, default_dtypes: bool,
                        k_pool: int, cap: int, nbytes: int, rot_dim: int,
                        n_queries: int, n_probes: int,
                        n_lists: int) -> bool:
    """Scalar core of the compressed-tier gate, also used by the sharded
    search (parallel/ivf.py, with the per-SHARD cap/nbytes) so the
    single-chip and multi-chip dispatch cannot drift."""
    if not (engine in ("auto", "bucketed") and supported
            and default_dtypes and k_pool <= _CELLS_MAX_K):
        return False
    if not _compressed_vmem_ok(cap, nbytes, rot_dim):
        return False
    if engine == "bucketed":
        return True
    load = n_queries * n_probes / max(n_lists, 1)
    return jax.default_backend() == "tpu" and load >= 8


def _compressed_vmem_ok(cap: int, nbytes: int, rot_dim: int) -> bool:
    """VMEM gate for the compressed-tier per-list Pallas blocks (the
    IVF-Flat cells tier gates the same way on _CELL_VMEM_BYTES): the
    dominant per-grid-cell operands are the transposed code block
    (nbytes, capp) u8, the slot mask (1, capp) and the two absolute
    tables (rot_dim, 128) f32 each. An index with few, very large lists
    (small n_lists at multi-million scale) would otherwise fail at
    Mosaic compile time instead of falling through to the recon/LUT
    tiers."""
    from raft_tpu.ops.pq_scan import _SC
    capp = ceildiv(max(cap, 1), _SC) * _SC
    block_bytes = nbytes * capp + capp + 2 * rot_dim * 128 * 4
    return block_bytes <= _PQ_CELL_BYTES


# Per-list VMEM budget for the compressed-scan blocks (double-buffered by
# the pipeline, so this is ~half the usable VMEM after queries/outputs).
_PQ_CELL_BYTES = 6 * 1024 * 1024


def _compressed_supported(index: Index) -> bool:
    """The compressed-domain Pallas scan covers the default config family:
    per-subspace codebooks with byte-aligned code fields (pq_bits=8, or
    pq_bits=4 with an even pq_dim — odd pq_dim leaves a half-byte field
    the nibble unpack cannot split). Other configs fall back to the
    recon / LUT-scan tiers."""
    return (index.codebook_kind == CodebookGen.PER_SUBSPACE
            and (index.pq_bits == 8
                 or (index.pq_bits == 4 and index.pq_dim % 2 == 0)))


@functools.partial(jax.jit,
                   static_argnums=(9, 10, 11, 12, 13, 14, 15, 16))
def _compressed_search(Q, centers, rot, codesT, abs_lo, abs_hi, invalid,
                       indices, crot_p, n_probes: int, k: int,
                       is_ip: bool, J: int, bits: int, qrows: int,
                       interpret: bool = False, cell_k: int = 0,
                       int8_lut=None):
    """The compressed-domain tier as ONE jitted program — coarse probe,
    rotation, cells inversion, Pallas scan, routing and the final merge:
    eager op-by-op orchestration of the same pipeline pays one dispatch
    per op and leaves the device waiting on the host between them.
    Index tensors ride as arguments so they are not baked into the HLO
    as constants.

    ``cell_k`` < k bounds the per-(query, probe) queue at cell_k while
    the final merge still keeps k of the pooled n_probes·cell_k
    candidates — the FAST over-retrieve mode of :func:`search_refined`
    (the in-kernel queue cost is linear in its k). 0 means exact
    (cell_k = k). The bound is a REGIME trade-off: on clustered data
    the whole true top-pool can live in the query's best list, where a
    per-probe top-cell_k forfeits it (measured at 1M: SIFT-u8 refined
    recall froze at 0.814 for ratio 2→16 under the bound, vs 0.974
    unbounded at ratio 2; structureless queries spread the pool over
    probes and lose nothing — 0.924 vs 0.933). A rank-split two-launch
    variant (pool-deep queue for the best 2 probe ranks only) was built
    and measured NO better than unbounding everything — a 2-of-48-rank
    launch alone cost 82 ms vs the full 48-rank launch's 104 ms, the
    per-launch floor dominating — so the dispatch stays single-launch
    and search() maps recall classes to the bound instead."""
    from raft_tpu.ops.pq_scan import permute_subspaces

    probe_ids = _select_clusters((Q, centers), n_probes, is_ip)
    rotq = jnp.matmul(Q, rot.T, precision=lax.Precision.HIGHEST)
    rotq_p = permute_subspaces(rotq, J, bits)
    return _compressed_scan_probes(rotq_p, probe_ids, codesT, abs_lo,
                                   abs_hi, invalid, indices, crot_p, k,
                                   is_ip, J, bits, qrows, interpret,
                                   cell_k=cell_k, int8_lut=int8_lut)


def _compressed_scan_probes(rotq_p, probe_ids, codesT, abs_lo, abs_hi,
                            invalid, indices, crot_p, k: int, is_ip: bool,
                            J: int, bits: int, qrows: int,
                            interpret: bool = False, cell_k: int = 0,
                            int8_lut=None):
    """Scan the GIVEN probed lists with the compressed-domain Pallas
    kernel: cells inversion, residual query shift, scan, routing and the
    per-query merge — returns best-first ``(q, k)`` candidates in true
    metric values (ip un-negated), no sqrt. The probe-chunkable core
    shared by :func:`_compressed_search` and the sharded fused
    scan→merge pipeline (parallel/ivf.py feeds one probe-column chunk at
    a time so each chunk's merge collective overlaps the next chunk's
    scan). ``rotq_p`` is the rotated queries already in the kernel's
    permuted subspace order. ``int8_lut`` is the optional quantized
    codeword-table tuple (``book_tables(..., int8=True)``'s scale/zero
    tail — abs_lo/abs_hi are then int8; see ops/pq_scan.py)."""
    from raft_tpu.ops.pq_scan import pq_fused_scan

    q, n_lists = rotq_p.shape[0], codesT.shape[0]
    cell_k = cell_k or k
    cell_list, bucket, route = _invert_probe_map_cells(
        probe_ids, n_lists, qrows)
    Qc = rotq_p[jnp.maximum(bucket, 0)]            # (max_cells, qrows, d)
    safe_cl = jnp.maximum(cell_list, 0)
    if not is_ip:
        # Residual-scale operands (book_tables): shift each cell's query
        # rows by its list's rotated center — ‖(q−c) − cw‖² ≡ the
        # absolute ADC distance, scored at residual magnitude where bf16
        # rounding is relative to the signal, not the embedding offset.
        Qc = Qc - crot_p[safe_cl][:, None, :]

    bd_, bi_ = pq_fused_scan(cell_list, Qc, codesT, abs_lo, abs_hi,
                             invalid, cell_k, J, bits, is_ip, interpret,
                             int8_lut=int8_lut)
    if is_ip:
        # score = q·(c + cw) = q·c + q·cw; the kernel reports −(q·cw).
        # q·c is constant within a cell, so adding it after the in-cell
        # selection preserves the selected set; the cross-cell merge
        # then ranks by the corrected totals. Computed in f32 HIGHEST
        # (permutation-invariant dot: rotq_p·crot_p ≡ rotq·crot).
        qc = jnp.matmul(rotq_p, crot_p.T,
                        precision=lax.Precision.HIGHEST)  # (q, n_lists)
        qc_pair = qc[jnp.maximum(bucket, 0), safe_cl[:, None]]
        bd_ = bd_ - qc_pair[:, :, None]
    # The kernel reports min-selection order for both metrics (negated
    # inner products); undo the negation after the final merge.
    best_d, best_i = _select_cells_ids(bd_, bi_, cell_list, indices, route,
                                       q, probe_ids.shape[1], k, "ivf_pq")
    if is_ip:
        best_d = -best_d
    return best_d, best_i


def _as_float(x) -> jax.Array:
    x = as_array(x)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(jnp.float32)
    return x


def _calculate_pq_dim(dim: int) -> int:
    """Ref: calculate_pq_dim (ivf_pq_build.cuh) — roughly dim/2, a multiple
    of 8, at least 1."""
    if dim <= 8:
        return max(1, dim // 2)
    r = dim // 2
    return max(8, (r // 8) * 8)


def make_rotation_matrix(
    key, dim: int, rot_dim: int, force_random: bool
) -> jax.Array:
    """(rot_dim, dim) orthonormal transform.

    Ref: make_rotation_matrix (ivf_pq_build.cuh) — identity-with-zero-pad
    unless ``force_random_rotation`` or rot_dim != dim, in which case the Q
    factor of a random normal matrix is used.
    """
    if not force_random and rot_dim == dim:
        return jnp.eye(dim, dtype=jnp.float32)
    if not force_random:
        # Pad-identity: rows are unit basis vectors (lossless embed).
        return jnp.eye(rot_dim, dim, dtype=jnp.float32)
    g = jax.random.normal(key, (max(rot_dim, dim), max(rot_dim, dim)), jnp.float32)
    q, _ = jnp.linalg.qr(g)
    return q[:rot_dim, :dim]


# ---------------------------------------------------------------------------
# Batched VQ codebook training (the role of train_per_subset:393 /
# train_per_cluster:473 — one small k-means per codebook, run as a single
# vmapped program here).


@functools.partial(jax.jit, static_argnums=(3, 4))
def _vq_train_batched(key, data, weights, book_size: int, n_iters: int,
                      init=None):
    """Train B codebooks at once: data (B, n, l), weights (B, n) — 0 weight
    masks padded rows. Returns (B, book_size, l). ``init`` warm-starts the
    EM from existing codebooks (B, book_size, l) — the OPQ alternation
    refines the previous iteration's books instead of re-seeding, which is
    what makes the rotation/codebook coordinate descent actually converge."""
    B, n, l = data.shape

    if init is not None:
        centers0 = init
    else:
        # Init: strided samples (valid rows first — padded rows carry
        # weight 0 but a strided pick over the sorted-valid layout is good
        # enough; the packing routine places valid rows first).
        stride = max(n // book_size, 1)
        centers0 = data[:, ::stride][:, :book_size]
        if centers0.shape[1] < book_size:
            reps = ceildiv(book_size, centers0.shape[1])
            centers0 = jnp.tile(centers0, (1, reps, 1))[:, :book_size]

    def em(_, centers):
        # (B, n, book) squared distances via batched matmul.
        d = (
            jnp.sum(data * data, axis=2)[:, :, None]
            + jnp.sum(centers * centers, axis=2)[:, None, :]
            - 2.0 * jnp.einsum("bnl,bkl->bnk", data, centers,
                               precision=lax.Precision.HIGHEST)
        )
        lab = jnp.argmin(d, axis=2)                       # (B, n)
        w = weights
        onehot = jax.nn.one_hot(lab, book_size, dtype=data.dtype)  # (B, n, k)
        wo = onehot * w[:, :, None]
        sums = jnp.einsum("bnk,bnl->bkl", wo, data)
        counts = jnp.sum(wo, axis=1)                      # (B, k)
        new = sums / jnp.maximum(counts, 1e-6)[:, :, None]
        return jnp.where((counts > 0)[:, :, None], new, centers)

    return lax.fori_loop(0, n_iters, em, centers0)


# Row-chunk length for encode: the per-chunk distance block is
# (chunk, pq_dim, book) f32 — at pq_dim=64, book=256 that is 64 KB/row, so
# 4096 rows bound the workspace at 256 MB; chunking keeps encode
# O(chunk·pq_dim·book) in HBM instead of materializing it for all n rows at
# once (the reference's process_and_fill_codes kernel never materializes it
# at all, ivf_pq_build.cuh:629 — it encodes as it packs).
_ENCODE_CHUNK = 4096

# engine="auto" only switches to the reconstruction-cache search while the
# (padded) bf16 cache stays below this; beyond it, the cache would defeat
# PQ's compression — the user must opt in with engine="bucketed".
_RECON_AUTO_BYTES = 4 * 1024 ** 3

# Native (unrefined) 8-bit PQ saturates near 0.8 recall@10 on
# structureless regimes (BENCH_r05); a SearchParams.min_recall above
# this makes search() run the exact-refine recipe internally.
_REFINE_RECALL_CLASS = 0.84

# Probe-concentration threshold below which the refine recipe's bounded
# per-cell queue is safe (see _probe_concentration).
_CONC_BOUND_SAFE = 0.5


@jax.jit
def _probe_concentration(Q, centers):
    """Median over queries of (d₍₁₎−d₍₀₎)/(d₍₁₎+d₍₀₎) of the coarse L2
    distances: →1 when each query sits INSIDE its best list's cluster
    (the true candidate pool then concentrates in that one probed list,
    where a per-probe top-k queue forfeits it), →0 when the two nearest
    centers are equidistant (structureless queries spread the pool over
    probes). Measured across the bench regimes, with the refined
    0.86-class recall the bounded queue achieves there:
    uniform-1M 0.01 (0.924 ✓) · clustered-loose-1M 0.40 (0.872 ✓) ·
    tight-blobs-200K 0.56 (0.687 ✗) · SIFT-u8-1M 0.82 (0.814 ✗) —
    _CONC_BOUND_SAFE = 0.5 sits exactly on the meets/fails boundary.
    One (q, n_lists) matmul + sort, measured once per (index, batch
    shape) and memoized like the bucket-capacity heuristic
    (_pick_engine)."""
    cn = jnp.sum(centers * centers, axis=1)
    cd = (jnp.sum(Q * Q, axis=1)[:, None] + cn[None, :]
          - 2.0 * jnp.matmul(Q, centers.T))
    cd = jnp.maximum(cd, 0.0)
    top2, _ = jax.lax.top_k(-cd, 2)          # only the 2 nearest needed
    d0, d1 = -top2[:, 0], -top2[:, 1]
    return jnp.median((d1 - d0) / jnp.maximum(d1 + d0, 1e-9))

# Row cap for the OPQ alternation's sub-trainset (see build step 3b).
_OPQ_TRAIN_ROWS = 100_000

def _chunked_rows(fn, *arrays):
    """Apply ``fn(rows...) -> (chunk, ...)`` over row chunks of
    ``arrays`` in a host loop (one compiled chunk shape: the tail chunk
    pads with zero rows)."""
    n = arrays[0].shape[0]
    if n <= _ENCODE_CHUNK:
        return fn(*arrays)
    outs = []
    for s in range(0, n, _ENCODE_CHUNK):
        chunk = [a[s:s + _ENCODE_CHUNK] for a in arrays]
        pad = _ENCODE_CHUNK - chunk[0].shape[0]
        if pad:
            chunk = [jnp.concatenate([c, jnp.zeros((pad,) + c.shape[1:],
                                                   c.dtype)])
                     for c in chunk]
        outs.append(fn(*chunk))
    return jnp.concatenate(outs)[:n]


@jax.jit
def _nearest_codes(r, books):
    """Nearest codeword per subspace: residuals (n, pq_dim, l) against
    books that broadcast to (n, pq_dim, k, l) → (n, pq_dim) int32, from
    squared differences summed over the l components (exact f32, no
    matmul)."""
    d = jnp.sum((r[:, :, None, :] - books) ** 2, axis=3)
    return jnp.argmin(d, axis=2).astype(jnp.int32)


def _encode(residuals: jax.Array, pq_centers: jax.Array) -> jax.Array:
    """Nearest-codeword ids per subspace: residuals (n, pq_dim, l) against
    per-subspace books (pq_dim, k, l) → (n, pq_dim) int32 (ref:
    process_and_fill_codes kernel's encode step, ivf_pq_build.cuh:629).
    Chunked over rows to bound the (chunk, pq_dim, book) workspace.

    On a v5e the earlier formulation — an expanded-norm einsum over the
    l (= 2) component axis, mapped over chunks with lax.map — returned
    the nearest codeword for only a quarter of the (row, subspace)
    entries of a 20K-row build (the same einsum on 2K rows, unchunked,
    was exact), which left IVF-PQ recall@10 at 0.11. Explicit
    differences in a host chunk loop avoid both constructs."""
    return _chunked_rows(lambda r: _nearest_codes(r, pq_centers[None]),
                         residuals)


def _encode_per_cluster(residuals, labels, pq_centers) -> jax.Array:
    """PER_CLUSTER encode: each row uses its own cluster's book
    (pq_centers (n_lists, k, l)). Chunked over rows, int32 ids, like
    :func:`_encode`."""
    return _chunked_rows(
        lambda r, lab: _nearest_codes(r, pq_centers[lab][:, None]),
        residuals, labels)


def _residuals(X, labels, centers, rot, pq_dim: int) -> jax.Array:
    """Rotated residuals reshaped to (n, pq_dim, pq_len)."""
    r = X - centers[labels]
    rr = jnp.matmul(r, rot.T, precision=lax.Precision.HIGHEST)
    n = rr.shape[0]
    return rr.reshape(n, pq_dim, rot.shape[0] // pq_dim)


@traced
def build(params: IndexParams, dataset, handle=None) -> Index:
    """Train the index (ref: ivf_pq::build → detail/ivf_pq_build.cuh:1074):
    subsample → balanced kmeans coarse centers → rotated residuals →
    codebooks (per-subspace or per-cluster VQ) → extend with the dataset."""
    X = as_array(dataset)
    expects(X.ndim == 2, "dataset must be (n_rows, dim)")
    n, dim = X.shape
    expects(n >= params.n_lists, "need at least n_lists rows")
    expects(4 <= params.pq_bits <= 8, "pq_bits must be in [4, 8]")
    Xf = _as_float(X)

    pq_dim = params.pq_dim or _calculate_pq_dim(dim)
    pq_len = ceildiv(dim, pq_dim)
    rot_dim = pq_dim * pq_len
    book_size = 1 << params.pq_bits

    state = RngState(seed=0)

    # 1. trainset + coarse centers (same scheme as IVF-Flat build).
    frac = min(max(params.kmeans_trainset_fraction, 0.0), 1.0)
    n_train = max(params.n_lists * 2, int(n * frac)) if frac < 1.0 else n
    n_train = min(n_train, n)
    stride = max(1, n // n_train)
    trainset = Xf[::stride][:n_train]

    kb = KMeansBalancedParams(
        n_iters=params.kmeans_n_iters, metric=DistanceType.L2Expanded,
        rng_state=state)
    centers = kmeans_balanced.fit(kb, trainset, params.n_lists)

    # 2. rotation (ref: random-rotation QR, ivf_pq_build.cuh).
    rot = make_rotation_matrix(state.next_key(), dim, rot_dim,
                               params.force_random_rotation)

    # 3. residuals of the trainset under their cluster assignment.
    labels = kmeans_balanced.predict(kb, centers, trainset)

    # 3b. OPQ-style alternation (TPU extension beyond the 23.04 surface,
    # evaluated per VERDICT r4 item 4): alternate training throwaway
    # codebooks with the orthogonal-Procrustes rotation update
    # R ← U·Vᵀ from SVD(X̂ᵀ·Xres) — the rotation that best aligns the
    # residual cloud with its current quantization ("Optimized Product
    # Quantization", the non-parametric variant). Helps when residual
    # variance is anisotropic across the subspace split; a no-op knob
    # (0) by default.
    if params.opq_iters > 0:
        # Rotation estimation converges on far fewer rows than codebook
        # training needs — cap the OPQ sub-trainset so the alternation's
        # extra live tensors (residuals + quantized reconstruction) stay
        # ~50 MB instead of scaling with the full trainset (a 1M build
        # with the full 500K trainset OOM'd a 16 GB chip).
        stride_o = max(1, trainset.shape[0] // _OPQ_TRAIN_ROWS)
        sub = trainset[::stride_o][:_OPQ_TRAIN_ROWS]
        # The sub-trainset is an exact subsample of trainset, whose
        # labels are already computed above — no second assignment pass.
        xres = sub - centers[labels[::stride_o][:_OPQ_TRAIN_ROWS]]
    books_it = None
    for _ in range(params.opq_iters):
        res = jnp.matmul(xres, rot.T, precision=lax.Precision.HIGHEST
                         ).reshape(-1, pq_dim, pq_len)
        data = jnp.swapaxes(res, 0, 1)
        w = jnp.ones(data.shape[:2], data.dtype)
        # Warm-start each alternation from the previous books: OPQ is a
        # coordinate descent on (rotation, codebooks) — re-seeding the VQ
        # from scratch every iteration (the old behavior) discards the
        # codebook coordinate's progress and the alternation stalls at
        # ~1% MSE gain; refining the same books converges monotonically.
        books_it = _vq_train_batched(state.next_key(), data, w,
                                     book_size,
                                     max(4, params.kmeans_n_iters // 2),
                                     init=books_it)
        codes_it = _encode(res, books_it)
        # X̂ = quantized rotated residuals; Xres = unrotated residuals.
        cw = jnp.take_along_axis(
            books_it[None], codes_it[:, :, None, None].astype(jnp.int32),
            axis=2)[:, :, 0, :].reshape(res.shape[0], rot_dim)
        u, _, vt = jnp.linalg.svd(
            jnp.matmul(cw.T, xres, precision=lax.Precision.HIGHEST),
            full_matrices=False)       # U (rot, min), Vt (min, dim)
        rot = jnp.matmul(u, vt, precision=lax.Precision.HIGHEST)
    if params.opq_iters > 0:
        xres = sub = None              # release before codebook training

    res = _residuals(trainset, labels, centers, rot, pq_dim)  # (nt, pq_dim, l)

    # 4. codebooks.
    if params.codebook_kind == CodebookGen.PER_SUBSPACE:
        data = jnp.swapaxes(res, 0, 1)                    # (pq_dim, nt, l)
        w = jnp.ones(data.shape[:2], data.dtype)
        # After OPQ alternation the throwaway books are already fitted to
        # (almost) this rotation's residual geometry — warm-starting the
        # production training from them keeps the alternation's codebook
        # progress instead of re-seeding and re-converging from scratch.
        pq_centers = _vq_train_batched(state.next_key(), data, w,
                                       book_size, params.kmeans_n_iters,
                                       init=books_it if params.opq_iters > 0
                                       else None)
    else:
        # PER_CLUSTER: pack each cluster's residual sub-vectors (over all
        # pq_dim positions, ref: train_per_cluster treats all sub-vectors of
        # a cluster as one VQ training set) into padded per-cluster blocks.
        flat = res.reshape(-1, pq_len)                    # (nt*pq_dim, l)
        flat_labels = jnp.repeat(labels, pq_dim)
        ids = jnp.arange(flat.shape[0], dtype=jnp.int32)
        blocks, _, sizes = _pack_lists(flat, flat_labels, ids, params.n_lists)
        cap_t = blocks.shape[1]
        slot = jnp.arange(cap_t, dtype=jnp.int32)[None, :]
        w = (slot < sizes[:, None]).astype(jnp.float32)
        pq_centers = _vq_train_batched(state.next_key(), blocks, w,
                                       book_size, params.kmeans_n_iters)

    index = Index(
        metric=params.metric,
        codebook_kind=params.codebook_kind,
        centers=centers,
        rotation_matrix=rot,
        pq_centers=pq_centers,
        pq_codes=jnp.zeros(
            (params.n_lists, 1, packed_row_bytes(pq_dim, params.pq_bits)),
            jnp.uint8),
        indices=jnp.full((params.n_lists, 1), -1,
                         validate_idx_dtype(params.idx_dtype)),
        list_sizes=jnp.zeros((params.n_lists,), jnp.int32),
        pq_bits=params.pq_bits,
        pq_dim=pq_dim,
        conservative_memory_allocation=params.conservative_memory_allocation,
    )
    if params.add_data_on_build:
        index = extend(index, X,
                       jnp.arange(n, dtype=index.indices.dtype))
        if params.retain_dataset:
            # Stored ids are the row numbering of ``dataset`` — keep a
            # reference (not a copy) so SearchParams.min_recall can
            # refine internally. extend() maintains or drops it.
            index._source = X
    return index


def _invalidate_caches(index: Index) -> None:
    """Drop derived per-index caches after a storage mutation: the lazy
    bf16 reconstruction (stale codes/capacity would silently corrupt
    bucketed search), the compressed-scan operands, and the measured
    bucket-capacity memo."""
    index._recon = None
    index._scan_ops = None
    index._scan_ops_i8 = None
    index.reset_search_cache()


def encode_rows(model, X) -> Tuple[jax.Array, jax.Array]:
    """Assign + encode rows against a trained model: returns ``(labels,
    packed code rows)``. The single definition of the
    predict→residual→encode→pack pipeline (ref: process_and_fill_codes,
    ivf_pq_build.cuh:724) shared by ``extend``, the sharded build and the
    sharded extend — ``model`` is any object with centers /
    rotation_matrix / pq_centers / codebook_kind / pq_dim / pq_bits
    (an Index or a ShardedIvfPq).

    The residual→encode→pack stages run per ROW CHUNK: a 10M-row build
    would otherwise materialize the full (n, pq_dim, pq_len) f32
    residual tensor (5.1 GB) next to the dataset and OOM the chip —
    only the labels and the packed u8 code rows ever exist at full n
    (the reference's process_and_fill_codes encodes as it packs for
    the same reason)."""
    kb = KMeansBalancedParams(metric=DistanceType.L2Expanded)
    labels = kmeans_balanced.predict(kb, model.centers, X)
    per_cluster = model.codebook_kind == CodebookGen.PER_CLUSTER

    def enc(xc, lc):
        res = _residuals(xc, lc, model.centers, model.rotation_matrix,
                         model.pq_dim)
        codes = (_encode_per_cluster(res, lc, model.pq_centers)
                 if per_cluster else _encode(res, model.pq_centers))
        return pack_codes(codes, model.pq_bits)

    return labels, _chunked_rows(enc, X, labels)


@traced
def extend(index: Index, new_vectors, new_indices=None, *,
           donate: bool = True) -> Index:
    """Encode + append rows in place at O(n_new) amortized cost.

    Ref: ivf_pq::extend (ivf_pq_build.cuh:873 →
    process_and_fill_codes:724; list growth ivf_flat_types.hpp:65-73).
    Only the *new* rows are encoded; their packed code rows scatter into
    each list's free slots via the shared donating scatter-append, so the
    existing codes are never gathered or copied. Storage grows by padding
    to the doubled capacity on overflow. The passed ``index`` is mutated
    and returned; arrays previously read off it must be re-read after the
    call. ``donate=False`` selects the copy-on-write scatter for
    mutations racing live reader threads (see ivf_flat.extend)."""
    X = _as_float(new_vectors)
    expects(X.ndim == 2 and X.shape[1] == index.dim, "dim mismatch")
    n_new = X.shape[0]
    if n_new == 0:
        return index
    default_ids = new_indices is None
    default_base = None
    if default_ids:
        # Auto ids allocate from max(existing id) + 1 (tracked on the
        # index) — ``index.size`` would collide after an explicit-id
        # extend and after delete shrinks the live count.
        default_base = _auto_id_base(index)
        new_indices = jnp.arange(default_base, default_base + n_new,
                                 dtype=index.indices.dtype)
    else:
        new_indices = as_array(new_indices).astype(index.indices.dtype)

    # Maintain the retained-dataset reference (min_recall refine): only
    # a default-numbered append onto a same-dtype source keeps the
    # id -> source-row mapping valid (ids [base, base+n) must name
    # source rows [len(source), len(source)+n)); anything else drops it.
    if index._source is not None:
        raw = as_array(new_vectors)
        if (default_ids and index._source.shape[0] == default_base
                and raw.dtype == index._source.dtype):
            index._source = jnp.concatenate([index._source, raw])
        else:
            index._source = None

    labels, codes = encode_rows(index, X)

    old_n = index.size
    if not old_n:
        min_cap = 0
        if not index.conservative_memory_allocation:
            counts = jnp.bincount(labels, length=index.n_lists)
            min_cap = next_pow2(int(jnp.max(counts)))
        packed, ids, sizes = _pack_lists(codes, labels, new_indices,
                                         index.n_lists, min_cap)
        index.pq_codes = packed.astype(jnp.uint8)
        index.indices, index.list_sizes = ids, sizes
        # Fresh fill: no tombstones — but an enable_tombstones
        # pre-attachment survives at the new capacity (see
        # ivf_flat.extend's bulk path).
        index.deleted = (None if index.deleted is None
                         else jnp.zeros(ids.shape, bool))
        index.n_deleted = 0
        _track_next_id(index, new_indices, default_base, n_new)
        index.epoch += 1  # serving caches must not outlive old contents
        _invalidate_caches(index)
        return index

    store, ids, sizes, _ = _append_in_place(
        index.pq_codes, index.indices, index.list_sizes, codes,
        new_indices, labels, index.conservative_memory_allocation,
        donate=donate)
    index.pq_codes, index.indices, index.list_sizes = store, ids, sizes
    index.deleted = _pad_deleted(index.deleted, store.shape[1])
    _track_next_id(index, new_indices, default_base, n_new)
    index.epoch += 1      # serving caches must not outlive old contents
    _invalidate_caches(index)
    return index


def _lut_scores(lut, codes, scale=None, acc_dtype=jnp.float32):
    """score[q, c] = Σ_j LUT[q, j, codes[q, c, j]] (+ per-subspace affine
    ``scale`` for the u8 LUT) via per-subspace one-hot matmuls on the MXU.
    ``acc_dtype`` is the accumulation dtype (search_params.
    internal_distance_dtype, ivf_pq_types.hpp:122-131 — half accumulation
    halves the score-tensor bandwidth at a bounded recall cost).

    Resolves the gather-vs-one-hot decision point flagged in SURVEY.md §7:
    measured ~9× faster than ``take_along_axis`` gathers on TPU v5e at the
    (256 q, 1024 cap, 16×256 LUT) probe-step shape (55.9 → 6.4 ms), with
    f32-summation-order-level agreement. On non-MXU backends (CPU test
    mesh) the gather formulation wins, so dispatch follows the backend.
    """
    J, B = lut.shape[1], lut.shape[2]
    acc_dtype = jnp.dtype(acc_dtype)

    if jax.default_backend() != "tpu":
        g = jnp.take_along_axis(lut, codes.transpose(0, 2, 1).astype(
            jnp.int32), axis=2).astype(acc_dtype)
        if scale is not None:
            g = g * scale[:, :, None].astype(acc_dtype)
        return jnp.sum(g, axis=1)

    def body(acc, j):
        oh = jax.nn.one_hot(codes[:, :, j], B, dtype=lut.dtype)
        term = jnp.einsum("qcb,qb->qc", oh, lut[:, j],
                          precision=lax.Precision.HIGHEST,
                          preferred_element_type=acc_dtype)
        if scale is not None:
            term = term * scale[:, j][:, None].astype(acc_dtype)
        return acc + term, None

    acc, _ = lax.scan(
        body, jnp.zeros((codes.shape[0], codes.shape[1]), acc_dtype),
        jnp.arange(J))
    return acc


@functools.partial(jax.jit, static_argnums=(1, 2))
def _select_clusters(args, n_probes: int, is_ip: bool):
    """Coarse top-n_probes (ref: select_clusters, ivf_pq_search.cuh:133 —
    gemm queries×centersᵀ with the norm-column trick + select_k)."""
    Q, centers = args
    if is_ip:
        cd = jnp.matmul(Q, centers.T, precision=lax.Precision.HIGHEST)
        _, probe_ids = select_k(cd, n_probes, select_min=False)
    else:
        cn = jnp.sum(centers * centers, axis=1)
        cd = cn[None, :] - 2.0 * jnp.matmul(Q, centers.T,
                                            precision=lax.Precision.HIGHEST)
        _, probe_ids = select_k(cd, n_probes, select_min=True)
    return probe_ids


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9, 10, 11))
def _pq_probe_scan(
    rotq, probe_ids, pq_codes, indices, list_sizes,
    k: int, is_ip: bool, per_cluster: bool, lut_dtype,
    pq_dim: int, pq_bits: int, internal_dtype=jnp.float32,
    pq_centers=None, centers_rot=None, deleted=None,
):
    """LUT-scored probe scan (ref: compute_similarity_kernel,
    ivf_pq_search.cuh:611 + select_k merge :1413).

    rotq: (q, rot_dim) rotated queries; centers_rot: (n_lists, rot_dim)
    rotated centers. Per probe step: residual LUT (q, pq_dim, book) from a
    batched matmul; the probed lists' bit-packed codes unpack on the VPU;
    list scores via take_along_axis gather over the code axis; running
    top-k fold. ``lut_dtype=uint8`` quantizes the LUT per (query, subspace)
    with an affine u8 code — the role of the reference's ``fp_8bit`` LUT
    (ivf_pq_search.cuh:70), trading ≤1/255-of-range error per subspace for
    a 4× smaller LUT.
    """
    q, rot_dim = rotq.shape
    n_lists, cap, _ = pq_codes.shape
    pq_len = rot_dim // pq_dim
    internal_dtype = jnp.dtype(internal_dtype)
    # ±inf exists in bf16/fp16; the carried best-k and per-step scores live
    # in internal_dtype (the reference's score_t, ivf_pq_types.hpp:122-131).
    from raft_tpu.core.sentinels import worst_value
    worst = worst_value(not is_ip, internal_dtype)
    slot = jnp.arange(cap, dtype=jnp.int32)[None, :]
    rq3 = rotq.reshape(q, pq_dim, pq_len)

    def body(carry, probe_col):
        best_d, best_i = carry
        lists = probe_col                                  # (q,)
        # Residual of each query against this probe's center, by subspace.
        c3 = centers_rot[lists].reshape(q, pq_dim, pq_len)
        books = pq_centers[lists] if per_cluster else pq_centers
        bsub = "qkl" if per_cluster else "jkl"
        bnorm_axes = (lambda b: jnp.sum(b * b, axis=2)[:, None, :]) if per_cluster \
            else (lambda b: jnp.sum(b * b, axis=2)[None, :, :])
        if is_ip:
            # score(x) ≈ q·c + (Rq)·codeword; the q·c term differs per
            # probed list and MUST be in the score or cross-list merge ranks
            # by the wrong quantity (ref: ivf_pq_search.cuh:757 adds the
            # query·cluster_center term). R has orthonormal columns, so
            # q·c = (Rq)·(Rc).
            lut = jnp.einsum(f"qjl,{bsub}->qjk", rq3, books,
                             precision=lax.Precision.HIGHEST)
            qc = jnp.sum(rq3 * c3, axis=(1, 2))            # (q,) = q·center
        else:
            r = rq3 - c3                                   # (q, pq_dim, l)
            lut = (
                jnp.sum(r * r, axis=2)[:, :, None]
                + bnorm_axes(books)
                - 2.0 * jnp.einsum(f"qjl,{bsub}->qjk", r, books,
                                   precision=lax.Precision.HIGHEST)
            )
            qc = jnp.zeros((q,), jnp.float32)

        codes = unpack_codes(pq_codes[lists], pq_dim, pq_bits)  # (q, cap, J)
        ids = indices[lists]
        invalid = slot >= list_sizes[lists][:, None]
        if deleted is not None:
            invalid |= deleted[lists]   # tombstones mask like padding
        # score[c] = Σ_j LUT[j, codes[c, j]] — one-hot matmuls on the MXU
        # (see _lut_scores: ~9× over take_along_axis gathers on TPU).
        if jnp.dtype(lut_dtype) == jnp.uint8:
            # Affine u8 quantization per (query, subspace) — fp_8bit analog.
            # The quantized table is integer-valued ≤ 255, exact in bf16.
            lmin = jnp.min(lut, axis=2, keepdims=True)
            scale = (jnp.max(lut, axis=2, keepdims=True) - lmin) / 255.0
            lut_q = jnp.round(
                (lut - lmin) / jnp.maximum(scale, 1e-30)).astype(jnp.uint8)
            scores = (_lut_scores(lut_q.astype(jnp.bfloat16), codes,
                                  scale=scale[..., 0],
                                  acc_dtype=internal_dtype)
                      + jnp.sum(lmin[..., 0], axis=1)[:, None]
                      .astype(internal_dtype))
        else:
            scores = _lut_scores(lut.astype(lut_dtype), codes,
                                 acc_dtype=internal_dtype)
        scores = scores + qc[:, None].astype(internal_dtype)
        scores = jnp.where(invalid, worst, scores)
        cat_d = jnp.concatenate([best_d, scores], axis=1)
        cat_i = jnp.concatenate([best_i, ids], axis=1)
        keys = cat_d if is_ip else -cat_d
        _, pos = lax.top_k(keys, k)
        return (jnp.take_along_axis(cat_d, pos, axis=1),
                jnp.take_along_axis(cat_i, pos, axis=1)), None

    init = (jnp.full((q, k), worst, internal_dtype),
            jnp.full((q, k), -1, indices.dtype))
    (best_d, best_i), _ = lax.scan(body, init, probe_ids.T)
    # Distances are reported f32 regardless of the internal accumulation
    # dtype (the reference's postprocess_distances writes float).
    return best_d.astype(jnp.float32), best_i


@traced
def search(
    params: SearchParams, index: Index, queries, k: int, handle=None,
) -> Tuple[jax.Array, jax.Array]:
    """Approximate search (ref: ivf_pq::search → detail/ivf_pq_search.cuh:
    1551; pylibraft neighbors/ivf_pq.pyx:568). Returns (distances,
    neighbors); L2 metrics report approximate squared (or sqrt'ed) distances
    reconstructed from the PQ scores, like the reference's
    postprocess_distances (:401)."""
    Q = _as_float(queries)
    expects(Q.ndim == 2 and Q.shape[1] == index.dim, "query dim mismatch")
    lut_dtype, internal_dtype = validate_search_dtypes(params)

    # Recall-class request above the native PQ ceiling: run the exact-
    # refine recipe internally (the reference pairs ivf_pq with
    # neighbors/refine.cuh the same way; here the engine dispatch does
    # it so the caller never spells "refined"). The mapping, measured
    # on the 1M regimes (BENCH_r05):
    #   (0.84, 0.9] → n_probes≥48, ratio 2 — structureless batches run
    #       the fast BOUNDED per-cell queue (9.4-9.8K QPS @ 0.924 uniform, BENCH_r05);
    #       concentrated batches are demoted to the pool-deep queue by
    #       the measured probe concentration (see search_refined — the
    #       bound would cap recall near native there).
    #   > 0.9      → n_probes≥64, ratio 4, always pool-deep — the
    #       robust class (0.997 SIFT-u8 / 0.96 uniform at ~0.25× the
    #       fast class's QPS).
    if (params.min_recall is not None
            and params.min_recall > _REFINE_RECALL_CLASS):
        if index._source is not None:
            import dataclasses
            robust = params.min_recall > 0.9
            ratio = 4 if robust else 2
            sp = dataclasses.replace(
                params, min_recall=None,
                n_probes=max(params.n_probes, 64 if robust else 48))
            return search_refined(sp, index, index._source, queries, k,
                                  refine_ratio=ratio, handle=handle,
                                  bound_queue=False if robust else None)
        from raft_tpu.core.logger import logger
        logger.warning(
            "min_recall=%.2f requested but the index retains no source "
            "dataset (loaded index, or extend with custom ids) - running "
            "the native PQ search; use search_refined(dataset=...) for "
            "the exact-refine recipe", params.min_recall)

    n_probes = min(params.n_probes, index.n_lists)
    # Static capacity clamp keeps search traceable (jit/scan over query
    # batches); empty slots are masked inside _pq_probe_scan.
    k = min(k, max(index.capacity, 1))
    is_ip = index.metric == DistanceType.InnerProduct

    # "auto" only switches to the recon-cache engine when the LUT dtype
    # knobs are at their defaults — an explicit lut_dtype/internal dtype
    # request (fp16/bf16/uint8) is honored by the LUT scan path (an explicit
    # engine="bucketed" overrides, documented on SearchParams).
    default_dtypes = (lut_dtype == jnp.float32
                      and internal_dtype == jnp.float32)
    interpret = pallas_interpret()
    # Compressed-domain tier dispatch, BEFORE the bucket-capacity
    # machinery: the packed-cells kernel has no bucket table, so
    # _pick_engine's measured capacity (one scalar device readback)
    # and its bucket-table memory fallback do not apply to it. Same
    # static preconditions as _pick_engine's bucketed gate. A pre-built
    # reconstruction cache (index.reconstructed()) opts into the recon
    # tier below instead.
    if _compressed_eligible(params, index, n_probes, k, Q.shape[0],
                            default_dtypes):
        int8 = bool(params.compressed_lut_int8)
        ops = index.compressed_scan_operands(int8_lut=int8)
        codesT, abs_lo, abs_hi, invalid, crot_p = ops[:5]
        best_d, best_i = _compressed_search(
            Q, index.centers, index.rotation_matrix, codesT, abs_lo,
            abs_hi, invalid, index.indices, crot_p, n_probes, k, is_ip,
            index.pq_dim, index.pq_bits,
            min(_CELL_QROWS, max(8, Q.shape[0])), interpret,
            int8_lut=ops[5] if int8 else None)
        if index.metric == DistanceType.L2SqrtExpanded:
            best_d = jnp.sqrt(jnp.maximum(best_d, 0.0))
        return best_d, best_i

    probe_ids = _select_clusters((Q, index.centers), n_probes, is_ip)

    rot = index.rotation_matrix
    rotq = jnp.matmul(Q, rot.T, precision=lax.Precision.HIGHEST)

    engine, cap_q = _pick_engine(
        params.engine, Q.shape[0], n_probes, index.n_lists, k,
        params.bucket_cap, index.rot_dim, probe_ids,
        allow_bucketed=default_dtypes,
        cap_cache=_auto_cap_cache(index))
    if engine == "bucketed":
        recon_bytes = index.pq_codes.shape[0] * index.pq_codes.shape[1] \
            * index.rot_dim * 2
        if index._recon is not None or recon_bytes <= _RECON_AUTO_BYTES:
            # Small index or a user-precomputed cache: score against the
            # resident bf16 reconstruction (fastest steady-state).
            best_d, best_i = _bucketed_probe_scan(
                rotq, index.reconstructed(),
                index.indices, index.list_sizes, probe_ids,
                k, not is_ip, False, cap_q, interpret,
                deleted=index.deleted)
        else:
            # Large index: decode blocks on the fly — PQ keeps its
            # compression, no _RECON_AUTO_BYTES memory cliff.
            centers_rot = jnp.matmul(index.centers, rot.T,
                                     precision=lax.Precision.HIGHEST)
            best_d, best_i = _bucketed_decode_scan(
                rotq, index.pq_codes, index.pq_centers, centers_rot,
                index.indices, index.list_sizes, probe_ids,
                k, is_ip,
                index.codebook_kind == CodebookGen.PER_CLUSTER,
                cap_q, index.pq_dim, index.pq_bits, interpret,
                deleted=index.deleted)
        if index.metric == DistanceType.L2SqrtExpanded:
            best_d = jnp.sqrt(jnp.maximum(best_d, 0.0))
        return best_d, best_i

    centers_rot = jnp.matmul(index.centers, rot.T,
                             precision=lax.Precision.HIGHEST)

    # Chunk the query axis: the LUT scan stages (q_chunk, cap, pq_dim)
    # gathered codes plus a (q_chunk, pq_dim, book) LUT per probe step —
    # unchunked at cap=2048, pq_dim=64 a 1000-query batch is ~0.5 GB of
    # gather per step (enough to take down the worker at 1M scale).
    cap = index.pq_codes.shape[1]
    per_q = max(cap * index.pq_dim * 4, index.pq_dim * 256 * 4)
    best_d, best_i = _chunked_over_queries(
        lambda rq, pid: _pq_probe_scan(
            rq, pid,
            index.pq_codes, index.indices, index.list_sizes,
            k, is_ip, index.codebook_kind == CodebookGen.PER_CLUSTER,
            lut_dtype, index.pq_dim, index.pq_bits,
            internal_dtype,
            pq_centers=index.pq_centers, centers_rot=centers_rot,
            deleted=index.deleted,
        ),
        rotq, probe_ids, per_q)
    if index.metric == DistanceType.L2SqrtExpanded:
        best_d = jnp.sqrt(jnp.maximum(best_d, 0.0))
    return best_d, best_i


@traced
def search_refined(
    params: SearchParams, index: Index, dataset, queries, k: int,
    refine_ratio: int = 2, handle=None,
    bound_queue: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Over-retrieve ``refine_ratio·k`` PQ candidates and exact-refine to
    k against ``dataset`` — the reference's standard recipe for lifting
    PQ recall past its quantization ceiling (neighbors/refine.cuh; the
    recipe the reference's benches pair with ivf_pq, and the one that
    clears the 0.86-class uniform-regime bar: plain 8-bit PQ reached
    0.791 there, BENCH_r05). ``dataset`` is the original
    row-major dataset the index was built over (the PQ index stores only
    codes); ``None`` uses the reference retained by build()
    (``Index._source``). Both stages run as jitted programs; the refine
    adds one candidate gather + a (q, ratio·k, dim) exact distance
    batch. Returns ``(distances, neighbors)`` like :func:`search`.
    Callers can request this recipe implicitly via
    ``SearchParams.min_recall`` instead.

    ``bound_queue`` (compressed fast path only): ``None`` (default)
    keeps each (query, probe) cell's in-kernel queue at k — ~1.7× the
    QPS — on query batches the measured probe concentration deems safe,
    and demotes concentrated batches to the pool-deep queue (on
    clustered data the best list can hold the whole true pool and the
    bound caps recall near the native class; see _probe_concentration /
    _compressed_search). ``True`` forces the bounded queue (no
    measurement — benchmarking/pinning), ``False`` forces pool-deep.
    The auto mode measures L2 coarse geometry only: InnerProduct
    indexes probe by IP, where the statistic is uncalibrated, so IP
    always runs pool-deep unless forced.
    """
    from raft_tpu.neighbors.refine import refine

    if dataset is None:
        dataset = index._source
        expects(dataset is not None,
                "search_refined(dataset=None) needs the build-retained "
                "dataset; this index has none (loaded, or extended with "
                "custom ids) - pass the dataset explicitly")
    expects(refine_ratio >= 1, "refine_ratio must be >= 1")
    if params.min_recall is not None:
        # The refine recipe is already running — a still-set min_recall
        # would re-trigger it inside the internal candidate search.
        import dataclasses
        params = dataclasses.replace(params, min_recall=None)
    refine_ratio = int(refine_ratio)
    if refine_ratio == 1:
        return search(params, index, queries, k, handle=handle)

    Q = _as_float(queries)
    lut_dtype, internal_dtype = validate_search_dtypes(params)
    default_dtypes = (lut_dtype == jnp.float32
                      and internal_dtype == jnp.float32)
    n_probes = min(params.n_probes, index.n_lists)
    is_ip = index.metric == DistanceType.InnerProduct
    # Same capacity clamp as search(): a tiny index degrades to fewer
    # candidates instead of tripping refine's k <= n_candidates check.
    k = min(k, max(index.capacity, 1))
    pool = min(refine_ratio * k, max(index.capacity, 1))
    # Compressed fast path: the refine pool is a candidate set (exact
    # re-rank follows), so with the bounded queue each (query, probe)
    # contributes its top-k only — the in-kernel queue cost stays that
    # of k, not ratio·k (measured 6.1K → ~10K QPS at the 1M uniform
    # config). The bound is only SAFE on structureless query loads:
    # bound_queue=None measures the probe concentration (memoized per
    # batch shape, inside the eligibility gate so ineligible configs
    # never pay the matmul+sync) and demotes concentrated batches to
    # the pool-deep queue, where the bound would cap recall near the
    # native class (see _probe_concentration / _compressed_search).
    # Under an outer jit, or for IP metric (uncalibrated geometry),
    # auto resolves pool-deep — correctness first.
    if (pool <= n_probes * k and Q.ndim == 2 and Q.shape[1] == index.dim
            and _compressed_eligible(params, index, n_probes, pool,
                                     Q.shape[0], default_dtypes)):
        if bound_queue is None:
            if is_ip or isinstance(Q, jax.core.Tracer):
                bound_queue = False
            elif index.n_lists < 2:
                bound_queue = False  # the single list holds every pool
            else:
                cache = index.__dict__.setdefault("_conc_cache", {})
                key = Q.shape
                if key not in cache:
                    cache[key] = float(
                        _probe_concentration(Q, index.centers))
                bound_queue = cache[key] < _CONC_BOUND_SAFE
        # The int8-table flag applies to the over-retrieve pass exactly
        # like plain search() (the ineligible branch below falls back to
        # search(), which honors it — the two branches must agree); the
        # refine re-rank is exact either way.
        int8 = bool(params.compressed_lut_int8)
        ops = index.compressed_scan_operands(int8_lut=int8)
        codesT, abs_lo, abs_hi, invalid, crot_p = ops[:5]
        _, i = _compressed_search(
            Q, index.centers, index.rotation_matrix, codesT, abs_lo,
            abs_hi, invalid, index.indices, crot_p, n_probes, pool,
            is_ip, index.pq_dim, index.pq_bits,
            min(_CELL_QROWS, max(8, Q.shape[0])),
            pallas_interpret(),
            min(k, pool) if bound_queue else 0,
            int8_lut=ops[5] if int8 else None)
    else:
        _, i = search(params, index, queries, pool, handle=handle)
    return refine(dataset, queries, i, k, metric=index.metric)


# ---------------------------------------------------------------------------
# Serialization (ref: detail/ivf_pq_serialize.cuh:38, kSerializationVersion=3,
# scalars + mdspans at :63-100).

# v4: pq_codes became bit-packed byte rows (+ explicit pq_dim scalar); the
# reference bumps its kSerializationVersion on layout changes the same way.
SERIALIZATION_VERSION = 4


@traced
def save(filename: str, index: Index, retry=None) -> None:
    """Ref: ivf_pq::serialize / pylibraft save (ivf_pq.pyx:719). The npz
    write runs under :func:`raft_tpu.core.retry.with_retry` (``retry``
    overrides :data:`~raft_tpu.core.retry.DEFAULT_IO_RETRY`) — same
    transient-OSError contract as ivf_flat.save."""
    from raft_tpu.core.retry import DEFAULT_IO_RETRY, with_retry

    payload = dict(
        version=np.int64(SERIALIZATION_VERSION),
        metric=np.int64(index.metric.value),
        codebook_kind=np.int64(index.codebook_kind.value),
        pq_bits=np.int64(index.pq_bits),
        pq_dim=np.int64(index.pq_dim),
        conservative=np.bool_(index.conservative_memory_allocation),
        centers=np.asarray(index.centers),
        rotation_matrix=np.asarray(index.rotation_matrix),
        pq_centers=np.asarray(index.pq_centers),
        pq_codes=np.asarray(index.pq_codes),
        indices=np.asarray(index.indices),
        list_sizes=np.asarray(index.list_sizes),
    )
    if index.n_deleted:
        # Tombstones are index content — dropping them on a save/load
        # round trip would resurrect deleted rows (see ivf_flat.save).
        payload["deleted"] = np.asarray(index.deleted)
    with_retry(lambda: np.savez(filename, **payload),
               retry or DEFAULT_IO_RETRY)


@traced
def load(filename: str, retry=None) -> Index:
    """Ref: ivf_pq::deserialize / pylibraft load (ivf_pq.pyx:765). IO
    retried like :func:`save`."""
    from raft_tpu.core.retry import DEFAULT_IO_RETRY, with_retry

    if not filename.endswith(".npz"):
        filename = filename + ".npz"

    def read():
        with np.load(filename) as z:
            return {k: z[k] for k in z.files}

    z = with_retry(read, retry or DEFAULT_IO_RETRY)
    version = int(z["version"])
    expects(version == SERIALIZATION_VERSION,
            f"serialization version mismatch: {version}"
            + (" (v3 unpacked-codes indexes predate the bit-packed "
               "layout; rebuild or re-save from a v3-era checkout)"
               if version == 3 else ""))
    # int64 ids require x64 — otherwise jnp.asarray silently truncates.
    validate_idx_dtype(z["indices"].dtype)
    deleted = z.get("deleted")
    return Index(
        metric=DistanceType(int(z["metric"])),
        codebook_kind=CodebookGen(int(z["codebook_kind"])),
        centers=jnp.asarray(z["centers"]),
        rotation_matrix=jnp.asarray(z["rotation_matrix"]),
        pq_centers=jnp.asarray(z["pq_centers"]),
        pq_codes=jnp.asarray(z["pq_codes"]),
        indices=jnp.asarray(z["indices"]),
        list_sizes=jnp.asarray(z["list_sizes"]),
        pq_bits=int(z["pq_bits"]),
        pq_dim=int(z["pq_dim"]),
        conservative_memory_allocation=bool(z["conservative"]),
        deleted=None if deleted is None else jnp.asarray(deleted),
        n_deleted=0 if deleted is None else int(deleted.sum()),
    )
