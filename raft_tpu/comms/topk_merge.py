"""Hierarchical top-k merge collectives for sharded search.

Ref: the reference merges per-rank kNN results with ``knn_merge_parts``
(neighbors/brute_force.cuh:80) after a plain allgather of candidates
(docs/source/using_comms.rst; SURVEY.md §2.12 item 4). Our sharded
consumers used to do the same — ``lax.all_gather`` every device's
(distances, ids) and re-sort the full candidate set on every device:
O(q·kk·n_dev) bytes received per device plus a replicated select over
n_dev·kk candidates.

This module folds the k-selection *into* the collective's steps, the
"fused computation-collective" recipe (arxiv 2305.06942), with an opt-in
bf16-quantized distance exchange in the spirit of EQuARX (arxiv
2506.17615) — ids stay exact int32/int64 and a final exact-distance
re-rank of the surviving candidates guards recall.

Engines (``topk_merge(..., engine=...)``, call INSIDE ``shard_map``):

* ``"allgather"`` — the baseline: one ``all_gather``, one replicated
  select. Bytes received per device: ``(n_dev-1)·q·kk·(4+idx)``.
* ``"ring"`` — pairwise-merge collective. On a power-of-two axis it runs
  the log-step butterfly (recursive doubling): step ``s`` exchanges the
  running top-w with the partner at distance ``2^s`` over ``ppermute``
  and pairwise-merges, ``w`` growing ``kk·2^(s+1)`` but capped at the
  final ``k``; total bytes ≈ ``log2(n_dev)·q·k·(4+idx)``. On a
  non-power-of-two axis it falls back to the linear ring (store-and-
  forward each neighbor's original candidates, merging every hop):
  ``(n_dev-1)·q·kk·(4+idx)`` bytes — same volume as allgather, but the
  select work distributes across steps instead of replicating one big
  sort. Results are IDENTICAL to the allgather engine: every engine
  selects under the same total order (distance, then lowest id), which
  makes hierarchical pairwise merging associative even under ties.
* ``"ring_bf16"`` — the ring engine with the exchanged distances
  quantized to bfloat16 (half the distance bytes; ids stay exact). The
  ring carries a guard margin of ``min(2k, n_dev·kk)`` candidates, and
  after the collective each device contributes the EXACT distances of
  the survivors it owns (a ``pmin``/``pmax`` reduction — every survivor
  came from exactly one device's local list), so reported distances are
  exact and a true top-k member is lost only if bf16 rounding pushes it
  below rank 2k. Opt-in: never chosen by "auto".
* ``"pipelined"`` / ``"pipelined_bf16"`` — the fused scan→merge
  pipeline (:func:`topk_merge_pipelined`): the PRODUCER chunks its scan
  over probe lists and each finished chunk's candidates ring-merge
  while the next chunk is still scanning, so exchange latency overlaps
  compute instead of sitting exposed after the full local scan (the
  chunked-producer half of the fused computation-collective recipe,
  arxiv 2305.06942 §4). Per-chunk candidate sets are DISJOINT (each
  probed list scans in exactly one chunk), so folding the per-chunk
  ring results under the shared total order is associative and the
  exact variant stays bit-identical to "ring"/"allgather". The bf16
  variant applies the ring_bf16 guard + exact re-rank PER CHUNK —
  a true top-k member is lost only if bf16 rounding pushes it below
  rank 2k *within its own chunk*, a strictly weaker condition than the
  unchunked bound. Chosen by "auto" when the probe count and device
  count make the overlap pay (:func:`resolve_merge_engine` with
  ``n_probes``); passed to plain :func:`topk_merge` (one unchunked
  candidate set — nothing to overlap) they degrade to the matching
  ring engine.
* ``"auto"`` — heuristics keyed on (q, k, n_dev); see
  :func:`resolve_merge_engine`.

The same pairwise-merge core also serves the single-host
``knn_merge_parts`` path (:func:`merge_parts`), with the tie order keyed
by concatenated position so it reproduces the historical
concat+select_k result bit-for-bit.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu.core.error import expects
from raft_tpu.core.sentinels import worst_value
from raft_tpu.util.pow2 import is_pow2
from raft_tpu.util.telemetry import SuppressibleStats

MERGE_ENGINES = ("auto", "allgather", "ring", "ring_bf16", "pipelined",
                 "pipelined_bf16")

#: Engines that chunk the producer scan and overlap the exchange
#: (resolve to a per-chunk ring via :func:`topk_merge_pipelined`).
PIPELINED_ENGINES = ("pipelined", "pipelined_bf16")

# auto crossover: below this many merged candidate scalars the latency of
# a multi-step ring chain beats its bandwidth/distributed-select win on
# the linear (non-pow2) topology, where ring moves the same bytes as
# allgather (see resolve_merge_engine).
_RING_MIN_WORK = 1 << 16

# Pipelined-dispatch knobs: "auto" only picks the pipelined engine when
# the scan is long enough to hide the exchange behind (>= 4 probe lists
# per chunk at >= 2 chunks), and each extra chunk re-exchanges up to a
# full k-wide partial, so the chunk count is capped — 4 chunks already
# hide ~3/4 of the exchange while bounding the volume inflation.
_PIPELINE_MAX_CHUNKS = 4
_PIPELINE_MIN_CHUNK_PROBES = 4
_PIPELINE_AUTO_MIN_PROBES = 16
_PIPELINE_AUTO_MIN_DEV = 4


def resolve_merge_engine(engine: str, n_queries: int, k: int,
                         n_dev: int, *, n_probes: Optional[int] = None
                         ) -> str:
    """Resolve "auto" to a concrete engine from (q, k, n_dev).

    Rules (documented in docs/sharded_search.md):

    * ``n_dev <= 2`` → "allgather": a single exchange already moves the
      minimum bytes; a ring adds steps for nothing.
    * ``n_dev >= 4`` with a chunkable producer (``n_probes`` >= 16, the
      IVF entry points pass their probe count) AND a merged volume
      clearing the ``_RING_MIN_WORK`` floor → "pipelined": the scan
      chunks over probe lists and the per-chunk ring exchange overlaps
      the remaining chunks' compute, hiding most of the exchange
      latency (bit-identical to "ring"). Tiny latency-bound merges
      keep the one-shot engines — there is no scan to hide a
      multi-chunk ring chain behind.
    * power-of-two ``n_dev >= 4`` → "ring": the butterfly moves
      ``log2(n_dev)/(n_dev-1)`` of the allgather bytes and distributes
      the select work.
    * other ``n_dev`` → "ring" only when the merged candidate volume
      ``q·k·n_dev`` is large enough (≥ 2^16 scalars) that distributing
      the select work pays for the longer latency chain; small merges
      stay on "allgather".

    ``n_probes`` is the producer-chunking hint: callers whose scan
    iterates probe lists (the sharded IVF paths) pass it so "auto" can
    weigh the pipelined engine; without it (plain merges, brute-force
    row scans) "auto" never picks "pipelined". "auto" never picks the
    bf16 variants: quantized exchange is a numerics opt-in, not a
    dispatch decision.
    """
    expects(engine in MERGE_ENGINES,
            f"unknown merge engine {engine!r} (one of {MERGE_ENGINES})")
    if engine != "auto":
        return engine
    if n_dev <= 2:
        return "allgather"
    if (n_probes is not None and n_dev >= _PIPELINE_AUTO_MIN_DEV
            and n_probes >= _PIPELINE_AUTO_MIN_PROBES
            and n_queries * k * n_dev >= _RING_MIN_WORK):
        # The merged-volume floor mirrors the non-pow2 ring rule: a
        # tiny (latency-bound) merge has almost no scan to hide the
        # multi-chunk ring chain behind, and each chunk re-exchanges a
        # k-wide partial — small serves stay on the one-shot engines.
        return "pipelined"
    if is_pow2(n_dev):
        return "ring"
    return "ring" if n_queries * k * n_dev >= _RING_MIN_WORK else "allgather"


def resolve_pipeline_chunks(engine: str, n_items: Optional[int],
                            n_dev: int, requested: int = 0) -> int:
    """Chunk count for the pipelined engines (1 = effectively unchunked).

    ``n_items`` is what the producer chunks over (probe lists for IVF,
    row tiles for brute force); ``requested`` > 0 overrides the
    heuristic (clamped to ``n_items``). The default targets
    ``_PIPELINE_MIN_CHUNK_PROBES`` items per chunk, capped at
    ``_PIPELINE_MAX_CHUNKS`` — more chunks hide marginally more latency
    but every chunk re-exchanges a (k + guard)-wide partial.
    """
    if engine not in PIPELINED_ENGINES or n_dev <= 1:
        return 1
    if n_items is None or n_items < 2:
        return 1
    if requested > 0:
        return min(requested, n_items)
    return max(1, min(_PIPELINE_MAX_CHUNKS,
                      n_items // _PIPELINE_MIN_CHUNK_PROBES))


def pipeline_chunk_bounds(n_items: int, n_chunks: int):
    """Even static split of ``n_items`` into ``n_chunks`` contiguous
    ``(lo, hi)`` ranges, remainder spread over the leading chunks (an
    odd ``n_items`` simply makes trailing chunks one item shorter — no
    padding, no dropped items)."""
    n_chunks = max(1, min(n_chunks, n_items))
    base, rem = divmod(n_items, n_chunks)
    bounds, lo = [], 0
    for c in range(n_chunks):
        hi = lo + base + (1 if c < rem else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def merge_comm_bytes(engine: str, n_queries: int, k: int, kk: int,
                     n_dev: int, idx_bytes: int = 4,
                     chunk_kks: Optional[Sequence[int]] = None,
                     participants: Optional[int] = None) -> int:
    """Estimated collective bytes RECEIVED per device for one merge.

    ``kk`` is the per-device candidate width (min(k, shard capacity)).
    The estimate covers the exchanged (distances, ids) payloads; the
    bf16 engine adds the exact-re-rank reduction (counted as one
    ring-allreduce of the survivor row at its guard width
    ``cap = min(2k, n_dev·kk)``: ``2·q·cap·4`` bytes).

    ``chunk_kks`` describes a CHUNKED dispatch (the pipelined engines):
    one logical merge runs N per-chunk ring exchanges at the listed
    per-chunk candidate widths, so the estimate is the sum of the
    per-chunk ring volumes — chunking trades some extra total bytes
    (each chunk exchanges up to a k-wide partial) for hiding the
    exchange behind the remaining chunks' scans. Without it the
    pipelined engines estimate as one ring at width ``kk`` (the
    degenerate single-chunk case).

    ``participants`` accounts a ROUTED dispatch (ISSUE 15): only that
    many shards contribute real candidates — the rest carry merge
    sentinels — so the estimate is the volume of the same merge over
    ``participants`` devices (0/1 participants → no meaningful exchange
    → 0 bytes), CAPPED at the full-mesh volume: a routed merge can
    always run the full collective with sentinel payloads, so a
    partial-participant topology that would move more (a 5-of-8 linear
    ring vs the 8-way butterfly) never charges more than the engine the
    dispatcher actually has.  Still ONE logical merge; the routed entry
    points pass their plan's participant count so the scraped exchange
    volume tracks probe locality instead of mesh size.
    """
    if participants is not None:
        p = min(n_dev, max(int(participants), 1))
        full = merge_comm_bytes(engine, n_queries, k, kk, n_dev,
                                idx_bytes, chunk_kks=chunk_kks)
        if p >= n_dev:
            return full
        return min(full, merge_comm_bytes(engine, n_queries, k, kk, p,
                                          idx_bytes,
                                          chunk_kks=chunk_kks))
    engine = resolve_merge_engine(engine, n_queries, k, n_dev)
    if n_dev <= 1:
        return 0
    if engine in PIPELINED_ENGINES:
        inner = "ring_bf16" if engine == "pipelined_bf16" else "ring"
        if not chunk_kks:
            chunk_kks = (kk,)
        return sum(merge_comm_bytes(inner, n_queries, k, ck, n_dev,
                                    idx_bytes) for ck in chunk_kks)
    k_out = min(k, n_dev * kk)
    if engine == "allgather":
        return (n_dev - 1) * n_queries * kk * (4 + idx_bytes)
    dist_bytes = 2 if engine == "ring_bf16" else 4
    cap = min(2 * k_out, n_dev * kk) if engine == "ring_bf16" else k_out
    if is_pow2(n_dev):
        total = 0
        w = kk
        for _ in range(n_dev.bit_length() - 1):
            total += n_queries * min(cap, w) * (dist_bytes + idx_bytes)
            w *= 2
    else:
        total = (n_dev - 1) * n_queries * kk * (dist_bytes + idx_bytes)
    if engine == "ring_bf16":
        total += 2 * n_queries * cap * 4  # exact re-rank pmin/pmax
    return total


class MergeDispatchStats(SuppressibleStats):
    """Host-side per-engine dispatch accounting for the scrape surface.

    The sharded search entry points (parallel/knn.py, parallel/ivf.py)
    call :meth:`record` once per HOST dispatch with the resolved engine
    and the :func:`merge_comm_bytes` estimate — putting the
    previously-bench-only exchange-volume estimator on the live metrics
    surface (``obs.registry.MergeDispatchCollector``).  One lock + two
    dict updates per sharded call, nothing near the device.  Counts are
    host dispatches: a caller that wraps an entry point in its own
    ``jax.jit``/``lax.scan`` records once per trace, not per replay
    (same caveat as any host-side counter under tracing).  ``suppress``
    (util/telemetry.py) drops a thread's shadow traffic — the recall
    probe's exact scans dispatch through the same entry points.
    """

    def __init__(self):
        super().__init__()
        self._lock = threading.Lock()
        self._dispatches: Dict[str, int] = {}
        self._bytes: Dict[str, int] = {}

    def record(self, engine: str, n_queries: int, k: int, kk: int,
               n_dev: int, idx_bytes: int = 4,
               chunk_kks: Optional[Sequence[int]] = None,
               participants: Optional[int] = None) -> None:
        """One LOGICAL merge dispatch. ``chunk_kks`` marks a chunked
        (pipelined) dispatch: the byte estimate sums the N per-chunk
        exchanges but the dispatch still counts ONCE — the scrape
        reports logical merges per search call, and counting every
        chunk exchange as a dispatch would inflate the per-query
        exchange-byte ratio N-fold after the pipeline lands.
        ``participants`` marks a routed (partial-shard) dispatch: the
        byte estimate covers the participating shards only, still as
        one logical merge (see :func:`merge_comm_bytes`)."""
        if self._suppressed():
            return
        est = merge_comm_bytes(engine, n_queries, k, kk, n_dev, idx_bytes,
                               chunk_kks=chunk_kks,
                               participants=participants)
        with self._lock:
            self._dispatches[engine] = self._dispatches.get(engine, 0) + 1
            self._bytes[engine] = self._bytes.get(engine, 0) + est

    def snapshot(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {engine: {"dispatches": self._dispatches[engine],
                             "est_bytes": self._bytes.get(engine, 0)}
                    for engine in sorted(self._dispatches)}

    def reset(self) -> None:
        with self._lock:
            self._dispatches.clear()
            self._bytes.clear()


#: Process-wide recorder the sharded entry points feed (scraped via
#: ``obs.registry.MergeDispatchCollector``; reset() is test-only).
merge_dispatch_stats = MergeDispatchStats()


def _ascending_keys(v, select_min: bool):
    """Map values so ascending sort order == best-first selection order
    (the polarity mapping of select_k's ``_to_descending_keys``, in
    native dtype so f64/bf16 keys keep their full resolution)."""
    if select_min:
        return v
    if jnp.issubdtype(v.dtype, jnp.floating):
        return -v
    if jnp.issubdtype(v.dtype, jnp.signedinteger):
        return ~v
    # unsigned: negation would wrap (key 0 must rank last, not first)
    return jnp.asarray(jnp.iinfo(v.dtype).max, v.dtype) - v


def _sorted_select(d, i, k: int, select_min: bool, tie=None):
    """Best-first top-k of candidate columns under the shared total order
    (distance, then ascending tie key — the ids by default). One sort
    serves every engine, so pairwise-hierarchical merging is associative
    even under distance ties and all engines agree bit-for-bit. ``d``
    keeps its dtype (the bf16 ring carries bf16 through the sort)."""
    keys = _ascending_keys(d, select_min)
    if tie is None:
        if select_min:            # keys IS d: two operands suffice
            out_d, out_i = lax.sort((d, i), dimension=1, num_keys=2)
        else:
            _, out_i, out_d = lax.sort((keys, i, d), dimension=1,
                                       num_keys=2)
        return out_d[:, :k], out_i[:, :k]
    _, _, out_d, out_i = lax.sort((keys, tie, d, i), dimension=1, num_keys=2)
    return out_d[:, :k], out_i[:, :k]


def _merge_two(ad, ai, bd, bi, k: int, select_min: bool):
    """Pairwise merge of two best-first candidate sets — the warp-select
    merge role of detail/knn_merge_parts.cuh, shared by every engine and
    by the single-host :func:`merge_parts`."""
    return _sorted_select(jnp.concatenate([ad, bd], axis=1),
                          jnp.concatenate([ai, bi], axis=1),
                          k, select_min)


def _ring_merge(dist, idx, cap: int, axis, select_min: bool, n_dev: int):
    """Fused merge-collective: pairwise top-``cap`` selection inside the
    ppermute steps. Butterfly (log steps) on a power-of-two axis, linear
    store-and-forward ring otherwise. Every device finishes with the
    identical best-first top-``cap`` of the union (total order ties to
    the lowest id), so the output is replicated by construction."""
    kk = dist.shape[1]
    carry_d, carry_i = _sorted_select(dist, idx, min(cap, kk), select_min)
    if is_pow2(n_dev):
        for s in range(n_dev.bit_length() - 1):
            perm = [(j, j ^ (1 << s)) for j in range(n_dev)]
            recv_d = lax.ppermute(carry_d, axis, perm)
            recv_i = lax.ppermute(carry_i, axis, perm)
            w = min(cap, kk * (2 << s))
            carry_d, carry_i = _merge_two(carry_d, carry_i, recv_d, recv_i,
                                          w, select_min)
    else:
        # Linear ring: forward each neighbor's ORIGINAL candidates around
        # the ring (store-and-forward) while merging every hop — payload
        # stays q·kk per step and every device sees every chunk once.
        perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]
        send_d, send_i = dist, idx
        for t in range(n_dev - 1):
            recv_d = lax.ppermute(send_d, axis, perm)
            recv_i = lax.ppermute(send_i, axis, perm)
            w = min(cap, kk * (t + 2))
            carry_d, carry_i = _merge_two(carry_d, carry_i, recv_d, recv_i,
                                          w, select_min)
            send_d, send_i = recv_d, recv_i
    # Both branches finish with width exactly cap: callers cap at
    # n_dev·kk and the final merge width is min(cap, kk·n_dev) = cap.
    return carry_d, carry_i


def topk_merge(dist, idx, k: int, axis, select_min: bool = True,
               engine: str = "allgather") -> Tuple[jax.Array, jax.Array]:
    """Merge per-device top-``kk`` candidates into the global top-k.

    Call INSIDE ``shard_map`` over ``axis``. ``dist``/``idx`` are this
    device's ``(n_queries, kk)`` candidates with GLOBAL ids (ids must be
    unique across devices — each database row lives on one shard).
    Returns replicated best-first ``(distances, ids)`` of width
    ``min(k, n_dev·kk)``, ties broken by lowest id. For float32 inputs
    the "allgather" and "ring" engines return identical arrays;
    "ring_bf16" additionally re-ranks the survivors with their exact
    local distances (see module docstring).
    """
    expects(dist.ndim == 2 and dist.shape == idx.shape,
            "dist/idx must be (n_queries, kk) per-device candidates")
    n_dev = lax.axis_size(axis)
    q, kk = dist.shape
    k_out = min(k, n_dev * kk)
    engine = resolve_merge_engine(engine, q, k, n_dev)
    if engine in PIPELINED_ENGINES:
        # One unchunked candidate set: there is no remaining scan to
        # overlap, so the pipelined engines degrade to their ring core
        # (consumers that chunk call topk_merge_pipelined instead).
        engine = "ring_bf16" if engine == "pipelined_bf16" else "ring"

    if n_dev == 1:
        return _sorted_select(dist, idx, k_out, select_min)

    if engine == "allgather":
        all_d = lax.all_gather(dist, axis, axis=1, tiled=True)
        all_i = lax.all_gather(idx, axis, axis=1, tiled=True)
        return _sorted_select(all_d, all_i, k_out, select_min)

    if engine == "ring":
        return _ring_merge(dist, idx, k_out, axis, select_min, n_dev)

    return _bf16_guarded_ring(dist, idx, k_out, axis, select_min, n_dev)


def _bf16_guarded_ring(dist, idx, k_out: int, axis, select_min: bool,
                       n_dev: int):
    """ring_bf16 core (shared with the per-chunk exchanges of
    :func:`topk_merge_pipelined`): quantized exchange with a 2k guard
    margin, exact re-rank. The carry STAYS bfloat16 through every
    ppermute hop (half the distance bytes on the wire); sorts compare
    bf16 directly (the bf16 total order is the f32 order restricted to
    representable values)."""
    kk = dist.shape[1]
    qd = dist.astype(jnp.bfloat16)
    cap = min(2 * k_out, n_dev * kk)
    _, surv_i = _ring_merge(qd, idx, cap, axis, select_min, n_dev)
    # Exact-distance re-rank: each survivor id lives in exactly one
    # device's local candidate list; that owner contributes the exact
    # f32 distance, everyone else the worst value, and a pmin/pmax
    # recovers the exact distance everywhere.
    owned = surv_i[:, :, None] == idx[:, None, :]        # (q, cap, kk)
    worst = worst_value(select_min)
    local = jnp.min(jnp.where(owned, dist[:, None, :], worst), axis=2) \
        if select_min else \
        jnp.max(jnp.where(owned, dist[:, None, :], worst), axis=2)
    exact = lax.pmin(local, axis) if select_min else lax.pmax(local, axis)
    return _sorted_select(exact, surv_i, k_out, select_min)


def topk_merge_pipelined(scan_chunk, n_chunks: int, k: int, axis,
                         select_min: bool = True,
                         quantized: bool = False
                         ) -> Tuple[jax.Array, jax.Array]:
    """Fused scan→select→exchange pipeline (the chunked-producer fused
    computation-collective, arxiv 2305.06942): call INSIDE ``shard_map``
    with ``scan_chunk(c) -> (dist, idx)`` producing this device's
    best-first candidates for producer chunk ``c`` (global ids; the
    chunks' candidate sets must be DISJOINT — each probed list / row
    range scans in exactly one chunk).

    Chunk ``c``'s per-chunk ring exchange depends only on chunk ``c``'s
    scan, so XLA's latency-hiding scheduler overlaps it with chunk
    ``c+1``'s compute — the double-buffered structure the eager chain
    scan→select→merge could never express (the full merge waited on the
    full local scan). Each device folds the replicated per-chunk merges
    into a running (k + guard) candidate set under the shared
    (distance, lowest-id) total order, which makes the grouping
    associative: the exact variant is BIT-IDENTICAL to
    ``topk_merge(concat(chunks), engine="ring"/"allgather")``.
    ``quantized`` applies the ring_bf16 guard + exact re-rank per chunk
    (recall bound per chunk — strictly weaker than the unchunked
    ring_bf16 bound; distances stay exact f32 after the re-rank).

    Returns replicated best-first ``(distances, ids)`` of width
    ``min(k, Σ_c n_dev·kk_c)`` — the same width the unchunked merge of
    the concatenated candidates would return.
    """
    n_dev = lax.axis_size(axis)
    acc_d = acc_i = None
    for c in range(n_chunks):
        # named_scope per chunk: the obs layer's HLO tag splitting the
        # chunk waves in profiler timelines (pure metadata, identical
        # compiled program — docs/observability.md).
        with jax.named_scope("raft.pipeline_chunk"):
            d, i = scan_chunk(c)
            expects(d.ndim == 2 and d.shape == i.shape,
                    "scan_chunk must yield (n_queries, kk) candidates")
            w_c = min(k, n_dev * d.shape[1])
            if n_dev == 1:
                cd, ci = _sorted_select(d, i, w_c, select_min)
            elif quantized:
                cd, ci = _bf16_guarded_ring(d, i, w_c, axis, select_min,
                                            n_dev)
            else:
                cd, ci = _ring_merge(d, i, w_c, axis, select_min, n_dev)
        if acc_d is None:
            acc_d, acc_i = cd, ci
        else:
            acc_d, acc_i = _merge_two(
                acc_d, acc_i, cd, ci,
                min(k, acc_d.shape[1] + cd.shape[1]), select_min)
    return acc_d, acc_i


def merge_parts(keys, vals, k: Optional[int] = None,
                select_min: bool = True,
                translations: Optional[Sequence[int]] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Single-host pairwise-merge core behind ``knn_merge_parts``.

    ``keys``/``vals`` are ``(n_parts, n_queries, kk)``; a binary tree of
    the same pairwise merge the collectives run reduces them to the
    global top-``k`` (default ``kk``). Ties are keyed by concatenated
    position — part-major, the reference's knn_merge_parts order — so
    the result is bit-for-bit the historical concat+select_k output.
    """
    expects(keys.ndim == 3 and vals.shape == keys.shape,
            "keys/vals must be (n_parts, n_queries, k)")
    n_parts, n_queries, kk = keys.shape
    if k is None:
        k = kk
    if translations is not None:
        off = jnp.asarray(translations, vals.dtype).reshape(n_parts, 1, 1)
        vals = vals + off
    # Per-part best-first sets with their global (part-major) positions as
    # tie keys; positions ride the merges as a second payload.
    base = (jnp.arange(n_parts, dtype=jnp.int32) * kk)[:, None, None]
    pos = base + jnp.broadcast_to(
        jnp.arange(kk, dtype=jnp.int32)[None, None, :], keys.shape)
    items = [(keys[p], pos[p], vals[p]) for p in range(n_parts)]
    if n_parts == 1:
        d, v = _sorted_select(keys[0], vals[0], min(k, kk), select_min,
                              tie=pos[0])
        return d, v
    while len(items) > 1:
        nxt = []
        for a in range(0, len(items) - 1, 2):
            (ad, ap, av), (bd, bp, bv) = items[a], items[a + 1]
            w = min(k, ad.shape[1] + bd.shape[1])
            cd = jnp.concatenate([ad, bd], axis=1)
            cp = jnp.concatenate([ap, bp], axis=1)
            cv = jnp.concatenate([av, bv], axis=1)
            if select_min:        # keys IS cd: three operands suffice
                sd, sp, sv = lax.sort((cd, cp, cv), dimension=1,
                                      num_keys=2)
            else:
                _, sp, sd, sv = lax.sort(
                    (_ascending_keys(cd, select_min), cp, cd, cv),
                    dimension=1, num_keys=2)
            nxt.append((sd[:, :w], sp[:, :w], sv[:, :w]))
        if len(items) % 2:
            nxt.append(items[-1])
        items = nxt
    out_d, _, out_v = items[0]
    return out_d[:, :k], out_v[:, :k]
