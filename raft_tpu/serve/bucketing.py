"""Shape bucketing: pad requests into a closed set of jit shapes.

Ref pattern: the role of the reference's precompiled template
instantiation matrix (cpp/src — a fixed grid of (T, IdxT, ...) kernels
compiled ahead of time so no user ever waits on nvcc; SURVEY.md §2.13).
On TPU the recompilation tax moves from types to SHAPES: every novel
``(n_queries, k)`` traces and compiles a fresh XLA program — observed
O(100 ms–10 s) per shape — which is fatal in an online runtime where
request sizes vary per call.

The fix is the classic serving recipe (live in TF-Serving/JAX serving
stacks as "shape bucketing"): quantize the query-count axis to a pow2
ladder and k to a small fixed grid, pad every request up to its bucket,
and pre-compile the full ``len(q_buckets) × len(k_grid)`` closed set at
startup (:func:`warmup`; with the persistent compilation cache on —
``core.compilation_cache`` — a later boot reads the programs back).
Steady-state traffic inside the grid then NEVER compiles —
``tests/test_serve.py`` proves it with a compile-event hook.

Padding is sound because every search path is row-independent: padded
query rows (zeros) compute garbage neighbors for themselves and are
sliced off before results leave the scheduler; they cannot perturb real
rows (each output row of the distance/top-k pipeline depends only on
its own query row). The wasted pad compute is bounded by the pow2
ladder at <2x and tracked per bucket as ``padded_slots`` in
``serve/stats.py``.
"""

from __future__ import annotations

from dataclasses import dataclass, replace as _dc_replace
from typing import Optional, Tuple

import numpy as np

from raft_tpu.core.error import expects
from raft_tpu.util.pow2 import next_pow2

#: Default k grid: the common serving points (top-1 lookup, top-10
#: retrieval, top-100 candidate generation for re-ranking).
DEFAULT_K_GRID = (1, 10, 100)


@dataclass(frozen=True)
class BucketGrid:
    """The closed set of jit shapes the runtime serves from.

    ``q_buckets`` — ascending query-count bucket sizes (use
    :meth:`pow2` for the standard pow2 ladder); a request with ``n``
    queries pads up to the smallest bucket >= n. ``k_grid`` — ascending
    k values; a request's k rounds up to the smallest grid k and the
    result is sliced back down (top-k at k' >= k prefixes to top-k
    under the same total order).
    """

    q_buckets: Tuple[int, ...]
    k_grid: Tuple[int, ...] = DEFAULT_K_GRID

    def __post_init__(self):
        for name, grid in (("q_buckets", self.q_buckets),
                           ("k_grid", self.k_grid)):
            expects(len(grid) >= 1, "%s must be non-empty", name)
            expects(all(int(g) == g and g >= 1 for g in grid),
                    "%s entries must be positive ints, got %s", name, grid)
            expects(tuple(sorted(set(grid))) == tuple(grid),
                    "%s must be strictly ascending, got %s", name, grid)

    @classmethod
    def pow2(cls, max_batch: int,
             k_grid: Tuple[int, ...] = DEFAULT_K_GRID) -> "BucketGrid":
        """The standard ladder: 1, 2, 4, ... up to ``max_batch`` rounded
        up to a power of two."""
        expects(max_batch >= 1, "max_batch must be >= 1, got %s", max_batch)
        top = next_pow2(max_batch)
        ladder = []
        b = 1
        while b <= top:
            ladder.append(b)
            b *= 2
        return cls(q_buckets=tuple(ladder), k_grid=tuple(k_grid))

    @property
    def max_batch(self) -> int:
        return self.q_buckets[-1]

    @property
    def max_k(self) -> int:
        return self.k_grid[-1]

    def bucket_queries(self, n: int) -> Optional[int]:
        """Smallest query bucket >= n, or None when n exceeds the grid
        (the caller chunks or serves out-of-grid)."""
        for b in self.q_buckets:
            if b >= n:
                return b
        return None

    def bucket_k(self, k: int) -> Optional[int]:
        """Smallest grid k >= requested k, or None when out of grid."""
        for g in self.k_grid:
            if g >= k:
                return g
        return None

    def bucket_for(self, n: int, k: int) -> Optional[Tuple[int, int]]:
        """The (q_bucket, k_bucket) this request pads into, or None if
        either axis falls outside the grid."""
        qb, kb = self.bucket_queries(n), self.bucket_k(k)
        if qb is None or kb is None:
            return None
        return (qb, kb)

    def shapes(self) -> Tuple[Tuple[int, int], ...]:
        """Every (q_bucket, k) shape — the closed set warmup compiles."""
        return tuple((qb, kb) for qb in self.q_buckets
                     for kb in self.k_grid)


def pad_queries(queries: np.ndarray, q_bucket: int) -> np.ndarray:
    """Pad query rows with zeros up to the bucket size (host-side; the
    pad rows' results are sliced off by the scheduler)."""
    queries = np.asarray(queries)
    n = queries.shape[0]
    expects(n <= q_bucket, "batch of %s rows exceeds bucket %s", n,
            q_bucket)
    if n == q_bucket:
        return queries
    pad = np.zeros((q_bucket - n,) + queries.shape[1:], queries.dtype)
    return np.concatenate([queries, pad], axis=0)


def warmup(searcher, grid: BucketGrid, include_degraded: bool = False,
           degrade_ladder: Optional[Tuple[float, ...]] = None,
           min_probes: int = 1) -> dict:
    """Pre-compile every bucket shape, so steady-state in-grid traffic
    never compiles.

    Runs one dummy search per ``grid.shapes()`` entry (zeros queries —
    the trace depends only on shapes/statics, never values).
    ``include_degraded=True`` additionally warms the liveness-operand
    trace (the program served while any shard is dead): the mask is a
    traced array operand, so warming with the all-live mask covers every
    future mask value. Returns a report dict: shapes warmed and the XLA
    compile events observed. The persistent compilation cache, where the
    process turned it on (``core.compilation_cache``), serves these
    compiles on a later boot; warmup itself leaves its placement alone.

    ``placement="list"`` (routed) searchers warm MORE than the grid
    shapes: a routed dispatch's program is keyed by the plan's pow2
    (query-group, local-probe-width) buckets, so each (q_bucket, k)
    shape additionally pre-compiles the closed routed ladder
    (``parallel.routing.route_shapes``) via
    :func:`~raft_tpu.parallel.ivf.sharded_routed_warmup` — steady-state
    routed traffic then never compiles regardless of how queries
    cluster.  The routed program is liveness-FREE (liveness is a
    routing input, not an operand), so ``include_degraded`` adds no
    extra routed traces.

    ``degrade_ladder`` (pass ``DegradePolicy.ladder`` + its
    ``min_probes``) additionally warms every reduced-``n_probes`` rung
    the deadline degradation ladder can serve at: ``n_probes`` is a
    STATIC jit argument, so a brownout that shrank it to an un-warmed
    value would compile in the hot path — exactly when latency is
    already collapsing.  Ignored for searchers without an ``n_probes``
    parameter (brute force)."""
    from raft_tpu.core.logger import logger
    from raft_tpu.serve.stats import CompileCounter

    # Without a health registry there IS no degraded trace to warm —
    # silently double-searching would report failure-readiness that
    # doesn't exist.
    expects(not include_degraded or getattr(searcher, "health", None)
            is not None,
            "include_degraded=True needs a searcher with ShardHealth")
    dim = searcher.dim
    shapes = grid.shapes()
    # The ladder's closed n_probes set (deduped: min_probes and int
    # truncation can collapse adjacent rungs onto one value).
    base_np = getattr(getattr(searcher, "_params", None), "n_probes", None)
    rung_probes: Tuple[int, ...] = ()
    if degrade_ladder is not None and base_np is not None:
        vals = {max(int(min_probes), int(int(base_np) * float(f)))
                for f in degrade_ladder}
        rung_probes = tuple(sorted(v for v in vals if v < int(base_np)))
    routed = (getattr(searcher, "mesh", None) is not None
              and getattr(getattr(searcher, "_index", None),
                          "placement", "row") == "list")
    routed_shapes = 0
    # Warmup's dummy dispatches go through the real entry points;
    # recording them would count synthetic traffic on the raft_merge_*
    # scrape — and for routed searchers pour fake probe load onto the
    # few lists nearest the all-zeros dummy, load the compactor's
    # placement balancer would then migrate REAL lists by.
    from raft_tpu.comms.topk_merge import merge_dispatch_stats

    suppress = merge_dispatch_stats.suppress()
    if routed:
        import contextlib

        from raft_tpu.parallel.routing import routing_stats
        stack = contextlib.ExitStack()
        stack.enter_context(suppress)
        stack.enter_context(routing_stats.suppress())
        suppress = stack
    with CompileCounter() as counter, suppress:
        for qb, kb in shapes:
            dummy = np.zeros((qb, dim), np.float32)
            # degraded=False pins the healthy trace even when a shard is
            # already dead at warmup time — otherwise recovery would hit
            # an un-warmed program and compile-storm in the hot path.
            searcher.search(dummy, kb, degraded=False)
            if include_degraded:
                searcher.search(dummy, kb, degraded=True)
            for npr in rung_probes:
                # One extra trace per ladder rung per shape: brownout
                # serving then reuses these instead of compiling.
                searcher.search(dummy, kb, degraded=False, n_probes=npr)
                if include_degraded:
                    searcher.search(dummy, kb, degraded=True,
                                    n_probes=npr)
            if routed:
                from raft_tpu.parallel.ivf import sharded_routed_warmup

                routed_shapes += sharded_routed_warmup(
                    searcher.mesh, searcher._params, searcher._index,
                    qb, kb, merge_engine=searcher.merge_engine)
                for npr in rung_probes:
                    routed_shapes += sharded_routed_warmup(
                        searcher.mesh,
                        _dc_replace(searcher._params, n_probes=npr),
                        searcher._index, qb, kb,
                        merge_engine=searcher.merge_engine)
    logger.debug("serve warmup: %s bucket shapes (+%s routed plan "
                 "shapes), %s XLA compiles", len(shapes), routed_shapes,
                 counter.count)
    return {"shapes": len(shapes), "degraded": bool(include_degraded),
            "routed_shapes": routed_shapes,
            "degrade_rungs": len(rung_probes),
            "compile_events": counter.count}
