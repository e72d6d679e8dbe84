"""Uniform search facade for the serving runtime.

Ref pattern: the reference exposes each index family as free functions
(brute_force::knn, ivf_flat::search, ivf_pq::search,
neighbors/brute_force.cuh / ivf_flat.cuh / ivf_pq.cuh) and leaves
composition to the application; the MNMG recipe adds per-rank shards
merged with knn_merge_parts (docs/source/using_comms.rst). The serving
runtime needs one object that hides which family and which deployment
(single-host vs sharded mesh) sits underneath, because the scheduler
(serve/scheduler.py) batches requests against an opaque ``search(q, k)``.

:class:`Searcher` is that facade. It threads through everything the
fault-tolerance and collective layers already provide:

* ``merge_engine`` — the top-k merge collective knob
  (comms/topk_merge.py) on every sharded call;
* ``ShardHealth`` — when any rank is dead, searches pass
  ``health.live_mask`` and serve DEGRADED (exact over survivors, never
  an exception), returning the per-query ``coverage`` fraction
  (docs/fault_tolerance.md);
* ``RetryPolicy`` — transient host-side failures retry with the
  deterministic backoff of ``core/retry.py``;
* ``epoch`` — the cache-invalidation key (serve/cache.py): bumped by
  every mutation (extend / delete / upsert / compact), so cached
  results can never outlive the index state they were computed against.

Write side (raft_tpu/lifecycle, docs/index_lifecycle.md): ``delete``
tombstones rows (exact-over-survivors immediately), ``upsert``
replaces rows under one epoch bump, ``compact`` publishes a
copy-on-write successor index by swapping one reference — in-flight
batches keep searching their dispatch-time snapshot.  Mutations
serialize on an internal lock; searches never take it (they read one
index reference, and every published state is internally consistent).

Durability (raft_tpu/lifecycle/wal.py, docs/durability.md): with a
``wal`` attached, every mutation appends its record — fsynced — BEFORE
the serving reference swaps (write-ahead order: a record exists iff
the epoch it stamps was ever observable), and publishes trigger the
log's snapshot cadence.  ``writable=False`` builds a read-only
follower endpoint: searches serve, mutations raise until a
``PromotionManager`` flips the flag.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Callable, List, Optional

import numpy as np

from raft_tpu.core.error import expects
from raft_tpu.core.retry import RetryPolicy, with_retry

_KINDS = ("brute_force", "ivf_flat", "ivf_pq")


@dataclass(frozen=True)
class SearchResult:
    """One request's answer: replicated host arrays.

    ``coverage`` is all-ones on healthy serves; under degraded serving
    it is the PR-2 per-query fraction of candidate rows actually
    searched (docs/fault_tolerance.md). ``degraded`` flags that a
    live_mask was applied.  ``hedged`` flags that the answer came from
    a hedged re-dispatch that beat the straggling primary.  The
    degradation-ladder fields (docs/fault_tolerance.md §ladder):
    ``quality`` is the served-quality class ("full" — the configured
    n_probes; "reduced" — a middle ladder rung; "brownout" — the
    deepest rung), ``degrade_reason`` names what forced the rung
    ("queue_pressure" / "deadline_budget"; None at full quality).
    """

    distances: np.ndarray   # (n_queries, k)
    indices: np.ndarray     # (n_queries, k)
    coverage: np.ndarray    # (n_queries,)
    degraded: bool = False
    hedged: bool = False
    quality: str = "full"
    degrade_reason: Optional[str] = None


class Searcher:
    """One serving endpoint over a brute-force / IVF-Flat / IVF-PQ index,
    single-host or sharded over a mesh. Build with the classmethods:

    >>> s = Searcher.brute_force(db, mesh=mesh, health=health)   # doctest: +SKIP
    >>> s = Searcher.ivf_flat(index, sp, mesh=mesh)              # doctest: +SKIP
    >>> res = s.search(queries, k=10)                            # doctest: +SKIP
    """

    def __init__(self, kind: str, *, mesh=None, db=None, index=None,
                 search_params=None, merge_engine: str = "auto",
                 health=None, retry: Optional[RetryPolicy] = None,
                 wal=None, writable: bool = True,
                 hedge=None, dispatch_hook=None,
                 sleep: Callable[[float], None] = time.sleep,
                 monotonic: Callable[[], float] = time.monotonic):
        expects(kind in _KINDS, "kind must be one of %s, got %r", _KINDS,
                kind)
        expects((db is not None) == (kind == "brute_force"),
                "brute_force takes db; IVF kinds take index")
        if kind != "brute_force":
            expects(index is not None and search_params is not None,
                    "IVF searchers need index + search_params")
        expects(health is None or mesh is not None,
                "ShardHealth only applies to sharded (mesh) searchers")
        expects(wal is None or (mesh is not None
                                and kind != "brute_force"),
                "a MutationLog records sharded IVF mutations (brute-"
                "force rows are positional — nothing stable to replay)")
        expects(hedge is None or health is not None,
                "hedging needs a ShardHealth (the hedge re-routes "
                "around SUSPECT shards; without health there is no "
                "suspicion to act on)")
        self.kind = kind
        self.mesh = mesh
        self.merge_engine = merge_engine
        self.health = health
        self.retry = retry
        self.wal = wal
        self.writable = writable
        # ``hedge``: a serve.hedge.HedgePolicy arming hedged replica
        # dispatch for routed (placement="list") indexes.
        # ``dispatch_hook``: called with each routed dispatch's
        # participating ranks AFTER the dispatch — the chaos seam
        # (ChaosMonkey.rank_hook) that advances the injected clock for
        # scripted stragglers, so hedging is testable deterministically.
        self.hedge = hedge
        self._dispatch_hook = dispatch_hook
        from raft_tpu.serve.hedge import HedgeStats
        from raft_tpu.serve.stats import ServeStats

        self.hedge_stats = HedgeStats()
        # Private per-dispatch-shape latency windows (the hedge budget's
        # evidence) — separate from any scheduler-owned ServeStats,
        # whose windows hold submit->complete times incl. queueing.
        self._dispatch_stats = ServeStats()
        self._sleep = sleep
        self._monotonic = monotonic
        self._index = index
        self._params = search_params
        self._db = db
        self._base_epoch = 0
        # Serializes mutations (extend/delete/upsert/compact) against
        # each other — a compaction racing an extend would publish a
        # successor missing the extend's rows.  Searches never take it.
        self._lock = threading.Lock()
        self._invalidation_hooks: List[Callable[[], None]] = []
        if kind == "brute_force" and mesh is not None:
            from raft_tpu.parallel.knn import shard_database

            # Pre-place once: the scheduler calls search per batch and a
            # host->device transfer of the database per request would
            # dominate serving latency.
            self._db = shard_database(mesh, self._db)

    # -- constructors ------------------------------------------------------
    @classmethod
    def brute_force(cls, db, mesh=None, **kw) -> "Searcher":
        """Exact kNN endpoint; ``mesh`` shards the database rows
        (``sharded_knn``), else single-host ``brute_force.knn``."""
        return cls("brute_force", mesh=mesh, db=db, **kw)

    @classmethod
    def ivf_flat(cls, index, search_params, mesh=None, **kw) -> "Searcher":
        """IVF-Flat endpoint over a built index (``ShardedIvfFlat`` when
        ``mesh`` is given, else the single-host ``ivf_flat.Index``)."""
        return cls("ivf_flat", mesh=mesh, index=index,
                   search_params=search_params, **kw)

    @classmethod
    def ivf_pq(cls, index, search_params, mesh=None, **kw) -> "Searcher":
        """IVF-PQ endpoint (``ShardedIvfPq`` / ``ivf_pq.Index``)."""
        return cls("ivf_pq", mesh=mesh, index=index,
                   search_params=search_params, **kw)

    # -- identity ----------------------------------------------------------
    @property
    def dim(self) -> int:
        """Query dimensionality (what warmup's dummy queries must have)."""
        if self.kind == "brute_force":
            return int(self._db.shape[1])
        return int(self._index.centers.shape[1])

    @property
    def epoch(self) -> int:
        """Monotonic index-content version — the cache-invalidation key.
        IVF indexes (single-host and sharded) carry their own counter,
        bumped by every extend even when called outside this facade;
        brute-force extends count in ``_base_epoch``."""
        return self._base_epoch + int(getattr(self._index, "epoch", 0))

    def add_invalidation_hook(
            self, hook: Callable[[], None]) -> Callable[[], None]:
        """Run ``hook()`` after every mutation (the scheduler registers
        its ResultCache.invalidate here). Returns an idempotent
        unsubscribe callable — a Searcher outlives its schedulers, so
        an unremovable hook would retain every retired cache forever."""
        with self._lock:
            self._invalidation_hooks.append(hook)

        def remove() -> None:
            with self._lock:
                try:
                    self._invalidation_hooks.remove(hook)
                except ValueError:
                    pass

        return remove

    def _fire_hooks(self) -> None:
        """Invoke the invalidation hooks OUTSIDE the mutation lock (a
        hook may take its own lock; holding ours across foreign code
        invites lock-order inversions)."""
        with self._lock:
            hooks = list(self._invalidation_hooks)
        for hook in hooks:
            hook()

    # -- durability --------------------------------------------------------
    def _require_writable(self) -> None:
        expects(self.writable,
                "read-only follower endpoint — mutations are rejected "
                "until promotion (lifecycle.wal.PromotionManager)")

    def _wal_append(self, kind: str, new_index, payload: dict) -> None:
        """Durably log one mutation at its POST-mutation epoch.  Called
        with the successor built but not yet published — the write-
        ahead order: a crash after the append replays the mutation
        (redo), a crash before it loses a mutation no reader ever saw."""
        if self.wal is not None:
            self.wal.append(kind, int(new_index.epoch), payload)

    def _published(self) -> None:
        """Post-publish duties: invalidation hooks (outside the lock),
        then the log's snapshot cadence (a snapshot rides the epoch the
        swap just committed)."""
        self._fire_hooks()
        if self.wal is not None:
            self.wal.maybe_snapshot(self._index, self.mesh)

    def publish_index(self, new_index, *, record=None,
                      expect_base_epoch: Optional[int] = None) -> None:
        """Publish an externally built copy-on-write successor under
        the snapshot-swap contract (elastic join/leave cutover,
        follower catch-up).  ``record=(kind, payload)`` logs the
        mutation write-ahead; ``expect_base_epoch`` asserts no
        concurrent mutation slipped in while the successor was being
        built (the elastic warmup window) instead of silently dropping
        it."""
        with self._lock:
            cur = int(getattr(self._index, "epoch", 0))
            if expect_base_epoch is not None:
                expects(cur == expect_base_epoch,
                        "concurrent mutation during publish: index "
                        "moved %s -> %s while the successor was built",
                        expect_base_epoch, cur)
            expects(int(new_index.epoch) > cur,
                    "publish must advance the epoch (%s -> %s)", cur,
                    int(new_index.epoch))
            if record is not None:
                kind, payload = record
                self._wal_append(kind, new_index, payload)
            self._index = new_index
        self._published()

    # -- serving -----------------------------------------------------------
    def _resolve_live(self, degraded: Optional[bool]):
        """The live_mask to pass, or None for the (bit-identical,
        liveness-free) healthy trace. ``degraded=True`` forces the
        liveness trace even when all ranks are live — warmup uses it to
        pre-compile the program served during future failures (the mask
        is a traced operand, so one trace covers every mask value)."""
        if self.health is None or degraded is False:
            return None
        if degraded or not self.health.all_live():
            return self.health.live_mask
        return None

    def _dispatch(self, queries: np.ndarray, k: int, live,
                  valid_rows=None, params=None, suspect=None,
                  plan_cb=None):
        params = params if params is not None else self._params
        if self.kind == "brute_force":
            if self.mesh is None:
                from raft_tpu.neighbors import brute_force

                return brute_force.knn(self._db, queries, k)
            from raft_tpu.parallel.knn import sharded_knn

            return sharded_knn(self.mesh, self._db, queries, k,
                               merge_engine=self.merge_engine,
                               live_mask=live)
        if self.kind == "ivf_flat":
            if self.mesh is None:
                from raft_tpu.neighbors import ivf_flat

                return ivf_flat.search(params, self._index, queries, k)
            from raft_tpu.parallel.ivf import sharded_ivf_flat_search

            return sharded_ivf_flat_search(self.mesh, params,
                                           self._index, queries, k,
                                           merge_engine=self.merge_engine,
                                           live_mask=live,
                                           valid_rows=valid_rows,
                                           suspect_mask=suspect,
                                           plan_cb=plan_cb)
        if self.mesh is None:
            from raft_tpu.neighbors import ivf_pq

            return ivf_pq.search(params, self._index, queries, k)
        from raft_tpu.parallel.ivf import sharded_ivf_pq_search

        return sharded_ivf_pq_search(self.mesh, params, self._index,
                                     queries, k,
                                     merge_engine=self.merge_engine,
                                     live_mask=live,
                                     valid_rows=valid_rows,
                                     suspect_mask=suspect,
                                     plan_cb=plan_cb)

    def _is_routed(self) -> bool:
        return (self.mesh is not None
                and getattr(self._index, "placement", "row") == "list")

    def _after_dispatch(self, plan, t0: float):
        """Post-dispatch health plumbing for one routed dispatch: run
        the chaos/dispatch hook with the plan's participants (scripted
        stragglers advance the injected clock HERE — deterministically),
        then attribute the elapsed time to every participant
        (``ShardHealth.observe_latency`` — the SUSPECT feed).  Returns
        ``(participant ranks, elapsed seconds)``."""
        from raft_tpu.parallel.routing import participant_ranks

        ranks = participant_ranks(plan)
        if self._dispatch_hook is not None:
            self._dispatch_hook(ranks)
        elapsed = self._monotonic() - t0
        if self.health is not None:
            for r in ranks:
                self.health.observe_latency(int(r), elapsed)
        return ranks, elapsed

    def _maybe_hedge(self, out, q, k: int, live, params, valid_rows,
                     suspect, ranks, elapsed: float):
        """The hedge decision for one completed routed dispatch: when
        the elapsed time outlived the per-bucket budget AND a
        participant has (newly) gone suspect, re-dispatch with the
        fresh suspect mask — every replicated list steers onto the
        healthy copy — and serve the faster-by-the-clock answer.
        Returns ``(result, hedged, elapsed_of_served)``."""
        bucket = (int(q.shape[0]), int(k))
        budget = self.hedge.budget(self._dispatch_stats.latency_quantile(
            bucket, self.hedge.quantile,
            min_samples=self.hedge.min_samples))
        if budget is None or elapsed <= budget:
            return out, False, elapsed
        prev = suspect if suspect is not None else np.zeros(
            self.health.n_ranks, bool)
        now = self.health.suspect_mask
        if not any(now[int(r)] and not prev[int(r)] for r in ranks):
            # Over budget but re-planning would repeat the same route
            # (no NEW suspect participant to steer around).
            self.hedge_stats.record(suppressed=True)
            return out, False, elapsed
        self.hedge_stats.record(fired=True)
        plan_box: list = []
        t1 = self._monotonic()
        out2 = self._dispatch(q, k, live, valid_rows=valid_rows,
                              params=params, suspect=now,
                              plan_cb=plan_box.append)
        elapsed2 = elapsed
        if plan_box:
            _, elapsed2 = self._after_dispatch(plan_box[-1], t1)
        if elapsed2 < elapsed:
            self.hedge_stats.record(won=True)
            return out2, True, elapsed2
        return out, True, elapsed

    def search(self, queries, k: int,
               degraded: Optional[bool] = None,
               span=None, valid_rows: Optional[int] = None,
               n_probes: Optional[int] = None
               ) -> SearchResult:
        """One synchronous search, already shaped (the scheduler owns
        bucketing/padding). ``degraded=None`` auto-selects: the healthy
        trace while every shard is live, the live_mask trace (exact over
        survivors + coverage) as soon as the health registry reports a
        dead rank. Retries under ``self.retry`` when set.

        ``n_probes`` overrides the configured probe count for THIS
        call (IVF kinds) — the degradation ladder's knob
        (serve/scheduler.DegradePolicy).  n_probes is a jit STATIC:
        only ladder-rung values pre-compiled by
        ``serve.bucketing.warmup(degrade_ladder=...)`` stay
        recompile-free in steady state.

        Routed (placement="list") searchers with a ShardHealth route
        around SUSPECT shards (plan_route suspect preference), feed
        per-shard dispatch latencies back into the health registry, and
        — with a :class:`~raft_tpu.serve.hedge.HedgePolicy` — hedge a
        dispatch that outlives its per-bucket budget to the replicas,
        first result by the injected clock wins (``SearchResult.hedged``).

        ``span`` (an :class:`raft_tpu.obs.trace.Span`) attaches the two
        device-boundary child spans — ``device_dispatch`` and
        ``device_get`` (the replicated-result pull).  ``device_dispatch``
        holds two measured children: ``enqueue`` (the family dispatch:
        Python glue, the query upload and the launch of the compiled
        program) and ``device_wait`` (``jax.block_until_ready``, so the
        span closes when the device finishes).  With no recording span
        the fence is SKIPPED: tracing off must not serialize the
        dispatch pipeline, and no span machinery touches the traced
        program either way (the compiled program is identical — the
        sanitized lane proves it)."""
        from raft_tpu.obs.trace import NULL_SPAN

        sp = span if span is not None else NULL_SPAN
        q = np.asarray(queries)
        expects(q.ndim == 2, "queries must be (n, dim), got %s", q.shape)
        expects(q.shape[1] == self.dim, "query dim %s != index dim %s",
                q.shape[1], self.dim)
        expects(k >= 1, "k must be >= 1, got %s", k)
        live = self._resolve_live(degraded)
        params = self._params
        if n_probes is not None and self.kind != "brute_force":
            import dataclasses

            params = dataclasses.replace(self._params,
                                         n_probes=int(n_probes))
        routed = self._is_routed()
        suspect = None
        if routed and self.health is not None:
            sus = self.health.suspect_mask
            if sus.any():
                suspect = sus
        track = routed and (self.health is not None
                            or self._dispatch_hook is not None)
        plan_box: list = []

        def attempt():
            return self._dispatch(q, k, live, valid_rows=valid_rows,
                                  params=params, suspect=suspect,
                                  plan_cb=plan_box.append if track
                                  else None)

        import jax

        hedged = False
        with sp.child("device_dispatch", kind=self.kind,
                      engine=self.merge_engine,
                      sharded=self.mesh is not None) as dd:
            t0 = self._monotonic()
            with dd.child("enqueue"):
                if self.retry is not None:
                    out = with_retry(attempt, self.retry, sleep=self._sleep,
                                     monotonic=self._monotonic)
                else:
                    out = attempt()
            if track and plan_box:
                ranks, elapsed = self._after_dispatch(plan_box[-1], t0)
                if self.hedge is not None and self.health is not None:
                    out, hedged, elapsed = self._maybe_hedge(
                        out, q, k, live, params, valid_rows, suspect,
                        ranks, elapsed)
                self._dispatch_stats.observe_latency(
                    (int(q.shape[0]), int(k)), elapsed)
            if dd.recording:
                # Fence so the span closes when the DEVICE finishes, not
                # when XLA accepted the async dispatch — device time is
                # real, host time stays separate.
                with dd.child("device_wait"):
                    jax.block_until_ready(out)
        # jax.device_get, not np.asarray: the result pull is the DECLARED
        # host boundary of the hot path, so it stays legal under the
        # sanitizer lane's jax.transfer_guard("disallow") (tests/conftest)
        # while any hidden implicit transfer inside the path still trips.
        with sp.child("device_get"):
            host = jax.device_get(out)
        if len(host) == 3:
            d, i, cov = host
            return SearchResult(d, i, cov, degraded=True, hedged=hedged)
        d, i = host
        return SearchResult(d, i, np.ones(q.shape[0], np.float32),
                            hedged=hedged)

    def shadow_probe(self, rank: int, queries, k: int) -> float:
        """One off-the-hot-path probe of a dead/suspect shard: dispatch
        the warmed DEGRADED trace with ``rank`` forced live in the mask
        (the mask is a traced operand — one trace covers every value,
        so probing compiles nothing and moves nothing implicitly) under
        suppressed telemetry (shadow traffic must not skew the serving
        scrapes or the placement balancer's loads).  Returns the
        injected-clock elapsed seconds; raises whatever the dispatch
        raises — the :class:`~raft_tpu.serve.recovery.RecoveryProber`
        turns (elapsed, exception) into its clean/dirty verdict.
        Probe latencies deliberately do NOT feed
        ``health.observe_latency``: the candidate's slowness is the
        prober's verdict to make, not new fleet-wide evidence."""
        expects(self.health is not None and self.mesh is not None,
                "shadow_probe needs a sharded searcher with ShardHealth")
        from raft_tpu.comms.topk_merge import merge_dispatch_stats
        from raft_tpu.parallel.routing import routing_stats

        q = np.asarray(queries)
        expects(q.ndim == 2 and q.shape[1] == self.dim,
                "probe queries must be (n, %s), got %s", self.dim,
                q.shape)
        live = self.health.live_mask
        live[int(rank)] = True
        plan_box: list = []
        track = self._is_routed()
        import jax

        t0 = self._monotonic()
        with merge_dispatch_stats.suppress(), routing_stats.suppress():
            out = self._dispatch(q, k, live,
                                 plan_cb=plan_box.append if track
                                 else None)
            jax.block_until_ready(out)
        if self._dispatch_hook is not None:
            from raft_tpu.parallel.routing import participant_ranks

            ranks = (participant_ranks(plan_box[-1]) if plan_box
                     else np.arange(self.health.n_ranks))
            # The probed rank always counts as a participant: a chaos
            # delay scripted against it must slow the probe even when
            # the plan happened to route every query elsewhere —
            # otherwise a vacuous probe would read clean and re-admit
            # a still-faulty shard.
            self._dispatch_hook(np.union1d(ranks, [int(rank)]))
        return self._monotonic() - t0

    # -- lifecycle ---------------------------------------------------------
    def extend(self, new_vectors, new_indices=None) -> None:
        """Grow the underlying index and bump the epoch (invalidating
        every cached result written against the old contents).

        Sharded endpoints keep the build-time contract: TOTAL rows after
        the extend must divide the mesh axis (pad the increment upstream
        — zero-row padding would otherwise surface as fake neighbors)."""
        self._require_writable()
        with self._lock:
            self._extend_locked(new_vectors, new_indices)
        self._published()

    def _mutable_snapshot(self):
        """Shallow copy of the served index for a mutate-then-swap
        publish: the module-level mutators write the COPY's fields, the
        served object stays internally consistent for lock-free readers
        (array values are immutable), and one reference assignment
        commits the whole mutation — the same snapshot contract
        compact() gets from its copy-on-write successor."""
        import copy

        return copy.copy(self._index)

    def _extend_locked(self, new_vectors, new_indices=None) -> None:
        if self.kind == "brute_force":
            import jax.numpy as jnp

            X = jnp.asarray(np.asarray(new_vectors))
            expects(X.ndim == 2 and X.shape[1] == self.dim,
                    "new_vectors must be (n, %s), got shape %s", self.dim,
                    X.shape)
            db = jnp.concatenate([jnp.asarray(self._db), X], axis=0)
            if self.mesh is not None:
                from raft_tpu.parallel.knn import shard_database

                n_dev = self.mesh.shape["data"]
                expects(db.shape[0] % n_dev == 0,
                        "extend would leave %s total rows, not divisible "
                        "by the %s-way mesh — pad the increment upstream",
                        db.shape[0], n_dev)
                db = shard_database(self.mesh, db)
            self._db = db
            self._base_epoch += 1
        elif self.mesh is not None:
            from raft_tpu.parallel.ivf import (sharded_ivf_flat_extend,
                                               sharded_ivf_pq_extend)

            fn = (sharded_ivf_flat_extend if self.kind == "ivf_flat"
                  else sharded_ivf_pq_extend)
            # Mutate a snapshot, publish by one reference swap: a
            # lock-free reader must never observe a half-assigned field
            # set (e.g. capacity-grown data next to old-cap indices).
            # donate=False: readers may hold dispatched searches
            # against the current buffers — donation would invalidate
            # them mid-flight.
            tmp = self._mutable_snapshot()
            if self.wal is not None and new_indices is None:
                # Pin auto-assigned ids explicitly so the record holds
                # the EXACT ids this extend assigns — replay after a
                # compact (which drops tombstoned ids and can lower
                # the stored max) would otherwise re-derive different
                # auto ids than the live run's tracker handed out.
                from raft_tpu.neighbors.ivf_flat import _auto_id_base

                base = _auto_id_base(tmp)
                n_new = int(np.asarray(new_vectors).shape[0])
                new_indices = np.arange(base, base + n_new,
                                        dtype=tmp.indices.dtype)
            fn(self.mesh, tmp, new_vectors, new_indices, donate=False)
            if self.wal is not None:
                self._wal_append("extend", tmp, dict(
                    vectors=np.asarray(new_vectors),
                    ids=np.asarray(new_indices)))
            self._index = tmp
        else:
            from raft_tpu.neighbors import ivf_flat, ivf_pq

            mod = ivf_flat if self.kind == "ivf_flat" else ivf_pq
            # extend bumps the Index's own .epoch (the counter this
            # facade's ``epoch`` property reads) — no _base_epoch bump,
            # or every extend would count twice. Snapshot-swap +
            # donate=False: see the sharded branch.
            tmp = self._mutable_snapshot()
            mod.extend(tmp, new_vectors, new_indices, donate=False)
            self._index = tmp

    def delete(self, ids) -> int:
        """Tombstone rows by stored id (raft_tpu/lifecycle): exact over
        the survivors immediately, no recompile per delete (the mask is
        a traced operand).  Returns how many slots were newly
        tombstoned; bumps the epoch (invalidating cached results) only
        when that count is non-zero.  IVF endpoints only — the
        brute-force database has no id-stable delete story."""
        expects(self.kind != "brute_force",
                "delete needs an IVF index (brute-force rows are "
                "positional; rebuild the endpoint instead)")
        self._require_writable()
        from raft_tpu.lifecycle import delete as _delete

        with self._lock:
            tmp = self._mutable_snapshot()
            n = _delete(tmp, ids, mesh=self.mesh)
            if n:
                # Log only committed deletes — an all-miss delete bumps
                # no epoch, so a record for it could never replay.
                self._wal_append("delete", tmp,
                                 dict(ids=np.asarray(ids)))
                self._index = tmp     # snapshot-swap publish
        if n:
            self._published()
        return n

    def upsert(self, new_vectors, new_indices) -> None:
        """Replace-or-insert rows by explicit id under ONE epoch bump
        (tombstone + extend; raft_tpu/lifecycle.upsert) — no reader
        observes the half-applied state as a committed epoch."""
        expects(self.kind != "brute_force",
                "upsert needs an IVF index (brute-force rows are "
                "positional; rebuild the endpoint instead)")
        self._require_writable()
        from raft_tpu.lifecycle import upsert as _upsert

        with self._lock:
            # Snapshot-swap publish + donate=False — see _extend_locked.
            tmp = self._mutable_snapshot()
            _upsert(tmp, new_vectors, new_indices, mesh=self.mesh,
                    donate=False)
            self._wal_append("upsert", tmp, dict(
                vectors=np.asarray(new_vectors),
                ids=np.asarray(new_indices)))
            self._index = tmp
        self._published()

    def compact(self, policy=None, pre_publish=None):
        """Run one compaction pass (raft_tpu/lifecycle/compact.py) and
        publish its copy-on-write successor index by swapping ONE
        reference under the mutation lock — in-flight batches keep
        searching their dispatch-time snapshot, whose cache entries die
        with the old epoch.  Returns the
        :class:`~raft_tpu.lifecycle.compact.CompactionReport`, or None
        when there was nothing to do.  ``pre_publish`` runs after the
        successor is built, before the swap (the chaos injection point:
        a fault there publishes nothing)."""
        expects(self.kind != "brute_force",
                "compact applies to IVF indexes (brute-force holds no "
                "tombstones)")
        self._require_writable()
        from raft_tpu.lifecycle import CompactionPolicy
        from raft_tpu.lifecycle import compact as _compact

        policy = policy or CompactionPolicy()
        with self._lock:
            # Liveness gates the placement balancer (a re-balance must
            # not assign lists onto a dead shard) — see compact().
            live = (self.health.live_mask
                    if self.health is not None else None)
            new, report = _compact(self._index, policy, mesh=self.mesh,
                                   live_mask=live)
            if report is None:
                return None
            if pre_publish is not None:
                pre_publish()
            if self.wal is not None:
                from raft_tpu.lifecycle.wal import _policy_payload

                payload = _policy_payload(policy)
                old_pm = getattr(self._index, "placement_map", None)
                new_pm = getattr(new, "placement_map", None)
                if new_pm is not None and new_pm is not old_pm:
                    # The pass balanced the placement off process-local
                    # routing_stats traffic — record the OUTCOME so
                    # replay migrates to it instead of re-deriving from
                    # traffic it no longer has.
                    payload["owner"] = np.asarray(new_pm.owner, np.int32)
                    payload["live"] = (np.asarray(live, bool)
                                       if live is not None else
                                       np.ones(new_pm.n_dev, bool))
                self._wal_append("compact", new, payload)
            self._index = new
        self._published()
        return report

    @property
    def tombstone_frac(self) -> float:
        """Fraction of stored slots tombstoned (the Compactor trigger
        statistic); 0.0 for brute-force endpoints."""
        if self.kind == "brute_force":
            return 0.0
        from raft_tpu.lifecycle import tombstone_frac as _frac

        return _frac(self._index)

    def __repr__(self) -> str:
        return ("Searcher(kind=%r, sharded=%s, epoch=%s, engine=%r)"
                % (self.kind, self.mesh is not None, self.epoch,
                   self.merge_engine))
