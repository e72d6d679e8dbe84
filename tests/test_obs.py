"""Unified observability layer (ISSUE 11): tracer, registry, recall probe.

Ref: the reference's observability is NVTX ranges + gbench fixtures
(cpp/internal/nvtx.hpp, cpp/bench/); the serving-runtime analog needs
request span trees, a Prometheus-shape scrape surface, and an online
recall estimate — all deterministic under the injected clock, proven
here with golden-file exports (tests/golden/), a threaded
scrape-under-traffic race, probe-vs-ground-truth accuracy, and
sanitized-lane cases showing instrumented steady-state serving compiles
nothing and trips no implicit transfer.
"""

import contextlib
import gc
import glob
import json
import os
import threading

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from raft_tpu.comms.health import ShardHealth
from raft_tpu.neighbors import ivf_flat
from raft_tpu.obs import (
    CacheCollector,
    CompactorCollector,
    MergeDispatchCollector,
    MetricsRegistry,
    NULL_SPAN,
    NULL_TRACER,
    RecallProbe,
    SearcherCollector,
    ServeStatsCollector,
    ShardHealthCollector,
    Tracer,
)
from raft_tpu.serve import (
    BatchPolicy,
    BatchScheduler,
    BucketGrid,
    ResultCache,
    Searcher,
    ServeStats,
    warmup,
)

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")

N_DEV = 4
DIM = 8
N_DB = 256


def _regen():
    """Set REGEN_OBS_GOLDEN=1 to rewrite the golden files from the
    current implementation (then REVIEW THE DIFF — the goldens are the
    spec of the export formats, not a snapshot of convenience)."""
    return os.environ.get("REGEN_OBS_GOLDEN") == "1"


def _check_golden(name: str, text: str) -> None:
    path = os.path.join(GOLDEN_DIR, name)
    if _regen():
        with open(path, "w") as f:
            f.write(text)
    with open(path) as f:
        expected = f.read()
    assert text == expected, (
        f"{name} drifted from the golden export — if the change is "
        f"intentional, regenerate with REGEN_OBS_GOLDEN=1 and review")


class _StepClock:
    """Injected monotonic clock: each read advances exactly 1ms, so
    every span boundary is a deterministic multiple of 0.001."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 0.001
        return self.t


@contextlib.contextmanager
def _gc_off():
    """Automatic collections off for the block: an enabled tracer
    records each one, reading the clock (a _StepClock advances on every
    read) and adding a ``gc`` span."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def _golden_trace() -> Tracer:
    """The deterministic span scenario both golden tests export: one
    batch root owning the batch's spans (the searcher's ``enqueue`` and
    ``device_wait`` under ``device_dispatch``, one collector pause),
    copied into its one member request's tree — the shape
    BatchScheduler._dispatch builds."""
    with _gc_off():
        tracer = Tracer(clock=_StepClock(), max_traces=16)
        root = tracer.request("serve.request", rows=3, k=5, bucket="4x8",
                              seq=1)
        with root.child("cache_lookup"):
            pass
        qw = root.child("queue_wait")
        qw.finish()
        batch = tracer.scoped("serve.batch", bucket="4x8", requests=1,
                              rows=3, padded=1, seq=0)
        with batch.child("batch_assembly", bucket="4x8", requests=1):
            pass
        with batch.child("device_dispatch", kind="brute_force",
                         engine="auto", sharded=True) as dd:
            with dd.child("enqueue"):
                pass
            with dd.child("device_wait"):
                pass
        with batch.child("device_get"):
            pass
        with batch.child("result_merge"):
            pass
        batch.child_at("gc", 0.0125, 0.0135, generation=0, collected=7)
        for c in batch.children:
            root.copy_child(c)
        root.finish(batch=0, degraded=False)
        batch.finish(degraded=False)
    return tracer


def _golden_registry() -> MetricsRegistry:
    """Deterministic registry state covering every exposition shape:
    labelled counter, multi-series gauge, integer vs float formatting,
    histogram buckets, and label-value escaping."""
    reg = MetricsRegistry()
    c = reg.counter("raft_demo_requests_total", "served requests",
                    labels=("bucket", "kind"))
    c.inc(3, bucket="8x10", kind="flat")
    c.inc(bucket="4x5", kind="pq")
    live = reg.gauge("raft_demo_live", "per-rank liveness",
                     labels=("rank",))
    for rank in range(3):
        live.set(float(rank != 1), rank=rank)
    frac = reg.gauge("raft_demo_frac", "a non-integer value")
    frac.set(0.8125)
    h = reg.histogram("raft_demo_latency_seconds", "request latency",
                      labels=("bucket",), buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.002, 0.05, 0.2):
        h.observe(v, bucket="8x10")
    esc = reg.gauge("raft_demo_info", "label-value escaping",
                    labels=("note",))
    esc.set(1, note='quote "q" back\\slash\nnewline')
    return reg


# ---------------------------------------------------------------------------
# Span / Tracer unit behavior


class TestSpan:
    def test_tree_shape_and_durations(self):
        tracer = Tracer(clock=_StepClock())
        root = tracer.request("r", a=1)
        child = root.child("c", b=2)
        child.finish()
        root.finish()
        t = root.tree()
        assert t["name"] == "r" and t["attrs"] == {"a": 1}
        assert [c["name"] for c in t["children"]] == ["c"]
        assert child.duration > 0 and root.end > child.end - 1e-12

    def test_finish_idempotent_first_wins(self):
        tracer = Tracer(clock=_StepClock())
        root = tracer.request("r")
        root.finish()
        end = root.end
        root.finish()
        assert root.end == end
        assert tracer.pending == 1          # published exactly once

    def test_child_at_uses_given_interval(self):
        tracer = Tracer(clock=_StepClock())
        root = tracer.request("r")
        sp = root.child_at("pre", 1.5, 2.5, x=1)
        assert sp.start == 1.5 and sp.end == 2.5 and sp.duration == 1.0

    def test_null_span_is_inert_and_shared(self):
        assert NULL_SPAN.child("x") is NULL_SPAN
        assert NULL_SPAN.child_at("x", 0, 1) is NULL_SPAN
        assert not NULL_SPAN.recording
        NULL_SPAN.annotate(a=1)
        NULL_SPAN.finish()
        assert NULL_SPAN.attrs == {} and NULL_SPAN.tree() == {}
        with NULL_SPAN as sp:
            assert sp is NULL_SPAN

    def test_disabled_tracer_hands_out_null_span(self):
        assert NULL_TRACER.request("r") is NULL_SPAN
        tracer = Tracer(enabled=False)
        assert tracer.request("r") is NULL_SPAN
        assert tracer.take() == []

    def test_ring_buffer_bound_and_dropped(self):
        tracer = Tracer(clock=_StepClock(), max_traces=2)
        for i in range(4):
            tracer.request("r%d" % i).finish()
        assert tracer.dropped == 2
        names = [s.name for s in tracer.take()]
        assert names == ["r2", "r3"]        # oldest evicted, order kept
        assert tracer.pending == 0          # take() drained

    def test_unique_tids(self):
        tracer = Tracer(clock=_StepClock())
        a, b = tracer.request("a"), tracer.request("b")
        assert a.tid != b.tid

    def test_enabled_tracer_records_collector_pauses(self):
        with _gc_off():
            tracer = Tracer(clock=_StepClock())
            assert tracer.take_pauses() == []
            gc.collect(1)
            gc.collect()
            pauses = tracer.take_pauses()
        assert [p[2] for p in pauses] == [1, 2]       # generation
        assert all(p[0] < p[1] for p in pauses)       # on its clock
        assert tracer.take_pauses() == []             # drained
        tracer.close()

    @pytest.mark.parametrize("how", ["disabled", "closed", "collected"])
    def test_gc_callbacks_unchanged(self, how):
        """A disabled tracer registers no gc hook; an enabled one's hook
        goes when it is closed or collected (the hook holds it only
        weakly, so a tracer with published spans — a reference cycle
        through their sink — still dies)."""
        gc.collect()                    # earlier tests' dead tracers
        before = list(gc.callbacks)
        if how == "disabled":
            Tracer(enabled=False)
            assert gc.callbacks == before
            return
        tracer = Tracer()
        tracer.request("r").finish()
        assert len(gc.callbacks) == len(before) + 1
        if how == "closed":
            tracer.close()
            tracer.close()                            # idempotent
        else:
            del tracer
            gc.collect()
        assert gc.callbacks == before


# ---------------------------------------------------------------------------
# Golden exports (bit-stable: injected clock + deterministic ordering)


class TestGoldenExports:
    def test_chrome_trace_golden(self):
        tracer = _golden_trace()
        _check_golden("obs_chrome_trace.json",
                      tracer.chrome_trace_json() + "\n")

    def test_chrome_trace_rebuild_bit_identical(self):
        assert (_golden_trace().chrome_trace_json()
                == _golden_trace().chrome_trace_json())

    def test_chrome_trace_event_invariants(self):
        doc = _golden_trace().chrome_trace()
        events = doc["traceEvents"]
        assert all(e["ph"] == "X" for e in events)
        assert all(isinstance(e["ts"], int) and isinstance(e["dur"], int)
                   for e in events)
        root = events[0]
        assert root["name"] == "serve.request"
        assert root["args"]["batch"] == 0
        kids = [e["name"] for e in events if e["tid"] == root["tid"]][1:]
        assert kids == ["cache_lookup", "queue_wait", "batch_assembly",
                        "device_dispatch", "enqueue", "device_wait",
                        "device_get", "result_merge", "gc"]
        batch = [e for e in events if e["name"] == "serve.batch"][0]
        # The member's copies carry the batch's own intervals.
        own = [(e["name"], e["ts"], e["dur"]) for e in events
               if e["tid"] == batch["tid"]][1:]
        assert own == [(e["name"], e["ts"], e["dur"]) for e in events
                       if e["tid"] == root["tid"]][3:]

    def test_json_export_roundtrip(self):
        tracer = _golden_trace()
        trees = json.loads(tracer.to_json())
        assert len(trees) == 2
        assert trees[0]["attrs"]["bucket"] == "4x8"
        assert len(trees[0]["children"]) == 7
        assert trees[1]["attrs"]["seq"] == trees[0]["attrs"]["batch"]

    def test_prometheus_golden(self):
        _check_golden("obs_scrape.prom",
                      _golden_registry().prometheus_text())

    def test_prometheus_rebuild_bit_identical(self):
        assert (_golden_registry().prometheus_text()
                == _golden_registry().prometheus_text())

    def test_snapshot_matches_exposition(self):
        snap = _golden_registry().snapshot()
        assert snap["raft_demo_requests_total"]["type"] == "counter"
        series = snap["raft_demo_requests_total"]["series"]
        assert {tuple(sorted(s["labels"].items())): s["value"]
                for s in series} == {
            (("bucket", "4x5"), ("kind", "pq")): 1.0,
            (("bucket", "8x10"), ("kind", "flat")): 3.0}
        h = snap["raft_demo_latency_seconds"]["series"][0]
        assert h["count"] == 4 and h["buckets"]["0.001"] == 1
        assert h["buckets"]["+Inf"] == 4


# ---------------------------------------------------------------------------
# Registry semantics


class TestRegistry:
    def test_redeclare_identical_returns_same(self):
        reg = MetricsRegistry()
        a = reg.counter("x_total", "h", labels=("l",))
        b = reg.counter("x_total", "other help", labels=("l",))
        assert a is b and len(reg) == 1

    def test_conflicting_redeclare_raises(self):
        reg = MetricsRegistry()
        reg.counter("x_total", labels=("l",))
        with pytest.raises(ValueError, match="already declared"):
            reg.gauge("x_total", labels=("l",))
        with pytest.raises(ValueError, match="already declared"):
            reg.counter("x_total", labels=("other",))

    def test_invalid_names_raise(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match="invalid metric name"):
            reg.counter("9bad")
        with pytest.raises(ValueError, match="invalid label name"):
            reg.counter("ok_total", labels=("bad-label",))

    def test_counter_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("c_total")
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1)

    def test_label_mismatch_raises(self):
        reg = MetricsRegistry()
        c = reg.counter("c_total", labels=("a",))
        with pytest.raises(ValueError, match="takes labels"):
            c.inc(b="x")
        with pytest.raises(ValueError, match="takes labels"):
            c.inc()

    def test_histogram_bucket_validation(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match="ascending"):
            reg.histogram("h", buckets=(0.1, 0.1))
        with pytest.raises(ValueError, match="ascending"):
            reg.histogram("h2", buckets=())

    def test_histogram_bucket_mismatch_raises(self):
        """A re-declaration with different buckets must raise, not
        silently hand back the first declaration's coarse buckets."""
        reg = MetricsRegistry()
        h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
        assert reg.histogram("h_seconds", buckets=(1.0, 0.1)) is h
        with pytest.raises(ValueError, match="already declared"):
            reg.histogram("h_seconds", buckets=(0.001, 0.01))

    def test_collector_unsubscribe(self):
        reg = MetricsRegistry()
        calls = []
        unsub = reg.register_collector(lambda: calls.append(1))
        reg.collect()
        unsub()
        unsub()                              # idempotent
        reg.collect()
        assert calls == [1]

    def test_scrape_under_traffic_race(self):
        """Writers hammer a counter + histogram + ServeStats while
        scrapers loop the full exposition: no exception, no torn line,
        and the post-join totals are exact (no lost increment)."""
        reg = MetricsRegistry()
        c = reg.counter("race_total", labels=("w",))
        h = reg.histogram("race_latency_seconds", buckets=(0.01, 0.1))
        stats = ServeStats()
        ServeStatsCollector(reg, stats)
        n_writers, n_iters = 4, 500
        barrier = threading.Barrier(n_writers + 2)
        errors = []

        def write(w):
            barrier.wait()
            for i in range(n_iters):
                c.inc(w=str(w))
                h.observe(0.001 * (i % 7))
                stats.count((8, 5), "requests")
                stats.observe_latency((8, 5), 0.001)

        def scrape():
            barrier.wait()
            try:
                for _ in range(50):
                    text = reg.prometheus_text()
                    for line in text.splitlines():
                        assert line.startswith(("#", "r"))
                    reg.snapshot()
            except Exception as e:          # surfaced after join
                errors.append(e)

        threads = [threading.Thread(target=write, args=(w,))
                   for w in range(n_writers)]
        threads += [threading.Thread(target=scrape) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert all(c.value(w=str(w)) == n_iters
                   for w in range(n_writers))
        text = reg.prometheus_text()
        assert ('race_latency_seconds_count %d' % (n_writers * n_iters)
                in text)
        assert ('raft_serve_requests_total{bucket="8x5"} %d'
                % (n_writers * n_iters)) in text


# ---------------------------------------------------------------------------
# Collectors: one scrape returns every island


@pytest.fixture(scope="module")
def mesh4():
    devs = np.array(jax.devices())
    assert devs.size >= N_DEV
    return Mesh(devs[:N_DEV], ("data",))


@pytest.fixture(scope="module")
def db():
    return np.random.default_rng(7).normal(
        size=(N_DB, DIM)).astype(np.float32)


class TestCollectors:
    def test_serve_stats_quantiles_and_samples(self):
        """Satellite: snapshot() now exposes p90/max and the live
        sample-window count — quantile confidence on the scrape."""
        stats = ServeStats()
        for ms in range(1, 101):
            stats.observe_latency((8, 5), ms / 1000.0)
        row = stats.snapshot()["buckets"]["8x5"]
        assert row["latency_p50"] == pytest.approx(0.050, abs=0.002)
        assert row["latency_p90"] == pytest.approx(0.090, abs=0.002)
        assert row["latency_p99"] == pytest.approx(0.099, abs=0.002)
        assert row["latency_max"] == pytest.approx(0.100)
        assert row["latency_samples"] == 100

        reg = MetricsRegistry()
        ServeStatsCollector(reg, stats)
        text = reg.prometheus_text()
        for q in ("p50", "p90", "p99", "max"):
            assert 'raft_serve_latency_seconds{bucket="8x5",q="%s"}' % q \
                in text
        assert 'raft_serve_latency_samples{bucket="8x5"} 100' in text

    def test_shard_health_gauge_and_flap_events(self):
        health = ShardHealth(4)
        reg = MetricsRegistry()
        col = ShardHealthCollector(reg, health)
        health.mark_dead(2)
        health.mark_live(2)                 # flap BETWEEN scrapes
        health.mark_dead(1)
        text = reg.prometheus_text()
        assert 'raft_shard_live{rank="1"} 0' in text
        assert 'raft_shard_live{rank="2"} 1' in text
        assert 'raft_shard_n_live 3' in text
        # The gauge alone would read "rank 2 fine" — the transition
        # counter keeps the die+revive visible.
        assert 'raft_shard_transitions_total{rank="2",to="dead"} 1' in text
        assert 'raft_shard_transitions_total{rank="2",to="live"} 1' in text
        col.close()
        health.mark_dead(0)                 # after close: not counted
        assert ('raft_shard_transitions_total{rank="0",to="dead"}'
                not in reg.prometheus_text())

    def test_record_threshold_fires_listener_once(self):
        from raft_tpu.comms import StatusT

        health = ShardHealth(2, failure_threshold=2)
        events = []
        health.add_listener(lambda rank, live: events.append((rank, live)))
        health.record(0, StatusT.ERROR)
        assert events == []                 # below the threshold
        health.record(0, StatusT.ERROR)
        health.record(0, StatusT.ERROR)     # already dead: no re-fire
        assert events == [(0, False)]

    def test_cache_collector(self):
        cache = ResultCache(capacity=4)
        reg = MetricsRegistry()
        CacheCollector(reg, cache)
        cache.get(0, np.zeros((1, 2), np.float32), 5)       # miss
        text = reg.prometheus_text()
        assert "raft_cache_misses_total 1" in text
        assert "raft_cache_capacity 4" in text

    def test_compactor_scrape_surface(self, db):
        """Satellite: pass failures and the last CompactionReport are
        scrapeable — a failed pass used to be one warning line."""
        from raft_tpu.lifecycle.compact import CompactionPolicy, Compactor

        index = ivf_flat.build(
            ivf_flat.IndexParams(n_lists=4, kmeans_n_iters=2), db)
        s = Searcher.ivf_flat(index, ivf_flat.SearchParams(n_probes=4))
        s.delete(np.arange(64))
        comp = Compactor(s, CompactionPolicy(trigger_frac=0.05))
        reg = MetricsRegistry()
        CompactorCollector(reg, comp)
        assert comp.should_run()
        report = comp.run_once()
        assert report is not None
        text = reg.prometheus_text()
        assert "raft_compactor_passes_total 1" in text
        assert ('raft_compactor_last_report{field="reclaimed_slots"} 64'
                in text)
        assert 'raft_compactor_last_report{field="epoch"}' in text

        # A raising pass lands on the scrape (counter + error label).
        def boom():
            raise RuntimeError("injected-compaction-fault")

        s.delete(np.arange(64, 128))
        comp._pre_publish = boom
        with pytest.raises(RuntimeError):
            comp.run_once(force=True)
        text = reg.prometheus_text()
        assert "raft_compactor_failures_total 1" in text
        assert "injected-compaction-fault" in text
        # Next success clears the failure flag.
        comp._pre_publish = None
        assert comp.run_once(force=True) is not None
        text = reg.prometheus_text()
        assert "raft_compactor_failures_total 1" in text
        assert "injected-compaction-fault" not in text

    def test_compactor_drift_signal_triggers(self, db):
        from raft_tpu.lifecycle.compact import CompactionPolicy, Compactor

        index = ivf_flat.build(
            ivf_flat.IndexParams(n_lists=4, kmeans_n_iters=2), db)
        s = Searcher.ivf_flat(index, ivf_flat.SearchParams(n_probes=4))
        drifted = [False]
        comp = Compactor(s, CompactionPolicy(trigger_frac=0.25),
                         drift_signal=lambda: drifted[0])
        assert not comp.should_run()        # no tombstones, no drift
        drifted[0] = True
        assert comp.should_run()            # query-aware trigger
        assert comp.last_should_run
        # Edge-triggered: a still-tripped flag must not force a full
        # compaction every daemon interval — one pass per episode.
        assert not comp.should_run()
        drifted[0] = False
        assert not comp.should_run()        # episode over: re-arms
        drifted[0] = True
        assert comp.should_run()            # fresh episode fires again

    def test_searcher_collector(self, db):
        index = ivf_flat.build(
            ivf_flat.IndexParams(n_lists=4, kmeans_n_iters=2), db)
        s = Searcher.ivf_flat(index, ivf_flat.SearchParams(n_probes=4))
        reg = MetricsRegistry()
        SearcherCollector(reg, s)
        s.delete(np.arange(32))
        text = reg.prometheus_text()
        assert s.epoch >= 1                 # the delete bumped it
        assert "raft_index_epoch %d" % s.epoch in text
        assert "raft_index_n_deleted 32" in text
        frac = 32.0 / N_DB
        assert ("raft_index_tombstone_frac %s" % repr(frac)) in text

    def test_merge_dispatch_collector(self, mesh4, db):
        from raft_tpu.comms.topk_merge import (MergeDispatchStats,
                                               merge_comm_bytes,
                                               merge_dispatch_stats)
        from raft_tpu.parallel import shard_database, sharded_knn

        placed = shard_database(mesh4, db)
        q = np.random.default_rng(3).normal(
            size=(8, DIM)).astype(np.float32)
        before = merge_dispatch_stats.snapshot()
        sharded_knn(mesh4, placed, q, 5, merge_engine="ring")
        after = merge_dispatch_stats.snapshot()
        gained = (after["ring"]["dispatches"]
                  - before.get("ring", {}).get("dispatches", 0))
        assert gained == 1
        est = merge_comm_bytes("ring", 8, 5, 5, N_DEV)
        assert (after["ring"]["est_bytes"]
                - before.get("ring", {}).get("est_bytes", 0)) == est

        # The collector publishes per-engine series from a private
        # recorder (process-global stats stay untouched by the test).
        stats = MergeDispatchStats()
        stats.record("ring", 8, 5, 5, N_DEV)
        reg = MetricsRegistry()
        MergeDispatchCollector(reg, stats=stats)
        text = reg.prometheus_text()
        assert 'raft_merge_dispatch_total{engine="ring"} 1' in text
        assert ('raft_merge_est_exchange_bytes_total{engine="ring"} %d'
                % est) in text

    def test_one_scrape_returns_every_island(self, mesh4, db):
        """Acceptance: serve + health + lifecycle + cache + merge-engine
        metrics in ONE valid Prometheus text scrape."""
        from raft_tpu.comms.topk_merge import MergeDispatchStats
        from raft_tpu.lifecycle.compact import Compactor

        health = ShardHealth(N_DEV)
        s = Searcher.brute_force(db, mesh=mesh4, health=health)
        grid = BucketGrid.pow2(8, k_grid=(5,))
        cache = ResultCache(capacity=8)
        sched = BatchScheduler(
            s, grid, BatchPolicy(max_batch=8, max_wait=0.0),
            cache=cache)
        mstats = MergeDispatchStats()
        mstats.record("allgather", 8, 5, 5, N_DEV)

        reg = MetricsRegistry()
        cols = [ServeStatsCollector(reg, sched.stats),
                ShardHealthCollector(reg, health),
                CacheCollector(reg, cache),
                SearcherCollector(reg, s),
                MergeDispatchCollector(reg, stats=mstats),
                CompactorCollector(reg, Compactor(s))]
        t = sched.submit(np.random.default_rng(5).normal(
            size=(4, DIM)).astype(np.float32), 5)
        sched.run_until_idle()
        assert t.done
        text = reg.prometheus_text()
        for fam in ("raft_serve_requests_total", "raft_shard_n_live",
                    "raft_cache_size", "raft_index_epoch",
                    "raft_merge_dispatch_total",
                    "raft_compactor_passes_total"):
            assert fam in text, fam
        # Valid exposition: every non-comment line is `name{...} value`,
        # every family has a TYPE line before its samples.
        typed = set()
        for line in text.splitlines():
            if line.startswith("# TYPE"):
                typed.add(line.split()[2])
            elif not line.startswith("#"):
                name = line.split("{")[0].split(" ")[0]
                base = name
                for suffix in ("_bucket", "_sum", "_count"):
                    if name.endswith(suffix) and \
                            name[:-len(suffix)] in typed:
                        base = name[:-len(suffix)]
                assert base in typed, line
                float(line.rsplit(" ", 1)[1])
        sched.close()


# ---------------------------------------------------------------------------
# Request tracing through the scheduler


class TestServeTracing:
    def _serve(self, db, mesh4, *, cache=None, n=3):
        clock = _StepClock()
        tracer = Tracer(clock=clock)
        s = Searcher.brute_force(db, mesh=mesh4)
        grid = BucketGrid.pow2(8, k_grid=(5,))
        sched = BatchScheduler(
            s, grid, BatchPolicy(max_batch=8, max_wait=0.0),
            cache=cache, clock=clock, tracer=tracer)
        q = np.random.default_rng(2).normal(
            size=(n, DIM)).astype(np.float32)
        t = sched.submit(q, 5)
        sched.run_until_idle()
        assert t.done
        return tracer, sched, q

    @staticmethod
    def _by_name(span):
        return {c.name: c for c in span.children}

    def test_sharded_dispatch_measures_enqueue_and_device_wait(self, db,
                                                                mesh4):
        """A sharded (pipelined) searcher's device_dispatch holds the
        two measured children, enqueue then device_wait, and nothing
        else."""
        from raft_tpu.parallel import sharded_ivf_flat_build

        tracer = Tracer(clock=_StepClock())
        params = ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=2)
        index = sharded_ivf_flat_build(mesh4, params, db)
        s = Searcher.ivf_flat(index, ivf_flat.SearchParams(n_probes=8),
                              mesh=mesh4, merge_engine="pipelined")
        q = np.random.default_rng(3).normal(
            size=(8, DIM)).astype(np.float32)
        root = tracer.request("serve.request")
        s.search(q, 5, span=root)
        root.finish()
        dd = self._by_name(root)["device_dispatch"]
        assert dd.attrs == {"kind": "ivf_flat", "engine": "pipelined",
                            "sharded": True}
        assert [c.name for c in dd.children] == ["enqueue", "device_wait"]
        enq, wait = dd.children
        assert dd.start <= enq.start < enq.end <= wait.start < wait.end \
            <= dd.end

    def test_device_dispatch_enqueue_then_device_wait(self, db, mesh4):
        """Under the scheduler, device_dispatch holds enqueue then
        device_wait: nested inside it, in that order, not overlapping."""
        tracer, sched, _ = self._serve(db, mesh4)
        batch = [sp for sp in tracer.take() if sp.name == "serve.batch"][0]
        dd = self._by_name(batch)["device_dispatch"]
        assert [c.name for c in dd.children] == ["enqueue", "device_wait"]
        enq, wait = dd.children
        assert dd.start < enq.start < enq.end < wait.start < wait.end \
            < dd.end
        sched.close()

    def test_batch_root_owns_its_tree(self, db, mesh4):
        """The serve.batch root owns assembly, dispatch, pull and merge
        as its own children and carries ``seq``; every member's tree
        carries the same intervals (subtrees included) plus
        ``batch=<seq>``."""
        with _gc_off():
            clock = _StepClock()
            tracer = Tracer(clock=clock)
            s = Searcher.brute_force(db, mesh=mesh4)
            sched = BatchScheduler(
                s, BucketGrid.pow2(8, k_grid=(5,)),
                BatchPolicy(max_batch=8, max_wait=0.0), clock=clock,
                tracer=tracer)
            rng = np.random.default_rng(4)
            for _ in range(2):
                tickets = [sched.submit(rng.normal(size=(n, DIM)).astype(
                    np.float32), 5) for n in (3, 2)]
                sched.run_until_idle()
        spans = tracer.take()
        batches = [sp for sp in spans if sp.name == "serve.batch"]
        assert [b.attrs["seq"] for b in batches] == [0, 1]
        batch = batches[1]
        assert [c.name for c in batch.children] == [
            "batch_assembly", "device_dispatch", "device_get",
            "result_merge"]
        for c in batch.children:
            assert batch.start <= c.start <= c.end <= batch.end

        def shape(sp):
            return (sp.name, sp.start, sp.end, sp.attrs,
                    [shape(c) for c in sp.children])

        members = [sp for sp in spans if sp.name == "serve.request"
                   and sp.attrs["batch"] == 1]
        assert sorted(m.attrs["seq"] for m in members) == \
            sorted(t.seq for t in tickets)
        for m in members:
            assert m.children[0].name == "queue_wait"
            assert [shape(c) for c in m.children[1:]] == \
                [shape(c) for c in batch.children]
        sched.close()

    def test_gc_pause_is_a_child_of_the_next_batch(self, db, mesh4):
        """A collection forced between two batches is a ``gc`` child,
        with its generation, of the second batch and of its members,
        not of the first."""
        with _gc_off():
            tracer = Tracer()
            s = Searcher.brute_force(db, mesh=mesh4)
            sched = BatchScheduler(
                s, BucketGrid.pow2(8, k_grid=(5,)),
                BatchPolicy(max_batch=8, max_wait=0.0), tracer=tracer)
            q = np.random.default_rng(5).normal(
                size=(2, DIM)).astype(np.float32)
            first = sched.submit(q, 5)
            sched.run_until_idle()
            gc.collect()
            second = sched.submit(q, 5)
            sched.run_until_idle()
        batches = [sp for sp in tracer.take() if sp.name == "serve.batch"]
        assert "gc" not in self._by_name(batches[0])
        assert "gc" not in self._by_name(first.span)
        for sp in (batches[1], second.span):
            pauses = [c for c in sp.children if c.name == "gc"]
            assert [p.attrs["generation"] for p in pauses] == [2]
            assert pauses[0].attrs["collected"] >= 0
            assert batches[0].end <= pauses[0].start <= pauses[0].end \
                <= batches[1].start
        sched.close()

    def test_live_spans_enter_profiler_ranges(self, db, mesh4, tmp_path):
        """Under jax.profiler, the batch root, each of its live spans and
        each collector pause land in the trace as host ranges
        ``raft_tpu::serve.<name>``, nested as the spans are."""
        tracer = Tracer()
        s = Searcher.brute_force(db, mesh=mesh4)
        sched = BatchScheduler(s, BucketGrid.pow2(8, k_grid=(5,)),
                               BatchPolicy(max_batch=8, max_wait=0.0),
                               tracer=tracer)
        q = np.random.default_rng(6).normal(
            size=(2, DIM)).astype(np.float32)
        sched.submit(q, 5)
        sched.run_until_idle()                      # compiles outside
        with jax.profiler.trace(str(tmp_path)):
            sched.submit(q, 5)
            gc.collect()
            sched.run_until_idle()
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        ranges = {}
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("raft_tpu::serve."):
                        ranges.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
        names = ["batch", "batch_assembly", "device_dispatch", "enqueue",
                 "device_wait", "device_get", "result_merge"]
        assert {"raft_tpu::serve." + n for n in names + ["gc"]} <= \
            set(ranges)
        (b0, b1), = ranges["raft_tpu::serve.batch"]
        for n in names[1:]:
            (s0, s1), = ranges["raft_tpu::serve." + n]
            assert b0 <= s0 <= s1 <= b1
        (d0, d1), = ranges["raft_tpu::serve.device_dispatch"]
        for n in ("enqueue", "device_wait"):
            (s0, s1), = ranges["raft_tpu::serve." + n]
            assert d0 <= s0 <= s1 <= d1
        sched.close()
        tracer.close()

    def test_complete_span_tree_per_request(self, db, mesh4):
        tracer, sched, _ = self._serve(db, mesh4)
        spans = tracer.take()
        roots = [s for s in spans if s.name == "serve.request"]
        assert len(roots) == 1
        root = roots[0]
        names = [c.name for c in root.children if c.name != "gc"]
        assert names == ["queue_wait", "batch_assembly",
                         "device_dispatch", "device_get", "result_merge"]
        # Every span closed, monotonic on the injected clock, children
        # inside the root's interval.
        assert root.end is not None
        for c in root.children:
            assert c.end is not None and c.end >= c.start
            assert c.end <= root.end
        # Host/device separation: the fenced device_dispatch interval
        # ends before the result pull starts.
        by = {c.name: c for c in root.children}
        assert by["device_dispatch"].end <= by["device_get"].start
        assert by["queue_wait"].end <= by["device_dispatch"].start
        assert by["device_dispatch"].attrs["kind"] == "brute_force"
        batch = [s for s in spans if s.name == "serve.batch"]
        assert len(batch) == 1 and batch[0].attrs["requests"] == 1
        sched.close()

    def test_cache_hit_short_circuits_trace(self, db, mesh4):
        tracer, sched, q = self._serve(db, mesh4,
                                       cache=ResultCache(capacity=8))
        tracer.take()
        t = sched.submit(q, 5)              # exact repeat: cache hit
        assert t.done
        spans = tracer.take()
        assert len(spans) == 1
        root = spans[0]
        assert root.attrs["cache"] == "hit"
        assert [c.name for c in root.children] == ["cache_lookup"]
        sched.close()

    def test_shed_request_trace_closed(self, db, mesh4):
        from raft_tpu.serve.scheduler import Overloaded

        clock = _StepClock()
        tracer = Tracer(clock=clock)
        s = Searcher.brute_force(db, mesh=mesh4)
        grid = BucketGrid.pow2(8, k_grid=(5,))
        sched = BatchScheduler(
            s, grid, BatchPolicy(max_batch=8, max_wait=10.0, max_queue=1),
            clock=clock, tracer=tracer)
        q = np.random.default_rng(2).normal(
            size=(2, DIM)).astype(np.float32)
        sched.submit(q, 5)
        with pytest.raises(Overloaded):
            sched.submit(q, 5)
        shed = [s for s in tracer.take() if s.attrs.get("shed")]
        assert len(shed) == 1 and shed[0].end is not None
        sched.run_until_idle()
        sched.close()

    def test_failed_batch_closes_spans_with_error(self, db, mesh4):
        clock = _StepClock()
        tracer = Tracer(clock=clock)
        s = Searcher.brute_force(db, mesh=mesh4)
        grid = BucketGrid.pow2(8, k_grid=(5,))
        sched = BatchScheduler(
            s, grid, BatchPolicy(max_batch=8, max_wait=0.0),
            clock=clock, tracer=tracer)
        t = sched.submit(np.random.default_rng(2).normal(
            size=(2, DIM)).astype(np.float32), 5)
        s._db = None                        # force the dispatch to raise
        sched.run_until_idle()
        with pytest.raises(Exception):
            t.result()
        spans = tracer.take()
        assert spans                        # roots still closed
        root = [sp for sp in spans if sp.name == "serve.request"][0]
        assert root.end is not None and "error" in root.attrs
        sched.close()

    def test_tracer_off_is_default_and_inert(self, db, mesh4, monkeypatch):
        """Tracing off: no spans, no fence, no profiler range from obs,
        no gc hook."""
        import raft_tpu.obs.trace as obs_trace

        ranges, fences = [], []
        monkeypatch.setattr(obs_trace, "push_range", ranges.append)
        monkeypatch.setattr(jax, "block_until_ready", fences.append)
        gc.collect()                    # earlier tests' dead tracers
        before = list(gc.callbacks)
        s = Searcher.brute_force(db, mesh=mesh4)
        grid = BucketGrid.pow2(8, k_grid=(5,))
        sched = BatchScheduler(s, grid,
                               BatchPolicy(max_batch=8, max_wait=0.0))
        assert sched.tracer is NULL_TRACER
        t = sched.submit(np.random.default_rng(2).normal(
            size=(3, DIM)).astype(np.float32), 5)
        gc.collect()
        sched.run_until_idle()
        assert t.done and t.span is NULL_SPAN
        assert NULL_TRACER.pending == 0
        assert fences == [] and gc.callbacks == before
        # Only the pauses of other, still enabled tracers of the process.
        assert set(ranges) <= {"serve.gc"}
        sched.close()


# ---------------------------------------------------------------------------
# Recall probe


def _np_truth(db, q, k):
    d = ((q * q).sum(1)[:, None] + (db * db).sum(1)[None, :]
         - 2.0 * q @ db.T)
    return np.argsort(d, axis=1)[:, :k]


class TestRecallProbe:
    def _ivf_searcher(self, db, n_probes):
        index = ivf_flat.build(
            ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4), db)
        return Searcher.ivf_flat(index,
                                 ivf_flat.SearchParams(n_probes=n_probes))

    def test_estimate_matches_brute_force_truth(self, db):
        """Acceptance: with rate=1.0 (zero sampling error) the probe's
        estimate equals the true mean recall of the served answers
        against numpy brute-force ground truth."""
        rng = np.random.default_rng(17)
        s = self._ivf_searcher(db, n_probes=2)   # lossy on purpose
        grid = BucketGrid.pow2(8, k_grid=(5,))
        probe = RecallProbe(s, rate=1.0, seed=3, max_pending=64)
        sched = BatchScheduler(s, grid,
                               BatchPolicy(max_batch=8, max_wait=0.0),
                               probe=probe)
        served = []
        for _ in range(8):
            q = rng.normal(size=(4, DIM)).astype(np.float32)
            t = sched.submit(q, 5)
            sched.run_until_idle()
            served.append((q, t.result().indices))
        assert probe.run_pending() == 8
        est = probe.recall()
        true = float(np.mean(
            [len(np.intersect1d(idx[r], _np_truth(db, q, 5)[r])) / 5.0
             for q, idx in served for r in range(q.shape[0])]))
        assert est == pytest.approx(true, abs=1e-9)
        assert 0.0 < est < 1.0              # lossy probes, real signal
        snap = probe.snapshot()
        assert snap["scanned"] == 8 and snap["buckets"]["4x5"]["samples"] \
            == 32
        sched.close()

    def test_sampling_is_deterministic(self, db):
        s = self._ivf_searcher(db, n_probes=8)

        def sampled_seq(seed):
            probe = RecallProbe(s, rate=0.3, seed=seed)
            q = np.zeros((1, DIM), np.float32)
            return [probe.offer(q, 5, np.zeros((1, 5), np.int64),
                                (1, 5), s.epoch) for _ in range(64)]

        a, b = sampled_seq(9), sampled_seq(9)
        assert a == b and any(a) and not all(a)
        assert sampled_seq(10) != a         # seed actually matters

    def test_rate_limit_drops_never_blocks(self, db):
        s = self._ivf_searcher(db, n_probes=8)
        probe = RecallProbe(s, rate=1.0, seed=0, max_pending=2)
        q = np.zeros((1, DIM), np.float32)
        for _ in range(5):
            probe.offer(q, 5, np.zeros((1, 5), np.int64), (1, 5),
                        s.epoch)
        snap = probe.snapshot()
        assert snap["pending"] == 2 and snap["dropped"] == 3

    def test_stale_epoch_discarded(self, db):
        s = self._ivf_searcher(db, n_probes=8)
        probe = RecallProbe(s, rate=1.0, seed=0)
        q = np.random.default_rng(0).normal(
            size=(1, DIM)).astype(np.float32)
        probe.offer(q, 5, np.zeros((1, 5), np.int64), (1, 5), s.epoch)
        s.delete(np.array([0]))            # epoch moves before the scan
        assert probe.run_pending() == 0
        assert probe.snapshot()["stale"] == 1

    def test_drift_flag_and_registry_publish(self, db):
        s = self._ivf_searcher(db, n_probes=1)   # very lossy
        reg = MetricsRegistry()
        probe = RecallProbe(s, rate=1.0, seed=1, window=64,
                            min_samples=8, drift_below=0.999,
                            registry=reg)
        grid = BucketGrid.pow2(8, k_grid=(5,))
        sched = BatchScheduler(s, grid,
                               BatchPolicy(max_batch=8, max_wait=0.0),
                               probe=probe)
        rng = np.random.default_rng(23)
        for _ in range(4):
            t = sched.submit(rng.normal(size=(4, DIM)).astype(np.float32),
                             5)
            sched.run_until_idle()
            assert t.done
        probe.run_pending()
        assert probe.sample_count() >= 8
        assert probe.recall() < 0.999       # n_probes=1 loses neighbors
        assert probe.drift
        text = reg.prometheus_text()
        assert 'raft_recall_estimate{bucket="4x5"}' in text
        assert "raft_recall_drift 1" in text
        assert "raft_recall_scanned_total 4" in text
        probe.close()
        sched.close()

    def test_degraded_answers_not_offered(self, db, mesh4):
        health = ShardHealth(N_DEV)
        s = Searcher.brute_force(db, mesh=mesh4, health=health)
        grid = BucketGrid.pow2(8, k_grid=(5,))
        probe = RecallProbe(s, rate=1.0, seed=0)
        sched = BatchScheduler(s, grid,
                               BatchPolicy(max_batch=8, max_wait=0.0),
                               probe=probe)
        health.mark_dead(1)
        t = sched.submit(np.random.default_rng(2).normal(
            size=(2, DIM)).astype(np.float32), 5)
        sched.run_until_idle()
        assert t.result().degraded
        assert probe.snapshot()["sampled"] == 0   # partial coverage
        sched.close()                             # is not recall loss

    def test_truth_fn_override(self, db):
        s = self._ivf_searcher(db, n_probes=8)
        calls = []

        def truth(q, k):
            calls.append(q.shape)
            return _np_truth(db, np.asarray(q), k)

        probe = RecallProbe(s, rate=1.0, seed=0, truth_fn=truth)
        q = db[:2] + 1e-4
        idx = _np_truth(db, q, 5)
        probe.offer(q, 5, idx, (2, 5), s.epoch)
        assert probe.run_pending() == 1
        assert probe.recall() == 1.0 and calls

    def test_pad_ids_are_not_recall_hits(self, db):
        """PAD_ID (-1) fills short answers when k exceeds the live
        candidates; a pad-vs-pad match must not inflate the estimate."""
        s = self._ivf_searcher(db, n_probes=8)
        pad = np.full((1, 5), -1, np.int64)
        served = pad.copy()
        served[0, 0] = 7                    # one real hit, four pads

        probe = RecallProbe(s, rate=1.0, seed=0,
                            truth_fn=lambda q, k: np.asarray(
                                [[7, 9, 11, -1, -1]]))
        probe.offer(np.zeros((1, DIM), np.float32), 5, served, (1, 5),
                    s.epoch)
        assert probe.run_pending() == 1
        assert probe.recall() == pytest.approx(1.0 / 5.0)   # not 3/5

    def test_shadow_scans_do_not_count_as_serving_merges(self, db,
                                                         mesh4):
        """The probe's exact scans dispatch through the same sharded
        entries the MergeDispatchCollector meters — they must not
        inflate the raft_merge_* serving metrics."""
        from raft_tpu.comms.topk_merge import merge_dispatch_stats

        s = Searcher.brute_force(db, mesh=mesh4)
        grid = BucketGrid.pow2(8, k_grid=(5,))
        probe = RecallProbe(s, rate=1.0, seed=0)
        sched = BatchScheduler(s, grid,
                               BatchPolicy(max_batch=8, max_wait=0.0),
                               probe=probe)
        t = sched.submit(np.random.default_rng(9).normal(
            size=(2, DIM)).astype(np.float32), 5)
        sched.run_until_idle()
        assert t.done
        before = merge_dispatch_stats.snapshot()
        assert probe.run_pending() == 1     # shadow scan: suppressed
        assert merge_dispatch_stats.snapshot() == before
        sched.close()

    def test_validation(self, db):
        s = self._ivf_searcher(db, n_probes=8)
        from raft_tpu.core.error import LogicError

        for kw in ({"rate": 1.5}, {"max_pending": 0}, {"window": 0},
                   {"min_samples": 0}, {"drift_below": 0.0}):
            with pytest.raises(LogicError):
                RecallProbe(s, **kw)


# ---------------------------------------------------------------------------
# Sanitized lane: instrumentation adds no transfers, no recompiles


@pytest.mark.sanitized
def test_instrumented_serving_steady_state(mesh4, db, sanitizer_lane):
    """Acceptance: steady-state serving with the tracer RECORDING, the
    registry scraping mid-traffic, and the probe sampling at 100% runs
    with zero implicit transfers and zero recompiles — instrumentation
    reads host state and declared boundaries only, and the compiled
    programs are identical to the uninstrumented ones."""
    rng = np.random.default_rng(41)
    health = ShardHealth(N_DEV)
    searcher = Searcher.brute_force(db, mesh=mesh4, health=health)
    grid = BucketGrid.pow2(8, k_grid=(5,))
    warmup(searcher, grid)
    tracer = Tracer()
    cache = ResultCache(capacity=16)
    reg = MetricsRegistry()
    probe = RecallProbe(searcher, rate=1.0, seed=5, registry=reg)
    sched = BatchScheduler(searcher, grid,
                           BatchPolicy(max_batch=8, max_wait=0.0),
                           cache=cache, tracer=tracer, probe=probe)
    ServeStatsCollector(reg, sched.stats)
    ShardHealthCollector(reg, health)
    CacheCollector(reg, cache)
    SearcherCollector(reg, searcher)
    MergeDispatchCollector(reg)
    # One full warm cycle: serve + probe ground-truth scan + scrape.
    t = sched.submit(rng.normal(size=(3, DIM)).astype(np.float32), 5)
    sched.run_until_idle()
    assert t.done and probe.run_pending() >= 0
    reg.prometheus_text()
    sanitizer_lane.mark_steady()

    tickets = []
    for n in (1, 4, 8, 2):
        tickets.append(sched.submit(
            rng.normal(size=(n, DIM)).astype(np.float32), 5))
        gc.collect()                        # a pause in every batch
        sched.run_until_idle()
    assert all(t.done for t in tickets)
    scanned = probe.run_pending()           # shadow exact scans
    text = reg.prometheus_text()            # scrape mid-everything
    assert "raft_serve_requests_total" in text
    assert scanned >= 1 and probe.recall() == 1.0   # brute force: exact
    spans = tracer.take()
    assert any(s.name == "serve.request" and
               [c.name for c in s.children if c.name != "gc"][-1]
               == "result_merge" for s in spans)
    # The recording tracer's whole tree was there: fenced device spans
    # and collector pauses, with nothing recompiled or transferred.
    steady = [s for s in spans if s.name == "serve.batch"][1:]
    assert steady and all(
        [c.name for c in s.children[1].children] == ["enqueue",
                                                     "device_wait"]
        and any(c.name == "gc" for c in s.children) for s in steady)
    assert sanitizer_lane.steady_compiles == 0
    sched.close()
    tracer.close()


@pytest.mark.sanitized
def test_tracer_off_identical_programs(mesh4, db, sanitizer_lane):
    """Zero-cost-when-disabled, program half: serving traced then
    untraced (and vice versa) retraces nothing — the tracer never
    becomes an operand of any compiled program."""
    rng = np.random.default_rng(43)
    searcher = Searcher.brute_force(db, mesh=mesh4)
    grid = BucketGrid.pow2(8, k_grid=(5,))
    warmup(searcher, grid)
    tracer = Tracer()
    traced = BatchScheduler(searcher, grid,
                            BatchPolicy(max_batch=8, max_wait=0.0),
                            tracer=tracer)
    plain = BatchScheduler(searcher, grid,
                           BatchPolicy(max_batch=8, max_wait=0.0))
    sanitizer_lane.mark_steady()
    q = rng.normal(size=(4, DIM)).astype(np.float32)
    t0 = traced.submit(q, 5)
    traced.run_until_idle()
    t1 = plain.submit(q, 5)
    plain.run_until_idle()
    np.testing.assert_array_equal(t0.result().indices,
                                  t1.result().indices)
    assert tracer.pending > 0 and NULL_TRACER.pending == 0
    assert sanitizer_lane.steady_compiles == 0
    traced.close()
    plain.close()


# ---------------------------------------------------------------------------
# Bench smoke (keeps bench/obs.py from rotting; same tier-1 contract as
# the serve/lifecycle/sharded families)


def test_bench_obs_family_smoke(capsys):
    from bench.obs import run

    run(quick=True)
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.strip()]
    recs = {}
    for line in lines:
        rec = json.loads(line)
        recs[rec["metric"]] = rec
    assert {"obs_tracer_off_qps", "obs_tracer_on_qps",
            "obs_tracer_overhead_pct", "obs_scrape_ms",
            "obs_probe_overhead_pct"} <= set(recs)
    assert recs["obs_tracer_off_qps"]["value"] > 0
    assert recs["obs_scrape_ms"]["value"] >= 0


# ---------------------------------------------------------------------------
# Durability + elastic telemetry on the scrape (ISSUE 17 satellite)


class TestDurabilityCollectors:
    def test_wal_collector_scrape_surface(self, mesh4, tmp_path):
        """Log bytes/records, the fsync latency histogram, snapshot
        markers and per-follower replay lag all land on one scrape —
        fed from host counters only (no file or device touch at scrape
        time)."""
        from raft_tpu.lifecycle import Follower, MutationLog, recover
        from raft_tpu.obs import WalCollector
        from raft_tpu.parallel import sharded_ivf_flat_build

        rng = np.random.default_rng(57)
        db = rng.normal(size=(256, DIM)).astype(np.float32)
        index = sharded_ivf_flat_build(
            mesh4, ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=2),
            db, placement="list")
        sp = ivf_flat.SearchParams(n_probes=8)
        clock = iter(np.arange(0.0, 100.0, 0.25))
        log = MutationLog(str(tmp_path), n_parts=2, fsync=True,
                          monotonic=lambda: float(next(clock)))
        log.snapshot(index, mesh4)
        primary = Searcher("ivf_flat", mesh=mesh4, index=index,
                           search_params=sp, wal=log)
        primary.delete(np.arange(16))
        primary.extend(rng.normal(size=(32, DIM)).astype(np.float32))

        fidx, flog = recover(mesh4, str(tmp_path), n_parts=2,
                             fsync=False)
        follower = Follower(
            Searcher("ivf_flat", mesh=mesh4, index=fidx,
                     search_params=sp, wal=flog), flog)
        primary.delete(np.arange(16, 24))      # follower now lags by 1
        follower.poll()

        reg = MetricsRegistry()
        col = WalCollector(reg, log.stats, followers=[follower])
        text = reg.prometheus_text()
        assert "raft_wal_records_total 3" in text
        assert "raft_wal_bytes_total" in text
        assert "raft_wal_snapshots_total 1" in text
        assert "raft_wal_head_epoch 3" in text
        assert "raft_wal_snapshot_epoch 0" in text
        assert 'raft_wal_replay_lag_epochs{follower="0"} 1' in text
        assert 'raft_wal_fsync_seconds_count 3' in text
        # Each fsync latency observed exactly once across scrapes.
        assert 'raft_wal_fsync_seconds_count 3' in reg.prometheus_text()
        follower.catch_up()
        assert ('raft_wal_replay_lag_epochs{follower="0"} 0'
                in reg.prometheus_text())
        col.close()
        log.close()
        flog.close()

    def test_promotion_counter_on_scrape(self, mesh4, tmp_path):
        from raft_tpu.lifecycle import (Follower, MutationLog,
                                        PromotionManager, recover)
        from raft_tpu.obs import WalCollector
        from raft_tpu.parallel import sharded_ivf_flat_build

        rng = np.random.default_rng(58)
        db = rng.normal(size=(256, DIM)).astype(np.float32)
        index = sharded_ivf_flat_build(
            mesh4, ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=2),
            db, placement="list")
        sp = ivf_flat.SearchParams(n_probes=8)
        log = MutationLog(str(tmp_path), n_parts=1, fsync=False)
        log.snapshot(index, mesh4)
        log.close()
        fidx, flog = recover(mesh4, str(tmp_path), n_parts=1,
                             fsync=False)
        follower = Follower(
            Searcher("ivf_flat", mesh=mesh4, index=fidx,
                     search_params=sp, wal=flog), flog)
        health = ShardHealth(N_DEV)
        mgr = PromotionManager(follower, health, primary_rank=0)
        reg = MetricsRegistry()
        WalCollector(reg, flog.stats, followers=[follower],
                     promotion=mgr)
        assert "raft_wal_promotions_total 0" in reg.prometheus_text()
        health.mark_dead(0)
        assert "raft_wal_promotions_total 1" in reg.prometheus_text()
        mgr.close()
        flog.close()

    def test_elastic_collector_scrape_surface(self, mesh4):
        from raft_tpu.lifecycle import join_shard, leave_shard
        from raft_tpu.lifecycle.elastic import ElasticStats, elastic_stats
        from raft_tpu.obs import ElasticCollector
        from raft_tpu.parallel import sharded_ivf_flat_build

        rng = np.random.default_rng(59)
        db = rng.normal(size=(256, DIM)).astype(np.float32)
        index = sharded_ivf_flat_build(
            mesh4, ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=2),
            db, placement="list")
        s = Searcher("ivf_flat", mesh=mesh4, index=index,
                     search_params=ivf_flat.SearchParams(n_probes=8))
        elastic_stats.reset()
        reg = MetricsRegistry()
        col = ElasticCollector(reg)            # defaults to the singleton
        assert col.stats is elastic_stats
        leave_shard(s, 3)
        join_shard(s, 3)
        text = reg.prometheus_text()
        assert "raft_elastic_joins_total 1" in text
        assert "raft_elastic_leaves_total 1" in text
        assert "raft_elastic_last_epoch 2" in text
        moved = [l for l in text.splitlines()
                 if l.startswith("raft_elastic_lists_moved_total")]
        assert moved and int(float(moved[0].split()[-1])) >= 1
        # An isolated stats object scrapes independently.
        reg2 = MetricsRegistry()
        ElasticCollector(reg2, stats=ElasticStats())
        assert "raft_elastic_joins_total 0" in reg2.prometheus_text()


# ---------------------------------------------------------------------------
# Tail-robustness collectors (ISSUE 19): suspect health, hedge,
# breaker, degradation ladder


class TestRobustnessCollectors:
    def test_shard_health_suspect_gauge_and_state_transitions(self):
        from raft_tpu.comms.health import LatencyPolicy
        from raft_tpu.obs import ShardHealthCollector

        health = ShardHealth(4, latency=LatencyPolicy())
        reg = MetricsRegistry()
        col = ShardHealthCollector(reg, health)
        health.mark_suspect(1)
        text = reg.prometheus_text()
        assert 'raft_shard_suspect{rank="1"} 1' in text
        assert 'raft_shard_suspect{rank="0"} 0' in text
        assert 'raft_shard_live{rank="1"} 1' in text   # suspect != dead
        assert 'raft_shard_n_suspect 1' in text
        assert 'raft_shard_n_live 4' in text
        # suspect edges are invisible to the binary transition counter
        # but land on the three-state feed
        assert ('raft_shard_state_transitions_total'
                '{rank="1",to="suspect"} 1') in text
        assert 'raft_shard_transitions_total{rank="1"' not in text
        health.mark_live(1)                 # re-admission between scrapes
        text = reg.prometheus_text()
        assert 'raft_shard_suspect{rank="1"} 0' in text
        assert ('raft_shard_state_transitions_total'
                '{rank="1",to="live"} 1') in text
        col.close()
        health.mark_suspect(2)              # after close: not counted
        assert ('raft_shard_state_transitions_total{rank="2"'
                not in reg.prometheus_text())

    def test_hedge_collector_scrape_surface(self):
        from raft_tpu.obs import HedgeCollector
        from raft_tpu.serve.hedge import HedgeStats

        class _S:
            hedge_stats = HedgeStats()

        s = _S()
        s.hedge_stats.record(fired=True, won=True)
        s.hedge_stats.record(suppressed=True)
        reg = MetricsRegistry()
        HedgeCollector(reg, s)
        text = reg.prometheus_text()
        assert "raft_hedge_fired_total 1" in text
        assert "raft_hedge_won_total 1" in text
        assert "raft_hedge_suppressed_total 1" in text

    def test_breaker_collector_scrape_surface(self):
        from raft_tpu.obs import BreakerCollector
        from raft_tpu.serve import RecoveryProber

        class _Stub:
            def shadow_probe(self, rank, queries, k):
                return 0.001

        health = ShardHealth(2)
        health.mark_dead(1)
        prober = RecoveryProber(_Stub(), health,
                                np.zeros((1, 4), np.float32), 4,
                                clean_threshold=3)
        reg = MetricsRegistry()
        BreakerCollector(reg, prober)
        text = reg.prometheus_text()
        assert 'raft_breaker_state{rank="0"} 0' in text   # closed
        assert 'raft_breaker_state{rank="1"} 2' in text   # open
        prober.step()
        text = reg.prometheus_text()
        assert 'raft_breaker_state{rank="1"} 1' in text   # half_open
        assert 'raft_breaker_clean_streak{rank="1"} 1' in text
        prober.step()
        prober.step()                                     # re-admitted
        text = reg.prometheus_text()
        assert 'raft_breaker_state{rank="1"} 0' in text
        assert "raft_breaker_probes_total 3" in text
        assert "raft_breaker_probes_clean_total 3" in text
        assert "raft_breaker_readmissions_total 1" in text
        prober.close()

    def test_degrade_collector_scrape_surface(self, mesh4, db):
        from raft_tpu.obs import DegradeCollector
        from raft_tpu.serve import BatchPolicy, BatchScheduler, BucketGrid

        s = Searcher.brute_force(db, mesh=mesh4)
        sched = BatchScheduler(
            s, BucketGrid.pow2(8, k_grid=(5, 10)),
            BatchPolicy(max_batch=8, max_wait=10.0, max_queue=10),
            clock=lambda: 0.0)
        reg = MetricsRegistry()
        DegradeCollector(reg, sched)
        text = reg.prometheus_text()
        assert "raft_degrade_brownout_level 0" in text
        assert "raft_degrade_queue_fill 0" in text
        sched.submit(np.zeros((1, DIM), np.float32), 5)
        sched.brownout_level = 2            # what a brownout dispatch sets
        text = reg.prometheus_text()
        assert "raft_degrade_brownout_level 2" in text
        assert "raft_degrade_queue_fill 0.1" in text
        sched.run_until_idle()
