"""Run one cell of the benchmark on the chip and print its result line.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The run makes the configuration's corpus on the device, builds the index
the cell's configuration names through the family's public ``build``,
serves it through ``Searcher`` and ``BatchScheduler`` (no result cache,
no deadlines, no degradation ladder, no hedging), warms the buckets the
traffic file names (and drives the traffic for its ``warm_drive_s``), then
drives the traffic for ``--seconds``; ``--seed`` draws the traffic. After
the window it reads the peak memory of each of the cell's chips, frees
the program's state and checks every answer of the window against the
exact reference (``benchmark/correct.py``). With ``--trace 1`` the window
runs under the profiler and with the program's request spans on, and the
line carries the per-layer metrics instead of the end-to-end ones.

Stdout's last line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``breakdown`` when traced) and, last,
``checks``: each compared number beside its limit, also printed as the
last lines of stderr. Earlier stderr lines give the set-up phases, the
window's compile count, the JAX events it recorded (compiles, cache
reads, traces: a window records none) and the generator's lateness.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

from benchmark import cells, correct, data, roofline, spans, trace  # noqa: E402
from benchmark.requests import no_mark  # noqa: E402

#: JAX's persistent compilation cache: a fixed directory inside the
#: checkout, whatever the environment names, so that only a cell's first
#: run in a checkout compiles and two checkouts share nothing.
CACHE_DIR = os.path.join(cells.ROOT, ".jax_cache")
#: Finished request traces the traced run keeps (every request of a
#: window; the tracer's ring buffer would otherwise drop the oldest).
MAX_TRACES = 1 << 22


class Run:
    """What the metric readers read."""

    def __init__(self, cell: str, cfg: dict, traffic: dict):
        self.cell = cell
        self.family = cfg["index"]["family"]
        self.k = int(traffic["k"])
        self.requests: list = []
        self.start = self.end = 0.0
        self.setup_s = 0.0
        self.timings: dict = {}
        self.peak_bytes = None      # the fullest chip's
        self.peaks: list = []       # each chip's, in device order
        self.recall = None
        self.stats: dict = {}
        self.trace = None
        self.work = None
        self.numbers: dict = {}
        self.control = None


def log(**fields) -> None:
    print(json.dumps(fields), file=sys.stderr, flush=True)


def require_chips(chips: int):
    """The devices, or exit non-zero: the benchmark has no CPU path."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        sys.exit("benchmark: the cell needs %d TPU chip(s); JAX found %d %s "
                 "device(s)" % (chips, len(devices), devices[0].platform))
    return devices


def lateness(requests: list) -> dict:
    late = np.array([r.sent - r.due for r in requests
                     if not math.isnan(r.sent)])
    if not late.size:
        return {}
    return {"late_p50_ms": 1e3 * float(np.percentile(late, 50)),
            "late_p99_ms": 1e3 * float(np.percentile(late, 99)),
            "late_max_ms": 1e3 * float(late.max())}


@contextlib.contextmanager
def jax_events():
    """Counts, by name, of the JAX monitoring events recorded while the
    block runs."""
    from jax import monitoring
    from jax._src import monitoring as registry

    seen: collections.Counter = collections.Counter()

    def on_event(event, **_):
        seen[event] += 1

    def on_duration(event, duration, **_):
        seen[event] += 1

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield seen
    finally:
        registry.unregister_event_listener(on_event)
        registry.unregister_event_duration_listener(on_duration)


def _program_spans(run: Run, offset_ns: float) -> list:
    """The program's per-batch host spans on the trace's clock."""
    out = []
    for _, ch in spans.batches(run):
        for name in ("batch_assembly", "device_dispatch", "device_get",
                     "result_merge"):
            if name in ch:
                out.append((name, ch[name].start * 1e9 + offset_ns,
                            ch[name].end * 1e9 + offset_ns))
    return out


def serve(cfg: dict, traffic: dict, timings: dict, searcher_hook=None):
    """Set-up up to the scheduler: ``(rows, pool, index, searcher, grid,
    warmup report)``, with the seconds of each phase put in ``timings``."""
    import jax
    from raft_tpu.core.compilation_cache import enable_compilation_cache
    from raft_tpu.serve import BucketGrid, warmup

    family = cells.load_module("families", cfg["index"]["family"])
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # No size bound, so no eviction: a bounded cache reads every entry's
    # access-time file on each write, and refuses all writes once one
    # entry lacks it (as entries written by an unbounded cache do).
    jax.config.update("jax_compilation_cache_max_size", -1)
    enable_compilation_cache()
    # Every program goes to the cache, so a warm run compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    t = time.perf_counter()
    X, pool = data.corpus(cfg)
    timings["data_s"] = time.perf_counter() - t
    t = time.perf_counter()
    index = family.build(X, cfg["index"])
    timings["build_s"] = time.perf_counter() - t
    searcher = family.searcher(index, cfg["index"])
    if searcher_hook is not None:
        searcher = searcher_hook(searcher)
    grid = BucketGrid(q_buckets=tuple(traffic["warm_buckets"]),
                      k_grid=(int(traffic["k"]),))
    t = time.perf_counter()
    warm = warmup(searcher, grid)
    timings["warmup_s"] = time.perf_counter() - t
    return X, pool, index, searcher, grid, warm


def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             traced: bool, cfg: dict = None, traffic: dict = None,
             t_start: float = None, device_kind: str = None,
             searcher_hook=None, control_precision: str = None):
    """One run of ``cell``; returns ``(result line, Run)``. ``cfg`` and
    ``traffic`` default to the files the cell names. ``searcher_hook``
    wraps the searcher before it is served (the fault tests);
    ``control_precision`` also reads the reference's own numbers at that
    lower precision on the same queries (``benchmark/control.py``)."""
    import jax
    from raft_tpu.obs import Tracer
    from raft_tpu.serve import BatchPolicy, BatchScheduler, CompileCounter

    t_start = T_PROCESS if t_start is None else t_start
    cfg = cfg or cells.load_json("configs", cell["config"])
    traffic = traffic or cells.load_json("traffic", cell["traffic"])
    loop = cells.load_module("loops", traffic["loop"])
    run = Run(cell["name"], cfg, traffic)
    X, pool, index, searcher, grid, warm = serve(
        cfg, traffic, run.timings, searcher_hook)
    drive_s = float(traffic.get("warm_drive_s", 0))
    if drive_s:
        # The traffic's own load through a scheduler of its own, so that
        # whatever the served path sets up on first use under load
        # happens in set-up, not in the window.
        t = time.perf_counter()
        loop.run(BatchScheduler(searcher, grid,
                                BatchPolicy(**traffic["policy"])),
                 pool, traffic, seed, drive_s)
        run.timings["drive_s"] = time.perf_counter() - t
    sched = BatchScheduler(
        searcher, grid, BatchPolicy(**traffic["policy"]),
        tracer=Tracer(max_traces=MAX_TRACES) if traced else None)
    # Set-up leaves a large heap; a full collection of it inside the
    # window would stall a dispatch for tens of milliseconds at a random
    # moment. Collect once now and keep what set-up made out of later
    # collections.
    gc.collect()
    gc.freeze()
    run.setup_s = time.perf_counter() - t_start
    log(phase="setup", setup_s=run.setup_s,
        warmup_compiles=warm["compile_events"], **run.timings)

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    mark = jax.profiler.TraceAnnotation if traced else no_mark
    try:
        with (trace.recording(log_dir) if traced
              else contextlib.nullcontext()):
            with mark("bench.sync"):
                sync = time.monotonic()
            with CompileCounter() as compiles, jax_events() as events, \
                    mark("bench.window"):
                run.requests, run.start, run.end = loop.run(
                    sched, pool, traffic, seed, seconds, mark=mark)
        run.peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                     for d in jax.devices()[:int(cell["chips"])]]
        run.peak_bytes = max((p for p in run.peaks if p is not None),
                             default=None)
        for r in run.requests:
            if r.ticket is not None and r.ticket.done and r.error is None:
                try:
                    r.ticket.result()
                except Exception as err:   # counted as failed
                    r.error = repr(err)
        log(phase="window", seconds=run.end - run.start,
            requests=len(run.requests), compiles=compiles.count,
            jax_events=dict(events), **lateness(run.requests))
        run.stats = sched.stats.snapshot()
        if traced:
            events = trace.load(log_dir)
            offset = trace.annotation(events, "bench.sync")[0] - sync * 1e9
            run.trace = trace.reduce(events, _program_spans(run, offset))
            log(phase="trace", programs=run.trace["programs"])
            batches = [np.concatenate([pool[r.rows] for r in members])
                       for members, _ in spans.batches(run)]
            if batches:
                work = cells.load_module("work", run.family).count(
                    index, cfg["index"]["search_params"], batches, run.k)
                kind = device_kind or jax.devices()[0].device_kind
                run.work = dict(work, **roofline.least_time(
                    work, roofline.peaks(kind)))
                log(phase="work", **run.work)
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    answered = [r for r in run.requests if r.answered]
    del sched, searcher, index
    gc.unfreeze()
    gc.collect()

    t = time.perf_counter()
    nums = {}
    if answered:
        results = [r.ticket.result() for r in answered]
        rows = np.concatenate([r.rows for r in answered])
        dist = np.concatenate([res.distances for res in results])
        ids = np.concatenate([res.indices for res in results])
        truth = correct.reference(X, pool, rows, run.k)
        nums = correct.numbers(X, pool, rows, dist, ids, truth)
        run.recall = 1.0 - nums["recall_short"]
        if control_precision:
            run.control = correct.numbers(
                X, pool, rows, *correct.reference(X, pool, rows, run.k,
                                                  control_precision), truth)
    run.numbers = nums
    checked = correct.checks(
        {n: nums.get(n, math.nan) for n in cfg["correct"]["limits"]},
        cfg["correct"]["limits"])
    log(phase="reference", seconds=time.perf_counter() - t,
        answers=sum(len(r.rows) for r in answered),
        queries=len(np.unique(rows)) if answered else 0, **nums)

    metrics = {}
    for m in cells.metrics_for(bench, cell["name"], traced):
        reader = cells.load_module("layers" if traced else "metrics",
                                   m["name"])
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.peak_bytes,
              "memory_peak_bytes_by_device": run.peaks}
    line = {"correct": bool(answered) and correct.passed(checked),
            "attempted": len(run.requests),
            "failed": len(run.requests) - len(answered),
            "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    line["checks"] = checked
    return line, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, args.workload)
    require_chips(int(cell["chips"]))
    line, _ = run_cell(bench, cell, args.seed, args.seconds,
                       bool(args.trace))
    for name, c in line["checks"].items():
        print("check %s %r limit %r" % (name, c["value"], c["limit"]),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
