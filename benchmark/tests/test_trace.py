"""The reduction from a profiler trace to busy time, program totals and
named idle gaps."""

import glob
import gzip
import json
import os

import pytest

from benchmark import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_events.json.gz")


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


DEV, HOST = "/device:TPU:0", "/host:CPU"


def test_busy_is_the_union_inside_the_window():
    events = [
        ev(HOST, "python3", "bench.window", 100, 1000),
        ev(HOST, "python3", "bench.pump", 100, 500),
        ev(HOST, "python3", "bench.wait", 700, 300),
        # overlapping ops count once; the part before the window not at all
        ev(DEV, "XLA Ops", "fusion.1", 50, 150),
        ev(DEV, "XLA Ops", "fusion.2", 150, 100),
        ev(DEV, "XLA Ops", "custom-call", 400, 200),
        ev(DEV, "XLA Modules", "jit_scan(7)", 50, 200),
        ev(DEV, "XLA Modules", "jit_scan(9)", 400, 200),
        ev(DEV, "XLA Modules", "jit_merge(3)", 1050, 100),
        ev(DEV, "XLA Ops", "fusion.9", 1050, 100),
    ]
    out = trace.reduce(events, host_spans=[("device_get", 600, 690)])
    assert out["window_s"] == pytest.approx(1000e-9)
    # [100,250) + [400,600) + [1050,1100)
    assert out["busy_s"] == pytest.approx(400e-9)
    assert out["programs"][0] == ["jit_scan", pytest.approx(350e-9)]
    assert dict(out["programs"])["jit_merge"] == pytest.approx(50e-9)
    ops = dict(out["device_ops"])
    assert ops["jit_scan/custom-call"] == pytest.approx(200e-9)
    assert ops["jit_scan/fusion.1"] == pytest.approx(100e-9)
    assert ops["jit_merge/fusion.9"] == pytest.approx(50e-9)
    # gaps: [250,400) in bench.pump, [600,1050) midpoint 825 in
    # bench.wait; the longest first
    assert out["idle_gaps"] == [["bench.wait", pytest.approx(450e-9)],
                                ["bench.pump", pytest.approx(150e-9)]]


def test_innermost_host_span_names_a_gap():
    events = [ev(HOST, "python3", "bench.window", 0, 100),
              ev(HOST, "python3", "bench.pump", 0, 100),
              ev(DEV, "XLA Ops", "a", 0, 10), ev(DEV, "XLA Ops", "b", 90, 10)]
    out = trace.reduce(events, host_spans=[("batch_assembly", 20, 80)])
    assert out["idle_gaps"] == [["batch_assembly", pytest.approx(80e-9)]]


def test_no_device_ops_reads_nothing():
    out = trace.reduce([ev(HOST, "python3", "bench.window", 0, 100)])
    assert out["busy_s"] is None and out["idle_gaps"] == []


def test_recorded_chip_trace():
    """100 ms of a traced ``sift1m-ivfflat-batch`` window on a TPU v5e;
    the expected numbers come from a separate edge-counting sweep."""
    with gzip.open(FIXTURE, "rt") as f:
        fixture = json.load(f)
    out = trace.reduce(fixture["events"], fixture["host_spans"])
    expect = fixture["expect"]
    assert out["busy_s"] == pytest.approx(expect["busy_s"])
    assert out["window_s"] == pytest.approx(expect["window_s"])
    assert [n for n, _ in out["programs"]] == expect["programs"]
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_ops"][0][0] == "jit__cells_search/fused_cells_knn.1"
    assert out["idle_gaps"] and all(n == "bench.pump"
                                    for n, _ in out["idle_gaps"])


def test_recording_keeps_annotations_without_the_python_tracer(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    with trace.recording(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            f(jnp.ones(4)).block_until_ready()
    events = trace.load(str(tmp_path))
    assert "bench.window" in {e["name"] for e in events}
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    raw = {ev.name for plane in jax.profiler.ProfileData.from_file(path).planes
           for line in plane.lines for ev in line.events}
    assert not any(n.startswith("$") for n in raw)   # no Python tracer
    assert trace.annotation(events, "bench.window")[1] > \
        trace.annotation(events, "bench.window")[0]
