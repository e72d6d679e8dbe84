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
SCOPES_FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                              "trace_scopes_events.json.gz")


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


def meta(program, op_names):
    return {"plane": trace.METADATA_PLANE, "line": "op_names",
            "name": program, "start_ns": 0.0, "dur_ns": 0.0,
            "op_names": op_names}


def test_busy_and_scopes_per_device():
    dev1 = "/device:TPU:1"
    events = [
        ev(HOST, "python3", "bench.window", 0, 1000),
        ev(DEV, "XLA Modules", "jit_search(7)", 0, 600),
        ev(DEV, "XLA Ops", "%fusion.1 = f32[8] fusion()", 0, 100),
        ev(DEV, "XLA Ops", "%scan.2 = f32[8] custom-call()", 100, 300),
        ev(DEV, "XLA Ops", "%copy.3 = f32[8] copy()", 400, 100),
        ev(dev1, "XLA Modules", "jit_search(7)", 0, 600),
        ev(dev1, "XLA Ops", "%scan.2 = f32[8] custom-call()", 50, 200),
        ev(dev1, "XLA Ops", "%all-gather.4 = f32[8] all-gather()", 300,
           250),
        # outside any program run: no scope
        ev(dev1, "XLA Ops", "%fusion.1 = f32[8] fusion()", 700, 100),
        meta("jit_search(7)", {
            "fusion.1": "jit(search)/ivf_flat.coarse_probe/sort",
            "scan.2": "jit(search)/raft.shard_scan/jit(kernel)/"
                      "ivf_flat.cells_scan/pallas_call",
            "all-gather.4": "jit(search)/raft.topk_merge/all_gather"}),
        meta("jit_other(9)", {"fusion.1": "jit(other)/elsewhere.x/add"}),
    ]
    out = trace.reduce(events)
    assert out["busy_by_device"] == {DEV: pytest.approx(500e-9),
                                     dev1: pytest.approx(550e-9)}
    assert out["busy_s"] == pytest.approx(525e-9)
    assert out["scopes"][DEV] == {
        "ivf_flat.coarse_probe": pytest.approx(100e-9),
        "raft.shard_scan": pytest.approx(300e-9),
        "ivf_flat.cells_scan": pytest.approx(300e-9),
        "": pytest.approx(100e-9)}
    assert out["scopes"][dev1] == {
        "raft.shard_scan": pytest.approx(200e-9),
        "ivf_flat.cells_scan": pytest.approx(200e-9),
        "raft.topk_merge": pytest.approx(250e-9),
        "": pytest.approx(100e-9)}
    # the labels that hold no other sum to the busy time
    for plane, scopes in out["scopes"].items():
        top = sum(v for k, v in scopes.items() if k != "ivf_flat.cells_scan")
        assert top == pytest.approx(out["busy_by_device"][plane])
    # the metadata never names an idle gap
    assert all(n != "jit_search(7)" for n, _ in out["idle_gaps"])


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


def test_recorded_chip_trace_with_scopes():
    """100 ms of a traced ``sift1m-ivfflat-batch`` window on a TPU v5e,
    with the program's op-name scope paths as ``load`` keeps them; the
    expected numbers come from a plain per-event sweep."""
    with gzip.open(SCOPES_FIXTURE, "rt") as f:
        fixture = json.load(f)
    out = trace.reduce(fixture["events"])
    paths = [p for ev in fixture["events"] if ev["plane"] == trace.METADATA_PLANE
             for p in ev["op_names"].values()]
    outermost = {next((x for x in p.split("/") if "." in x and "(" not in x),
                      "") for p in paths} | {""}
    assert "raft_tpu::select_k.select_k" not in outermost  # in route_select
    for plane, want in fixture["expect"].items():
        busy, scopes = out["busy_by_device"][plane], out["scopes"][plane]
        assert busy == pytest.approx(want["busy_s"])
        assert {k: v for k, v in scopes.items() if k} == {
            k: pytest.approx(v) for k, v in want["scopes"].items()}
        assert {"ivf_flat.cells_scan", "ivf_flat.cells_invert",
                "ivf_flat.coarse_probe", "ivf_flat.route_select",
                "ivf_flat.id_gather"} <= set(scopes)
        top = sum(v for k, v in scopes.items() if k in outermost)
        assert top == pytest.approx(want["op_s"])
        assert busy <= top < 1.01 * busy        # overlapping async copies
    assert out["busy_s"] == pytest.approx(
        sum(out["busy_by_device"].values()) / len(out["busy_by_device"]))
    kernel = dict(out["device_ops"])["jit__cells_search/fused_cells_knn.1"]
    scan = out["scopes"]["/device:TPU:0"]["ivf_flat.cells_scan"]
    assert kernel <= scan < 1.01 * kernel


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


def test_load_keeps_the_op_names_of_the_hlo(tmp_path):
    """A real profiler trace (of the CPU backend, whose programs run on
    host threads): the metadata plane's HLO names each operation's
    scope path."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("ivf_flat.cells_scan"):
            y = jnp.sin(x) @ x
        with jax.named_scope("raft.topk_merge"):
            return jax.lax.top_k(y, 3)

    x = jnp.ones((16, 16))
    f(x)[0].block_until_ready()
    with trace.recording(str(tmp_path)):
        f(x)[0].block_until_ready()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with open(path, "rb") as fh:
        programs = trace.hlo_op_names(fh.read())
    (ops,) = [v for k, v in programs.items() if k.startswith("jit_f(")]
    paths = set(ops.values())
    assert any(p.startswith("jit(f)/ivf_flat.cells_scan/") for p in paths)
    assert any(p.startswith("jit(f)/raft.topk_merge/") for p in paths)
    with open(path, "rb") as fh:
        assert trace.hlo_op_names(fh.read(), programs=set()) == {}
