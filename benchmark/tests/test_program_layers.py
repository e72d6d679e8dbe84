"""The readers of the program's dispatch, turnaround and collector-pause
spans, on span trees built by hand."""

import math
from types import SimpleNamespace

import pytest

from benchmark import cells
from benchmark.requests import Request
from raft_tpu.obs.trace import NULL_SPAN, Span

LAYERS = ("dispatch_ms.batch", "turnaround_ms.batch",
          "turnaround_max_ms.batch", "gc_ms.batch")

# Three batches in dispatch order, in seconds: assembly, enqueue,
# device_wait, device_get, result_merge, and the collector pauses the
# batch took (a 50-ms one in set-up, which the first batch carries, a
# 0.2-ms pause before the second, a 5-ms one before the third).
BATCHES = [
    dict(asm=(1.000, 1.001), enq=(1.001, 1.002), wait=(1.002, 1.030),
         get=(1.030, 1.0305), merge=(1.0305, 1.031),
         gc=[(0.900, 0.950, 2)]),
    dict(asm=(1.0325, 1.033), enq=(1.033, 1.0345), wait=(1.0345, 1.062),
         get=(1.062, 1.0625), merge=(1.0625, 1.063),
         gc=[(1.032, 1.0322, 0)]),
    dict(asm=(1.070, 1.071), enq=(1.071, 1.072), wait=(1.072, 1.100),
         get=(1.100, 1.1005), merge=(1.1005, 1.101),
         gc=[(1.064, 1.069, 2)]),
]


def _member(seq: int, b: dict, new_program: bool = True) -> Span:
    """One member request's tree, as the scheduler copies it (the parent
    program's tree has no enqueue, device_wait, gc or batch)."""
    root = Span("serve.request", clock=lambda: 0.0)
    root.child_at("queue_wait", b["asm"][0] - 1e-3, b["asm"][0])
    root.child_at("batch_assembly", *b["asm"])
    dd = root.child_at("device_dispatch", b["enq"][0], b["wait"][1])
    if new_program:
        dd.child_at("enqueue", *b["enq"])
        dd.child_at("device_wait", *b["wait"])
    root.child_at("device_get", *b["get"])
    root.child_at("result_merge", *b["merge"])
    if new_program:
        for start, end, gen in b["gc"]:
            root.child_at("gc", start, end, generation=gen, collected=9)
        root.annotate(batch=seq)
    return root


def _run(new_program: bool = True, members: int = 2):
    requests = []
    for seq, b in enumerate(BATCHES):
        for _ in range(members):
            r = Request(None, b["asm"][0])
            r.done = b["merge"][1]
            r.ticket = SimpleNamespace(span=_member(seq, b, new_program))
            requests.append(r)
    return SimpleNamespace(requests=requests)


def _read(name: str, run):
    return cells.load_module("layers", name).read(run)


def test_dispatch_ms_is_the_mean_enqueue():
    assert _read("dispatch_ms.batch", _run()) == pytest.approx(
        (1.0 + 1.5 + 1.0) / 3)


def test_turnaround_runs_from_device_wait_end_to_next_enqueue_end():
    # 1.0345 - 1.030 and 1.072 - 1.062.
    assert _read("turnaround_ms.batch", _run()) == pytest.approx(
        (4.5 + 10.0) / 2)
    assert _read("turnaround_max_ms.batch", _run()) == pytest.approx(10.0)


def test_gc_ms_sums_every_pause_of_a_batch_once():
    # 0.2 and 5 ms in the batches after the first, whatever the number
    # of members; the first batch's pause, from set-up, is left out.
    for members in (1, 3):
        assert _read("gc_ms.batch", _run(members=members)) == \
            pytest.approx((0.2 + 5.0) / 2)


def test_gc_ms_reads_every_gc_child_not_one_per_name():
    run = _run()
    for r in run.requests[-2:]:
        r.ticket.span.child_at("gc", 1.0695, 1.0705, generation=0,
                               collected=1)
    assert _read("gc_ms.batch", run) == pytest.approx((0.2 + 6.0) / 2)


@pytest.mark.parametrize("name", LAYERS)
def test_none_without_spans(name):
    assert _read(name, SimpleNamespace(requests=[])) is None
    untraced = _run()
    for r in untraced.requests:
        r.ticket.span = NULL_SPAN
    assert _read(name, untraced) is None


@pytest.mark.parametrize("name", LAYERS)
def test_none_on_a_program_without_these_spans(name):
    """The parent program's trees: the readers find nothing and raise
    nothing."""
    assert _read(name, _run(new_program=False)) is None


def test_unanswered_requests_are_left_out():
    run = _run()
    run.requests[-1].done = math.nan
    run.requests[-2].done = math.nan      # the third batch is unanswered
    assert _read("turnaround_ms.batch", run) == pytest.approx(4.5)
    assert _read("gc_ms.batch", run) == pytest.approx(0.2)


def test_cells_scan_ms_is_per_batch_and_device():
    read = cells.load_module("layers", "cells_scan_ms.batch").read
    run = _run()
    run.trace = {"scopes": {
        "/device:TPU:0": {"ivf_flat.cells_scan": 0.030, "": 0.002},
        "/device:TPU:1": {"ivf_flat.cells_scan": 0.036}}}
    # 33 ms a device over the three batches
    assert read(run) == pytest.approx(11.0)
    run.trace = {"scopes": {"/device:TPU:0": {"": 0.002}}}
    assert read(run) is None
    run.trace = None
    assert read(run) is None
