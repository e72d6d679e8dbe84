"""The load generators against a fake scheduler on an injected clock."""

import time

import numpy as np

from benchmark import cells
from benchmark.loops import open as open_loop


class _Ticket:
    def __init__(self):
        self.done = False
        self.span = None

    def result(self):
        return None


class FakeScheduler:
    """Answers everything queued at each pump; once the clock passes
    ``stall_at`` the pump that finds it so jumps the clock ``stall_s``
    ahead first, as a stalled device would."""

    def __init__(self, clock, jump, stall_at=None, stall_s=0.0):
        self.clock, self.jump = clock, jump
        self.stall_at, self.stall_s = stall_at, stall_s
        self.queue, self.submitted = [], []

    def submit(self, queries, k):
        t = _Ticket()
        self.queue.append(t)
        self.submitted.append(len(queries))
        return t

    def pump(self):
        if not self.queue:
            return 0
        if self.stall_at is not None and self.clock() >= self.stall_at:
            self.jump[0] += self.stall_s
            self.stall_at = None
        done, self.queue = self.queue, []
        for t in done:
            t.done = True
        return len(done)


def _clock():
    jump = [0.0]
    return (lambda: time.perf_counter() + jump[0]), jump


TRAFFIC_OPEN = {"loop": "open", "rate_rps": 300, "shape_seed": 0,
                "request_queries": {"min": 1, "max": 32}, "k": 10,
                "drain_s": 5}
POOL = np.zeros((100, 4), np.float32)


def test_open_loop_times_from_due_so_a_stall_reaches_later_requests():
    clock, jump = _clock()
    t0 = clock()
    sched = FakeScheduler(clock, jump, stall_at=t0 + 0.3, stall_s=0.4)
    reqs, start, end = open_loop.run(sched, POOL, TRAFFIC_OPEN, 5, 1.0,
                                     clock=clock)
    assert len(reqs) == 300 and all(r.answered for r in reqs)
    # The stall jumped the clock from t0 + 0.3 to t0 + 0.7: every request
    # due in between is answered after t0 + 0.7, late by the rest of the
    # stall, though the generator could only send it once the clock moved.
    hit = [r for r in reqs if t0 + 0.31 <= r.due < t0 + 0.69]
    assert len(hit) > 50
    for r in hit:
        assert r.done >= t0 + 0.7 and r.done - r.due >= t0 + 0.7 - r.due
    after = [r for r in reqs if r.due > t0 + 0.8]
    assert after and np.median([r.done - r.due for r in after]) < 0.05


def test_open_loop_offers_the_same_work_for_every_seed():
    a = open_loop.schedule(TRAFFIC_OPEN, 1, 2.0)
    b = open_loop.schedule(TRAFFIC_OPEN, 2**31 + 17, 2.0)
    assert len(a[0]) == 600
    assert sorted(a[0]) == sorted(b[0]) and not np.array_equal(a[0], b[0])
    np.testing.assert_allclose(np.sort(np.diff(a[1], prepend=0)),
                               np.sort(np.diff(b[1], prepend=0)))
    assert a[0].min() >= 1 and a[0].max() <= 32


def test_closed_loop_keeps_each_client_to_one_request():
    closed = cells.load_module("loops", "closed")
    traffic = {"loop": "closed", "clients": 4,
               "request_queries": {"n": 8}, "k": 10}
    clock, jump = _clock()
    sched = FakeScheduler(clock, jump)
    reqs, start, end = closed.run(sched, POOL, traffic, 3, 0.2, clock=clock)
    assert len(reqs) >= 8 and len(reqs) % 4 == 0
    assert set(sched.submitted) == {8}
    assert all(r.answered and r.sent == r.due for r in reqs)
    assert end >= start + 0.2 > max(r.sent for r in reqs)
    qps = cells.load_module("metrics", "qps").read(
        type("R", (), {"requests": reqs, "start": start, "end": end}))
    assert abs(qps - 8 * len(reqs) / (end - start)) < 1e-6
