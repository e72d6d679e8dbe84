"""Open loop: independent users, each request sent when it is due,
whatever the server is doing (Poisson arrivals at ``rate_rps``).

The window holds ``rate_rps * seconds`` requests of 1 to ``max`` queries,
log-uniform. Their sizes and the gaps between arrivals are drawn once from
``shape_seed``; ``--seed`` only shuffles their order (and picks the pool
rows), so every seed offers the same work. A generator thread sleeps until
each request is due and submits it; the calling thread pumps the
scheduler and stamps completions. Latency runs from the time a request was
due, so a stall shows in every request due during it. The window closes
when every request is answered, or ``drain_s`` after its last arrival;
what is unanswered then has failed.
"""

from __future__ import annotations

import collections
import threading
import time

import numpy as np

from benchmark.requests import Request, cyclic_rows, no_mark, stamp

#: How long the loop waits for a new arrival before pumping again, so a
#: queued batch dispatches within this of turning ripe.
POLL_S = 250e-6
#: Lead between the schedule's start and the first arrival.
LEAD_S = 0.01


def schedule(traffic: dict, seed: int, seconds: float):
    """``(sizes, due offsets in seconds)`` of the window's requests."""
    n = max(1, int(round(float(traffic["rate_rps"]) * seconds)))
    lo = int(traffic["request_queries"]["min"])
    hi = int(traffic["request_queries"]["max"])
    base = np.random.default_rng(int(traffic["shape_seed"]))
    sizes = np.floor(np.exp(base.uniform(np.log(lo), np.log(hi + 1), n)))
    sizes = np.clip(sizes.astype(np.int64), lo, hi)
    gaps = np.diff(np.sort(base.uniform(0.0, seconds, n)), prepend=0.0)
    order = np.random.default_rng(seed)
    return order.permutation(sizes), np.cumsum(order.permutation(gaps))


def run(sched, pool: np.ndarray, traffic: dict, seed: int, seconds: float,
        mark=no_mark, clock=time.perf_counter, sleep=time.sleep):
    """``(requests, start, end)`` of one window."""
    k = int(traffic["k"])
    sizes, offsets = schedule(traffic, seed, seconds)
    cursor = int(np.random.default_rng(seed).integers(len(pool)))
    starts = cursor + np.concatenate([[0], np.cumsum(sizes)[:-1]])
    requests = []
    arrived: collections.deque = collections.deque()
    wake = threading.Event()

    def generate():
        # Each request is made just before it is due, so that nothing the
        # generator does ahead of time makes the first ones late.
        for first, n, off in zip(starts, sizes, offsets):
            r = Request(cyclic_rows(int(first), int(n), len(pool)),
                        start + off)
            requests.append(r)
            delay = r.due - clock()
            if delay > 0:
                sleep(delay)
            r.sent = clock()
            with mark("bench.submit"):
                try:
                    r.ticket = sched.submit(pool[r.rows], k)
                except Exception as err:   # counted as failed
                    r.error = repr(err)
            arrived.append(r)
            wake.set()

    gen = threading.Thread(target=generate, name="bench-open-loop",
                           daemon=True)
    start = clock() + LEAD_S
    gen.start()
    limit = start + seconds + float(traffic["drain_s"])
    waiting = []
    end = start
    while True:
        while arrived:
            r = arrived.popleft()
            if r.ticket is not None:
                waiting.append(r)
        with mark("bench.pump"):
            completed = sched.pump()
        if completed:
            end = clock()
            waiting = stamp(waiting, end)
        if not waiting and not arrived and not gen.is_alive():
            break
        if clock() > limit:
            break
        if not completed:
            with mark("bench.wait"):
                wake.wait(POLL_S)
                wake.clear()
    gen.join(timeout=max(0.0, limit - clock()) + 1.0)
    made = list(requests)
    # Requests the generator never reached count as attempted and failed.
    for first, n, off in list(zip(starts, sizes, offsets))[len(made):]:
        made.append(Request(cyclic_rows(int(first), int(n), len(pool)),
                            start + off))
    return made, start, max(end, start + seconds)
