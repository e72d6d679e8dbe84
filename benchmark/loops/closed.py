"""Closed loop: ``clients`` callers, each sending its next request as soon
as the last one is answered (a batch job's workers).

Every request holds ``request_queries.n`` queries, read cyclically from
the pool from a place drawn from the seed. One thread submits and pumps
the scheduler. New requests stop at ``seconds``; the window closes when
the last of them is answered, so a rate taken over it counts all its work
and all its time.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.requests import Request, cyclic_rows, no_mark, stamp


def run(sched, pool: np.ndarray, traffic: dict, seed: int, seconds: float,
        mark=no_mark, clock=time.perf_counter, sleep=time.sleep):
    """``(requests, start, end)`` of one window."""
    k = int(traffic["k"])
    n = int(traffic["request_queries"]["n"])
    free = int(traffic["clients"])
    cursor = int(np.random.default_rng(seed).integers(len(pool)))
    requests, waiting = [], []
    start = clock()
    stop = end = start + seconds
    while True:
        now = clock()
        while free and now < stop:
            r = Request(cyclic_rows(cursor, n, len(pool)), now)
            cursor += n
            r.sent = now
            with mark("bench.submit"):
                try:
                    r.ticket = sched.submit(pool[r.rows], k)
                except Exception as err:   # counted as failed
                    r.error = repr(err)
            requests.append(r)
            if r.ticket is None:
                break
            waiting.append(r)
            free -= 1
        if not waiting:
            break
        with mark("bench.pump"):
            completed = sched.pump()
        if completed:
            end = clock()
            still = stamp(waiting, end)
            free += len(waiting) - len(still)
            waiting = still
        else:
            sleep(50e-6)
    return requests, start, end
