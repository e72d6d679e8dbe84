"""Find the knee of an open-loop cell: the highest offered rate the
served path keeps up with.

    python3 -m benchmark.sweep --workload <cell> --seed <n> \\
        --rates 200,400,800 --seconds 10

One set-up (corpus, build, warmup), then one window per rate through a
fresh ``BatchScheduler``. Each step prints the offered and completed
request rates, the requests still unanswered when the window closed (the
queue a window that cannot keep up leaves), the failed ones and the p50
and p99 latency from the due time. The rate a traffic file fixes is a
share of the knee read off this table; the benchmark's own runs never
sweep. Exits non-zero without a TPU.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import math
import sys

import numpy as np

from benchmark import cells, run as bench_run

#: Longest wait for a step's last answers after its window closed.
DRAIN_S = 5.0


def step(sched, pool, traffic: dict, rate: float, seed: int,
         seconds: float) -> dict:
    loop = cells.load_module("loops", traffic["loop"])
    t = copy.deepcopy(traffic)
    t["rate_rps"] = rate
    # A step past the knee only has to show that it could not keep up.
    t["drain_s"] = min(float(t["drain_s"]), DRAIN_S)
    requests, start, end = loop.run(sched, pool, t, seed, seconds)
    close = start + seconds
    lat = np.array([r.done - r.due if r.answered else math.inf
                    for r in requests])
    return {"offered_rps": len(requests) / seconds,
            "completed_rps": sum(r.answered and r.done <= close
                                 for r in requests) / seconds,
            "queue_at_close": int(sum(r.due <= close and not
                                      (r.answered and r.done <= close)
                                      for r in requests)),
            "failed": int(sum(not r.answered for r in requests)),
            "p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "p99_ms": 1e3 * float(np.sort(lat)[
                max(0, math.ceil(0.99 * len(lat)) - 1)]),
            "drain_s": end - close,
            **bench_run.lateness(requests)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, args.workload)
    bench_run.require_chips(int(cell["chips"]))
    from raft_tpu.serve import BatchPolicy, BatchScheduler

    cfg = cells.load_json("configs", cell["config"])
    traffic = cells.load_json("traffic", cell["traffic"])
    timings: dict = {}
    _, pool, _, searcher, grid, _ = bench_run.serve(cfg, traffic, timings)
    print(json.dumps({"setup": timings}), flush=True)
    gc.collect()
    gc.freeze()    # as a benchmark run does before its window
    for rate in (float(r) for r in args.rates.split(",")):
        sched = BatchScheduler(searcher, grid,
                               BatchPolicy(**traffic["policy"]))
        out = step(sched, pool, traffic, rate, args.seed, args.seconds)
        print(json.dumps({"rate_rps": rate, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
