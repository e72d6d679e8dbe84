"""The readings the limits of ``correct`` are set from.

    python3 -m benchmark.control --workload <cell> --seeds 1,2,... \\
        --control-seeds 101,102,103 --seconds 3

Runs the cell's served window (at its own load, ``--seconds`` long) once
per seed in one process and prints the numbers ``benchmark/correct.py``
compares: for ``--seeds`` the program's, for ``--control-seeds`` each
control's. The controls are what the configuration's ``correct.controls``
names:

- ``{"kind": "reference", "precision": p}``: the exact reference itself
  put in the program's place, computed at the lower matmul precision
  ``p``, over the same queries as the program's window;
- ``{"kind": "program", "index_params": {...}, "search_params": {...}}``:
  the program with its own path switched (those parameters replaced),
  such as fewer lists probed.

The last line summarizes, per number, the largest program reading (the
lower end of its limit) and each control's smallest reading (an upper
end).
The benchmark's own runs never run this. Exits non-zero without a TPU.
"""

from __future__ import annotations

import argparse
import copy
import json
import math
import sys
import time

from benchmark import cells, run as bench_run


def readings(bench: dict, cell: dict, seeds: list, control_seeds: list,
             seconds: float, cfg: dict = None, traffic: dict = None,
             device_kind: str = None, emit=print) -> dict:
    cfg = cfg or cells.load_json("configs", cell["config"])
    program, controlled = [], {}
    for seed in seeds:
        _, run = bench_run.run_cell(bench, cell, seed, seconds, False,
                                    cfg=cfg, traffic=traffic,
                                    t_start=time.perf_counter(),
                                    device_kind=device_kind)
        program.append(run.numbers)
        emit(json.dumps({"role": "program", "seed": seed, **run.numbers}))
    for name, control in cfg["correct"]["controls"].items():
        controlled[name] = []
        for seed in control_seeds:
            if control["kind"] == "reference":
                _, run = bench_run.run_cell(
                    bench, cell, seed, seconds, False, cfg=cfg,
                    traffic=traffic, t_start=time.perf_counter(),
                    device_kind=device_kind,
                    control_precision=control["precision"])
                nums = run.control
            else:
                low = copy.deepcopy(cfg)
                for part in ("index_params", "search_params"):
                    low["index"][part].update(control.get(part, {}))
                _, run = bench_run.run_cell(
                    bench, cell, seed, seconds, False, cfg=low,
                    traffic=traffic, t_start=time.perf_counter(),
                    device_kind=device_kind)
                nums = run.numbers
            controlled[name].append(nums)
            emit(json.dumps({"role": name, "seed": seed, **nums}))
    names = sorted(set().union(*program))
    summary = {n: dict({"program_max": max((p[n] for p in program),
                                            default=math.nan)},
                       **{c + "_min": min((r[n] for r in rows),
                                          default=math.nan)
                          for c, rows in controlled.items()})
               for n in names}
    emit(json.dumps({"summary": summary}))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, args.workload)
    bench_run.require_chips(int(cell["chips"]))

    def seeds(text):
        return [int(s) for s in text.split(",") if s]

    readings(bench, cell, seeds(args.seeds), seeds(args.control_seeds),
             args.seconds, emit=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
