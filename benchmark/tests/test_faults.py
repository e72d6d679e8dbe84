"""A run whose timed path is broken underneath comes out not correct.

Each test drives the rest of a run (tiny corpus on the CPU, the look for
a chip skipped) with the searcher wrapped so that it breaks the answers
in one way a served search can: answers altered where they are produced
(one answer of each batch, or all of them), half of each batch left out
(its rows answered with the other half's), or the previous batch's
answers returned unchanged. The exchange between chips does not exist
on one chip.
"""

import time

import numpy as np
import pytest

from benchmark import cells, run as bench_run
from raft_tpu.serve import SearchResult

BENCH = cells.load_benchmark()


def tiny(config, traffic_name):
    """A cell of ``config`` under ``traffic_name`` at a size the CPU
    holds (every width kept, the scale cut)."""
    cell = {"name": "%s-%s" % (config, traffic_name), "config": config,
            "traffic": traffic_name, "chips": 1}
    cfg = cells.load_json("configs", config)
    traffic = cells.load_json("traffic", traffic_name)
    cfg["corpus"].update(rows=20_000, queries=2_000, clusters=50)
    cfg["index"]["index_params"]["n_lists"] = 64
    cfg["index"]["search_params"]["n_probes"] = 8
    traffic["warm_drive_s"] = 0.2
    if traffic["loop"] == "closed":
        traffic["request_queries"]["n"] = 64
        traffic["policy"]["max_batch"] = 256
        traffic["warm_buckets"] = [256]
    else:
        traffic["rate_rps"] = 100
        traffic["policy"]["max_batch"] = 64
        traffic["warm_buckets"] = [16, 32, 64]
    return cell, cfg, traffic


class Broken:
    def __init__(self, inner, fault):
        self.inner, self.fault, self.last = inner, fault, None

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def search(self, queries, k, **kw):
        res = self.inner.search(queries, k, **kw)
        d, i = res.distances.copy(), res.indices.copy()
        n = 20_000
        if self.fault == "one_answer_altered":
            i[0, 0] = (i[0, 0] + 1) % n
        elif self.fault == "answers_altered":
            i = (i + 1) % n
        elif self.fault == "half_left_out":
            h = len(i) // 2
            d[h:2 * h], i[h:2 * h] = d[:h], i[:h]
        elif self.fault == "stale":
            prev, self.last = self.last, (d, i)
            if prev is not None and prev[0].shape == d.shape:
                d, i = prev
        return SearchResult(d, i, res.coverage)


def run_tiny(config, traffic_name, hook=None, seed=7):
    cell, cfg, traffic = tiny(config, traffic_name)
    line, _ = bench_run.run_cell(BENCH, cell, seed, 1.0, False, cfg=cfg,
                                 traffic=traffic,
                                 t_start=time.perf_counter(),
                                 searcher_hook=hook)
    return line


def test_sound_run_is_correct():
    line = run_tiny("sift1m-ivfflat", "closed-4x256")
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"


FAULTS = ["answers_altered", "half_left_out", "stale"]


# Every mix under ``traffic/``: the committed cell's, and the open loop an
# online cell of the same configuration would drive.
@pytest.mark.parametrize("config,traffic_name,fault", [
    (config["name"], t, fault)
    for config in BENCH["configs"]
    for t in ("closed-4x256", "open-rag-1to32")
    for fault in FAULTS + ["one_answer_altered"]
])
def test_broken_answers_are_not_correct(config, traffic_name, fault):
    line = run_tiny(config, traffic_name, lambda s: Broken(s, fault))
    assert not line["correct"], line["checks"]
