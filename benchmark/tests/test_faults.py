"""A run whose timed path is broken underneath comes out not correct.

Each test drives the rest of a run (tiny corpus on the CPU, the look for
a chip skipped) with the searcher wrapped so that it breaks the answers
in one way a served search can: answers altered where they are produced
(one answer of each batch, or all of them), half of each batch left out
(its rows answered with the other half's), or the previous batch's
answers returned unchanged. The exchange between chips does not exist
on one chip. The same holds for IVF-Flat sharded over four devices, which
can also lose the exchange: every shard but the first left out of the
merge.
"""

import copy
import time

import numpy as np
import pytest

from benchmark import cells, run as bench_run
from raft_tpu.comms.health import ShardHealth
from raft_tpu.serve import SearchResult, Searcher

BENCH = cells.load_benchmark()

#: A deployment kept here and not in ``BENCHMARK.json``: big-ann-benchmarks'
#: DEEP shape (96-d float32, L2, k=10, 10,000 queries) at 4M rows, as
#: sharded IVF-Flat over four devices with list placement. Its limits are
#: ``sift1m-ivfflat``'s until a cell of its own reads its own.
DEEP_SHARDED = {
    "corpus": {"generator": "blobs", "rows": 4_000_000, "queries": 10_000,
               "dim": 96, "dtype": "float32", "metric": "l2",
               "clusters": 1000, "std": 5.0, "center_box": [-10.0, 10.0],
               "seed": 0, "shards": 4},
    "index": {"family": "ivf_flat_sharded", "shards": 4,
              "index_params": {"n_lists": 1024},
              "search_params": {"n_probes": 32},
              "build": {"placement": "list"}},
    "correct": {"limits": {"bad_ids": 0, "dist_err": 3.5e-06,
                           "recall_short": 1.8e-04}},
}
LOCAL = {"deep4m-ivfflat-sharded": DEEP_SHARDED}
CONFIGS = [c["name"] for c in BENCH["configs"]] + sorted(LOCAL)


def configuration(name: str) -> dict:
    if name in LOCAL:
        return copy.deepcopy(LOCAL[name])
    return cells.load_json("configs", name)


def tiny(config, traffic_name):
    """A cell of ``config`` under ``traffic_name`` at a size the CPU
    holds (every width kept, the scale cut)."""
    cfg = configuration(config)
    cell = {"name": "%s-%s" % (config, traffic_name), "config": config,
            "traffic": traffic_name,
            "chips": int(cfg["index"].get("shards", 1))}
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


def without_exchange(searcher):
    """The sharded searcher with every shard but the first marked dead:
    their candidates never reach the merge."""
    health = ShardHealth(searcher.mesh.size)
    for rank in range(1, searcher.mesh.size):
        health.mark_dead(rank)
    return Searcher.ivf_flat(searcher._index, searcher._params,
                             mesh=searcher.mesh, health=health)


def breaking(fault):
    if fault == "exchange_left_out":
        return without_exchange
    return lambda s: Broken(s, fault)


def run_tiny(config, traffic_name, hook=None, seed=7):
    cell, cfg, traffic = tiny(config, traffic_name)
    line, _ = bench_run.run_cell(BENCH, cell, seed, 1.0, False, cfg=cfg,
                                 traffic=traffic,
                                 t_start=time.perf_counter(),
                                 searcher_hook=hook)
    return line


@pytest.mark.parametrize("config", CONFIGS)
def test_sound_run_is_correct(config):
    line = run_tiny(config, "closed-4x256")
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"
    chips = int(configuration(config)["index"].get("shards", 1))
    assert len(line["device"]["memory_peak_bytes_by_device"]) == chips


FAULTS = ["answers_altered", "half_left_out", "stale"]


# Every mix under ``traffic/``: the committed cell's, and the open loop an
# online cell of the same configuration would drive; the sharded
# deployment under the closed mix its four-chip cell would drive.
@pytest.mark.parametrize("config,traffic_name,fault", [
    (config["name"], t, fault)
    for config in BENCH["configs"]
    for t in ("closed-4x256", "open-rag-1to32")
    for fault in FAULTS + ["one_answer_altered"]
] + [(name, "closed-4x256", fault) for name in sorted(LOCAL)
     for fault in FAULTS + ["one_answer_altered", "exchange_left_out"]])
def test_broken_answers_are_not_correct(config, traffic_name, fault):
    line = run_tiny(config, traffic_name, breaking(fault))
    assert not line["correct"], line["checks"]
