"""Every piece a cell names is found by its name, and a new one is one
more file."""

import json
import os

import pytest

from benchmark import cells

BENCH = cells.load_benchmark()


def test_every_named_file_loads():
    for cfg in BENCH["configs"]:
        c = cells.load_json("configs", cfg["name"])
        assert os.path.join(cells.ROOT, cfg["file"]) == os.path.join(
            cells.HERE, "configs", cfg["name"] + ".json")
        cells.load_module("families", c["index"]["family"])
        cells.load_module("work", c["index"]["family"])
        cells.load_module("corpus", c["corpus"]["generator"])
        assert set(c["correct"]["limits"]) <= {
            "bad_ids", "dist_err", "dist_err_p50", "recall_short"}
    for cell in BENCH["workloads"]:
        assert cells.load_json("configs", cell["config"])
        traffic = cells.load_json("traffic", cell["traffic"])
        cells.load_module("loops", traffic["loop"])
    for m in BENCH["end_to_end"]:
        assert callable(cells.load_module("metrics", m["name"]).read)
    for m in BENCH["per_layer"]:
        assert callable(cells.load_module("layers", m["name"]).read)


def test_new_traffic_file_is_found_without_an_edit():
    name = "zz-test-only-mix"
    path = os.path.join(cells.HERE, "traffic", name + ".json")
    mix = dict(cells.load_json("traffic", "closed-4x256"), clients=2)
    with open(path, "w") as f:
        json.dump(mix, f)
    try:
        assert cells.load_json("traffic", name)["clients"] == 2
    finally:
        os.remove(path)


def test_names_cannot_leave_the_directory():
    with pytest.raises(ValueError):
        cells.load_json("traffic", "../BENCHMARK")


def test_metrics_for_follows_workloads_keys():
    bench = {
        "end_to_end": [
            {"name": "qps", "workloads": ["a-batch"]},
            {"name": "p99_ms", "workloads": ["a-online"]},
            {"name": "setup_s"}],
        "per_layer": [
            {"name": "host_ms.batch", "moves": "qps",
             "workloads": ["a-batch"]},
            {"name": "sched_wait_ms.online", "moves": "p99_ms"},
            {"name": "build_s", "moves": "setup_s"}]}
    names = {c: {m["name"] for m in cells.metrics_for(bench, c, False)}
             for c in ("a-batch", "a-online")}
    assert names == {"a-batch": {"qps", "setup_s"},
                     "a-online": {"p99_ms", "setup_s"}}
    layers = {c: {m["name"] for m in cells.metrics_for(bench, c, True)}
              for c in ("a-batch", "a-online")}
    assert layers == {"a-batch": {"host_ms.batch", "build_s"},
                      "a-online": {"sched_wait_ms.online", "build_s"}}


def test_every_cell_reports_setup_and_a_layer():
    for cell in BENCH["workloads"]:
        e2e = {m["name"] for m in cells.metrics_for(BENCH, cell["name"],
                                                    False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cells.metrics_for(BENCH, cell["name"], True)
