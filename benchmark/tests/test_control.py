"""The controls of ``correct`` come out not correct, and the program
correct, under each configuration's own limits (at a size a test run
holds; the chip readings the limits were set from are in PERF.md)."""

import pytest

from benchmark import cells, control, correct
from benchmark.tests.test_faults import BENCH, tiny

CONFIGS = sorted({c["name"] for c in BENCH["configs"]})


@pytest.mark.parametrize("config", CONFIGS)
def test_controls_read_and_program_passes(config):
    cell, cfg, traffic = tiny(config, "closed-4x256")
    controls = cfg["correct"]["controls"]
    # Scale the program's own controls to the tiny index: the same share
    # of its probes.
    full = cells.load_json("configs", config)["index"]["search_params"]
    for spec in controls.values():
        if "n_probes" in spec.get("search_params", {}):
            share = spec["search_params"]["n_probes"] / full["n_probes"]
            spec["search_params"]["n_probes"] = max(1, round(
                share * cfg["index"]["search_params"]["n_probes"]))
    rows = []
    summary = control.readings(BENCH, cell, [5], [6], 1.0, cfg=cfg,
                               traffic=traffic, emit=rows.append)
    limits = cfg["correct"]["limits"]
    program = {n: {"value": summary[n]["program_max"], "limit": lim}
               for n, lim in limits.items()}
    assert correct.passed(program), program
    for name, spec in controls.items():
        ctrl = {n: {"value": summary[n][name + "_min"], "limit": lim}
                for n, lim in limits.items()}
        if spec["kind"] == "reference":
            assert not correct.passed(ctrl), (name, ctrl)
        else:
            # Fewer probes at a tiny size may still find every neighbor;
            # the chip readings decide the limit, this checks the wiring.
            assert (summary["recall_short"][name + "_min"]
                    >= summary["recall_short"]["program_max"])
    assert len(rows) == 2 + len(controls)


def test_every_configuration_names_its_controls():
    for c in CONFIGS:
        controls = cells.load_json("configs", c)["correct"]["controls"]
        assert controls
        assert {s["kind"] for s in controls.values()} <= {
            "reference", "program"}
