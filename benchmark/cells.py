"""Finds every piece of a cell by its name.

``BENCHMARK.json`` (at the root of the checkout) names each cell's
configuration and traffic mix; the files behind those names are

- ``configs/<config>.json``: the deployment (corpus, index family and its
  parameters, what decides ``correct``);
- ``traffic/<traffic>.json``: the mix (load generator, request sizes,
  batch policy, the buckets to warm);
- ``loops/<loop>.py``: the load generator a traffic file names;
- ``families/<family>.py``: how an index family is built and served;
- ``work/<family>.py``: the work an index family's search requires;
- ``metrics/<metric>.py`` and ``layers/<metric>.py``: one reader per
  end-to-end and per-layer metric.

A later cell, mix or metric is one more file: nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _path(kind: str, name: str, ext: str) -> str:
    if not _NAME.match(name):
        raise ValueError("bad %s name %r" % (kind, name))
    return os.path.join(HERE, kind, name + ext)


def load_benchmark(path: str = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError("no workload %r in BENCHMARK.json" % name)


def load_json(kind: str, name: str) -> dict:
    """``configs/<name>.json`` or ``traffic/<name>.json``."""
    with open(_path(kind, name, ".json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` as a module (names may hold dots, so the file
    is loaded by its path, not imported by a dotted name). Loaded once
    per process, like an import, so the jitted functions it defines keep
    their compiled programs across runs."""
    path = _path(kind, name, ".py")
    key = "benchmark._%s_%s" % (kind, re.sub(r"\W", "_", name))
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]


def metrics_for(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports: end-to-end ones with
    ``--trace 0``, per-layer ones with ``--trace 1``. An entry without a
    ``workloads`` key applies to every cell (a per-layer one: every cell
    that reports the end-to-end metric it moves)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]
