"""The run command refuses to run without a TPU, and without the
program, and prints no result line either way."""

import json
import os
import shutil
import subprocess
import sys

from benchmark import cells

BENCH = cells.load_benchmark()


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    cmd = BENCH["command"] + ["--workload", BENCH["workloads"][0]["name"],
                              "--seed", "3", "--seconds", "1",
                              "--trace", "0"]
    cmd[0] = sys.executable if cmd[0].startswith("python") else cmd[0]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_exits_nonzero_on_the_cpu():
    out = _run(cells.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(cells.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_command_names_nothing_outside_its_paths():
    assert BENCH["command"][:3] == ["python3", "-m", "benchmark.run"]
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        assert json.load(f) == BENCH
