"""CPU rehearsal of chip_smoke.py.

The script's phase functions run here at a tiny size on the CPU test
mesh, through the same Searcher + BatchScheduler path the chip run
serves; only the expectation that TPU searches resolve to Pallas kernels
is stubbed, since on the CPU every engine is XLA. The platform check
itself is exercised as it is: without a TPU the script exits non-zero
and prints no result.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod   # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


def _phases(out: str) -> dict:
    recs = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    return {(r.get("phase"), r.get("placement"), r.get("merge_engine")): r
            for r in recs}


def test_one_chip_phases_at_tiny_size(smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "expected_pallas", lambda *a: [])
    size = smoke.Size(n_rows=8192, n_pool=1024, n_requests=24,
                      max_batch=64, n_lists=32, n_clusters=32,
                      min_checked=64, n_encode=2048)
    assert smoke.run_one_chip(size, seed=0) == []
    phases = _phases(capsys.readouterr().out)
    for kind in ("brute_force", "ivf_flat", "ivf_pq"):
        rec = phases[(kind, None, None)]
        assert rec["requests"] == 24
        assert rec["serve_compiles"] == 0
        assert rec["recall_at_10"] >= smoke.RECALL_FLOOR[kind]
        assert set(rec["engine_by_bucket"]) == {
            "1", "2", "4", "8", "16", "32", "64"}
        # Every served query is checked, and batches used several buckets.
        n_checked = sum(n for n, _ in rec["recall_by_bucket"].values())
        assert n_checked == rec["rows"]
        assert len(rec["recall_by_bucket"]) >= 3
    pq = phases[("ivf_pq", None, None)]
    assert pq["code_agreement"] >= smoke.PQ_CODE_AGREEMENT
    assert pq["rel_error"] <= smoke.PQ_MAX_REL_ERROR


def test_four_chip_phases_on_virtual_devices(smoke, capsys):
    size = smoke.Size(n_rows=16384, n_pool=1024, n_requests=12,
                      max_batch=64, n_lists=32, n_clusters=32,
                      min_checked=64)
    assert smoke.run_four_chips(size, seed=0) == []
    phases = _phases(capsys.readouterr().out)
    for placement in ("row", "list"):
        devs = phases[("sharded_build", placement, None)]["shard_devices"]
        assert all(len(v) == 4 for v in devs.values())
        for engine in ("auto", "pipelined"):
            rec = phases[("sharded_ivf_flat", placement, engine)]
            assert rec["recall_at_10"] >= smoke.RECALL_FLOOR["ivf_flat"]


def test_failed_check_fails_the_phase(smoke, monkeypatch, capsys):
    """A phase whose searches miss the Pallas expectation is reported
    failed (the CPU resolves every bucket to XLA)."""
    size = smoke.Size(n_rows=2048, n_pool=256, n_requests=4,
                      max_batch=16, n_lists=16, n_clusters=16, min_checked=4)
    X, pool = smoke.make_data(size, seed=0)
    truth = smoke.exact_knn(X, pool, smoke.K)
    from raft_tpu.serve import BucketGrid, Searcher

    failures = []
    smoke.run_phase("brute_force", failures, smoke._serve_one,
                    "brute_force", Searcher.brute_force(X), size,
                    smoke.request_plan(size, 0), pool, truth,
                    BucketGrid.pow2(size.max_batch, k_grid=(10,)))
    assert failures == ["brute_force"]
    assert "no compiled Pallas kernel" in capsys.readouterr().err


def test_resolved_engines_reads_mosaic_kernels(smoke):
    mods = {
        "jax_ir0001_jit__fused_knn_compile.mlir":
            "module @jit__fused_knn {\n"
            "  func.func public @main(%arg0: tensor<256x128xf32>, "
            "%arg1: tensor<1000x128xf32>) {\n"
            "    stablehlo.custom_call @tpu_custom_call(%arg0)\n",
        "jax_ir0002_jit__probe_scan_compile.mlir":
            "module @jit__probe_scan {\n"
            "  func.func public @main(%arg0: tensor<8x128xf32>) {\n",
        # A (dim, dim) rotation is not the dim-query bucket.
        "jax_ir0003_jit__compressed_search_compile.mlir":
            "module @jit__compressed_search {\n"
            "  func.func public @main(%arg0: tensor<512x128xf32>, "
            "%arg1: tensor<128x128xf32>) {\n"
            "    stablehlo.custom_call @tpu_custom_call(%arg0)\n",
    }
    assert smoke.resolved_engines(mods, (8, 128, 256, 512, 1024), 128) == {
        8: "xla", 128: "xla", 256: "pallas", 512: "pallas", 1024: "xla"}
    assert smoke.pallas_programs(mods) == ["jit__compressed_search",
                                           "jit__fused_knn"]
    size = smoke.ONE_CHIP
    assert smoke.expected_pallas("brute_force", (1, 1024), size) == [1, 1024]
    assert smoke.expected_pallas("ivf_pq", (128, 256, 1024), size) == [
        256, 1024]


def test_main_without_tpu_exits_nonzero(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_exits_nonzero(tmp_path):
    """Run as a program from a directory holding nothing else of the
    repo, without a TPU: non-zero exit, no result line."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_recall_is_checked_per_engine(smoke):
    """An engine that answered too few queries, or one below the floor,
    fails the phase even when the recall over all queries would pass."""
    truth = np.arange(40).reshape(4, 10)
    good = truth.copy()
    bad = truth + 100
    answers = [(np.array([0, 1]), good[:2], 1),       # xla bucket
               (np.array([2, 3]), good[2:], 256),     # pallas bucket
               (np.array([0]), bad[:1], 2)]           # xla, all wrong
    engines = {1: "xla", 2: "xla", 256: "pallas"}
    by = smoke.recall_by(answers, truth, engines.get)
    assert by == {"pallas": [2, 1.0], "xla": [3, 2 / 3]}
    assert smoke.recall_by(answers, truth, str) == {
        "1": [2, 1.0], "2": [1, 0.0], "256": [2, 1.0]}
    smoke.check_recall("t", by, ["pallas"], 0.95, 2)
    with pytest.raises(smoke.PhaseFailed, match="engine xla"):
        smoke.check_recall("t", by, engines.values(), 0.7, 2)
    with pytest.raises(smoke.PhaseFailed, match="fewer than 3"):
        smoke.check_recall("t", by, engines.values(), 0.5, 3)


def test_request_plan_flushes_groups_of_one_to_eight(smoke):
    sizes, flush = smoke.request_plan(smoke.ONE_CHIP, seed=0)
    assert sizes.shape == flush.shape == (200,)
    assert (sizes[:2] == (1, 1000)).all()
    assert sizes.min() >= 1 and sizes.max() <= 1000
    ends = np.flatnonzero(flush)
    assert ends[-1] == 199
    gaps = np.diff(np.concatenate([[-1], ends]))
    assert gaps.min() >= 1 and gaps.max() <= 8 and (gaps == 1).any()
