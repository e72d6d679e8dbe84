"""int64 neighbor-id support (ref: the int64_t IdxT runtime surface,
cpp/src/neighbors/brute_force_knn_int64_t_float.cu, ivf_pq_types.hpp IdxT).

int64 ids require the global jax_enable_x64 flag, so the positive tests run
in a subprocess with JAX_ENABLE_X64=1 (the role of the reference's typed
test shards, e.g. ann_ivf_pq/test_float_int64_t.cu)."""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_X64_SCRIPT = r"""
import jax, numpy as np, jax.numpy as jnp
assert jax.config.jax_enable_x64
from raft_tpu.neighbors import brute_force, ivf_flat, ivf_pq

rng = np.random.default_rng(0)
db = rng.normal(size=(2000, 16)).astype(np.float32)
q = rng.normal(size=(50, 16)).astype(np.float32)

# brute force: int64 ids + offset past 2^31
d, i = brute_force.knn(db, q, 5, idx_dtype=jnp.int64,
                       global_id_offset=1 << 32)
assert i.dtype == jnp.int64, i.dtype
assert int(i.min()) >= 1 << 32
d32, i32 = brute_force.knn(db, q, 5)
np.testing.assert_array_equal(np.asarray(i) - (1 << 32), np.asarray(i32))

# ivf_flat: build/search/save/load with int64 ids
idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4,
                                          idx_dtype=jnp.int64), db)
assert idx.indices.dtype == jnp.int64
d, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), idx, q, 5)
assert i.dtype == jnp.int64, i.dtype
import tempfile, os
f = os.path.join(tempfile.mkdtemp(), "idx")
ivf_flat.save(f, idx)
loaded = ivf_flat.load(f)
assert loaded.indices.dtype == jnp.int64

# ivf_pq: int64 ids through the LUT scan
pidx = ivf_pq.build(ivf_pq.IndexParams(n_lists=8, pq_dim=8,
                                       kmeans_n_iters=4,
                                       idx_dtype=jnp.int64), db)
assert pidx.indices.dtype == jnp.int64
d, i = ivf_pq.search(ivf_pq.SearchParams(n_probes=8, engine="scan"),
                     pidx, q, 5)
assert i.dtype == jnp.int64, i.dtype

# int64 ids through the packed-cells / compressed tiers (the merge carries
# int32 slot positions; the winners' ids, gathered after it, must keep
# the 64-bit dtype; engine="bucketed" forces the kernels in interpret
# mode on CPU)
d, ic = ivf_flat.search(ivf_flat.SearchParams(n_probes=8,
                                              engine="bucketed"), idx, q, 5)
assert ic.dtype == jnp.int64, ic.dtype
d, i32ref = ivf_flat.search(ivf_flat.SearchParams(n_probes=8,
                                                  engine="scan"), idx, q, 5)
np.testing.assert_array_equal(np.asarray(ic), np.asarray(i32ref))
d, ip = ivf_pq.search(ivf_pq.SearchParams(n_probes=8, engine="bucketed"),
                      pidx, q, 5)
assert ip.dtype == jnp.int64, ip.dtype

# extend with explicit int64 ids beyond 2^31
idx2 = ivf_flat.build(
    ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4, idx_dtype=jnp.int64,
                         add_data_on_build=False), db)
big = jnp.arange(1 << 33, (1 << 33) + len(db), dtype=jnp.int64)
idx2 = ivf_flat.extend(idx2, db, big)
d, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), idx2, q, 5)
assert int(np.asarray(i).min()) >= 1 << 33

# pylibraft surface (the reference binds int64_t ids, ivf_pq.pyx)
from pylibraft.neighbors import ivf_flat as pl_flat
pl_idx = pl_flat.build(
    pl_flat.IndexParams(n_lists=8, kmeans_n_iters=4, idx_dtype="int64"), db)
pd, pi = pl_flat.search(pl_flat.SearchParams(n_probes=8), pl_idx, q, 5)
assert np.asarray(pi).dtype == np.int64, np.asarray(pi).dtype
print("OK")
"""


def test_int64_ids_end_to_end_x64_subprocess():
    env = dict(os.environ)
    env.update({"JAX_ENABLE_X64": "1", "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": _REPO})
    out = subprocess.run([sys.executable, "-c", _X64_SCRIPT], env=env,
                         cwd=_REPO, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_int64_without_x64_fails_fast():
    from raft_tpu.core.error import RaftError
    from raft_tpu.neighbors import brute_force

    db = np.zeros((10, 4), np.float32)
    with pytest.raises(RaftError, match="x64"):
        brute_force.knn(db, db, 2, idx_dtype=jnp.int64)


def test_load_int64_without_x64_fails_fast(tmp_path):
    """load() must not silently truncate int64 ids saved by an x64 process
    (the deserialize path previously skipped the validate_idx_dtype guard
    that build() applies)."""
    from raft_tpu.core.error import RaftError
    from raft_tpu.neighbors import ivf_flat, ivf_pq

    rng = np.random.default_rng(0)
    db = rng.normal(size=(256, 8)).astype(np.float32)
    for mod, params in ((ivf_flat, ivf_flat.IndexParams(n_lists=4,
                                                        kmeans_n_iters=2)),
                        (ivf_pq, ivf_pq.IndexParams(n_lists=4, pq_dim=4,
                                                    kmeans_n_iters=2))):
        idx = mod.build(params, db)
        f = str(tmp_path / f"{mod.__name__}.npz")
        mod.save(f, idx)
        # Rewrite the indices payload as int64, as an x64 save would emit.
        z = dict(np.load(f))
        z["indices"] = np.asarray(z["indices"], dtype=np.int64)
        np.savez(f, **z)
        with pytest.raises(RaftError, match="x64"):
            mod.load(f)


def test_idx_dtype_rejects_non_integer():
    from raft_tpu.core.error import RaftError
    from raft_tpu.neighbors import brute_force

    db = np.zeros((10, 4), np.float32)
    with pytest.raises(RaftError, match="idx_dtype"):
        brute_force.knn(db, db, 2, idx_dtype=jnp.float32)
