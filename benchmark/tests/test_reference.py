"""The copied exact reference against numpy."""

import jax.numpy as jnp
import numpy as np

from benchmark.reference import exact_knn, pair_distances


def _data(seed=0, n=3000, q=70, d=16):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, d)).astype(np.float32),
            rng.normal(size=(q, d)).astype(np.float32))


def test_exact_knn_matches_numpy():
    X, Q = _data()
    d, i = exact_knn(jnp.asarray(X), Q, 10, tile=1000, q_tile=32)
    full = ((Q[:, None, :].astype(np.float64) - X[None]) ** 2).sum(axis=2)
    ref = np.argsort(full, axis=1)[:, :10]
    np.testing.assert_array_equal(i, ref)
    np.testing.assert_allclose(d, np.take_along_axis(full, ref, axis=1),
                               rtol=1e-4, atol=1e-3)


def test_pair_distances_match_numpy():
    X, Q = _data(1)
    ids = np.random.default_rng(2).integers(0, len(X), size=(len(Q), 5))
    got = pair_distances(jnp.asarray(X), Q, ids, q_tile=16)
    ref = ((Q[:, None, :].astype(np.float64) - X[ids]) ** 2).sum(axis=2)
    np.testing.assert_allclose(got, ref, rtol=1e-5)


def test_lower_precision_reads_coarser():
    X, Q = _data(3)
    exact = exact_knn(jnp.asarray(X), Q, 10, tile=1000)[0]
    high = exact_knn(jnp.asarray(X), Q, 10, tile=1000, precision="high")[0]
    one = exact_knn(jnp.asarray(X), Q, 10, tile=1000,
                    precision="default")[0]
    err_high = np.abs(high - exact).max()
    err_one = np.abs(one - exact).max()
    assert 0 < err_high < err_one
