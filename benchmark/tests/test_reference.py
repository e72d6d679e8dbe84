"""The copied exact reference against numpy."""

import jax.numpy as jnp
import numpy as np
import pytest

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


def _sharded(X, shards=4):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:shards]), ("data",))
    Xs = jax.device_put(X, NamedSharding(mesh, P("data")))
    assert len({s.device for s in Xs.addressable_shards}) == shards
    return Xs


@pytest.mark.parametrize("precision", ["highest", "high", "default"])
def test_sharded_corpus_reads_the_one_device_answer(precision):
    """Ties included: the data repeats rows, so many distances tie."""
    X, Q = _data(4, n=4000)
    X[2000:2500] = X[:500]
    one = exact_knn(jnp.asarray(X), Q, 10, tile=500, q_tile=32,
                    precision=precision)
    four = exact_knn(_sharded(X), Q, 10, tile=500, q_tile=32,
                     precision=precision)
    np.testing.assert_array_equal(four[1], one[1])
    np.testing.assert_array_equal(four[0], one[0])
    assert (one[1] >= 2000).any() and (one[1] < 2000).any()


def test_sharded_pair_distances_equal_the_one_device_ones():
    X, Q = _data(5, n=4000)
    ids = np.random.default_rng(6).integers(-5, len(X) + 5, size=(len(Q), 7))
    one = pair_distances(jnp.asarray(X), Q, ids, q_tile=16)
    four = pair_distances(_sharded(X), Q, ids, q_tile=16)
    assert four.dtype == one.dtype == np.float32
    np.testing.assert_array_equal(four, one)
