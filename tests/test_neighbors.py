"""Neighbors layer tests.

Modeled on the reference's test scheme (SURVEY.md §4): brute-force results
are compared exactly against a naive host kNN (the role of ``naive_knn``,
cpp/internal/raft_internal/neighbors/naive_knn.cuh:85); ANN indexes are
checked with **recall thresholds** against exact ground truth
(cpp/test/neighbors/ann_utils.cuh:121-162 ``eval_neighbours``), with
IVF-Flat's ``min_recall ≈ n_probes/n_lists`` style lower bound
(cpp/test/neighbors/ann_ivf_flat.cuh:111,146-153).
"""

import numpy as np
import pytest

from raft_tpu.distance.distance_types import DistanceType
from raft_tpu.neighbors import (
    brute_force,
    eps_neighbors_l2sq,
    ivf_flat,
    knn_merge_parts,
    refine,
)


def _naive_knn(queries, db, k, metric="sqeuclidean"):
    if metric == "inner_product":
        d = queries @ db.T
        idx = np.argsort(-d, axis=1)[:, :k]
    else:
        d = ((queries[:, None, :] - db[None]) ** 2).sum(-1)
        if metric == "euclidean":
            d = np.sqrt(d)
        idx = np.argsort(d, axis=1)[:, :k]
    return np.take_along_axis(d, idx, axis=1), idx


def _recall(found, truth):
    n, k = truth.shape
    hits = sum(len(np.intersect1d(found[i], truth[i])) for i in range(n))
    return hits / (n * k)


class TestBruteForce:
    @pytest.mark.parametrize("metric", ["sqeuclidean", "euclidean", "inner_product"])
    def test_matches_naive(self, rng, metric):
        db = rng.normal(size=(500, 16)).astype(np.float32)
        q = rng.normal(size=(40, 16)).astype(np.float32)
        d, i = brute_force.knn(db, q, 10, metric=metric)
        dn, ins = _naive_knn(q, db, 10, metric)
        assert _recall(np.asarray(i), ins) > 0.99
        np.testing.assert_allclose(np.asarray(d), dn, rtol=1e-3, atol=1e-3)

    def test_tiled_path(self, rng):
        """Force multiple db tiles to exercise the scan merge."""
        db = rng.normal(size=(3000, 8)).astype(np.float32)
        q = rng.normal(size=(16, 8)).astype(np.float32)
        d, i = brute_force.tiled_brute_force_knn(q, db, 5, tile_db=512)
        _, ins = _naive_knn(q, db, 5)
        assert _recall(np.asarray(i), ins) == 1.0

    def test_generic_metric_tiled(self, rng):
        db = np.abs(rng.normal(size=(1200, 8))).astype(np.float32)
        q = np.abs(rng.normal(size=(10, 8))).astype(np.float32)
        d, i = brute_force.tiled_brute_force_knn(
            q, db, 4, metric=DistanceType.L1, tile_db=500
        )
        dl1 = np.abs(q[:, None, :] - db[None]).sum(-1)
        ins = np.argsort(dl1, axis=1)[:, :4]
        assert _recall(np.asarray(i), ins) == 1.0

    def test_multi_part_merge(self, rng):
        parts = [rng.normal(size=(n, 8)).astype(np.float32) for n in (300, 500, 200)]
        q = rng.normal(size=(20, 8)).astype(np.float32)
        d, i = brute_force.knn(parts, q, 8)
        db = np.concatenate(parts)
        _, ins = _naive_knn(q, db, 8)
        assert _recall(np.asarray(i), ins) == 1.0

    def test_knn_merge_parts(self, rng):
        keys = rng.random(size=(3, 10, 4)).astype(np.float32)
        vals = np.tile(np.arange(4, dtype=np.int32), (3, 10, 1))
        mk, mv = knn_merge_parts(keys, vals, translations=[0, 100, 200])
        flat_k = keys.transpose(1, 0, 2).reshape(10, 12)
        off = np.array([0, 100, 200])[:, None] + np.arange(4)
        flat_v = np.tile(off.reshape(-1), (10, 1))
        order = np.argsort(flat_k, axis=1)[:, :4]
        np.testing.assert_allclose(np.asarray(mk),
                                   np.take_along_axis(flat_k, order, 1), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(mv),
                                      np.take_along_axis(flat_v, order, 1))


class TestRefine:
    def test_refine_improves_candidates(self, rng):
        db = rng.normal(size=(400, 8)).astype(np.float32)
        q = rng.normal(size=(15, 8)).astype(np.float32)
        _, truth = _naive_knn(q, db, 5)
        # Candidates: true top-5 shuffled into 20 noisy candidates.
        cand = np.concatenate(
            [truth, rng.integers(0, 400, size=(15, 15))], axis=1
        ).astype(np.int32)
        d, i = refine(db, q, cand, 5)
        # Random noise candidates may duplicate a true id, displacing one
        # slot; near-perfect recall is the correct expectation.
        assert _recall(np.asarray(i), truth) > 0.97

    def test_refine_handles_invalid(self, rng):
        db = rng.normal(size=(50, 4)).astype(np.float32)
        q = rng.normal(size=(3, 4)).astype(np.float32)
        cand = np.full((3, 8), -1, np.int32)
        cand[:, 0] = [5, 6, 7]
        d, i = refine(db, q, cand, 1)
        np.testing.assert_array_equal(np.asarray(i)[:, 0], [5, 6, 7])


class TestEpsNeighborhood:
    def test_matches_naive(self, rng):
        x = rng.normal(size=(40, 4)).astype(np.float32)
        y = rng.normal(size=(60, 4)).astype(np.float32)
        eps_sq = 4.0
        adj, vd = eps_neighbors_l2sq(x, y, eps_sq)
        dn = ((x[:, None, :] - y[None]) ** 2).sum(-1)
        np.testing.assert_array_equal(np.asarray(adj), dn < eps_sq)
        np.testing.assert_array_equal(np.asarray(vd)[:-1], (dn < eps_sq).sum(1))
        assert int(vd[-1]) == int((dn < eps_sq).sum())


class TestIvfFlat:
    def _data(self, rng, n=5000, d=16):
        return rng.normal(size=(n, d)).astype(np.float32)

    def test_recall_high_probes(self, rng):
        db = self._data(rng)
        q = rng.normal(size=(50, 16)).astype(np.float32)
        params = ivf_flat.IndexParams(n_lists=32, kmeans_n_iters=10)
        index = ivf_flat.build(params, db)
        assert index.size == 5000
        d, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=32), index, q, 10)
        _, truth = _naive_knn(q, db, 10)
        # All lists probed → exact (ref: ann_ivf_flat recall bound with
        # n_probes == n_lists is 1.0 minus ties).
        assert _recall(np.asarray(i), truth) > 0.99

    def test_recall_partial_probes(self, rng):
        db = self._data(rng)
        q = rng.normal(size=(50, 16)).astype(np.float32)
        params = ivf_flat.IndexParams(n_lists=32, kmeans_n_iters=10)
        index = ivf_flat.build(params, db)
        d, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), index, q, 10)
        _, truth = _naive_knn(q, db, 10)
        # min_recall style bound (ref: ann_ivf_flat.cuh:146-153) — 8/32
        # probes on gaussian data lands far above the n_probes/n_lists floor.
        assert _recall(np.asarray(i), truth) > 0.5

    def test_distances_are_exact_for_found(self, rng):
        db = self._data(rng, n=2000)
        q = rng.normal(size=(10, 16)).astype(np.float32)
        index = ivf_flat.build(ivf_flat.IndexParams(n_lists=16), db)
        d, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=16), index, q, 5)
        i = np.asarray(i)
        d = np.asarray(d)
        for r in range(10):
            expect = ((q[r] - db[i[r]]) ** 2).sum(-1)
            np.testing.assert_allclose(d[r], expect, rtol=1e-3, atol=1e-3)

    def test_extend(self, rng):
        db = self._data(rng, n=1000)
        extra = rng.normal(size=(500, 16)).astype(np.float32)
        params = ivf_flat.IndexParams(n_lists=8)
        index = ivf_flat.build(params, db)
        index2 = ivf_flat.extend(index, extra)
        assert index2.size == 1500
        q = rng.normal(size=(10, 16)).astype(np.float32)
        d, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), index2, q, 5)
        full = np.concatenate([db, extra])
        _, truth = _naive_knn(q, full, 5)
        assert _recall(np.asarray(i), truth) > 0.99

    def test_cells_search_names_its_stages(self):
        """The packed-cells program carries a named scope per stage, so
        a profiler trace names each device operation by the stage that
        ran it."""
        import jax.numpy as jnp

        from raft_tpu.neighbors.ivf_flat import _cells_search

        f32 = jnp.float32
        args = (jnp.zeros((16, 8), f32), jnp.zeros((4, 8), f32),
                jnp.zeros((4, 128, 8), f32), jnp.zeros((4, 128), jnp.int32),
                jnp.zeros((4,), jnp.int32))
        text = _cells_search.lower(*args, 2, 5, True, False, 8, False,
                                   True).as_text(debug_info=True)
        for stage in ("coarse_probe", "cells_invert", "cells_scan",
                      "id_gather", "route_select"):
            assert "ivf_flat.%s/" % stage in text, stage

    def test_extend_in_place_o_n_new(self, rng):
        """extend() appends at O(n_new): when the new rows fit the existing
        capacity, the storage buffer is donated and aliased (no repack),
        and a small extend is far cheaper than a rebuild (ref: the
        amortized list-growth contract, ivf_flat_types.hpp:65-73)."""
        import time

        db = rng.normal(size=(20_000, 16)).astype(np.float32)
        params = ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=4)
        index = ivf_flat.build(params, db)
        if index.data.shape[1] == int(np.max(np.asarray(index.list_sizes))):
            # Fullest list sits exactly at a power of two — force one
            # growth so the no-growth path below has guaranteed headroom.
            index = ivf_flat.extend(index, db[:1])
        cap0 = index.data.shape[1]
        free = cap0 - int(np.max(np.asarray(index.list_sizes)))
        n_extra = min(32, free)
        size0 = index.size
        ptr0 = index.data.unsafe_buffer_pointer()
        extra = rng.normal(size=(n_extra, 16)).astype(np.float32)
        out = ivf_flat.extend(index, extra)
        assert out is index  # in-place contract: mutates and returns self
        assert index.size == size0 + n_extra
        assert index.data.shape[1] == cap0
        # Donated scatter → XLA aliases output onto the same buffer.
        assert index.data.unsafe_buffer_pointer() == ptr0
        # Timed: a same-shape second extend (compile cached) beats rebuild.
        extra2 = rng.normal(size=(n_extra, 16)).astype(np.float32)
        t0 = time.perf_counter()
        import jax
        jax.block_until_ready(ivf_flat.extend(index, extra2).data)
        t_extend = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(ivf_flat.build(params, db).data)
        t_build = time.perf_counter() - t0
        assert t_extend < t_build / 3, (t_extend, t_build)

    def test_extend_growth_preserves_rows(self, rng):
        """Overflow grows capacity by padding: existing rows keep slots,
        results match a from-scratch build of the union."""
        db = rng.normal(size=(2000, 16)).astype(np.float32)
        index = ivf_flat.build(
            ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4), db)
        cap0 = index.data.shape[1]
        big = rng.normal(size=(4000, 16)).astype(np.float32)
        index = ivf_flat.extend(index, big)
        assert index.data.shape[1] > cap0
        assert index.size == 6000
        q = rng.normal(size=(10, 16)).astype(np.float32)
        d, i = ivf_flat.search(
            ivf_flat.SearchParams(n_probes=8), index, q, 5)
        _, truth = _naive_knn(q, np.concatenate([db, big]), 5)
        assert _recall(np.asarray(i), truth) > 0.99

    def test_save_load_roundtrip(self, rng, tmp_path):
        db = self._data(rng, n=800)
        index = ivf_flat.build(ivf_flat.IndexParams(n_lists=8), db)
        f = str(tmp_path / "ivf_flat_index.npz")
        ivf_flat.save(f, index)
        loaded = ivf_flat.load(f)
        q = rng.normal(size=(5, 16)).astype(np.float32)
        d1, i1 = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), index, q, 3)
        d2, i2 = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), loaded, q, 3)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)

    def test_inner_product_metric(self, rng):
        db = self._data(rng, n=2000)
        q = rng.normal(size=(20, 16)).astype(np.float32)
        params = ivf_flat.IndexParams(n_lists=16, metric=DistanceType.InnerProduct)
        index = ivf_flat.build(params, db)
        d, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=16), index, q, 5)
        _, truth = _naive_knn(q, db, 5, metric="inner_product")
        assert _recall(np.asarray(i), truth) > 0.95


def test_refine_host_matches_device(rng):
    """Host (native thread-pool) refine == device refine (ref: host
    overload of raft::neighbors::refine, detail/refine.cuh:162)."""
    from raft_tpu.neighbors.refine import refine, refine_host

    ds = rng.normal(size=(400, 16)).astype(np.float32)
    q = rng.normal(size=(16, 16)).astype(np.float32)
    d2 = ((q[:, None] - ds[None]) ** 2).sum(-1)
    cand = np.argsort(d2, 1)[:, :25][:, ::-1].copy().astype(np.int32)
    hd, hi = refine_host(ds, q, cand, 5)
    dd, di = refine(ds, q, cand, 5)
    np.testing.assert_array_equal(hi, np.asarray(di))
    np.testing.assert_allclose(hd, np.asarray(dd), rtol=1e-4)


def test_ivf_flat_uint8_native_storage(rng, tmp_path):
    """u8 datasets stay u8 in the index, through serialization, and search
    exactly like the f32 path (ref: the int8/uint8 native input paths,
    loadAndComputeDist<int8>, detail/ivf_flat_search.cuh:456)."""
    db = rng.integers(0, 256, size=(1500, 16)).astype(np.uint8)
    Q = rng.integers(0, 256, size=(50, 16)).astype(np.uint8)
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=4),
                         db)
    assert idx.data.dtype == np.uint8
    ed, ei = brute_force.knn(db.astype(np.float32), Q.astype(np.float32), 5)
    d, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=8), idx, Q, 5)
    assert _recall(np.asarray(i), np.asarray(ei)) > 0.999
    path = str(tmp_path / "idx_u8.npz")
    ivf_flat.save(path, idx)
    assert ivf_flat.load(path).data.dtype == np.uint8


class TestIvfFlatQuantized:
    """8-bit storage parity (ref: the reference's ivf_flat<int8/uint8>
    instantiations and their bench coverage, cpp/bench/neighbors/knn.cuh).
    8-bit values are exact in bf16, so quantized indexes must agree with
    the f32 index on integer-valued data."""

    @pytest.mark.parametrize("dtype", [np.uint8, np.int8])
    def test_quantized_matches_f32(self, rng, dtype):
        lo, hi = (0, 256) if dtype == np.uint8 else (-128, 128)
        db = rng.integers(lo, hi, size=(4000, 32)).astype(dtype)
        q = db[:25].astype(np.float32)
        params = ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=4)
        idx8 = ivf_flat.build(params, db)
        assert idx8.data.dtype == dtype        # stored quantized
        idxf = ivf_flat.build(params, db.astype(np.float32))
        for engine in ("scan", "bucketed"):
            sp = ivf_flat.SearchParams(n_probes=16, engine=engine,
                                       bucket_cap=64)
            d8, i8 = ivf_flat.search(sp, idx8, q, 5)
            df, if_ = ivf_flat.search(sp, idxf, q, 5)
            np.testing.assert_array_equal(np.asarray(i8), np.asarray(if_))
            np.testing.assert_allclose(np.asarray(d8), np.asarray(df),
                                       rtol=1e-5, atol=1e-2)

    @pytest.mark.parametrize("dtype", [np.uint8, np.int8])
    def test_quantized_float_queries(self, rng, dtype):
        """Non-integer float queries against quantized storage: the
        bucketed engine's split hi/lo query matmul (qsplit) must keep f32
        query precision — a plain bf16 query cast would perturb rankings
        vs the scan engine, which scores bf16-stored rows with f32
        queries (ADVICE r3: the parity test above only used
        integer-valued queries)."""
        lo, hi = (0, 256) if dtype == np.uint8 else (-128, 128)
        db = rng.integers(lo, hi, size=(4000, 32)).astype(dtype)
        q = db[:40].astype(np.float32) + rng.normal(
            scale=0.37, size=(40, 32)).astype(np.float32)
        idx8 = ivf_flat.build(
            ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=4), db)
        ds, is_ = ivf_flat.search(
            ivf_flat.SearchParams(n_probes=16, engine="scan"), idx8, q, 5)
        dbk, ibk = ivf_flat.search(
            ivf_flat.SearchParams(n_probes=16, engine="bucketed",
                                  bucket_cap=64), idx8, q, 5)
        np.testing.assert_array_equal(np.asarray(is_), np.asarray(ibk))
        # atol covers f32 cancellation noise in qn+yn-2g at ~5e5-magnitude
        # squared norms (~|x|^2*eps*n_ops); without qsplit the bf16 query
        # rounding error is ~1000x this and the index assert above fails.
        np.testing.assert_allclose(np.asarray(ds), np.asarray(dbk),
                                   rtol=1e-4, atol=5.0)

    def test_quantized_extend_and_roundtrip(self, rng, tmp_path):
        db = rng.integers(0, 256, size=(2000, 16)).astype(np.uint8)
        idx = ivf_flat.build(
            ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=3), db)
        extra = rng.integers(0, 256, size=(100, 16)).astype(np.uint8)
        idx = ivf_flat.extend(idx, extra)
        assert idx.data.dtype == np.uint8 and idx.size == 2100
        f = str(tmp_path / "u8idx")
        ivf_flat.save(f, idx)
        loaded = ivf_flat.load(f)
        assert loaded.data.dtype == np.uint8

    def test_bf16_storage_preserved(self, rng):
        """bfloat16 datasets keep bf16 list storage (2x less memory) and
        search stays near-exact (bf16 has ~3 decimal digits)."""
        import jax.numpy as jnp

        db = rng.normal(size=(3000, 16)).astype(np.float32)
        idx = ivf_flat.build(
            ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=4),
            jnp.asarray(db).astype(jnp.bfloat16))
        assert idx.data.dtype == jnp.bfloat16
        q = rng.normal(size=(25, 16)).astype(np.float32)
        d, i = ivf_flat.search(ivf_flat.SearchParams(n_probes=16), idx, q, 5)
        _, truth = _naive_knn(q, db, 5)
        assert _recall(np.asarray(i), truth) > 0.9


def test_brute_force_cosine_polarity(rng):
    """Cosine/correlation brute-force kNN must return the NEAREST rows
    (pairwise emits 1 - similarity distance form; round-4 review catch:
    pairing the reference's similarity-form polarity with our
    distance-form values returned the farthest rows)."""
    from raft_tpu.distance.distance_types import DistanceType

    a = rng.standard_normal((200, 32)).astype(np.float32)
    q = rng.standard_normal((10, 32)).astype(np.float32)
    an = a / np.linalg.norm(a, axis=1, keepdims=True)
    qn = q / np.linalg.norm(q, axis=1, keepdims=True)
    for metric in (DistanceType.CosineExpanded,
                   DistanceType.CorrelationExpanded):
        d, i = brute_force.knn(a, q, 5, metric=metric)
        if metric == DistanceType.CosineExpanded:
            dm = 1.0 - qn @ an.T
        else:
            ac = a - a.mean(1, keepdims=True)
            qc = q - q.mean(1, keepdims=True)
            dm = 1.0 - (qc / np.linalg.norm(qc, axis=1, keepdims=True)) @ (
                ac / np.linalg.norm(ac, axis=1, keepdims=True)).T
        ref = np.sort(dm, axis=1)[:, :5]
        np.testing.assert_allclose(np.sort(np.asarray(d), 1), ref,
                                   rtol=1e-3, atol=1e-3)


def test_refine_cosine_polarity(rng):
    from raft_tpu.distance.distance_types import DistanceType
    from raft_tpu.neighbors.refine import refine

    db = rng.standard_normal((500, 16)).astype(np.float32)
    q = rng.standard_normal((20, 16)).astype(np.float32)
    cand = np.broadcast_to(np.arange(50, dtype=np.int32), (20, 50)).copy()
    d, i = refine(db, q, cand, 5, metric=DistanceType.CosineExpanded)
    qn = q / np.linalg.norm(q, axis=1, keepdims=True)
    cn = db[:50] / np.linalg.norm(db[:50], axis=1, keepdims=True)
    ref = np.sort(1.0 - qn @ cn.T, axis=1)[:, :5]
    np.testing.assert_allclose(np.sort(np.asarray(d), 1), ref,
                               rtol=1e-3, atol=1e-3)
