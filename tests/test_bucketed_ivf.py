"""Bucketed IVF-Flat probe engine + batched fused-kNN kernel.

Ref comparison style: recall/agreement thresholds per the reference's ANN
test scheme (cpp/test/neighbors/ann_utils.cuh:121-162)."""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.neighbors import brute_force, ivf_flat, ivf_pq
from raft_tpu.ops.fused_knn import fused_batch_knn


def test_fused_batch_knn_matches_naive(rng):
    B, m, n, d, k = 6, 16, 96, 24, 5
    Q = rng.normal(size=(B, m, d)).astype(np.float32)
    DB = rng.normal(size=(B, n, d)).astype(np.float32)
    sizes = rng.integers(8, n + 1, size=(B,))
    invalid = np.arange(n)[None, :] >= sizes[:, None]

    dists, ids = fused_batch_knn(Q, DB, jnp.asarray(invalid), k,
                                 interpret=True)
    dists, ids = np.asarray(dists), np.asarray(ids)
    for b in range(B):
        dn = ((Q[b][:, None] - DB[b][None]) ** 2).sum(-1)
        dn[:, sizes[b]:] = np.inf
        np.testing.assert_allclose(
            np.sort(dists[b], 1), np.sort(dn, 1)[:, :k], atol=1e-4)
        np.testing.assert_array_equal(
            np.sort(ids[b], 1), np.sort(np.argsort(dn, 1)[:, :k], 1))


def test_fused_batch_knn_ip(rng):
    B, m, n, d, k = 3, 8, 64, 16, 4
    Q = rng.normal(size=(B, m, d)).astype(np.float32)
    DB = rng.normal(size=(B, n, d)).astype(np.float32)
    invalid = np.zeros((B, n), bool)
    dists, ids = fused_batch_knn(Q, DB, jnp.asarray(invalid), k, metric="ip",
                                 interpret=True)
    for b in range(B):
        g = Q[b] @ DB[b].T
        np.testing.assert_allclose(
            np.sort(np.asarray(dists)[b], 1), np.sort(g, 1)[:, -k:],
            atol=1e-4)


def test_fused_batch_knn_starved_lists(rng):
    """Lists with fewer than k valid rows across multiple db tiles must
    report -1 ids at inf distance, never duplicated/stale real ids."""
    B, m, n, d, k = 4, 8, 512, 16, 5
    Q = rng.normal(size=(B, m, d)).astype(np.float32)
    DB = rng.normal(size=(B, n, d)).astype(np.float32)
    sizes = np.array([2, 3, 0, 7])  # all < k or barely above
    invalid = np.arange(n)[None, :] >= sizes[:, None]
    dists, ids = fused_batch_knn(Q, DB, jnp.asarray(invalid), k, bd=256,
                                 interpret=True)
    dists, ids = np.asarray(dists), np.asarray(ids)
    for b in range(B):
        nvalid = min(int(sizes[b]), k)
        assert np.all(np.isinf(dists[b][:, nvalid:]))
        assert np.all(ids[b][:, nvalid:] == -1), ids[b]
        if nvalid:
            finite = ids[b][:, :nvalid]
            assert np.all(finite >= 0) and np.all(finite < sizes[b])
            for r in range(m):  # no duplicates among real ids
                assert len(set(finite[r])) == nvalid


def test_bucketed_matches_scan_engine(rng):
    n, d, qn, k = 3000, 24, 150, 10
    db = rng.normal(size=(n, d)).astype(np.float32)
    Q = rng.normal(size=(qn, d)).astype(np.float32)
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=24, kmeans_n_iters=5),
                         db)
    sp_scan = ivf_flat.SearchParams(n_probes=6, engine="scan")
    sp_buck = ivf_flat.SearchParams(n_probes=6, engine="bucketed",
                                    bucket_cap=qn)
    sd, si = ivf_flat.search(sp_scan, idx, Q, k)
    bd, bi = ivf_flat.search(sp_buck, idx, Q, k)
    agree = np.mean([
        len(np.intersect1d(np.asarray(si)[r], np.asarray(bi)[r])) / k
        for r in range(qn)])
    assert agree > 0.999, f"bucketed(full cap) != scan: {agree}"
    np.testing.assert_allclose(np.sort(np.asarray(bd), 1),
                               np.sort(np.asarray(sd), 1), atol=1e-3)


def test_cells_tier_k200_matches_scan(rng):
    """k in (128, 256] must hit the widened cells tier (two-lane-group
    k-pass queue; VERDICT r5 item 4: 'k=200 search hits the cells tier')
    and agree with the exact scan engine."""
    from raft_tpu.neighbors.ivf_flat import _CELLS_MAX_K, _cells_eligible

    assert _CELLS_MAX_K == 256
    n, d, qn, k = 4000, 24, 64, 200
    assert _cells_eligible("bucketed", k, 0, 512, d, qn, 8, 16)
    db = rng.normal(size=(n, d)).astype(np.float32)
    Q = rng.normal(size=(qn, d)).astype(np.float32)
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=5),
                         db)
    sp_scan = ivf_flat.SearchParams(n_probes=8, engine="scan")
    sp_cell = ivf_flat.SearchParams(n_probes=8, engine="bucketed")
    sd, si = ivf_flat.search(sp_scan, idx, Q, k)
    cd, ci = ivf_flat.search(sp_cell, idx, Q, k)
    agree = np.mean([
        len(np.intersect1d(np.asarray(si)[r], np.asarray(ci)[r])) / k
        for r in range(qn)])
    assert agree > 0.999, f"cells(k=200) != scan: {agree}"
    np.testing.assert_allclose(np.sort(np.asarray(cd), 1),
                               np.sort(np.asarray(sd), 1), atol=1e-3)


def test_pq_compressed_k200_matches_scan(rng):
    """The compressed PQ tier at k in (128, 256] must agree with the LUT
    scan engine (same widened queue)."""
    n, d, qn, k = 4000, 32, 64, 160
    db = rng.normal(size=(n, d)).astype(np.float32)
    Q = rng.normal(size=(qn, d)).astype(np.float32)
    idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=16, kmeans_n_iters=5, pq_dim=16), db)
    sd, si = ivf_pq.search(ivf_pq.SearchParams(n_probes=8, engine="scan"),
                           idx, Q, k)
    cd, ci = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=8, engine="bucketed"), idx, Q, k)
    agree = np.mean([
        len(np.intersect1d(np.asarray(si)[r], np.asarray(ci)[r])) / k
        for r in range(qn)])
    assert agree > 0.98, f"compressed(k=160) != scan: {agree}"


@pytest.mark.parametrize("kind", [ivf_pq.CodebookGen.PER_SUBSPACE,
                                  ivf_pq.CodebookGen.PER_CLUSTER])
def test_ivf_pq_bucketed_matches_lut_scan(rng, kind):
    """ADC over the reconstruction cache must rank like the LUT scan — the
    two are the same math (‖R·q − (R·c + codeword)‖²); bf16 recon storage
    may flip only distance-degenerate tail entries."""
    n, d, qn, k = 3000, 32, 150, 10
    db = rng.normal(size=(n, d)).astype(np.float32)
    Q = rng.normal(size=(qn, d)).astype(np.float32)
    idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=16, kmeans_n_iters=5, pq_dim=16,
                           codebook_kind=kind), db)
    ed, ei = brute_force.knn(db, Q, k)
    sd, si = ivf_pq.search(ivf_pq.SearchParams(n_probes=8, engine="scan"),
                           idx, Q, k)
    bd, bi = ivf_pq.search(
        ivf_pq.SearchParams(n_probes=8, engine="bucketed", bucket_cap=qn),
        idx, Q, k)
    rec_s = np.mean([len(np.intersect1d(np.asarray(si)[r],
                                        np.asarray(ei)[r])) / k
                     for r in range(qn)])
    rec_b = np.mean([len(np.intersect1d(np.asarray(bi)[r],
                                        np.asarray(ei)[r])) / k
                     for r in range(qn)])
    assert rec_b >= rec_s - 0.02, (rec_b, rec_s)
    agree = np.mean([len(np.intersect1d(np.asarray(si)[r],
                                        np.asarray(bi)[r])) / k
                     for r in range(qn)])
    assert agree > 0.95, agree


def test_ivf_pq_recon_cache_no_tracer_poisoning(rng):
    """reconstructed() under jit must not persist a tracer on the index
    (later eager searches would raise UnexpectedTracerError)."""
    import jax

    db = rng.normal(size=(1500, 32)).astype(np.float32)
    Q = rng.normal(size=(40, 32)).astype(np.float32)
    idx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=8, kmeans_n_iters=3, pq_dim=16), db)
    sp = ivf_pq.SearchParams(n_probes=4, engine="bucketed", bucket_cap=40)
    d1, i1 = jax.jit(lambda q: ivf_pq.search(sp, idx, q, 5))(Q)
    d2, i2 = ivf_pq.search(sp, idx, Q, 5)  # eager after traced
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-3)


def test_bucketed_measured_cap_skewed_queries(rng):
    """Hot-list contention: every query's best probe is the same list, so a
    mean-sized bucket_cap would drop best-rank probes (the round-1 policy
    bug). bucket_cap=0 sizes from the measured max per-list load and must
    agree with the scan engine exactly."""
    n, d, qn, k = 3000, 24, 200, 10
    db = rng.normal(size=(n, d)).astype(np.float32)
    # All queries land on one cluster of the database -> one hot list.
    hot = db[:40].mean(0)
    Q = (hot[None, :] + 0.05 * rng.normal(size=(qn, d))).astype(np.float32)
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=24, kmeans_n_iters=5),
                         db)
    sd, si = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=6, engine="scan"), idx, Q, k)
    bd, bi = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=6, engine="bucketed", bucket_cap=0),
        idx, Q, k)
    agree = np.mean([
        len(np.intersect1d(np.asarray(si)[r], np.asarray(bi)[r])) / k
        for r in range(qn)])
    assert agree > 0.999, f"measured-cap bucketed != scan on skew: {agree}"


def test_search_traceable_under_jit(rng, monkeypatch):
    """search must stay jittable. engine='auto'/'bucketed' now trace
    through the packed-cells tier (round 4 — fully traceable, no
    capacity measurement); with the cells tier unavailable, a traced
    bucketed request with cap=0 still raises the clear bucket_cap error
    (no data-dependent capacity can be measured under a trace)."""
    import jax

    from raft_tpu.core.error import RaftError
    from raft_tpu.neighbors import ivf_flat as impl

    db = rng.normal(size=(2000, 16)).astype(np.float32)
    Q = rng.normal(size=(50, 16)).astype(np.float32)
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=4),
                         db)
    sp = ivf_flat.SearchParams(n_probes=8)
    d_jit, i_jit = jax.jit(lambda q: ivf_flat.search(sp, idx, q, 5))(Q)
    d_e, i_e = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=8, engine="scan"), idx, Q, 5)
    np.testing.assert_array_equal(np.asarray(i_jit), np.asarray(i_e))
    # bucketed under jit now resolves to the traceable cells tier
    d_b, i_b = jax.jit(lambda q: ivf_flat.search(
        ivf_flat.SearchParams(n_probes=8, engine="bucketed"),
        idx, q, 5))(Q)
    np.testing.assert_array_equal(np.asarray(i_b), np.asarray(i_e))
    # legacy bucket-table engine (cells gated off): traced cap=0 raises
    monkeypatch.setattr(impl, "_CELL_VMEM_BYTES", 0)
    with pytest.raises(RaftError, match="bucket_cap"):
        jax.jit(lambda q: ivf_flat.search(
            ivf_flat.SearchParams(n_probes=8, engine="bucketed"),
            idx, q, 5))(Q)


def test_bucketed_auto_cap_recall(rng):
    """Tight auto bucket_cap loses at most the documented overflow — recall
    stays above the reference's n_probes/n_lists lower bound
    (ann_ivf_flat.cuh:146-153)."""
    n, d, qn, k = 3000, 24, 200, 10
    db = rng.normal(size=(n, d)).astype(np.float32)
    Q = rng.normal(size=(qn, d)).astype(np.float32)
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=5),
                         db)
    ed, ei = brute_force.knn(db, Q, k)
    bd, bi = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=8, engine="bucketed"), idx, Q, k)
    rec = np.mean([
        len(np.intersect1d(np.asarray(bi)[r], np.asarray(ei)[r])) / k
        for r in range(qn)])
    assert rec >= 8 / 16, f"recall {rec} below n_probes/n_lists bound"


def test_measured_cap_cached_per_index(rng, monkeypatch):
    """The auto/measured capacity readback runs once per (index, query
    shape) and is memoized on the index (the per-index batch-size
    heuristic role of detail/ivf_pq_search.cuh:1517); extend() changes
    occupancy and invalidates it."""
    from raft_tpu.neighbors import ivf_flat as impl

    db = rng.normal(size=(3000, 16)).astype(np.float32)
    Q = rng.normal(size=(200, 16)).astype(np.float32)
    idx = impl.build(impl.IndexParams(n_lists=16, kmeans_n_iters=4), db)

    # The measured-capacity machinery belongs to the legacy bucket-table
    # engine; gate the round-4 cells tier off to exercise it.
    monkeypatch.setattr(impl, "_CELL_VMEM_BYTES", 0)

    calls = []
    real = impl._front_rank_contention

    def counting(probe_ids, n_lists):
        calls.append(1)
        return real(probe_ids, n_lists)

    monkeypatch.setattr(impl, "_front_rank_contention", counting)
    sp = impl.SearchParams(n_probes=8, engine="bucketed")
    d1, i1 = impl.search(sp, idx, Q, 5)
    assert len(calls) == 1
    d2, i2 = impl.search(sp, idx, Q, 5)
    assert len(calls) == 1  # cache hit: no second device readback
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    # different batch shape -> separate measurement
    impl.search(sp, idx, Q[:64], 5)
    assert len(calls) == 2
    # extend invalidates (occupancy changed)
    impl.extend(idx, db[:8], np.arange(8, dtype=np.int32))
    impl.search(sp, idx, Q, 5)
    assert len(calls) == 3


def test_skew_bound_never_drops_best_probe(rng, monkeypatch):
    """Extreme skew: every query's rank-0 probe is the same list, with
    n_lists > 8*n_probes so the 8x-mean-load bound (128) sits BELOW the
    rank-0 contention (256) — the floor must win, so each query's
    nearest-list candidates survive and its true NN is found. Explicit
    engine='bucketed' with bucket_cap=0 forces the measured sizing on
    every backend (auto would pick scan off-TPU). The round-4 cells
    tier is gated off — it has no capacity to measure (drop-free by
    construction; covered by the parity tests above)."""
    from raft_tpu.neighbors import ivf_flat as impl

    monkeypatch.setattr(impl, "_CELL_VMEM_BYTES", 0)

    # One tight hot cluster + scattered others across 64 lists.
    hot = rng.normal(size=(400, 8)).astype(np.float32) * 0.05
    rest = rng.normal(size=(6000, 8)).astype(np.float32) + 8.0
    db = np.concatenate([hot, rest])
    idx = impl.build(impl.IndexParams(n_lists=64, kmeans_n_iters=5), db)
    # All queries sit in the hot cluster -> rank-0 contention = n_queries
    # = 256 > next_pow2(8 * (256*4//64)) = 128.
    Q = hot[:256] + rng.normal(size=(256, 8)).astype(np.float32) * 0.01
    sp = impl.SearchParams(n_probes=4, engine="bucketed", bucket_cap=0)
    d, i = impl.search(sp, idx, Q, 1)
    assert idx.__dict__["_auto_cap_cache"][(256, 4)] >= 256  # floor bound
    dn = ((Q[:, None, :] - db[None]) ** 2).sum(-1)
    truth = dn.argmin(1)
    assert np.mean(np.asarray(i)[:, 0] == truth) > 0.99


@pytest.mark.parametrize("kind", ["per_subspace", "per_cluster"])
def test_pq_bucketed_decode_scan_matches_recon(rng, monkeypatch, kind):
    """Above the recon-cache budget the bucketed engine decodes list
    blocks on the fly; results must match the recon-cached engine
    exactly (both decode the same codes to bf16)."""
    from raft_tpu.neighbors import ivf_pq as pq

    db = rng.normal(size=(3000, 32)).astype(np.float32)
    Q = rng.normal(size=(100, 32)).astype(np.float32)
    params = pq.IndexParams(
        n_lists=16, pq_dim=16, kmeans_n_iters=4,
        codebook_kind=pq.CodebookGen.PER_CLUSTER if kind == "per_cluster"
        else pq.CodebookGen.PER_SUBSPACE)
    idx = pq.build(params, db)
    sp = pq.SearchParams(n_probes=8, engine="bucketed", bucket_cap=64)
    # Pre-build the cache: PER_SUBSPACE would otherwise dispatch to the
    # round-4 compressed-domain kernel tier (covered in
    # test_pq_compressed.py) instead of the recon tier under test here.
    idx.reconstructed()
    dr, ir = pq.search(sp, idx, Q, 5)        # recon path (small index)
    assert idx._recon is not None
    idx._recon = None
    monkeypatch.setattr(pq, "_RECON_AUTO_BYTES", 0)
    # Keep the compressed-domain kernel out of the dispatch so the
    # beyond-budget branch under test (block decode-scan) is exercised.
    monkeypatch.setattr(pq, "_compressed_supported", lambda _i: False)
    dd, id_ = pq.search(sp, idx, Q, 5)       # decode path
    assert idx._recon is None                # never materialized the cache
    np.testing.assert_array_equal(np.asarray(ir), np.asarray(id_))
    np.testing.assert_allclose(np.asarray(dr), np.asarray(dd),
                               rtol=1e-3, atol=1e-3)
