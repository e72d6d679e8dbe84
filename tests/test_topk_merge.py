"""Merge-collective tests: the ring / ring_bf16 engines must reproduce the
allgather engine exactly on 1/2/4/8 simulated devices (conftest forces the
8-virtual-CPU-device backend), including k > shard, distance ties, and the
bf16 engine's exact-re-rank recall guard (ISSUE 1 tentpole)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from raft_tpu.comms.topk_merge import (
    MERGE_ENGINES, merge_comm_bytes, merge_dispatch_stats, merge_parts,
    pipeline_chunk_bounds, resolve_merge_engine, resolve_pipeline_chunks,
    topk_merge, topk_merge_pipelined)


def _mesh(n_dev):
    devs = np.array(jax.devices())
    assert devs.size >= 8, "conftest must force 8 virtual devices"
    return Mesh(devs[:n_dev], ("data",))


def _merge_on_mesh(mesh, dist, idx, k, select_min, engine):
    """dist/idx: (n_dev, q, kk) host arrays — row d is device d's local
    candidates; returns the replicated merged (distances, ids)."""
    fn = jax.shard_map(
        lambda dd, ii: topk_merge(dd[0], ii[0], k, "data",
                                  select_min=select_min, engine=engine),
        mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P(None, None), P(None, None)), check_vma=False)
    d, i = jax.jit(fn)(jnp.asarray(dist), jnp.asarray(idx))
    return np.asarray(d), np.asarray(i)


def _host_truth(dist, idx, k, select_min):
    """Host reference: global top-k under the shared (distance, id) order."""
    n_dev, q, kk = dist.shape
    flat_d = dist.transpose(1, 0, 2).reshape(q, n_dev * kk)
    flat_i = idx.transpose(1, 0, 2).reshape(q, n_dev * kk)
    keys = flat_d if select_min else -flat_d
    order = np.lexsort((flat_i, keys), axis=1)[:, :min(k, n_dev * kk)]
    return (np.take_along_axis(flat_d, order, 1),
            np.take_along_axis(flat_i, order, 1))


class TestEngineExactness:
    # 3 and 6 exercise the non-power-of-two linear (store-and-forward)
    # ring branch of _ring_merge; the pow2 sizes the log-step butterfly.
    @pytest.mark.parametrize("n_dev", [1, 2, 3, 4, 6, 8])
    @pytest.mark.parametrize("q,kk,k", [(4, 6, 5), (3, 2, 10), (1, 8, 8),
                                        (7, 3, 64)])
    @pytest.mark.parametrize("select_min", [True, False])
    def test_ring_matches_allgather(self, rng, n_dev, q, kk, k, select_min):
        mesh = _mesh(n_dev)
        dist = rng.normal(size=(n_dev, q, kk)).astype(np.float32)
        idx = rng.permutation(n_dev * q * kk).astype(np.int32) \
            .reshape(n_dev, q, kk)
        base_d, base_i = _merge_on_mesh(mesh, dist, idx, k, select_min,
                                        "allgather")
        td, ti = _host_truth(dist, idx, k, select_min)
        np.testing.assert_array_equal(base_d, td)
        np.testing.assert_array_equal(base_i, ti)
        for engine in ("ring", "ring_bf16", "auto"):
            d, i = _merge_on_mesh(mesh, dist, idx, k, select_min, engine)
            np.testing.assert_array_equal(base_d, d, err_msg=engine)
            np.testing.assert_array_equal(base_i, i, err_msg=engine)

    @pytest.mark.parametrize("n_dev", [2, 4, 5, 7, 8])
    def test_ties_resolve_identically(self, rng, n_dev):
        """Mass distance ties: the shared lowest-id tie order must make
        every engine (and every device of the butterfly) agree exactly."""
        mesh = _mesh(n_dev)
        q, kk, k = 5, 4, 9
        dist = rng.integers(0, 3, size=(n_dev, q, kk)).astype(np.float32)
        idx = rng.permutation(n_dev * q * kk).astype(np.int32) \
            .reshape(n_dev, q, kk)
        base = _merge_on_mesh(mesh, dist, idx, k, True, "allgather")
        np.testing.assert_array_equal(
            base[1], _host_truth(dist, idx, k, True)[1])
        for engine in ("ring", "ring_bf16"):
            d, i = _merge_on_mesh(mesh, dist, idx, k, True, engine)
            np.testing.assert_array_equal(base[0], d, err_msg=engine)
            np.testing.assert_array_equal(base[1], i, err_msg=engine)

    def test_k_larger_than_total(self, rng):
        """k beyond every shard's candidates: output clamps to n_dev*kk
        (the sharded consumers' capacity contract)."""
        mesh = _mesh(4)
        dist = rng.normal(size=(4, 3, 2)).astype(np.float32)
        idx = rng.permutation(24).astype(np.int32).reshape(4, 3, 2)
        for engine in ("allgather", "ring", "ring_bf16"):
            d, i = _merge_on_mesh(mesh, dist, idx, 50, True, engine)
            assert d.shape == (3, 8) and i.shape == (3, 8)
            np.testing.assert_array_equal(
                np.sort(i, axis=1),
                np.sort(idx.transpose(1, 0, 2).reshape(3, 8), axis=1))

    def test_bf16_rerank_exact_distances(self, rng):
        """The quantized engine must report EXACT f32 distances (the
        re-rank recovers them from the owning shard) and full recall on
        f32 data — recall@k == 1.0 vs the exact engine."""
        mesh = _mesh(8)
        q, kk, k = 16, 32, 10
        dist = (rng.normal(size=(8, q, kk)) ** 2).astype(np.float32)
        idx = rng.permutation(8 * q * kk).astype(np.int32).reshape(8, q, kk)
        base_d, base_i = _merge_on_mesh(mesh, dist, idx, k, True,
                                        "allgather")
        d, i = _merge_on_mesh(mesh, dist, idx, k, True, "ring_bf16")
        recall = np.mean([len(np.intersect1d(i[r], base_i[r])) / k
                          for r in range(q)])
        assert recall == 1.0
        np.testing.assert_array_equal(base_d, d)   # exact after re-rank

    def test_int64_ids(self, rng):
        """ids stay exact at int64 under x64 (the quantized exchange only
        touches distances)."""
        if not jax.config.jax_enable_x64:
            pytest.skip("x64 disabled in this suite config")
        mesh = _mesh(4)
        dist = rng.normal(size=(4, 3, 4)).astype(np.float32)
        idx = rng.permutation(48).astype(np.int64).reshape(4, 3, 4)
        base = _merge_on_mesh(mesh, dist, idx, 6, True, "allgather")
        ring = _merge_on_mesh(mesh, dist, idx, 6, True, "ring")
        assert ring[1].dtype == np.int64
        np.testing.assert_array_equal(base[1], ring[1])


def _pipelined_on_mesh(mesh, dist, idx, k, select_min, n_chunks,
                       quantized=False):
    """dist/idx: (n_dev, q, kk); the chunk callback slices candidate
    columns — the disjoint-chunk contract of topk_merge_pipelined."""
    kk = dist.shape[2]
    bounds = pipeline_chunk_bounds(kk, n_chunks)

    def body(dd, ii):
        def scan_chunk(c):
            lo, hi = bounds[c]
            return dd[0][:, lo:hi], ii[0][:, lo:hi]

        return topk_merge_pipelined(scan_chunk, len(bounds), k, "data",
                                    select_min=select_min,
                                    quantized=quantized)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=(P(None, None), P(None, None)),
                       check_vma=False)
    d, i = jax.jit(fn)(jnp.asarray(dist), jnp.asarray(idx))
    return np.asarray(d), np.asarray(i)


class TestPipelinedMerge:
    """The fused scan→merge pipeline (ISSUE 14): per-chunk ring merges
    folded under the shared total order must be BIT-IDENTICAL to the
    unchunked engines over the concatenated candidates — on 1/2/4/8
    devices (and the non-pow2 linear ring), for chunk counts that do
    and do not divide the candidate width, with k above the per-chunk
    width, and under mass distance ties."""

    @pytest.mark.parametrize("n_dev", [1, 2, 3, 4, 8])
    @pytest.mark.parametrize("q,kk,k,n_chunks", [
        (4, 6, 5, 2),      # even-ish chunks
        (3, 7, 10, 3),     # 7 columns into 3 chunks: 3/2/2 (odd split)
        (5, 4, 16, 4),     # k > per-chunk candidates (and > kk)
        (2, 9, 3, 5),      # more chunks than needed, tiny k
    ])
    @pytest.mark.parametrize("select_min", [True, False])
    def test_matches_allgather(self, rng, n_dev, q, kk, k, n_chunks,
                               select_min):
        mesh = _mesh(n_dev)
        dist = rng.normal(size=(n_dev, q, kk)).astype(np.float32)
        idx = rng.permutation(n_dev * q * kk).astype(np.int32) \
            .reshape(n_dev, q, kk)
        base_d, base_i = _merge_on_mesh(mesh, dist, idx, k, select_min,
                                        "allgather")
        d, i = _pipelined_on_mesh(mesh, dist, idx, k, select_min,
                                  n_chunks)
        np.testing.assert_array_equal(base_d, d)
        np.testing.assert_array_equal(base_i, i)

    @pytest.mark.parametrize("n_dev", [2, 4, 8])
    def test_ties_bit_identical(self, rng, n_dev):
        """Mass integer-valued ties: the chunk folding must keep the
        lowest-id total order exactly (associativity under ties)."""
        mesh = _mesh(n_dev)
        q, kk, k = 5, 8, 9
        dist = rng.integers(0, 3, size=(n_dev, q, kk)).astype(np.float32)
        idx = rng.permutation(n_dev * q * kk).astype(np.int32) \
            .reshape(n_dev, q, kk)
        base = _merge_on_mesh(mesh, dist, idx, k, True, "allgather")
        for n_chunks in (2, 3):
            d, i = _pipelined_on_mesh(mesh, dist, idx, k, True, n_chunks)
            np.testing.assert_array_equal(base[0], d)
            np.testing.assert_array_equal(base[1], i)

    def test_quantized_chunks_rerank_exact_distances(self, rng):
        """pipelined_bf16: per-chunk guard + exact re-rank — reported
        distances are exact f32 and recall holds on well-separated
        data (the per-chunk bound is weaker than unchunked ring_bf16)."""
        mesh = _mesh(8)
        q, kk, k = 16, 32, 10
        dist = (rng.normal(size=(8, q, kk)) ** 2).astype(np.float32)
        idx = rng.permutation(8 * q * kk).astype(np.int32) \
            .reshape(8, q, kk)
        base_d, base_i = _merge_on_mesh(mesh, dist, idx, k, True,
                                        "allgather")
        d, i = _pipelined_on_mesh(mesh, dist, idx, k, True, 4,
                                  quantized=True)
        recall = np.mean([len(np.intersect1d(i[r], base_i[r])) / k
                          for r in range(q)])
        assert recall == 1.0
        np.testing.assert_array_equal(base_d, d)

    def test_plain_topk_merge_degrades_pipelined_to_ring(self, rng):
        """engine="pipelined" through the unchunked topk_merge API (one
        candidate set, nothing to overlap) must equal the ring engine."""
        mesh = _mesh(4)
        dist = rng.normal(size=(4, 3, 6)).astype(np.float32)
        idx = rng.permutation(72).astype(np.int32).reshape(4, 3, 6)
        ring = _merge_on_mesh(mesh, dist, idx, 8, True, "ring")
        pipe = _merge_on_mesh(mesh, dist, idx, 8, True, "pipelined")
        np.testing.assert_array_equal(ring[0], pipe[0])
        np.testing.assert_array_equal(ring[1], pipe[1])


class TestShardedPipelinedConsumers:
    """End-to-end sharded searches on the pipelined engines must match
    the allgather engine bit-for-bit (float data — distance ties at the
    per-shard truncation boundary resolve canonically by id on the
    pipelined path, see docs/sharded_search.md)."""

    @pytest.mark.parametrize("engine", ["pipelined", "pipelined_bf16"])
    def test_sharded_knn_pipelined_agrees(self, rng, engine):
        from raft_tpu.parallel import sharded_knn

        mesh = _mesh(8)
        db = rng.normal(size=(1024, 16)).astype(np.float32)
        q = rng.normal(size=(32, 16)).astype(np.float32)
        # 128 rows per shard in 16-row scan tiles: 3 chunks of whole tiles.
        bd, bi = sharded_knn(mesh, db, q, k=10, merge_engine="allgather",
                             tile_db=16)
        d, i = sharded_knn(mesh, db, q, k=10, merge_engine=engine,
                           pipeline_chunks=3, tile_db=16)
        np.testing.assert_array_equal(np.asarray(bd), np.asarray(d))
        np.testing.assert_array_equal(np.asarray(bi), np.asarray(i))

    @pytest.mark.parametrize("tier", ["scan", "bucketed"])
    @pytest.mark.parametrize("n_probes,chunks", [(7, 3), (8, 0), (5, 2)])
    def test_sharded_ivf_flat_pipelined_grid(self, rng, tier, n_probes,
                                             chunks):
        """Odd n_probes not divisible by the chunk count, auto chunking,
        both scan tiers — bit-identical to allgather."""
        from raft_tpu.neighbors import ivf_flat
        from raft_tpu.parallel import (sharded_ivf_flat_build,
                                       sharded_ivf_flat_search)

        mesh = _mesh(8)
        db = rng.normal(size=(2048, 16)).astype(np.float32)
        q = rng.normal(size=(24, 16)).astype(np.float32)
        params = ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=4)
        sharded = sharded_ivf_flat_build(mesh, params, db)
        sp = ivf_flat.SearchParams(n_probes=n_probes, engine=tier)
        bd, bi = sharded_ivf_flat_search(mesh, sp, sharded, q, 10,
                                         merge_engine="allgather")
        d, i = sharded_ivf_flat_search(mesh, sp, sharded, q, 10,
                                       merge_engine="pipelined",
                                       pipeline_chunks=chunks)
        np.testing.assert_array_equal(np.asarray(bi), np.asarray(i))
        np.testing.assert_array_equal(np.asarray(bd), np.asarray(d))

    def test_sharded_ivf_flat_k_exceeds_chunk_capacity(self, rng):
        """k larger than any chunk's probed capacity: per-chunk widths
        clamp and the fold still reproduces the unchunked result."""
        from raft_tpu.neighbors import ivf_flat
        from raft_tpu.parallel import (sharded_ivf_flat_build,
                                       sharded_ivf_flat_search)

        mesh = _mesh(4)
        db = rng.normal(size=(256, 8)).astype(np.float32)
        q = rng.normal(size=(6, 8)).astype(np.float32)
        params = ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=3)
        sharded = sharded_ivf_flat_build(mesh, params, db)
        sp = ivf_flat.SearchParams(n_probes=6, engine="scan")
        bd, bi = sharded_ivf_flat_search(mesh, sp, sharded, q, 50,
                                         merge_engine="allgather")
        d, i = sharded_ivf_flat_search(mesh, sp, sharded, q, 50,
                                       merge_engine="pipelined",
                                       pipeline_chunks=3)
        np.testing.assert_array_equal(np.asarray(bi), np.asarray(i))
        np.testing.assert_array_equal(np.asarray(bd), np.asarray(d))

    @pytest.mark.parametrize("tier", ["scan", "bucketed"])
    def test_sharded_ivf_pq_pipelined_agrees(self, rng, tier):
        """Both PQ tiers (LUT scan + compressed Pallas cells) through
        the pipeline — bit-identical to allgather."""
        from raft_tpu.neighbors import ivf_pq
        from raft_tpu.parallel import (sharded_ivf_pq_build,
                                       sharded_ivf_pq_search)

        mesh = _mesh(8)
        db = rng.normal(size=(2048, 32)).astype(np.float32)
        q = rng.normal(size=(16, 32)).astype(np.float32)
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=16,
                                    kmeans_n_iters=4)
        sharded = sharded_ivf_pq_build(mesh, params, db)
        sp = ivf_pq.SearchParams(n_probes=7, engine=tier)
        bd, bi = sharded_ivf_pq_search(mesh, sp, sharded, q, 10,
                                       merge_engine="allgather")
        d, i = sharded_ivf_pq_search(mesh, sp, sharded, q, 10,
                                     merge_engine="pipelined",
                                     pipeline_chunks=3)
        np.testing.assert_array_equal(np.asarray(bi), np.asarray(i))
        np.testing.assert_array_equal(np.asarray(bd), np.asarray(d))

    def test_degraded_live_mask_neutralizes_per_chunk(self, rng):
        """A dead shard under the pipeline: every chunk neutralizes, the
        result is exact over survivors and equals the unchunked degraded
        path (coverage included)."""
        from raft_tpu.neighbors import ivf_flat
        from raft_tpu.parallel import (sharded_ivf_flat_build,
                                       sharded_ivf_flat_search)

        mesh = _mesh(4)
        db = rng.normal(size=(1024, 16)).astype(np.float32)
        q = rng.normal(size=(12, 16)).astype(np.float32)
        params = ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=3)
        sharded = sharded_ivf_flat_build(mesh, params, db)
        sp = ivf_flat.SearchParams(n_probes=6, engine="scan")
        live = np.array([True, False, True, True])
        bd, bi, bcov = sharded_ivf_flat_search(
            mesh, sp, sharded, q, 10, merge_engine="allgather",
            live_mask=live)
        d, i, cov = sharded_ivf_flat_search(
            mesh, sp, sharded, q, 10, merge_engine="pipelined",
            pipeline_chunks=3, live_mask=live)
        np.testing.assert_array_equal(np.asarray(bi), np.asarray(i))
        np.testing.assert_array_equal(np.asarray(bd), np.asarray(d))
        np.testing.assert_allclose(np.asarray(bcov), np.asarray(cov))

    def test_tombstones_ride_the_pipeline(self, rng):
        """Deleted rows (the traced tomb operand) stay masked in every
        chunk — pipelined equals unchunked on the tombstoned index."""
        from raft_tpu.lifecycle import delete
        from raft_tpu.neighbors import ivf_flat
        from raft_tpu.parallel import (sharded_ivf_flat_build,
                                       sharded_ivf_flat_search)

        mesh = _mesh(4)
        db = rng.normal(size=(512, 16)).astype(np.float32)
        q = rng.normal(size=(8, 16)).astype(np.float32)
        params = ivf_flat.IndexParams(n_lists=8, kmeans_n_iters=3)
        sharded = sharded_ivf_flat_build(mesh, params, db)
        n = delete(sharded, np.arange(0, 512, 5), mesh=mesh)
        assert n > 0
        sp = ivf_flat.SearchParams(n_probes=8, engine="scan")
        bd, bi = sharded_ivf_flat_search(mesh, sp, sharded, q, 10,
                                         merge_engine="allgather")
        assert not np.intersect1d(np.asarray(bi),
                                  np.arange(0, 512, 5)).size
        d, i = sharded_ivf_flat_search(mesh, sp, sharded, q, 10,
                                       merge_engine="pipelined",
                                       pipeline_chunks=2)
        np.testing.assert_array_equal(np.asarray(bi), np.asarray(i))
        np.testing.assert_array_equal(np.asarray(bd), np.asarray(d))


class TestResolveAndBytes:
    def test_resolve_rules(self):
        assert resolve_merge_engine("ring", 1, 1, 8) == "ring"
        assert resolve_merge_engine("auto", 100, 10, 1) == "allgather"
        assert resolve_merge_engine("auto", 100, 10, 2) == "allgather"
        assert resolve_merge_engine("auto", 100, 10, 8) == "ring"
        # non-pow2: ring only at large merged volume
        assert resolve_merge_engine("auto", 4, 10, 6) == "allgather"
        assert resolve_merge_engine("auto", 4096, 128, 6) == "ring"
        # quantized exchange is opt-in, never auto
        for q, k, n in ((1, 1, 2), (10_000, 256, 64)):
            assert resolve_merge_engine("auto", q, k, n) != "ring_bf16"
        with pytest.raises(Exception):
            resolve_merge_engine("bogus", 1, 1, 2)

    def test_pipelined_resolution_rules(self):
        """auto picks pipelined only with a probe hint, n_probes >= 16,
        n_dev >= 4 AND a merged volume clearing the small-merge floor;
        never for plain merges; bf16 variants stay opt-in."""
        assert resolve_merge_engine("auto", 1024, 100, 8,
                                    n_probes=32) == "pipelined"
        assert resolve_merge_engine("auto", 1024, 100, 4,
                                    n_probes=16) == "pipelined"
        assert resolve_merge_engine("auto", 1024, 100, 8,
                                    n_probes=8) == "ring"
        assert resolve_merge_engine("auto", 1024, 100, 2,
                                    n_probes=64) == "allgather"
        # tiny latency-bound merges keep the one-shot engines even with
        # a chunkable producer (the _RING_MIN_WORK floor)
        assert resolve_merge_engine("auto", 1, 10, 8,
                                    n_probes=64) == "ring"
        assert resolve_merge_engine("auto", 1, 10, 6,
                                    n_probes=64) == "allgather"
        assert resolve_merge_engine("auto", 1024, 100, 8) == "ring"
        assert resolve_merge_engine("pipelined", 1, 1, 2) == "pipelined"
        for q, k, n in ((1, 1, 4), (10_000, 256, 64)):
            assert "bf16" not in resolve_merge_engine("auto", q, k, n,
                                                      n_probes=64)

    def test_pipeline_chunk_helpers(self):
        assert resolve_pipeline_chunks("ring", 32, 8) == 1
        assert resolve_pipeline_chunks("pipelined", 32, 1) == 1
        assert resolve_pipeline_chunks("pipelined", 32, 8) == 4
        assert resolve_pipeline_chunks("pipelined", 7, 8) == 1
        assert resolve_pipeline_chunks("pipelined", 7, 8, requested=3) == 3
        assert resolve_pipeline_chunks("pipelined", 2, 8,
                                       requested=16) == 2
        # bounds: contiguous, disjoint, cover [0, n), remainder leading
        for n_items, n_chunks in ((7, 3), (8, 4), (5, 8), (1, 1)):
            b = pipeline_chunk_bounds(n_items, n_chunks)
            assert b[0][0] == 0 and b[-1][1] == n_items
            assert all(b[i][1] == b[i + 1][0] for i in range(len(b) - 1))
            assert all(hi > lo for lo, hi in b)

    def test_pipelined_bytes_sum_per_chunk(self):
        """One logical pipelined merge = N chunk ring exchanges: the
        estimate sums the per-chunk volumes (more total bytes than one
        unchunked ring — the price of the overlap) and the dispatch
        recorder counts ONE dispatch, not N."""
        ring = merge_comm_bytes("ring", 32, 10, 40, 8)
        piped = merge_comm_bytes("pipelined", 32, 10, 40, 8,
                                 chunk_kks=[10, 10, 10, 10])
        assert piped == 4 * merge_comm_bytes("ring", 32, 10, 10, 8)
        assert piped >= ring
        # degenerate: no chunk info = one ring at full width
        assert merge_comm_bytes("pipelined", 32, 10, 40, 8) == ring
        assert merge_comm_bytes(
            "pipelined_bf16", 32, 10, 40, 8, chunk_kks=[10, 10]) \
            == 2 * merge_comm_bytes("ring_bf16", 32, 10, 10, 8)

        merge_dispatch_stats.reset()
        try:
            merge_dispatch_stats.record("pipelined", 32, 10, 40, 8,
                                        chunk_kks=[10, 10, 10, 10])
            snap = merge_dispatch_stats.snapshot()
            assert snap["pipelined"]["dispatches"] == 1
            assert snap["pipelined"]["est_bytes"] == piped
        finally:
            merge_dispatch_stats.reset()

    def test_ring_bytes_below_allgather(self):
        """The acceptance bar: ring moves fewer bytes at n_dev >= 4. The
        bf16 engine pays a 2k guard margin + the exact-re-rank reduction,
        so its crossover sits at n_dev >= 8."""
        for n_dev in (4, 8, 16):
            for q, k in ((32, 10), (1000, 100)):
                ag = merge_comm_bytes("allgather", q, k, k, n_dev)
                assert merge_comm_bytes("ring", q, k, k, n_dev) < ag, \
                    (n_dev, q, k)
                if n_dev >= 8:
                    assert merge_comm_bytes("ring_bf16", q, k, k,
                                            n_dev) < ag, (n_dev, q, k)
        assert merge_comm_bytes("ring", 32, 10, 10, 1) == 0


class TestShardedConsumers:
    """The rewired sharded search paths give identical results per engine."""

    @pytest.mark.parametrize("engine", ["ring", "ring_bf16"])
    def test_sharded_knn_engines_agree(self, rng, engine):
        from raft_tpu.parallel import sharded_knn

        mesh = _mesh(8)
        db = rng.normal(size=(1024, 16)).astype(np.float32)
        q = rng.normal(size=(32, 16)).astype(np.float32)
        bd, bi = sharded_knn(mesh, db, q, k=10, merge_engine="allgather")
        d, i = sharded_knn(mesh, db, q, k=10, merge_engine=engine)
        np.testing.assert_array_equal(np.asarray(bi), np.asarray(i))
        np.testing.assert_allclose(np.asarray(bd), np.asarray(d),
                                   rtol=0, atol=0)

    @pytest.mark.parametrize("engine", ["ring", "ring_bf16"])
    def test_sharded_ivf_flat_engines_agree(self, rng, engine):
        from raft_tpu.neighbors import ivf_flat
        from raft_tpu.parallel import (sharded_ivf_flat_build,
                                       sharded_ivf_flat_search)

        mesh = _mesh(8)
        db = rng.normal(size=(2048, 16)).astype(np.float32)
        q = rng.normal(size=(24, 16)).astype(np.float32)
        params = ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=4)
        sharded = sharded_ivf_flat_build(mesh, params, db)
        sp = ivf_flat.SearchParams(n_probes=8, engine="scan")
        bd, bi = sharded_ivf_flat_search(mesh, sp, sharded, q, 10,
                                         merge_engine="allgather")
        d, i = sharded_ivf_flat_search(mesh, sp, sharded, q, 10,
                                       merge_engine=engine)
        np.testing.assert_array_equal(np.asarray(bi), np.asarray(i))
        np.testing.assert_allclose(np.asarray(bd), np.asarray(d), atol=1e-6)

    def test_sharded_ivf_pq_ring_agrees(self, rng):
        from raft_tpu.neighbors import ivf_pq
        from raft_tpu.parallel import (sharded_ivf_pq_build,
                                       sharded_ivf_pq_search)

        mesh = _mesh(8)
        db = rng.normal(size=(2048, 32)).astype(np.float32)
        q = rng.normal(size=(16, 32)).astype(np.float32)
        params = ivf_pq.IndexParams(n_lists=16, pq_dim=16, kmeans_n_iters=4)
        sharded = sharded_ivf_pq_build(mesh, params, db)
        sp = ivf_pq.SearchParams(n_probes=8, engine="scan")
        bd, bi = sharded_ivf_pq_search(mesh, sp, sharded, q, 10,
                                       merge_engine="allgather")
        d, i = sharded_ivf_pq_search(mesh, sp, sharded, q, 10,
                                     merge_engine="ring")
        np.testing.assert_array_equal(np.asarray(bi), np.asarray(i))
        np.testing.assert_allclose(np.asarray(bd), np.asarray(d), atol=1e-6)

    def test_sharded_balanced_fit_ring_quality(self, rng):
        """The reseed candidate merge through the collective keeps the
        fit quality of the allgather-era path."""
        from raft_tpu.parallel import sharded_kmeans_balanced_fit

        mesh = _mesh(8)
        X = rng.normal(size=(2048, 16)).astype(np.float32)
        X[:1024] += 5.0
        c_ring = sharded_kmeans_balanced_fit(mesh, X, 32, n_iters=8,
                                             merge_engine="ring")
        c_ag = sharded_kmeans_balanced_fit(mesh, X, 32, n_iters=8,
                                           merge_engine="allgather")

        def cost(c):
            d = ((X[:, None, :] - np.asarray(c)[None]) ** 2).sum(-1)
            return d.min(1).mean()

        assert cost(c_ring) <= cost(c_ag) * 1.05


def test_merge_parts_matches_concat_select(rng):
    """The single-host pairwise-merge core reproduces concat+select_k
    bit-for-bit (position tie order), odd part counts included."""
    from raft_tpu.matrix.select_k import select_k

    for n_parts in (1, 2, 3, 5):
        keys = rng.random(size=(n_parts, 9, 4)).astype(np.float32)
        vals = np.tile(np.arange(4, dtype=np.int32), (n_parts, 9, 1))
        trans = list(range(0, 100 * n_parts, 100))
        mk, mv = merge_parts(jnp.asarray(keys), jnp.asarray(vals),
                             translations=trans)
        flat_k = keys.transpose(1, 0, 2).reshape(9, -1)
        flat_v = (np.array(trans)[:, None] + np.arange(4)) \
            .reshape(-1)[None].repeat(9, 0)
        ok, pos = select_k(jnp.asarray(flat_k), 4)
        np.testing.assert_allclose(np.asarray(mk), np.asarray(ok))
        np.testing.assert_array_equal(
            np.asarray(mv), np.take_along_axis(flat_v, np.asarray(pos), 1))


def test_merge_parts_unsigned_keys_select_max():
    """Unsigned keys under select_min=False: negation wraps, so the key
    mapping must go through iinfo.max - v (the select_k rule). Key 0 must
    rank LAST, not first."""
    keys = jnp.asarray(np.array([[[0, 5, 3]], [[7, 2, 0]]], np.uint32))
    vals = jnp.asarray(np.array([[[10, 11, 12]], [[20, 21, 22]]], np.int32))
    mk, mv = merge_parts(keys, vals, select_min=False)
    np.testing.assert_array_equal(np.asarray(mk), [[7, 5, 3]])
    np.testing.assert_array_equal(np.asarray(mv), [[20, 11, 12]])


def test_comms_axis_size_inside_shard_map():
    """Comms.get_size() without a bound mesh resolves the axis size as
    ``lax.axis_size`` of the bound axis."""
    from raft_tpu.comms import Comms

    mesh = _mesh(4)
    comms = Comms(axis="data")
    fn = jax.shard_map(lambda x: x[0] * comms.get_size(), mesh=mesh,
                       in_specs=(P("data"),), out_specs=P(None),
                       check_vma=False)
    out = jax.jit(fn)(jnp.ones((4, 2), jnp.int32))
    np.testing.assert_array_equal(np.asarray(out), np.full((2,), 4))


def test_bench_sharded_family_smoke(capsys):
    """Tier-1 multi-device smoke of the bench merge-engine family: one
    tiny run must emit one JSON row per engine with qps + estimated
    exchanged bytes, ring < allgather (ISSUE 1 bench/CI satellite)."""
    import json

    import bench as bench_pkg  # noqa: F401  (package import side effects)
    from bench import sharded as bench_sharded

    bench_sharded.run(quick=True)
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.strip()]
    by_engine = {r["engine"]: r for r in rows if "engine" in r
                 and r["metric"] != "sharded_pipeline_ms"}
    assert {"allgather", "ring", "ring_bf16"} <= set(by_engine)
    for r in by_engine.values():
        assert r["value"] > 0
        assert r["est_exchange_bytes"] >= 0
    n_dev = by_engine["ring"]["mesh_devices"]
    if n_dev >= 4:
        assert (by_engine["ring"]["est_exchange_bytes"]
                < by_engine["allgather"]["est_exchange_bytes"])
    # pipeline family (ISSUE 14): compute + per-engine total and
    # exposed-comm rows, all engines incl. the pipelined pair.
    pipe = [r for r in rows if r["metric"] == "sharded_pipeline_ms"]
    phases = {(r["engine"], r["phase"]) for r in pipe}
    assert ("local_scan", "compute") in phases
    for eng in ("allgather", "ring", "ring_bf16", "pipelined",
                "pipelined_bf16"):
        assert (eng, "total") in phases and (eng, "exposed_comm") in phases
    assert all(r["value"] >= 0 for r in pipe)
    piped = [r for r in pipe if r["engine"] == "pipelined"
             and r["phase"] == "total"]
    if n_dev >= 4:
        assert piped[0]["pipeline_chunks"] >= 2


class TestKnnMergePartsEdgeCases:
    """knn_merge_parts edge inputs (ISSUE 5 satellite): single part,
    parts with fewer real candidates than k (sentinel-padded), and a
    fully dead (all-sentinel) part — the exact shapes the degraded
    serving path feeds the merge."""

    def test_single_part_sorts_and_translates(self, rng):
        from raft_tpu.neighbors.brute_force import knn_merge_parts

        keys = rng.random(size=(1, 5, 4)).astype(np.float32)
        vals = np.tile(np.arange(4, dtype=np.int32), (1, 5, 1))
        mk, mv = knn_merge_parts(jnp.asarray(keys), jnp.asarray(vals),
                                 translations=[100])
        order = np.argsort(keys[0], axis=1)
        np.testing.assert_allclose(np.asarray(mk),
                                   np.take_along_axis(keys[0], order, 1))
        np.testing.assert_array_equal(
            np.asarray(mv), np.take_along_axis(vals[0] + 100, order, 1))

    def test_k_exceeds_real_candidates_per_part(self, rng):
        """Parts padded to k slots with the +inf/-1 sentinels (the knn()
        small-part convention): every real candidate from every part
        must outrank every sentinel, and only the overflow tail may be
        sentinel."""
        from raft_tpu.neighbors.brute_force import knn_merge_parts

        k = 6
        n_parts, q, real = 2, 3, 2          # 4 real candidates < k = 6
        keys = np.full((n_parts, q, k), np.inf, np.float32)
        vals = np.full((n_parts, q, k), -1, np.int32)
        keys[:, :, :real] = rng.random(
            size=(n_parts, q, real)).astype(np.float32)
        vals[:, :, :real] = np.arange(real, dtype=np.int32)
        mk, mv = knn_merge_parts(jnp.asarray(keys), jnp.asarray(vals),
                                 translations=[0, 10])
        mk, mv = np.asarray(mk), np.asarray(mv)
        total_real = n_parts * real
        assert np.isfinite(mk[:, :total_real]).all()
        assert (mv[:, :total_real] >= 0).all()
        # The overflow tail is exactly the sentinel pair.
        assert np.isinf(mk[:, total_real:]).all()
        assert (mv[:, total_real:] == -1).all()
        # And the real prefix is the sorted union of the parts' reals.
        want = np.sort(keys[:, :, :real].transpose(1, 0, 2).reshape(q, -1),
                       axis=1)
        np.testing.assert_allclose(mk[:, :total_real], want)

    @pytest.mark.parametrize("select_min", [True, False])
    def test_all_sentinel_dead_part_is_neutral(self, rng, select_min):
        """A fully dead part (all ±inf/-1 — what neutralize_dead emits
        for a dead shard) must not perturb the merge: result equals the
        merge of the surviving parts alone."""
        from raft_tpu.neighbors.brute_force import knn_merge_parts

        worst = np.inf if select_min else -np.inf
        live = rng.random(size=(2, 4, 3)).astype(np.float32)
        vals = np.tile(np.arange(3, dtype=np.int32), (2, 4, 1))
        dead_k = np.full((1, 4, 3), worst, np.float32)
        dead_v = np.full((1, 4, 3), -1, np.int32)
        keys3 = np.concatenate([live[:1], dead_k, live[1:]], axis=0)
        vals3 = np.concatenate([vals[:1], dead_v, vals[1:]], axis=0)
        mk3, mv3 = knn_merge_parts(jnp.asarray(keys3), jnp.asarray(vals3),
                                   select_min=select_min,
                                   translations=[0, 100, 200])
        mk2, mv2 = knn_merge_parts(jnp.asarray(live), jnp.asarray(vals),
                                   select_min=select_min,
                                   translations=[0, 200])
        np.testing.assert_array_equal(np.asarray(mk3), np.asarray(mk2))
        np.testing.assert_array_equal(np.asarray(mv3), np.asarray(mv2))

    def test_all_parts_dead_returns_sentinels(self):
        from raft_tpu.neighbors.brute_force import knn_merge_parts

        keys = np.full((3, 2, 4), np.inf, np.float32)
        vals = np.full((3, 2, 4), -1, np.int32)
        mk, mv = knn_merge_parts(jnp.asarray(keys), jnp.asarray(vals))
        assert np.isinf(np.asarray(mk)).all()
        assert (np.asarray(mv) == -1).all()
