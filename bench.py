#!/usr/bin/env python
"""Round benchmark: one JSON line per tracked metric, headline LAST.

Each line is {"metric", "value", "unit", "vs_baseline", ...}; the lines
cover the tracked families (distance, select_k, fused_l2_nn, IVF search
at 100K and 1M, 1M build, balanced k-means, sparse, the serving and
sharded families) — the gbench-family role of cpp/bench/*.

Every timed region ends in ``jax.block_until_ready``; scan metrics are
the median of >=5 repeats with their spread (bench/common.py), and
compute-bound metrics carry an achieved-FLOP/s + MFU column against the
device's bf16 peak from the table in bench/common.py (f32 paths run the
MXU in multi-pass mode and are expected to sit well below it). Engines
and capacities are pinned so the numbers measure the chip, not dispatch
heuristics. ``vs_baseline`` is 1.0: there is no measured baseline on the
current tree yet.

Every family runs in this one process. A family that fails prints an
error row, the others still run, and the process then exits non-zero.
"""

import json
import sys
import time

import numpy as np

def _emit(metric, value, unit, **extra):
    rec = {"metric": metric, "value": round(float(value), 1),
           "unit": unit, "vs_baseline": 1.0}
    for k, v in extra.items():
        rec[k] = round(float(v), 4) if isinstance(v, float) else v
    print(json.dumps(rec), flush=True)


def _spread(st):
    return round((st["max_s"] - st["min_s"]) / max(st["median_s"], 1e-12)
                 * 100, 1)


def _eager_qps(fn, q, reps=16, rounds=7):
    """Pipelined eager dispatch, one ``block_until_ready`` per round —
    the shared timing protocol of the 1M/4M/SIFT families (eager search
    calls, as a server makes them). QPS is per row of ``q``.

    Outlier-robust: ≥7 rounds, rounds beyond 5 MADs from the median are
    rejected (the reference's bench flushes L2 + times with events for
    the same reason, cpp/bench/common/benchmark.hpp:93-148), and the
    reported spread is that of the surviving rounds."""
    import jax

    jax.block_until_ready(fn(q))
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(q)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / reps)
    t = np.sort(np.asarray(times))
    med = float(np.median(t))
    mad = float(np.median(np.abs(t - med)))
    keep = t[np.abs(t - med) <= max(5.0 * mad, 0.02 * med)]
    med = float(np.median(keep))
    return q.shape[0] / med, (keep[-1] - keep[0]) / med * 100


def _family():
    import jax
    import jax.numpy as jnp

    from bench.common import device_peaks, scan_stats, wall_stats
    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.cluster.kmeans_types import KMeansBalancedParams
    from raft_tpu.distance.fused_l2_nn import fused_l2_nn_min_reduce
    from raft_tpu.distance.pairwise import distance as pairwise
    from raft_tpu.distance.distance_types import DistanceType
    from raft_tpu.matrix.select_k import select_k
    from raft_tpu.neighbors import ivf_flat, ivf_pq
    from raft_tpu.random.make_blobs import make_blobs

    rng = np.random.default_rng(0)
    peak = device_peaks()["bf16_flops"]

    # -- pairwise cosine: round-1 shape (2048^2 x 128), a compute-bound
    # shape (8192^2 x 256), and the same at bf16 MXU precision (the knob
    # users flip when ~1e-3 relative error is acceptable) — the MFU
    # evidence VERDICT r2 weak #2 asked for.
    for (m, d, prec, name) in (
            (2048, 128, "highest", "pairwise_cosine_2048_gpairs"),
            (8192, 256, "highest", "pairwise_cosine_8192x256_gpairs"),
            (8192, 256, "default", "pairwise_cosine_8192x256_bf16_gpairs")):
        a = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
        st = scan_stats(
            lambda x, y, p=prec: pairwise(
                x, y, metric=DistanceType.CosineExpanded, precision=p),
            a, (b,))
        s = st["median_s"]
        v = m * m / s / 1e9
        flops = 2.0 * m * m * d / s
        _emit(name, v, "Gpairs/s", spread_pct=_spread(st),
              flops_t=flops / 1e12, mfu_pct=round(flops / peak * 100, 2))

    # -- select_k: round-1 small shape + the large-len stream-engine shape.
    m = jnp.asarray(rng.normal(size=(1000, 10000)).astype(np.float32))
    st = scan_stats(lambda x: select_k(x, 10), m)
    v = 1000 / st["median_s"]
    _emit("select_k_b1000_l10000_krows", v, "rows/s",
          spread_pct=_spread(st))

    m = jnp.asarray(rng.normal(size=(64, 131072)).astype(np.float32))
    st = scan_stats(lambda x: select_k(x, 128), m)
    v = 64 / st["median_s"]
    _emit("select_k_b64_l131072_k128_krows", v, "rows/s",
          spread_pct=_spread(st))

    # -- fused_l2_nn (the k-means inner loop)
    x = jnp.asarray(rng.normal(size=(8192, 64)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(1024, 64)).astype(np.float32))
    st = scan_stats(lambda q: fused_l2_nn_min_reduce(q, y), x)
    s = st["median_s"]
    v = 8192 / s
    flops = 2.0 * 8192 * 1024 * 64 / s
    _emit("fused_l2_nn_8192x1024_rows", v, "rows/s",
          spread_pct=_spread(st), flops_t=flops / 1e12,
          mfu_pct=round(flops / peak * 100, 2))

    # -- IVF search QPS at 100K x 128, pinned tuned engine, measured as a
    # jitted scan over perturbed query batches (searches are traceable
    # with an explicit bucket_cap), so the number excludes dispatch. The
    # index tensors ride as scan_stats ``extra`` arguments — a closure
    # would bake them into the program as constants (tens of MB of HLO).
    X, _ = make_blobs(100_000, 128, n_clusters=200, seed=3)
    Q = X[:1000]
    fidx = ivf_flat.build(ivf_flat.IndexParams(n_lists=256), X)
    spf = ivf_flat.SearchParams(n_probes=32, engine="bucketed",
                                bucket_cap=128)

    def flat_search(q, centers, data, indices, sizes):
        idx = ivf_flat.Index(metric=fidx.metric, centers=centers,
                             data=data, indices=indices, list_sizes=sizes)
        return ivf_flat.search(spf, idx, q, 10)

    st = scan_stats(flat_search, Q,
                    (fidx.centers, fidx.data, fidx.indices,
                     fidx.list_sizes))
    v = 1000 / st["median_s"]
    _emit("ivf_flat_search_100k_qps", v, "qps", spread_pct=_spread(st))

    pidx = ivf_pq.build(ivf_pq.IndexParams(n_lists=256), X)
    recon = pidx.reconstructed()  # decode once, outside the scan
    spq = ivf_pq.SearchParams(n_probes=32, engine="bucketed", bucket_cap=128)

    def pq_search(q, centers, rot, books, codes, indices, sizes, rec):
        idx = ivf_pq.Index(metric=pidx.metric,
                           codebook_kind=pidx.codebook_kind,
                           centers=centers, rotation_matrix=rot,
                           pq_centers=books, pq_codes=codes,
                           indices=indices, list_sizes=sizes,
                           pq_bits=pidx.pq_bits, pq_dim=pidx.pq_dim,
                           _recon=rec)
        return ivf_pq.search(spq, idx, q, 10)

    st = scan_stats(pq_search, Q,
                    (pidx.centers, pidx.rotation_matrix, pidx.pq_centers,
                     pidx.pq_codes, pidx.indices, pidx.list_sizes, recon))
    v = 1000 / st["median_s"]
    _emit("ivf_pq_search_100k_qps", v, "qps", spread_pct=_spread(st))
    del fidx, pidx, X, Q, recon

    # -- fused_l2_nn acceptance shape (VERDICT r4 item 3: >=15% MFU at
    # 8192x4096x128-class shapes, spread <=15%) — the Pallas kernel path.
    x = jnp.asarray(rng.normal(size=(8192, 128)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(4096, 128)).astype(np.float32))
    st = scan_stats(lambda q: fused_l2_nn_min_reduce(q, y), x)
    s = st["median_s"]
    flops = 2.0 * 8192 * 4096 * 128 / s
    _emit("fused_l2_nn_8192x4096x128_rows", 8192 / s, "rows/s",
          spread_pct=_spread(st), flops_t=flops / 1e12,
          mfu_pct=round(flops / peak * 100, 2))

    # -- balanced k-means fit: wall time of whole fits
    Xk, _ = make_blobs(100_000, 64, n_clusters=100, seed=7)
    p = KMeansBalancedParams(n_iters=10)
    for _ in range(2):                          # compile + steady-state
        c = kmeans_balanced.fit(p, Xk, 512)     # warm (the first timed
        jax.block_until_ready(c)                # fit after compile still
    fits = []                                   # carries a ~2x outlier)
    for _ in range(5):
        t0 = time.perf_counter()
        c = kmeans_balanced.fit(p, Xk, 512)
        jax.block_until_ready(c)
        fits.append(time.perf_counter() - t0)
    fits.sort()
    med = float(np.median(fits))
    _emit("kmeans_balanced_fit_100k_s", med, "s",
          spread_pct=round((fits[-1] - fits[0]) / med * 100, 1))
    del Xk

    # -- sparse pairwise L2 at 50K dims (block-staged engine)
    from raft_tpu.sparse import distance as sparse_distance
    from raft_tpu.sparse.types import CSR

    d_sp, nnz_row, rows = 50_000, 50, 2048
    cols = rng.integers(0, d_sp, size=rows * nnz_row).astype(np.int32)
    valsv = rng.normal(size=rows * nnz_row).astype(np.float32)
    indptr = np.arange(0, rows * nnz_row + 1, nnz_row, dtype=np.int32)
    ca = CSR(jnp.asarray(indptr), jnp.asarray(cols), jnp.asarray(valsv),
             (rows, d_sp))
    st = wall_stats(lambda: sparse_distance.pairwise_distance(
        ca, ca, metric="euclidean"))
    _emit("sparse_l2_2048x50kd_s", st["median_s"], "s",
          spread_pct=_spread(st))
    del ca


def _recall(found, truth):
    k = truth.shape[1]
    return float(np.mean([len(np.intersect1d(found[r], truth[r])) / k
                          for r in range(truth.shape[0])]))


def _family_1m():
    """1M-scale build + QPS-at-recall. Clustered queries are the
    recall=1.0 regime; uniform queries the structureless worst case."""
    import jax
    import jax.numpy as jnp

    from bench.common import block, scan_stats
    from raft_tpu.neighbors import brute_force, ivf_flat, ivf_pq
    from raft_tpu.random.make_blobs import make_blobs

    rng = np.random.default_rng(11)
    X, _ = make_blobs(1_000_000, 128, n_clusters=1000, seed=5,
                      cluster_std=5.0)
    jax.block_until_ready(X)

    # Build wall time: median of 3 timed builds after the compile warm
    # (the first call includes any residual compiles; reported alongside).
    t0 = time.perf_counter()
    fidx = ivf_flat.build(ivf_flat.IndexParams(n_lists=1024), X)
    jax.block_until_ready(fidx.data)
    warm = time.perf_counter() - t0
    builds = []
    for _ in range(3):
        fidx = None  # free the previous index before rebuilding — two
        # live 1M indexes force HBM defrag stalls (observed 40x outliers)
        t0 = time.perf_counter()
        fidx = ivf_flat.build(ivf_flat.IndexParams(n_lists=1024), X)
        jax.block_until_ready(fidx.data)
        builds.append(time.perf_counter() - t0)
    builds.sort()
    _emit("ivf_build_1m_s", float(np.median(builds)), "s",
          first_call_s=round(warm, 1),
          spread_pct=round((builds[-1] - builds[0])
                           / max(np.median(builds), 1e-9) * 100, 1))

    # Query regimes: clustered (db point + sigma=1 noise) and uniform.
    qc = jnp.asarray(np.asarray(X[:1000])
                     + rng.normal(size=(1000, 128)).astype(np.float32))
    qu = jnp.asarray(rng.normal(size=(1000, 128)).astype(np.float32) * 10)
    truth = {}
    for name, q in (("clustered", qc), ("uniform", qu)):
        _, ti = brute_force.knn(X, q, 10)
        truth[name] = np.asarray(ti)

    # Index tensors ride as scan arguments (a closure would bake ~0.5 GB
    # of constants into the compiled program; see _family).
    # bucket_cap=0 resolves to the round-4 packed-cells tier.
    sp = ivf_flat.SearchParams(n_probes=32, engine="bucketed")

    def flat_search(q, centers, data, indices, sizes):
        idx = ivf_flat.Index(metric=fidx.metric, centers=centers,
                             data=data, indices=indices, list_sizes=sizes)
        return ivf_flat.search(sp, idx, q, 10)

    for qname, q in (("clustered", qc), ("uniform", qu)):
        d, i = ivf_flat.search(sp, fidx, q, 10)
        rec = _recall(np.asarray(i), truth[qname])
        st = scan_stats(flat_search, q,
                        (fidx.centers, fidx.data, fidx.indices,
                         fidx.list_sizes), iters=64, repeats=3)
        _emit(f"ivf_flat_1m_qps_{qname}", 1000 / st["median_s"], "qps",
              recall_at_10=round(rec, 3), n_probes=32,
              spread_pct=_spread(st))

    # Sharded sanity at 1M (VERDICT r5 item 1 "done" bar): the same index
    # on a 1-device mesh must track single-chip QPS — the sharded body
    # now runs the production cells engine + the merge collective.
    from jax.sharding import Mesh

    from raft_tpu.parallel import ShardedIvfFlat, sharded_ivf_flat_search
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("data",))
    shidx = ShardedIvfFlat(metric=fidx.metric, centers=fidx.centers,
                           data=fidx.data[None], indices=fidx.indices[None],
                           list_sizes=fidx.list_sizes[None])
    d, i = sharded_ivf_flat_search(mesh1, sp, shidx, qc, 10)
    rec = _recall(np.asarray(i), truth["clustered"])
    qps, spread = _eager_qps(
        lambda qq: sharded_ivf_flat_search(mesh1, sp, shidx, qq, 10), qc)
    _emit("ivf_flat_1m_qps_sharded1", qps, "qps",
          recall_at_10=round(rec, 3), n_probes=32, mesh_devices=1,
          spread_pct=round(spread, 1))
    del fidx, shidx

    pidx = ivf_pq.build(ivf_pq.IndexParams(n_lists=1024), X)
    pidx.compressed_scan_operands()  # cache once, outside the timed loops

    # Tracked PQ metrics measure the round-4 compressed-domain tier
    # (memory = packed codes + scan operands — ivf_pq_search.cuh:611
    # parity); the recon tier (decompressed bf16 cache) is tracked
    # separately below. The clustered row and the uniform _native row
    # are the unrefined engine; the headline uniform row requests the
    # 0.86 recall class and the engine refines internally (min_recall —
    # no caller-side "refined" spelling; VERDICT r4 item 2 / r5 item 2).
    spq = ivf_pq.SearchParams(n_probes=32, engine="bucketed",
                              bucket_cap=256)
    for qname, q in (("clustered", qc), ("uniform_native", qu)):
        d, i = ivf_pq.search(spq, pidx, q, 10)
        rec = _recall(np.asarray(i), truth[qname.split("_")[0]])
        qps, spread = _eager_qps(
            lambda qq: ivf_pq.search(spq, pidx, qq, 10), q)
        _emit(f"ivf_pq_1m_qps_{qname}", qps, "qps",
              recall_at_10=round(rec, 3), n_probes=32, engine="compressed",
              spread_pct=round(spread, 1))

    # int8 LUT flag (ISSUE 14): quantized codeword tables on the same
    # compressed tier — the recall trade recorded next to the f32 rows.
    sp8 = ivf_pq.SearchParams(n_probes=32, engine="bucketed",
                              bucket_cap=256, compressed_lut_int8=True)
    pidx.compressed_scan_operands(int8_lut=True)  # cache outside loops
    d, i = ivf_pq.search(sp8, pidx, qc, 10)
    rec = _recall(np.asarray(i), truth["clustered"])
    qps, spread = _eager_qps(
        lambda qq: ivf_pq.search(sp8, pidx, qq, 10), qc)
    _emit("ivf_pq_1m_qps_clustered_int8lut", qps, "qps",
          recall_at_10=round(rec, 3), n_probes=32,
          engine="compressed+int8lut", spread_pct=round(spread, 1))

    spr = ivf_pq.SearchParams(n_probes=32, engine="bucketed",
                              bucket_cap=256, min_recall=0.86)
    d, i = ivf_pq.search(spr, pidx, qu, 10)
    rec = _recall(np.asarray(i), truth["uniform"])
    qps, spread = _eager_qps(
        lambda qq: ivf_pq.search(spr, pidx, qq, 10), qu)
    _emit("ivf_pq_1m_qps_uniform", qps, "qps",
          recall_at_10=round(rec, 3), min_recall=0.86,
          engine="compressed+refine", spread_pct=round(spread, 1))

    # Sharded sanity for PQ (compressed tier per shard + merge).
    from raft_tpu.parallel import ShardedIvfPq, sharded_ivf_pq_search
    shp = ShardedIvfPq(
        metric=pidx.metric, codebook_kind=pidx.codebook_kind,
        centers=pidx.centers, rotation_matrix=pidx.rotation_matrix,
        pq_centers=pidx.pq_centers, pq_codes=pidx.pq_codes[None],
        indices=pidx.indices[None], list_sizes=pidx.list_sizes[None],
        pq_bits=pidx.pq_bits, pq_dim=pidx.pq_dim)
    d, i = sharded_ivf_pq_search(mesh1, spq, shp, qc, 10)
    rec = _recall(np.asarray(i), truth["clustered"])
    qps, spread = _eager_qps(
        lambda qq: sharded_ivf_pq_search(mesh1, spq, shp, qq, 10), qc)
    _emit("ivf_pq_1m_qps_sharded1", qps, "qps",
          recall_at_10=round(rec, 3), n_probes=32, mesh_devices=1,
          spread_pct=round(spread, 1))
    del X, shp

    # Recon tier (decompressed bf16 cache — the r3 default), kept tracked.
    block(pidx.reconstructed())
    d, i = ivf_pq.search(spq, pidx, qc, 10)
    rec = _recall(np.asarray(i), truth["clustered"])
    qps, spread = _eager_qps(
        lambda qq: ivf_pq.search(spq, pidx, qq, 10), qc)
    _emit("ivf_pq_1m_qps_clustered_recon", qps, "qps",
          recall_at_10=round(rec, 3), n_probes=32, engine="recon",
          spread_pct=round(spread, 1))
    del pidx


def _family_4m():
    """Beyond the old recon-cache budget: 4M×128 (decompressed bf16 form
    ≈ 4.3 GB > the r3 4 GB auto budget) through the compressed-domain
    tier — the regime that previously had no fast path (254 QPS on-the-
    fly decode; VERDICT r4 item 1 asks for a >4GB-index config in the
    tracked bench). Memory stays packed codes + scan operands."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.neighbors import brute_force, ivf_pq
    from raft_tpu.random import make_blobs

    rng = np.random.default_rng(5)
    X, _ = make_blobs(4_000_000, 128, n_clusters=2000, cluster_std=5.0,
                      seed=11)
    X = jnp.asarray(X)
    jax.block_until_ready(X)
    q = jnp.asarray(np.asarray(X[:1000])
                    + rng.normal(size=(1000, 128)).astype(np.float32))
    _, ti = brute_force.knn(X, q, 10)
    truth = np.asarray(ti)

    t0 = time.perf_counter()
    pidx = ivf_pq.build(ivf_pq.IndexParams(n_lists=2048), X)
    jax.block_until_ready(pidx.pq_codes)
    build_s = time.perf_counter() - t0
    del X
    pidx.compressed_scan_operands()
    spq = ivf_pq.SearchParams(n_probes=32, engine="bucketed")
    d, i = ivf_pq.search(spq, pidx, q, 10)
    rec = _recall(np.asarray(i), truth)
    qps, spread = _eager_qps(
        lambda qq: ivf_pq.search(spq, pidx, qq, 10), q, reps=8)
    _emit("ivf_pq_4m_qps_clustered", qps, "qps",
          recall_at_10=round(rec, 3), n_probes=32, engine="compressed",
          build_s=round(build_s, 1), spread_pct=round(spread, 1))


def _family_sift1m_u8():
    """SIFT-format u8 end-to-end: a 1M×128 uint8 dataset flows through the
    native bvecs writer/reader (native/host_runtime.cpp — the reference's
    SIFT-shaped bench culture, cpp/bench/neighbors/knn.cuh params), builds
    u8-storage IVF-Flat and IVF-PQ indexes, and reports search QPS +
    recall@10 (VERDICT r4 item 5: every prior 1M number was synthetic
    make_blobs f32)."""
    import os
    import tempfile

    import jax
    import jax.numpy as jnp

    from raft_tpu import _native
    from raft_tpu.neighbors import brute_force, ivf_flat, ivf_pq

    n, d, n_q = 1_000_000, 128, 1_000
    path = os.path.join(tempfile.gettempdir(), "raft_tpu_sift1m.bvecs")
    qpath = os.path.join(tempfile.gettempdir(), "raft_tpu_sift1m_q.bvecs")
    if not (os.path.exists(path) and os.path.exists(qpath)):
        # SIFT-like u8: clustered non-negative descriptors (host-side —
        # regenerating device-side would dodge the IO path under test).
        rng = np.random.default_rng(11)
        centers = rng.uniform(20.0, 200.0, size=(1000, d))
        assign = rng.integers(0, 1000, size=n)
        db_h = np.clip(centers[assign]
                       + rng.normal(scale=18.0, size=(n, d)),
                       0, 255).astype(np.uint8)
        qsel = rng.integers(0, n, size=n_q)
        q_h = np.clip(db_h[qsel].astype(np.float64)
                      + rng.normal(scale=6.0, size=(n_q, d)),
                      0, 255).astype(np.uint8)
        _native.write_bvecs(path, db_h)
        _native.write_bvecs(qpath, q_h)
        del db_h, q_h
    db_u8 = _native.read_bvecs(path)
    q_u8 = _native.read_bvecs(qpath)
    assert db_u8.shape == (n, d) and q_u8.shape == (n_q, d)

    X = jax.device_put(db_u8)
    Q = jax.device_put(q_u8.astype(np.float32))
    _, ti = brute_force.knn(X.astype(jnp.float32), Q, 10)
    truth = np.asarray(ti)

    fidx = ivf_flat.build(ivf_flat.IndexParams(n_lists=1024), X)
    assert fidx.data.dtype == np.uint8          # quantized at rest
    spf = ivf_flat.SearchParams(n_probes=32, engine="bucketed")
    _, i = ivf_flat.search(spf, fidx, Q, 10)
    rec = _recall(np.asarray(i), truth)
    qps, spread = _eager_qps(
        lambda q: ivf_flat.search(spf, fidx, q, 10), Q, reps=12)
    _emit("ivf_flat_sift1m_u8_qps", qps, "qps",
          recall_at_10=round(rec, 3), n_probes=32,
          spread_pct=round(spread, 1))
    del fidx

    pidx = ivf_pq.build(ivf_pq.IndexParams(n_lists=1024), X)
    spq = ivf_pq.SearchParams(n_probes=32, engine="bucketed",
                              bucket_cap=256)
    _, i = ivf_pq.search(spq, pidx, Q, 10)
    rec = _recall(np.asarray(i), truth)
    qps, spread = _eager_qps(
        lambda q: ivf_pq.search(spq, pidx, q, 10), Q, reps=12)
    _emit("ivf_pq_sift1m_u8_qps", qps, "qps",
          recall_at_10=round(rec, 3), n_probes=32,
          spread_pct=round(spread, 1))

    # The real-format dataset through the refine recipe (VERDICT r5
    # item 5b). SIFT-shaped clustered data concentrates the true pool
    # in the query's own list, so the robust recall class (> 0.9:
    # unbounded pool-deep queue) is the one that demonstrates the
    # recipe here — the fast bounded class is a structureless-regime
    # recipe (see ivf_pq._compressed_search).
    spr = ivf_pq.SearchParams(n_probes=32, engine="bucketed",
                              bucket_cap=256, min_recall=0.95)
    _, i = ivf_pq.search(spr, pidx, Q, 10)
    rec = _recall(np.asarray(i), truth)
    qps, spread = _eager_qps(
        lambda q: ivf_pq.search(spr, pidx, q, 10), Q, reps=12)
    _emit("ivf_pq_sift1m_u8_qps_refined", qps, "qps",
          recall_at_10=round(rec, 3), min_recall=0.95,
          engine="compressed+refine", spread_pct=round(spread, 1))
    del pidx


def _family_10m():
    """10M×128 compressed-domain config (VERDICT r5 item 8): packed codes
    ≈ 640 MB; the decompressed-bf16 form (~2.6 GB + a 2× f32 transient)
    is past what the recon tier could hold alongside the dataset — this
    row proves the no-decompression memory story at a scale the recon
    tier could never touch (the reference's answer is managed-memory
    spill, detail/ivf_pq_build.cuh:1108-1124; ours is native capacity).
    Built with retain_dataset=False so the index holds packed codes +
    scan operands only."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.neighbors import brute_force, ivf_pq
    from raft_tpu.random import make_blobs

    rng = np.random.default_rng(17)
    X, _ = make_blobs(10_000_000, 128, n_clusters=4000, cluster_std=5.0,
                      seed=23)
    X = jnp.asarray(X)
    jax.block_until_ready(X)
    q = jnp.asarray(np.asarray(X[:1000])
                    + rng.normal(size=(1000, 128)).astype(np.float32))
    _, ti = brute_force.knn(X, q, 10)
    truth = np.asarray(ti)

    t0 = time.perf_counter()
    # trainset_fraction 0.05 = 500K training rows (ample for 4096
    # clusters); the default 0.5 would stage a 2.6 GB trainset copy next
    # to the 5.1 GB dataset and OOM the 16 GB chip.
    pidx = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=4096, retain_dataset=False,
                           kmeans_trainset_fraction=0.05), X)
    jax.block_until_ready(pidx.pq_codes)
    build_s = time.perf_counter() - t0
    del X  # the index retains nothing — codes + model only
    pidx.compressed_scan_operands()
    spq = ivf_pq.SearchParams(n_probes=32, engine="bucketed")
    d, i = ivf_pq.search(spq, pidx, q, 10)
    rec = _recall(np.asarray(i), truth)
    qps, spread = _eager_qps(
        lambda qq: ivf_pq.search(spq, pidx, qq, 10), q, reps=6, rounds=5)
    _emit("ivf_pq_10m_qps_clustered", qps, "qps",
          recall_at_10=round(rec, 3), n_probes=32, engine="compressed",
          build_s=round(build_s, 1), spread_pct=round(spread, 1))


def _family_serve():
    """Online-serving runtime metrics (ISSUE 5): steady-state served QPS
    per scheduler max_batch vs the per-request baseline, padded-slot
    waste of the pow2 bucket grid, exact-query cache hit rate, and the
    one-time warmup cost. Body lives in bench/serve.py (shared with the
    tier-1 smoke test)."""
    from bench.serve import run

    run(quick=False)


def _family_lifecycle():
    """Mutable-index lifecycle metrics (ISSUE 8): upsert churn
    throughput, search QPS vs tombstone fraction, compaction pass cost,
    and serve p99 with a compaction publish landing mid-stream. Body
    lives in bench/lifecycle.py (shared with the tier-1 smoke test)."""
    from bench.lifecycle import run

    run(quick=False)


def _family_analyze():
    """Static-gate metrics (ISSUE 9): full-tree graft-analyze wall
    time cold (fresh cache) vs warm (incremental cache hit) and the
    resulting speedup — the gate runs on every CI invocation, so its
    cost is tracked like any hot path.  Body lives in bench/analyze.py
    (shared with the tier-1 smoke test)."""
    from bench.analyze import run

    run(quick=False)


def _family_obs():
    """Observability-overhead metrics (ISSUE 11): tracer-on vs
    tracer-off serving QPS delta, full-registry scrape cost, and
    recall-probe overhead at 1% sampling.  Body lives in bench/obs.py
    (shared with the tier-1 smoke test)."""
    from bench.obs import run

    run(quick=False)


def _family_sharded():
    """Merge-engine metrics for the sharded search paths (ISSUE 1): QPS +
    estimated per-device exchange bytes per engine (allgather | ring |
    ring_bf16) over the full mesh, so the BENCH trajectory tracks the
    hierarchical merge collective's comm-volume win. Body lives in
    bench/sharded.py (shared with the tier-1 smoke test)."""
    from bench.sharded import run

    run(quick=False)


def _family_routing():
    """Probe-locality routing metrics (ISSUE 15): QPS, mean shard
    fan-out, and estimated exchange bytes for placement="list" vs the
    row-sharded baseline at uniform / clustered / hot query draws.
    Body lives in bench/sharded.py (shared with the tier-1 smoke)."""
    from bench.sharded import run_routing

    run_routing(quick=False)


def _family_degrade():
    """Tail-robustness metrics (ISSUE 19): p99 + coverage with a 10x
    straggler under hedged vs unhedged dispatch, recall-vs-latency down
    the brownout ladder's n_probes rungs, and circuit-breaker
    re-admission cost. Body lives in bench/degrade.py (shared with the
    tier-1 smoke test)."""
    from bench.degrade import run

    run(quick=False)


def _sift_like(n_db=10_000, n_q=1_000, dim=128, seed=0):
    rng = np.random.default_rng(seed)
    db = rng.integers(0, 256, size=(n_db, dim)).astype(np.float32)
    q = rng.integers(0, 256, size=(n_q, dim)).astype(np.float32)
    return db, q


def _numpy_knn_qps(db, q, k, reps=3):
    def run():
        d = ((q * q).sum(1)[:, None] + (db * db).sum(1)[None, :]
             - 2.0 * q @ db.T)
        return np.argpartition(d, k, axis=1)[:, :k]

    run()
    t0 = time.perf_counter()
    for _ in range(reps):
        run()
    return q.shape[0] / ((time.perf_counter() - t0) / reps)


def _headline():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from raft_tpu.neighbors import brute_force

    k = 10
    R = 512
    db_h, q_h = _sift_like()
    db = jax.device_put(db_h)
    q0 = jax.device_put(q_h)

    @jax.jit
    def run_all(q0, db):
        def body(acc, i):
            d, idx = brute_force.knn(db, q0 + i * jnp.float32(1e-4), k)
            return acc + d[0, 0] + idx[0, 0].astype(jnp.float32), None

        acc, _ = lax.scan(body, jnp.float32(0),
                          jnp.arange(R, dtype=jnp.float32))
        d0, i0 = brute_force.knn(db, q0, k)
        return acc, d0, i0

    acc, d0, i0 = run_all(q0, db)
    np.asarray(acc)
    best = np.inf
    for _ in range(4):
        t0 = time.perf_counter()
        acc, d0, i0 = run_all(q0, db)
        np.asarray(acc)
        best = min(best, (time.perf_counter() - t0) / R)
    qps = q_h.shape[0] / best

    dn = ((q_h * q_h).sum(1)[:, None] + (db_h * db_h).sum(1)[None, :]
          - 2.0 * q_h @ db_h.T)
    truth = np.argsort(dn, axis=1)[:, :k]
    found = np.asarray(i0)
    hits = sum(len(np.intersect1d(found[r], truth[r]))
               for r in range(q_h.shape[0]))
    recall = hits / truth.size
    if recall < 0.999:
        print(json.dumps({"metric": "bf_knn_sift10k_qps", "value": 0.0,
                          "unit": "qps", "vs_baseline": 0.0,
                          "error": f"recall {recall:.4f} < 1.0"}))
        sys.exit(1)

    cpu_qps = _numpy_knn_qps(db_h, q_h, k)
    _emit("bf_knn_sift10k_qps", qps, "qps",
          vs_numpy_host=round(qps / cpu_qps, 3))


def _run_family(fn, error_metric) -> bool:
    """Run one bench family; a failure emits an error row (and its
    traceback on stderr) instead of killing the rest, and returns False.
    The exception (whose traceback frames pin the family's device
    arrays — a failed 10M family once kept 5 GB alive and starved the
    next one) is cleared and the frames collected before the next family
    runs."""
    import gc
    import traceback

    ok = True
    try:
        fn()
    except Exception as e:
        ok = False
        traceback.print_exc()
        print(json.dumps({"metric": error_metric,
                          "value": 0.0, "unit": "", "vs_baseline": 0.0,
                          "error": repr(e)[:200]}), flush=True)
    gc.collect()
    return ok


def main():
    from bench.common import device_peaks
    from raft_tpu.core.compilation_cache import enable_compilation_cache

    device_peaks()  # an unknown device is an error before any family runs
    enable_compilation_cache()
    families = [(_family, "bench_family_error"),
                (_family_analyze, "bench_analyze_error")]
    if "--no-1m" not in sys.argv:
        families += [
            (_family_sharded, "bench_sharded_error"),
            (_family_routing, "bench_routing_error"),
            (_family_serve, "bench_serve_error"),
            (_family_obs, "bench_obs_error"),
            (_family_lifecycle, "bench_lifecycle_error"),
            (_family_degrade, "bench_degrade_error"),
            (_family_1m, "bench_1m_error"),
            (_family_sift1m_u8, "bench_sift1m_error"),
            (_family_4m, "bench_4m_error"),
            (_family_10m, "bench_10m_error"),
        ]
    failed = [err for fn, err in families if not _run_family(fn, err)]
    _headline()
    if failed:
        print("bench: failed families: %s" % ", ".join(failed),
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
