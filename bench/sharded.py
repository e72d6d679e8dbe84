"""Sharded-search merge-engine bench family (ISSUE 1 bench satellite;
ISSUE 14 adds the ``pipeline`` sub-family, ISSUE 15 the ``routing``
family — :func:`run_routing`).

Measures ``sharded_knn`` and sharded IVF-Flat search QPS per merge
engine — allgather | ring | ring_bf16 | pipelined — over the full
device mesh, and reports each engine's estimated per-device collective
exchange bytes (:func:`raft_tpu.comms.topk_merge.merge_comm_bytes`) so
the BENCH trajectory records the comm-volume win alongside the
throughput. One JSON row per (algo, engine), bench.py-style.

The ``pipeline`` family separates COMPUTE time from EXPOSED-COMM time
per engine: the compute baseline is the identical per-shard scan on a
single-device mesh over one shard's rows (no collective in the
program), timed exactly like the full-mesh runs (each round ends in
``block_until_ready``), and ``exposed_comm_ms = total −
compute`` — so "exchange hidden at 4+ shards" is a measured number per
engine, not a claim. Rows: ``sharded_pipeline_ms`` with
``phase=total|compute|exposed_comm`` per engine.

``quick=True`` is the CI smoke shape (tiny db, few repeats, runs on the
8-virtual-CPU-device mesh in tier-1); the full shape is the tracked
bench family wired into bench.py.
"""

from __future__ import annotations

import json
import time

import numpy as np


def _emit(metric, value, unit, _nd: int = 1, **extra):
    rec = {"metric": metric, "value": round(float(value), _nd),
           "unit": unit, "vs_baseline": 1.0}
    rec.update(extra)
    print(json.dumps(rec), flush=True)


def _qps(fn, q, reps, rounds):
    """Pipelined eager dispatch, one ``block_until_ready`` per round —
    the bench.py _eager_qps protocol (sharded searches are eager calls
    around a jitted shard_map)."""
    return q.shape[0] / _sec_per_call(fn, q, reps, rounds)


def _sec_per_call(fn, q, reps, rounds):
    import jax

    jax.block_until_ready(fn(q))  # compile + warm
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(q)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / reps)
    return float(np.median(times))


def run(quick: bool = False) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from raft_tpu.comms.topk_merge import merge_comm_bytes
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.parallel import (sharded_ivf_flat_build,
                                   sharded_ivf_flat_search, sharded_knn)

    devs = np.array(jax.devices())
    mesh = Mesh(devs, ("data",))
    n_dev = devs.size
    rng = np.random.default_rng(3)

    if quick:
        n, d, nq, k, reps, rounds = 1024, 16, 32, 10, 2, 2
        n_lists, n_probes = 16, 8
    else:
        n, d, nq, k, reps, rounds = 262_144, 128, 1024, 100, 8, 5
        n_lists, n_probes = 256, 32
    n -= n % n_dev
    shard = n // n_dev

    db = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(nq, d)).astype(np.float32))

    for engine in ("allgather", "ring", "ring_bf16"):
        qps = _qps(lambda qq, e=engine: sharded_knn(
            mesh, db, qq, k, merge_engine=e), q, reps, rounds)
        _emit("sharded_knn_qps", qps, "qps", engine=engine,
              mesh_devices=n_dev, n_db=n, dim=d, k=k,
              est_exchange_bytes=merge_comm_bytes(
                  engine, nq, k, min(k, shard), n_dev))

    params = ivf_flat.IndexParams(n_lists=n_lists, kmeans_n_iters=4)
    sharded = sharded_ivf_flat_build(mesh, params, db)
    sp = ivf_flat.SearchParams(n_probes=n_probes)
    cap = int(sharded.indices.shape[1] * sharded.indices.shape[2])
    for engine in ("allgather", "ring", "ring_bf16"):
        qps = _qps(lambda qq, e=engine: sharded_ivf_flat_search(
            mesh, sp, sharded, qq, k, merge_engine=e), q, reps, rounds)
        _emit("sharded_ivf_flat_qps", qps, "qps", engine=engine,
              mesh_devices=n_dev, n_db=n, dim=d, k=k, n_probes=n_probes,
              est_exchange_bytes=merge_comm_bytes(
                  engine, nq, k, min(k, cap), n_dev))

    # ---- pipeline family (ISSUE 14): compute vs exposed-comm per engine.
    # Compute baseline: the IDENTICAL per-shard scan volume on a
    # 1-device mesh (one shard's rows, same model shape / n_probes / k)
    # — a compiled program with NO collective, timed by the same
    # protocol. exposed_comm = total − compute is then the measured
    # exchange exposure each engine leaves on the critical path; the
    # pipelined engines' job is driving it toward zero at 4+ shards.
    from raft_tpu.comms.topk_merge import (pipeline_chunk_bounds,
                                           resolve_pipeline_chunks)

    mesh1 = Mesh(devs[:1], ("data",))
    sharded1 = sharded_ivf_flat_build(mesh1, params, db[:shard],
                                      centers=sharded.centers)
    compute_s = _sec_per_call(
        lambda qq: sharded_ivf_flat_search(mesh1, sp, sharded1, qq, k),
        q, reps, rounds)
    _emit("sharded_pipeline_ms", compute_s * 1e3, "ms", _nd=3, phase="compute",
          engine="local_scan", mesh_devices=n_dev, n_db=n, dim=d, k=k,
          n_probes=n_probes)
    lcap = int(sharded.indices.shape[2])
    for engine in ("allgather", "ring", "ring_bf16", "pipelined",
                   "pipelined_bf16"):
        total_s = _sec_per_call(
            lambda qq, e=engine: sharded_ivf_flat_search(
                mesh, sp, sharded, qq, k, merge_engine=e),
            q, reps, rounds)
        n_chunks = resolve_pipeline_chunks(engine, n_probes, n_dev)
        chunk_kks = [min(k, (hi - lo) * lcap) for lo, hi in
                     pipeline_chunk_bounds(n_probes, n_chunks)] \
            if n_chunks > 1 else None
        est = merge_comm_bytes(engine, nq, k, min(k, cap), n_dev,
                               chunk_kks=chunk_kks)
        _emit("sharded_pipeline_ms", total_s * 1e3, "ms", _nd=3, phase="total",
              engine=engine, mesh_devices=n_dev, n_db=n, dim=d, k=k,
              n_probes=n_probes, pipeline_chunks=n_chunks,
              est_exchange_bytes=est)
        _emit("sharded_pipeline_ms", max(0.0, total_s - compute_s) * 1e3,
              "ms", _nd=3, phase="exposed_comm", engine=engine,
              mesh_devices=n_dev, n_db=n, dim=d, k=k, n_probes=n_probes,
              pipeline_chunks=n_chunks, est_exchange_bytes=est)


def routing_workload(rng, n: int, d: int, nq: int, n_blobs: int = 16):
    """Blob-structured db + three query draws at rising probe locality
    (shared by :func:`run_routing` and the tier-1 routed bench test).
    Real retrieval corpora are clustered — that structure is exactly
    what the affinity-aware list placement converts into locality:
    centroid-neighbor lists co-locate, so queries around few anchors
    probe few shards.  Draws: ``low`` jitters around many anchors
    (probes spread), ``medium`` around 4, ``high`` around 1 (a hot
    working set)."""
    blobs = rng.normal(size=(n_blobs, d)).astype(np.float32) * 6.0
    lab = rng.integers(0, n_blobs, size=n)
    db = (blobs[lab] + rng.normal(size=(n, d))).astype(np.float32)

    def draw(n_anchors: int) -> np.ndarray:
        anchors = db[rng.integers(0, n, size=n_anchors)]
        picks = anchors[rng.integers(0, n_anchors, size=nq)]
        return (picks + 0.05 * rng.normal(size=(nq, d))
                ).astype(np.float32)

    return db, (("low", draw(max(n_blobs, 16))), ("medium", draw(4)),
                ("high", draw(1)))


def run_routing(quick: bool = False) -> None:
    """Routing bench family (ISSUE 15): ``placement="list"`` vs
    ``placement="row"`` at low / medium / high probe locality
    (:func:`routing_workload`).

    Per (placement, locality) the family reports QPS, the mean shard
    fan-out factor (shards participating per query — always n_dev for
    the row placement), the batch participant count, and the estimated
    per-device exchange bytes (``merge_comm_bytes``; routed dispatches
    account participating shards only).  The routed exchange estimate
    must sit strictly below the row baseline on the clustered draws,
    with the gap growing as locality rises — the bench row the
    acceptance gate reads."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from raft_tpu.comms.topk_merge import merge_comm_bytes
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.parallel import (plan_route, sharded_ivf_flat_build,
                                   sharded_ivf_flat_search)
    from raft_tpu.parallel.ivf import _routed_probe_flat

    devs = np.array(jax.devices())
    mesh = Mesh(devs, ("data",))
    n_dev = devs.size
    rng = np.random.default_rng(5)

    if quick:
        n, d, nq, k, reps, rounds = 4096, 16, 64, 10, 2, 2
        n_lists, n_probes = 32, 4
    else:
        n, d, nq, k, reps, rounds = 262_144, 64, 1024, 100, 8, 5
        n_lists, n_probes = 256, 16
    n -= n % n_dev

    db_h, draws = routing_workload(rng, n, d, nq)
    db = jnp.asarray(db_h)
    params = ivf_flat.IndexParams(n_lists=n_lists, kmeans_n_iters=8)
    row = sharded_ivf_flat_build(mesh, params, db)
    lst = sharded_ivf_flat_build(mesh, params, db, centers=row.centers,
                                 placement="list")
    sp = ivf_flat.SearchParams(n_probes=n_probes)

    cap_row = int(row.indices.shape[1] * row.indices.shape[2])
    cap_list = int(lst.indices.shape[2])
    for locality, q_h in draws:
        q = jnp.asarray(q_h)
        probe_h = np.asarray(jax.device_get(_routed_probe_flat(
            q, lst.centers, n_probes=min(n_probes, n_lists),
            inner_is_l2=True)))
        plan = plan_route(probe_h, lst.placement_map)
        for placement, index in (("row", row), ("list", lst)):
            qps = _qps(lambda qq, i=index: sharded_ivf_flat_search(
                mesh, sp, i, qq, k), q, reps, rounds)
            if placement == "row":
                fanout, participants = n_dev, n_dev
                est = merge_comm_bytes("auto", nq, k, min(k, cap_row),
                                       n_dev)
            else:
                fanout, participants = plan.fanout_mean, plan.participants
                est = merge_comm_bytes(
                    "auto", nq, k, min(k, plan.pb * cap_list), n_dev,
                    participants=plan.participants)
            _emit("sharded_routed_qps", qps, "qps", placement=placement,
                  locality=locality, mesh_devices=n_dev, n_db=n, dim=d,
                  k=k, n_probes=n_probes, fanout_mean=round(fanout, 3),
                  participants=participants, est_exchange_bytes=est)


if __name__ == "__main__":
    import sys

    run(quick="--quick" in sys.argv)
    run_routing(quick="--quick" in sys.argv)
