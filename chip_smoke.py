#!/usr/bin/env python
"""Smoke test of the served vector-search path on a TPU.

Drives the path a deployment serves through once -- indexes built with
the public ``build`` calls, wrapped in ``Searcher`` and answered through
``BatchScheduler`` -- at the shapes of ann-benchmarks
``sift-128-euclidean`` (128-d float32, L2, k=10), over a corpus that
``raft_tpu.random.make_blobs`` generates on the device from ``--seed``.

    python chip_smoke.py             # one chip: brute force, IVF-Flat and
                                     # IVF-PQ over 1,000,000 rows
    python chip_smoke.py --chips 4   # four chips: sharded IVF-Flat over
                                     # 4,000,000 rows, row and list placement

Each phase serves a mix of request sizes from 1 to 1000 queries, in
groups of 1 to 8 requests per flush so that batches land in small and
large buckets, and checks every answer: the shape, finite distances, ids
inside the corpus, and recall@10 of every served query against an exact
search written here with plain ``jnp`` (no raft_tpu kernel). Recall is
checked per engine -- each bucket is served by an XLA program or a
Pallas kernel, and each engine the grid uses must answer at least
``min_checked`` queries at the floor. On one chip it also checks that no
compile happens once the bucket grid is warm, that the searches resolved
to compiled Pallas kernels wherever those are the TPU's engine, and that
the IVF-PQ codes ``encode_rows`` writes are the nearest codewords by a
host search.

Without a TPU the script exits non-zero before doing anything else.
Everything runs in this one process: a second process could not reach a
chip this one holds. Earlier stdout lines are one JSON object per phase;
the last line is ``{"ok": true, "device": {...}}``, printed only when
every phase passed.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

DIM = 128
K = 10
N_PROBES = 32
CLUSTER_STD = 5.0
RECALL_FLOOR = {"brute_force": 0.999, "ivf_flat": 0.95, "ivf_pq": 0.80}
# IVF-PQ encode check: share of codes that must be the host's nearest
# codeword (f32 device vs f64 host may split near-ties), and the largest
# quantization error as a share of the residual energy.
PQ_CODE_AGREEMENT = 0.999
PQ_MAX_REL_ERROR = 0.1


@dataclass(frozen=True)
class Size:
    """Scale of one run. The widths (DIM, K, N_PROBES) never change."""

    n_rows: int        # corpus rows
    n_pool: int        # held-out query rows the requests draw from
    n_requests: int
    max_batch: int     # largest request; the bucket grid tops out above it
    n_lists: int
    n_clusters: int    # make_blobs clusters
    min_checked: int   # served queries each engine must answer
    n_encode: int = 0  # rows of the IVF-PQ encode check


ONE_CHIP = Size(n_rows=1_000_000, n_pool=10_000, n_requests=200,
                max_batch=1000, n_lists=1024, n_clusters=1000,
                min_checked=100, n_encode=20_000)
# 4M x 128 f32 is 512 MB of corpus per chip. Fewer requests: each
# routed (list placement) batch shape not seen before is a compile.
FOUR_CHIPS = Size(n_rows=4_000_000, n_pool=10_000, n_requests=24,
                  max_batch=1000, n_lists=1024, n_clusters=1000,
                  min_checked=500)


class PhaseFailed(Exception):
    """A phase ran but its output broke one of the checks."""


def require_tpu():
    """The first TPU device, or exit non-zero: there is no CPU path."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("chip_smoke: needs a TPU; JAX found %r" % dev.platform)
    return dev


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


def make_data(size: Size, seed: int):
    """``(corpus on the device, host query pool)`` from one make_blobs
    draw: the pool is held-out rows of the same clusters."""
    from raft_tpu.random import make_blobs

    data, _ = make_blobs(size.n_rows + size.n_pool, DIM,
                         n_clusters=size.n_clusters,
                         cluster_std=CLUSTER_STD, seed=seed)
    X = data[:size.n_rows]
    pool = np.asarray(jax.device_get(data[size.n_rows:]))
    del data
    return jax.block_until_ready(X), pool


def request_plan(size: Size, seed: int):
    """``(sizes, flush)``: log-uniform request sizes in [1, max_batch]
    (the first two are the two ends of the mix), and whether the
    scheduler is flushed after each request -- groups of 1 to 8
    requests, so batches fill every part of the bucket grid."""
    rng = np.random.default_rng(seed)
    sizes = np.exp(rng.uniform(0.0, np.log(size.max_batch),
                               size.n_requests)).astype(np.int64)
    sizes = np.clip(sizes, 1, size.max_batch)
    sizes[:2] = (1, size.max_batch)
    ends = np.cumsum(rng.integers(1, 9, size.n_requests)) - 1
    flush = np.zeros(size.n_requests, bool)
    flush[ends[ends < size.n_requests]] = True
    flush[-1] = True
    return sizes, flush


def served_rows(sizes: np.ndarray, n_pool: int) -> int:
    """Pool rows the requests read (they walk the pool cyclically)."""
    return int(min(n_pool, sizes.sum()))


@functools.partial(jax.jit, static_argnames="k")
def _exact_tile(q, x, base, k):
    d = (jnp.sum(q * q, axis=1)[:, None] + jnp.sum(x * x, axis=1)[None, :]
         - 2.0 * jnp.matmul(q, x.T, precision=lax.Precision.HIGHEST))
    neg, i = lax.top_k(-d, k)
    return -neg, i + base


def exact_knn(X, queries: np.ndarray, k: int, tile: int = 125_000,
              q_tile: int = 1024) -> np.ndarray:
    """Exact L2 top-k ids: a matmul and ``lax.top_k`` per (query tile,
    corpus tile), merged with one more ``top_k``. Independent of
    raft_tpu's kernels."""
    dev = next(iter(X.devices()))
    out = []
    for qs in range(0, len(queries), q_tile):
        q = jax.device_put(np.asarray(queries[qs:qs + q_tile], np.float32),
                           dev)
        best_d = best_i = None
        for s in range(0, X.shape[0], tile):
            d, i = _exact_tile(q, X[s:s + tile], jnp.int32(s), k)
            if best_d is not None:
                d = jnp.concatenate([best_d, d], axis=1)
                i = jnp.concatenate([best_i, i], axis=1)
                neg, pos = lax.top_k(-d, k)
                d, i = -neg, jnp.take_along_axis(i, pos, axis=1)
            best_d, best_i = d, i
        out.append(np.asarray(best_i))
    return np.concatenate(out)


def dispatched_bucket(ticket) -> int:
    """The query bucket of the batch that answered a request, read from
    the request's trace (``batch_assembly`` span, "<q>x<k>")."""
    for span in ticket.span.children:
        if span.name == "batch_assembly":
            return int(span.attrs["bucket"].split("x")[0])
    raise PhaseFailed("request %d has no batch_assembly span" % ticket.seq)


def serve(searcher, grid, pool: np.ndarray, plan, k: int,
          n_rows: int) -> dict:
    """Send every request of ``plan`` through one traced BatchScheduler
    and check every answer. Returns ``answers`` -- ``(pool rows, ids,
    dispatched bucket)`` per request -- and the window's counters."""
    from raft_tpu.obs import Tracer
    from raft_tpu.serve import BatchPolicy, BatchScheduler, CompileCounter

    sizes, flush = plan
    sched = BatchScheduler(searcher, grid,
                           BatchPolicy(max_batch=grid.max_batch,
                                       max_queue=len(sizes)),
                           tracer=Tracer(max_traces=len(sizes)))
    sent = []
    off = 0
    with CompileCounter() as compiles:
        t0 = time.perf_counter()
        for n, last in zip(sizes, flush):
            rows = (np.arange(n) + off) % pool.shape[0]
            off += int(n)
            sent.append((rows, sched.submit(pool[rows], k)))
            if last:
                sched.flush()
        sched.close()
        seconds = time.perf_counter() - t0
    answers = []
    for rows, ticket in sent:
        res = ticket.result()
        if res.indices.shape != (rows.size, k) or \
                res.distances.shape != (rows.size, k):
            raise PhaseFailed("answer shape %s for %s queries"
                              % (res.indices.shape, rows.size))
        if not np.all(np.isfinite(res.distances)):
            raise PhaseFailed("non-finite distances in an answer")
        if res.indices.min() < 0 or res.indices.max() >= n_rows:
            raise PhaseFailed("ids outside [0, %d)" % n_rows)
        answers.append((rows, res.indices, dispatched_bucket(ticket)))
    return {"answers": answers, "requests": len(sizes),
            "rows": int(sizes.sum()), "serve_s": seconds,
            "compiles": compiles.count}


def recall_by(answers, truth: np.ndarray, key) -> dict:
    """``{key(bucket): [queries, recall@k]}`` over every served query."""
    hits: dict = {}
    seen: dict = {}
    for rows, ids, bucket in answers:
        t = truth[rows]
        g = key(bucket)
        hits[g] = hits.get(g, 0) + int(
            (ids[:, :, None] == t[:, None, :]).any(axis=2).sum())
        seen[g] = seen.get(g, 0) + rows.size
    k = truth.shape[1]
    return {g: [seen[g], hits[g] / (seen[g] * k)] for g in sorted(seen)}


def check_recall(kind: str, by_engine: dict, engines, floor: float,
                 min_checked: int) -> None:
    """Every engine in ``engines`` answered ``min_checked`` queries, each
    at ``floor``."""
    for e in sorted(set(engines)):
        n, rec = by_engine.get(e, (0, 0.0))
        if n < min_checked:
            raise PhaseFailed("%s: engine %s answered %d queries, fewer "
                              "than %d" % (kind, e, n, min_checked))
        if rec < floor:
            raise PhaseFailed("%s: recall@%d %.4f < %.3f on engine %s"
                              % (kind, K, rec, floor, e))


def pq_encode_check(index, X, n: int) -> dict:
    """Encode the first ``n`` corpus rows with ``ivf_pq.encode_rows``
    and compare with a float64 host search of the same model: the share
    of (row, subspace) codes that are the nearest codeword, and the
    quantization error of the device's codes as a share of the residual
    energy (PER_SUBSPACE books)."""
    from raft_tpu.neighbors import ivf_pq

    labels, packed = ivf_pq.encode_rows(index, X[:n])
    codes = np.asarray(ivf_pq.unpack_codes(packed, index.pq_dim,
                                           index.pq_bits))
    x = np.asarray(X[:n], np.float64)
    centers = np.asarray(index.centers, np.float64)
    rot = np.asarray(index.rotation_matrix, np.float64)
    books = np.asarray(index.pq_centers, np.float64)[None]
    res = ((x - centers[np.asarray(labels)]) @ rot.T).reshape(
        n, index.pq_dim, index.pq_len)
    agree = err = 0.0
    for s in range(0, n, 500):
        d = ((res[s:s + 500, :, None, :] - books) ** 2).sum(axis=3)
        c = codes[s:s + 500]
        agree += float((d.argmin(axis=2) == c).sum())
        err += float(np.take_along_axis(d, c[..., None], axis=2).sum())
    return {"code_agreement": agree / codes.size,
            "rel_error": err / float((res ** 2).sum())}


@contextlib.contextmanager
def dumped_modules():
    """Collect the StableHLO of every program JAX compiles inside the
    block (``jax_dump_ir_to``): ``{file name: module text}``."""
    mods: dict = {}
    with tempfile.TemporaryDirectory() as d:
        jax.config.update("jax_dump_ir_to", d)
        try:
            yield mods
        finally:
            jax.config.update("jax_dump_ir_to", "")
            for name in sorted(os.listdir(d)):
                with open(os.path.join(d, name)) as f:
                    mods[name] = f.read()


def resolved_engines(mods: dict, q_buckets, dim: int) -> dict:
    """Per query bucket: "pallas" when a compiled program whose first
    argument is that bucket's (q, dim) f32 queries holds a Mosaic kernel
    (``tpu_custom_call``), else "xla". The search programs take the
    queries first; matching any argument would let a (dim, dim) rotation
    stand in for the dim-query bucket. Interpret-mode Pallas lowers to
    plain HLO, so it reads as "xla" too."""
    out = {}
    for qb in q_buckets:
        arg = "%%arg0: tensor<%dx%dxf32>" % (qb, dim)
        hit = False
        for text in mods.values():
            sig = next((ln for ln in text.splitlines()
                        if "func.func public @main(" in ln), "")
            if arg in sig and "tpu_custom_call" in text:
                hit = True
                break
        out[qb] = "pallas" if hit else "xla"
    return out


def pallas_programs(mods: dict) -> list:
    """Names of the compiled programs that hold a Mosaic kernel."""
    names = set()
    for name, text in mods.items():
        if "tpu_custom_call" in text:
            names.add(name.split("_", 2)[2].rsplit("_compile", 1)[0])
    return sorted(names)


def expected_pallas(kind: str, q_buckets, size: Size) -> list:
    """Buckets whose TPU engine is a Pallas kernel: every bucket of the
    fused brute-force kNN, and the IVF buckets whose probe load reaches
    8 (query, probe) pairs per list, where the packed-cells (IVF-Flat)
    and compressed-domain (IVF-PQ) scans take over from the XLA scan."""
    if kind == "brute_force":
        return list(q_buckets)
    return [qb for qb in q_buckets
            if qb * min(N_PROBES, size.n_lists) / size.n_lists >= 8]


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def run_phase(name: str, failures: list, fn, *args):
    """Run one phase and return its result; a failure is reported and
    recorded (the result is then None), and the other phases still run."""
    try:
        return fn(*args)
    except Exception:
        failures.append(name)
        print("chip_smoke: phase %s failed" % name, file=sys.stderr)
        traceback.print_exc()
        return None


def _serve_one(kind: str, searcher, size: Size, plan, pool, truth, grid,
               **extra) -> None:
    from raft_tpu.serve import warmup

    with dumped_modules() as mods:
        t0 = time.perf_counter()
        report = warmup(searcher, grid)
        warm_s = time.perf_counter() - t0
    engines = resolved_engines(mods, grid.q_buckets, DIM)
    out = serve(searcher, grid, pool, plan, K, size.n_rows)
    by_engine = recall_by(out["answers"], truth, engines.get)
    _, rec = recall_by(out["answers"], truth, lambda b: "all")["all"]
    emit(phase=kind, warmup_s=warm_s,
         warmup_compiles=report["compile_events"],
         requests=out["requests"], rows=out["rows"],
         serve_s=out["serve_s"], serve_compiles=out["compiles"],
         recall_at_10=rec, recall_floor=RECALL_FLOOR[kind], recall_by_engine=by_engine,
         recall_by_bucket=recall_by(out["answers"], truth, str),
         engine_by_bucket={str(q): e for q, e in engines.items()},
         pallas_programs=pallas_programs(mods),
         peak_bytes_in_use=peak_bytes(), **extra)
    missing = [qb for qb in expected_pallas(kind, grid.q_buckets, size)
               if engines[qb] != "pallas"]
    if missing:
        raise PhaseFailed("%s: no compiled Pallas kernel at buckets %s"
                          % (kind, missing))
    if out["compiles"]:
        raise PhaseFailed("%s: %d compiles while serving a warm grid"
                          % (kind, out["compiles"]))
    check_recall(kind, by_engine, engines.values(), RECALL_FLOOR[kind],
                 size.min_checked)


def run_one_chip(size: Size, seed: int) -> list:
    """Brute force, IVF-Flat and IVF-PQ on one device, each behind a
    warmed BatchScheduler. Returns the names of the failed phases."""
    from raft_tpu.neighbors import ivf_flat, ivf_pq
    from raft_tpu.serve import BucketGrid, Searcher

    t0 = time.perf_counter()
    X, pool = make_data(size, seed)
    data_s = time.perf_counter() - t0
    plan = request_plan(size, seed)
    truth = exact_knn(X, pool[:served_rows(plan[0], size.n_pool)], K)
    emit(phase="data", rows=size.n_rows, dim=DIM, seed=seed,
         data_s=data_s)
    grid = BucketGrid.pow2(size.max_batch, k_grid=(K,))
    failures: list = []

    def build(kind):
        t0 = time.perf_counter()
        if kind == "ivf_flat":
            index = ivf_flat.build(
                ivf_flat.IndexParams(n_lists=size.n_lists), X)
            jax.block_until_ready(index.data)
        else:
            index = ivf_pq.build(
                ivf_pq.IndexParams(n_lists=size.n_lists, pq_bits=8), X)
            jax.block_until_ready(index.pq_codes)
        emit(phase=kind + "_build", build_s=time.perf_counter() - t0,
             peak_bytes_in_use=peak_bytes())
        return index

    def phase(kind, mod):
        index = build(kind)
        extra = {}
        if kind == "ivf_pq":
            extra = pq_encode_check(index, X, size.n_encode)
        _serve_one(kind, getattr(Searcher, kind)(
                       index, mod.SearchParams(n_probes=N_PROBES)),
                   size, plan, pool, truth, grid, **extra)
        if kind == "ivf_pq":
            if extra["code_agreement"] < PQ_CODE_AGREEMENT:
                raise PhaseFailed("ivf_pq: %.6f of the codes are the "
                                  "nearest codeword (< %s)"
                                  % (extra["code_agreement"],
                                     PQ_CODE_AGREEMENT))
            if extra["rel_error"] > PQ_MAX_REL_ERROR:
                raise PhaseFailed("ivf_pq: quantization error %.4f of the "
                                  "residual energy (> %s)"
                                  % (extra["rel_error"], PQ_MAX_REL_ERROR))

    run_phase("brute_force", failures, _serve_one, "brute_force",
              Searcher.brute_force(X), size, plan, pool, truth, grid)
    for kind, mod in (("ivf_flat", ivf_flat), ("ivf_pq", ivf_pq)):
        run_phase(kind, failures, phase, kind, mod)
    return failures


def run_four_chips(size: Size, seed: int) -> list:
    """Sharded IVF-Flat over a 4-device mesh: one build per placement
    (the list build reuses the row build's coarse model), each served
    under the auto and pipelined merge engines from one query bucket
    (each bucket is a compile per placement and engine; no warmup).
    Returns the names of the failed phases."""
    from jax.sharding import Mesh

    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.parallel import sharded_ivf_flat_build
    from raft_tpu.serve import BucketGrid, Searcher
    from raft_tpu.util.pow2 import next_pow2

    devices = jax.devices()
    if len(devices) < 4:
        raise PhaseFailed("--chips 4 needs 4 devices, found %d"
                          % len(devices))
    mesh = Mesh(np.array(devices[:4]), ("data",))
    X, pool = make_data(size, seed)
    plan = request_plan(size, seed)
    truth = exact_knn(X, pool[:served_rows(plan[0], size.n_pool)], K)
    emit(phase="data", rows=size.n_rows, dim=DIM, seed=seed, mesh=4)
    grid = BucketGrid(q_buckets=(next_pow2(size.max_batch),), k_grid=(K,))
    params = ivf_flat.IndexParams(n_lists=size.n_lists)
    failures: list = []

    def build(placement, centers):
        t0 = time.perf_counter()
        index = sharded_ivf_flat_build(mesh, params, X, centers=centers,
                                       placement=placement)
        jax.block_until_ready(index.data)
        build_s = time.perf_counter() - t0
        shard_devices = {
            name: sorted({str(s.device) for s in
                          getattr(index, name).addressable_shards})
            for name in ("data", "indices", "list_sizes")}
        emit(phase="sharded_build", placement=placement, build_s=build_s,
             shard_devices=shard_devices,
             shard_shape=list(index.data.addressable_shards[0].data.shape))
        if any(len(v) != 4 for v in shard_devices.values()):
            raise PhaseFailed("%s placement: shards not on 4 distinct "
                              "devices: %s" % (placement, shard_devices))
        return index

    def phase(index, placement, engine):
        searcher = Searcher.ivf_flat(
            index, ivf_flat.SearchParams(n_probes=N_PROBES), mesh=mesh,
            merge_engine=engine)
        out = serve(searcher, grid, pool, plan, K, size.n_rows)
        n, rec = recall_by(out["answers"], truth, lambda b: "all")["all"]
        emit(phase="sharded_ivf_flat", placement=placement,
             merge_engine=engine, requests=out["requests"],
             rows=out["rows"], serve_s=out["serve_s"],
             serve_compiles=out["compiles"], recall_at_10=rec,
             recall_floor=RECALL_FLOOR["ivf_flat"], queries_checked=n)
        check_recall("sharded_%s_%s" % (placement, engine), {"all": (n, rec)},
                     ["all"], RECALL_FLOOR["ivf_flat"], size.min_checked)

    centers = None
    for placement in ("row", "list"):
        index = run_phase("sharded_build_" + placement, failures, build,
                          placement, centers)
        if index is None:
            continue
        centers = index.centers
        for engine in ("auto", "pipelined"):
            run_phase("sharded_%s_%s" % (placement, engine), failures,
                      phase, index, placement, engine)
        # The next placement's build must not share HBM with this index.
        del index
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = require_tpu()
    from raft_tpu.core.compilation_cache import enable_compilation_cache

    enable_compilation_cache()
    if args.chips == 4:
        failures = run_four_chips(FOUR_CHIPS, args.seed)
    else:
        failures = run_one_chip(ONE_CHIP, args.seed)
    if failures:
        print("chip_smoke: failed phases: %s" % ", ".join(failures),
              file=sys.stderr)
        return 1
    emit(ok=True, device={"platform": dev.platform,
                          "kind": dev.device_kind,
                          "count": len(jax.devices())})
    return 0


if __name__ == "__main__":
    sys.exit(main())
