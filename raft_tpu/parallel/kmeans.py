"""Multi-device k-means: shard samples, allreduce the sufficient statistics.

Ref pattern: cuML's kmeans-MG built purely from RAFT comms primitives
(SURVEY.md §2.12 item 4; docs/source/using_comms.rst) — each rank assigns
its rows to the current centroids, computes local (sum, count) per cluster,
and an allreduce produces the new global centroids on every rank.

TPU-native: the EM step is one ``shard_map`` body — fused L2 argmin on the
local shard, ``segment_sum`` for local stats, ``lax.psum`` over the mesh
axis for the global reduction. The full fit loops the jitted step.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from raft_tpu.comms.topk_merge import resolve_merge_engine, topk_merge
from raft_tpu.core.error import expects
from raft_tpu.core.sentinels import worst_value
from raft_tpu.distance.fused_l2_nn import fused_l2_nn_min_reduce


def _em_body(axis: str, n_clusters: int):
    def step(X_local, centroids):
        dists, labels = fused_l2_nn_min_reduce(X_local, centroids)
        sums = jax.ops.segment_sum(X_local, labels, num_segments=n_clusters)
        counts = jax.ops.segment_sum(
            jnp.ones((X_local.shape[0],), X_local.dtype), labels,
            num_segments=n_clusters)
        inertia_local = jnp.sum(dists)
        # Global sufficient statistics over ICI (ref: allreduce of
        # sums/counts in kmeans-MG).
        sums = lax.psum(sums, axis)
        counts = lax.psum(counts, axis)
        inertia = lax.psum(inertia_local, axis)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        new = jnp.where((counts > 0)[:, None], new, centroids)
        return new, inertia

    return step


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "k"))
def _sharded_em_step_jit(X, centroids, *, mesh, axis, k):
    # jit around shard_map is load-bearing: un-jitted shard_map runs in the
    # eager SPMD interpreter (~10x slower, measured on the CPU mesh).
    fn = jax.shard_map(
        _em_body(axis, k), mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=(P(None, None), P()), check_vma=False)
    return fn(X, centroids)


def sharded_kmeans_step(
    mesh: Mesh, X, centroids, axis: str = "data"
) -> Tuple[jax.Array, jax.Array]:
    """One EM step with X row-sharded over ``mesh[axis]``; returns the new
    (replicated) centroids and the global inertia."""
    X = jnp.asarray(X)
    centroids = jnp.asarray(centroids)
    k = centroids.shape[0]
    expects(X.shape[0] % mesh.shape[axis] == 0,
            "rows must divide the mesh axis (pad first)")
    return _sharded_em_step_jit(X, centroids, mesh=mesh, axis=axis, k=k)


def sharded_kmeans_fit(
    mesh: Mesh, X, centroids0, n_iters: int = 20, axis: str = "data"
) -> Tuple[jax.Array, jax.Array]:
    """Full distributed Lloyd fit: jit one step over the mesh, loop it.

    Returns ``(centroids, inertia)``, both replicated.
    """
    X = jnp.asarray(X)
    centroids = jnp.asarray(centroids0)
    k = centroids.shape[0]
    expects(X.shape[0] % mesh.shape[axis] == 0,
            "rows must divide the mesh axis (pad first)")
    inertia = jnp.asarray(worst_value(True), X.dtype)
    for _ in range(n_iters):
        centroids, inertia = _sharded_em_step_jit(X, centroids, mesh=mesh,
                                                  axis=axis, k=k)
    return centroids, inertia


# ---------------------------------------------------------------------------
# Distributed balanced k-means (the trainer behind IVF indexes) — the
# sharded analog of cluster/kmeans_balanced._balanced_em.


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "n_iters", "n_clusters",
                              "engine"))
def _sharded_balanced_em_jit(X, centroids0, *, mesh, axis, n_iters,
                             n_clusters, engine="allgather"):
    """Balancing EM entirely inside one jitted shard_map: assignment and
    sufficient statistics are local + psum (ref: balancing_em_iters,
    detail/kmeans_balanced.cuh:616, distributed per the kmeans-MG recipe);
    the adjust_centers re-seed picks GLOBAL top-cost samples with the
    shared merge collective (comms/topk_merge.py) over (cost, global row
    id), then fetches the winning rows from their owning shards with one
    psum — n_clusters·dim of reduction traffic instead of all-gathering
    every device's k·dim candidate rows."""
    n_dev = mesh.shape[axis]

    def body(X_local, c0):
        n_local = X_local.shape[0]
        threshold = jnp.maximum(
            jnp.asarray(1.0, X_local.dtype),
            jnp.asarray(0.25 * n_local * n_dev / n_clusters, X_local.dtype))

        def em(_, centroids):
            dists, labels = fused_l2_nn_min_reduce(X_local, centroids)
            sums = lax.psum(
                jax.ops.segment_sum(X_local, labels,
                                    num_segments=n_clusters), axis)
            counts = lax.psum(
                jax.ops.segment_sum(
                    jnp.ones((n_local,), X_local.dtype), labels,
                    num_segments=n_clusters), axis)
            new = sums / jnp.maximum(counts, 1.0)[:, None]
            new = jnp.where((counts > 0)[:, None], new, centroids)

            # adjust_centers: global top-cost rows via the shared merge
            # collective — merge (cost, global row id) pairs, then one
            # psum fetches each winning row from its owning shard (every
            # global id lives on exactly one device).
            kk = min(n_clusters, n_local)
            top_d, top_i = lax.top_k(dists, kk)
            gid = lax.axis_index(axis) * n_local + top_i
            _, win = topk_merge(top_d[None], gid[None], n_clusters, axis,
                                select_min=False, engine=engine)
            win = win[0]                                  # (k,) global ids
            rel = win - lax.axis_index(axis) * n_local
            owned = (rel >= 0) & (rel < n_local)
            rows = X_local[jnp.clip(rel, 0, n_local - 1)]
            seeds = lax.psum(
                jnp.where(owned[:, None], rows, 0.0), axis)  # (k, d)

            order = jnp.argsort(counts)
            rank = jnp.argsort(order)
            n_small = jnp.sum(counts < threshold)
            reseed = rank < n_small
            return jnp.where(reseed[:, None], seeds[rank], new)

        return lax.fori_loop(0, n_iters, em, c0)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis, None), P(None, None)),
                       out_specs=P(None, None), check_vma=False)
    return fn(X, centroids0)


def sharded_kmeans_balanced_fit(
    mesh: Mesh, X, n_clusters: int, n_iters: int = 20, axis: str = "data",
    merge_engine: str = "auto",
) -> jax.Array:
    """Distributed balanced k-means over row-sharded data (ref:
    kmeans_balanced::fit distributed per the MNMG recipe,
    docs/source/using_comms.rst) — the center trainer for sharded IVF
    builds at dataset sizes beyond one device's HBM.

    Flat (non-hierarchical) balancing EM: initial centroids are evenly
    strided global rows, each iteration is local-assign + psum'd
    statistics + global top-cost re-seeding. Returns replicated
    (n_clusters, dim) centroids.
    """
    X = jnp.asarray(X)
    n = X.shape[0]
    expects(n % mesh.shape[axis] == 0,
            "rows must divide the mesh axis (pad first)")
    expects(n >= n_clusters, "need at least n_clusters rows")
    centroids0 = X[:: max(n // n_clusters, 1)][:n_clusters]
    engine = resolve_merge_engine(merge_engine, 1, n_clusters,
                                  mesh.shape[axis])
    return _sharded_balanced_em_jit(X, centroids0, mesh=mesh, axis=axis,
                                    n_iters=n_iters, n_clusters=n_clusters,
                                    engine=engine)
