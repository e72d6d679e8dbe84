"""Balanced hierarchical k-means — the trainer behind IVF indexes.

Ref: cpp/include/raft/cluster/kmeans_balanced.cuh (fit:75, predict:133,
fit_predict:198) with detail in cluster/detail/kmeans_balanced.cuh:
predict_core:83 (gemm distances + argmin), adjust_centers:522 (re-seed
under-populated clusters from high-cost samples), balancing_em_iters:616,
build_clusters:703, and the mesocluster-based ``build_hierarchical`` (train
√n_clusters mesoclusters, then split each into fine clusters proportional to
its population).

TPU-native re-design:

* ``predict`` = fused-L2-argmin on the MXU (same gemm-based distance trick
  as predict_core);
* the balancing EM iteration runs under jit with static shapes; the
  "adjust centers" pass re-seeds empty/underweight clusters from the
  highest-cost samples — expressed with sorts/masks instead of the
  reference's atomics-based kernel;
* hierarchical build runs the fine-cluster stage as a single *masked*
  balanced EM: every fine centroid is owned by one mesocluster and the
  assignment step only considers centroids owned by the sample's
  mesocluster. Ownership masking decouples the EM into exactly the
  per-mesocluster sub-problems of the reference's ``build_hierarchical``
  host loop — but as ONE jitted program with O(1) host round-trips
  instead of O(mesoclusters) synchronized device calls, each of which
  leaves the device idle while the host waits on it.

Integer dtypes (SIFT-style uint8/int8) are accepted and mapped to float32
on entry, the role of ``utils::mapping<T>`` in the reference.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu.core.error import expects
from raft_tpu.core.mdarray import as_array
from raft_tpu.cluster.kmeans_types import KMeansBalancedParams
from raft_tpu.distance.distance_types import DistanceType
from raft_tpu.distance.fused_l2_nn import fused_l2_nn_min_reduce
from raft_tpu.distance.pairwise import distance as pairwise_distance_fn
from raft_tpu.util.pow2 import ceildiv
from raft_tpu.core.nvtx import traced

# Threshold ratio below which a cluster is considered under-populated and
# eligible for re-seeding (ref: adjust_centers uses average/4 as the small-
# cluster threshold, cluster/detail/kmeans_balanced.cuh:522ff).
_SMALL_RATIO = 0.25


def _as_float(x) -> jax.Array:
    x = as_array(x)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(jnp.float32)
    return x


def _labels(X, centroids, metric: DistanceType) -> jax.Array:
    """Metric-dispatched nearest-centroid labels (ref: predict_core:83):
    fused L2+argmin for the L2 family, pairwise + argmin/argmax otherwise."""
    if metric in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded):
        _, labels = fused_l2_nn_min_reduce(X, centroids)
        return labels
    from raft_tpu.distance.distance_types import value_form_select_min

    # pairwise emits distance form for cosine/correlation (1 - sim), so
    # polarity follows the VALUE form, not the reference's kernel form.
    d = pairwise_distance_fn(X, centroids, metric=metric)
    return (jnp.argmin(d, axis=1) if value_form_select_min(metric)
            else jnp.argmax(d, axis=1)).astype(jnp.int32)


@traced
def predict(
    params: KMeansBalancedParams, centroids, X
) -> jax.Array:
    """Nearest-centroid labels (ref: kmeans_balanced::predict,
    cluster/kmeans_balanced.cuh:133 → predict_core:83)."""
    return _labels(_as_float(X), _as_float(centroids), params.metric)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _balanced_em(X, centroids0, n_iters: int, n_clusters: int,
                 fast: bool = False):
    """Balancing EM (ref: balancing_em_iters, detail/kmeans_balanced.cuh:616):
    each iteration assigns, recomputes means, then re-seeds under-populated
    clusters from the highest-cost samples (adjust_centers:522).

    ``fast`` runs every assignment except the LAST iteration's with the
    split-bf16 fused kernel (y rounded to bf16, x recovered by a hi/lo
    double matmul — ~2× the f32 MFU, argmin agreement 0.996 measured;
    ref keeps the analogous fusedL2NN in f32, detail/fused_l2_nn.cuh:129).
    Near-tied intermediate assignments may flip, perturbing intermediate
    means at bf16-rounding scale; the final iteration is exact f32, so
    the returned centroids are an exact-assignment fixed-point step."""
    threshold = jnp.maximum(
        jnp.asarray(1.0, X.dtype),
        jnp.asarray(_SMALL_RATIO * X.shape[0] / n_clusters, X.dtype))

    def _body(centroids, bf16):
        dists, labels = fused_l2_nn_min_reduce(X, centroids, bf16=bf16)
        sums = jax.ops.segment_sum(X, labels, num_segments=n_clusters)
        counts = jax.ops.segment_sum(
            jnp.ones((X.shape[0],), X.dtype), labels, num_segments=n_clusters)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        new = jnp.where((counts > 0)[:, None], new, centroids)

        # adjust_centers: rank clusters by population; rank samples by cost.
        # The i-th most under-populated cluster is re-seeded to the i-th
        # highest-cost sample (a deterministic variant of the reference's
        # probabilistic pick from high-cost samples).
        order = jnp.argsort(counts)                      # ascending population
        rank = jnp.argsort(order)                        # cluster -> its rank
        n_small = jnp.sum(counts < threshold)
        top_cost = jnp.argsort(-dists)[:n_clusters]      # top-cost sample ids
        reseed = rank < n_small                          # smallest n_small clusters
        seeds = X[top_cost[rank]]                        # (k, d) candidate seeds
        return jnp.where(reseed[:, None], seeds, new)

    if fast and n_iters > 0:
        c = lax.fori_loop(0, n_iters - 1,
                          lambda _, c: _body(c, "split"), centroids0)
        return _body(c, None)
    return lax.fori_loop(0, n_iters, lambda _, c: _body(c, None),
                         centroids0)


@functools.partial(jax.jit, static_argnums=(2,))
def _predict_and_count(X, centroids, metric: DistanceType):
    """Labels + per-cluster populations in one device call."""
    labels = _labels(X, centroids, metric)
    counts = jax.ops.segment_sum(
        jnp.ones((X.shape[0],), jnp.int32), labels,
        num_segments=centroids.shape[0])
    return labels, counts


# Row-block / centroid-tile caps for the masked assignment scan: the
# materialized distance tile is (block, ktile) f32 = 512 MB max, whatever
# n and n_clusters are. Small problems clamp both to their own size.
_ASSIGN_BLOCK = 65536
_ASSIGN_KTILE = 2048


@functools.partial(jax.jit, static_argnums=(5, 6))
def _hierarchical_fine_em(X, meso_labels, owner, seed_slots, key,
                          n_iters: int, n_clusters: int):
    """Fine-cluster stage of ``build_hierarchical`` as one jitted program.

    Ref: detail/kmeans_balanced.cuh build_hierarchical — the reference loops
    over mesoclusters on the host, gathering each mesocluster's members and
    running ``build_clusters`` on them. Here the same sub-problems run
    simultaneously:

    * seeding is a *masked k-means++*: cost-weight sampling (Gumbel trick +
      per-group segment-argmax) of each mesocluster's rank-r seed, one
      round per rank — every group picks its r-th seed in the same O(n·d)
      sweep, so the whole seeding costs max-quota passes over X instead of
      k — ≈O(√k) when mesocluster populations are balanced (which the
      balancing meso EM maintains; adversarial skew degrades towards O(k),
      the price of exact per-group D² sequencing). Within a group the
      picks are sequential in r, which is
      the D²-sampling of kmeansPlusPlus restricted per group (the first
      seed of each group falls out as a uniform pick, all costs starting
      equal);
    * the EM assignment adds an ownership mask (centroid j is only visible
      to samples whose mesocluster is ``owner[j]``), which makes the joint
      EM decompose into the reference's independent per-mesocluster fits
      while staying a single static-shape XLA program. The fine EM runs
      plain masked Lloyd iterations; under-population repair
      (adjust_centers) is deferred to the unmasked final polish —
      measured recall/balance on 1M clustered rows matched the per-subfit
      reseeding it replaces.

    ``owner`` is (n_clusters,) int32: the owning mesocluster of each fine
    centroid. ``seed_slots`` is (max_quota, n_meso) int32: the fine-centroid
    id of mesocluster m's rank-r seed, or -1 past m's quota. Assignment
    scans row blocks × centroid tiles so the live distance tile is bounded
    regardless of n and n_clusters.
    """
    n, d = X.shape
    n_meso = seed_slots.shape[1]
    rows = jnp.arange(n, dtype=jnp.int32)

    # --- masked k-means++ seeding, one round per quota rank
    def seed_round(r, carry):
        seeds, mind = carry
        slot = seed_slots[r]                             # (n_meso,)
        valid = slot >= 0
        z = (jnp.log(jnp.maximum(mind, 1e-12))
             + jax.random.gumbel(jax.random.fold_in(key, r), (n,), X.dtype))
        segmax = jax.ops.segment_max(z, meso_labels, num_segments=n_meso)
        cand = jnp.where(z == segmax[meso_labels], rows, n)
        pick = jnp.clip(
            jax.ops.segment_min(cand, meso_labels, num_segments=n_meso),
            0, n - 1)                                    # (n_meso,)
        S = X[pick]                                      # (n_meso, d)
        seeds = seeds.at[jnp.where(valid, slot, n_clusters)].set(
            S, mode="drop")
        dnew = jnp.sum((X - S[meso_labels]) ** 2, axis=1)
        upd = valid[meso_labels]
        return seeds, jnp.where(upd, jnp.minimum(mind, dnew), mind)

    centroids0, _ = lax.fori_loop(
        0, seed_slots.shape[0], seed_round,
        (jnp.zeros((n_clusters, d), X.dtype),
         jnp.full((n,), jnp.asarray(1e30, X.dtype))))

    # --- masked balanced EM (row-blocked × centroid-tiled assignment)
    block = min(_ASSIGN_BLOCK, ceildiv(n, 256) * 256)
    ktile = min(_ASSIGN_KTILE, ceildiv(n_clusters, 256) * 256)

    nb = ceildiv(n, block)
    pad = nb * block - n
    Xp = jnp.concatenate([X, jnp.zeros((pad, d), X.dtype)]) if pad else X
    gp = (jnp.concatenate([meso_labels,
                           jnp.full((pad,), -1, meso_labels.dtype)])
          if pad else meso_labels)
    Xb = Xp.reshape(nb, block, d)
    gb = gp.reshape(nb, block)
    w = (gp >= 0).astype(X.dtype)

    nkt = ceildiv(n_clusters, ktile)
    padk = nkt * ktile - n_clusters
    owner_p = (jnp.concatenate([owner, jnp.full((padk,), -2, owner.dtype)])
               if padk else owner)
    ow_tiles = owner_p.reshape(nkt, ktile)

    def assign(C):
        Cp = (jnp.concatenate([C, jnp.zeros((padk, d), C.dtype)])
              if padk else C)
        c_tiles = Cp.reshape(nkt, ktile, d)
        cn_tiles = jnp.sum(c_tiles * c_tiles, axis=2)

        def blk(_, inp):
            xb, grp = inp
            xn = jnp.sum(xb * xb, axis=1)

            def ctile(carry, tile):
                best_d, best_i, base = carry
                Ct, cnt, owt = tile
                # Same expanded-L2 + running-argmin scheme as
                # fused_l2_nn_min_reduce, with the ownership mask folded in
                # before the argmin (the shared helper has no mask hook).
                dtile = jnp.maximum(
                    xn[:, None] + cnt[None, :]
                    - 2.0 * jnp.matmul(xb, Ct.T), 0.0)
                dtile = jnp.where(owt[None, :] == grp[:, None], dtile,
                                  jnp.inf)
                ti = jnp.argmin(dtile, axis=1).astype(jnp.int32)
                td = jnp.take_along_axis(dtile, ti[:, None], axis=1)[:, 0]
                upd = td < best_d
                return (jnp.where(upd, td, best_d),
                        jnp.where(upd, ti + base, best_i),
                        base + ktile), None

            init = (jnp.full((xb.shape[0],), jnp.inf, X.dtype),
                    jnp.zeros((xb.shape[0],), jnp.int32), jnp.int32(0))
            (_, bi, _), _ = lax.scan(ctile, init,
                                     (c_tiles, cn_tiles, ow_tiles))
            return 0, bi

        _, lab = lax.scan(blk, 0, (Xb, gb))
        return lab.reshape(-1)

    def body(_, C):
        labels = assign(C)
        sums = jax.ops.segment_sum(Xp * w[:, None], labels,
                                   num_segments=n_clusters)
        cnts = jax.ops.segment_sum(w, labels, num_segments=n_clusters)
        new = sums / jnp.maximum(cnts, 1.0)[:, None]
        return jnp.where((cnts > 0)[:, None], new, C)

    return lax.fori_loop(0, n_iters, body, centroids0)


@traced
def build_clusters(
    params: KMeansBalancedParams, X, n_clusters: int, key=None
) -> jax.Array:
    """Train ``n_clusters`` balanced centroids on X (ref: build_clusters,
    detail/kmeans_balanced.cuh:703): random-subsample init + balancing EM."""
    X = _as_float(X)
    n = X.shape[0]
    expects(n >= n_clusters, "need at least n_clusters samples")
    if key is None:
        key = params.rng_state.next_key()
    if n_clusters <= 64:
        # Small k: k-means++ seeding avoids the merged-blob local optimum
        # the EM balancing pass cannot escape.
        from raft_tpu.cluster.kmeans import init_plus_plus

        centroids0 = init_plus_plus(key, X, n_clusters)
    else:
        # Large k: evenly strided samples (the reference seeds from the
        # trainset at stride n/k — deterministic and spread out).
        stride = n // n_clusters
        centroids0 = X[:: max(stride, 1)][:n_clusters]
    return _balanced_em(X, centroids0, params.n_iters, n_clusters,
                        jax.default_backend() == "tpu")


@traced
def fit(
    params: KMeansBalancedParams, X, n_clusters: int
) -> jax.Array:
    """Train centroids, hierarchically for large k.

    Ref: kmeans_balanced::fit (cluster/kmeans_balanced.cuh:75) →
    build_hierarchical (detail/kmeans_balanced.cuh): for large problems train
    √k mesoclusters first, then split each mesocluster's members into a share
    of the fine clusters proportional to its population, finally polish with
    balancing EM over the full set.
    """
    X = _as_float(X)
    n, d = X.shape
    expects(n >= n_clusters, "need at least n_clusters samples")

    # Small problems: direct balanced EM.
    if n_clusters <= 256 or n < 4 * n_clusters:
        return build_clusters(params, X, n_clusters)

    # Hierarchical: mesoclusters, then a masked fine EM (device-resident).
    # Host↔device traffic for the whole build: ONE (n_meso,)-int transfer
    # (the mesocluster populations, to compute the static quota split).
    n_meso = int(math.ceil(math.sqrt(n_clusters)))
    meso_params = KMeansBalancedParams(
        n_iters=params.n_iters, metric=params.metric, rng_state=params.rng_state
    )
    meso_centroids = build_clusters(meso_params, X, n_meso)
    meso_labels, counts_dev = _predict_and_count(X, meso_centroids,
                                                 params.metric)
    counts = np.asarray(counts_dev)

    # Fine-cluster quota per mesocluster ∝ population (ref: build_hierarchical
    # computes fine_clusters_nums proportional to mesocluster sizes).
    quota = np.maximum(1, np.floor(counts / n * n_clusters)).astype(np.int64)
    while quota.sum() < n_clusters:
        quota[np.argmax(counts / np.maximum(quota, 1))] += 1
    while quota.sum() > n_clusters:
        cand = np.where(quota > 1)[0]
        quota[cand[np.argmin(counts[cand] / quota[cand])]] -= 1

    owner_h = np.repeat(np.arange(n_meso), quota).astype(np.int32)
    rank_h = np.concatenate([np.arange(q) for q in quota]).astype(np.int32)
    # Round the round count up to a power of two so repeat builds with
    # slightly different quota skew reuse one XLA compilation (extra rounds
    # are all -1 slots, skipped by the valid mask).
    max_q = 1 << (int(quota.max()) - 1).bit_length()
    seed_slots = np.full((max_q, n_meso), -1, np.int32)
    seed_slots[rank_h, owner_h] = np.arange(n_clusters, dtype=np.int32)
    centroids = _hierarchical_fine_em(
        X, meso_labels, jnp.asarray(owner_h), jnp.asarray(seed_slots),
        params.rng_state.next_key(), params.n_iters, n_clusters)

    # Final polish over the full dataset (drops the ownership constraint and
    # re-seeds under-populated clusters — the role of the reference's trailing
    # balancing_em_iters over the full fine set).
    return _balanced_em(X, centroids, max(2, params.n_iters // 2), n_clusters,
                        jax.default_backend() == "tpu")


@traced
def fit_predict(
    params: KMeansBalancedParams, X, n_clusters: int
) -> Tuple[jax.Array, jax.Array]:
    """Ref: kmeans_balanced::fit_predict (cluster/kmeans_balanced.cuh:198)."""
    centroids = fit(params, X, n_clusters)
    return centroids, predict(params, centroids, X)
