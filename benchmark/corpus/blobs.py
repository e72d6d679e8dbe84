"""Gaussian blobs, the corpus generator of ``chip_smoke.py``.

The same distribution as ``raft_tpu.random.make_blobs`` (its copy, so that
a change to the program cannot change the benchmark's data): cluster
centers uniform in ``center_box``, rows assigned to clusters in turn and
shuffled, isotropic normal noise of ``std``. Centers and rows come from
the configuration's own ``seed``: a deployment's data set is fixed, so
every run builds the same index and does the same work, and a run's
``--seed`` draws only its traffic. One jitted program makes ``rows +
queries`` rows; the last ``queries`` are held out as the pool the traffic
draws its queries from, so queries follow the corpus.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _blobs(key, n: int, dim: int, n_clusters: int, std: float, lo: float,
           hi: float):
    centers_key, kl, kn = jax.random.split(key, 3)
    centers = jax.random.uniform(centers_key, (n_clusters, dim), jnp.float32,
                                 minval=lo, maxval=hi)
    labels = jax.random.permutation(
        kl, jnp.arange(n, dtype=jnp.int32) % n_clusters)
    noise = std * jax.random.normal(kn, (n, dim), jnp.float32)
    return jnp.take(centers, labels, axis=0) + noise


def make(corpus: dict):
    n, q = int(corpus["rows"]), int(corpus["queries"])
    lo, hi = corpus["center_box"]
    data = _blobs(jax.random.PRNGKey(int(corpus["seed"])), n + q,
                  int(corpus["dim"]), int(corpus["clusters"]),
                  float(corpus["std"]), float(lo), float(hi))
    pool = np.asarray(jax.device_get(data[n:]))
    rows = jax.block_until_ready(data[:n])
    return rows, pool
