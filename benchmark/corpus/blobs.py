"""Gaussian blobs, the corpus generator of ``chip_smoke.py``.

The same distribution as ``raft_tpu.random.make_blobs`` (its copy, so that
a change to the program cannot change the benchmark's data): cluster
centers uniform in ``center_box``, rows assigned to clusters in turn and
shuffled, isotropic normal noise of ``std``. Centers and rows come from
the configuration's own ``seed``: a deployment's data set is fixed, so
every run builds the same index and does the same work, and a run's
``--seed`` draws only its traffic. One draw makes ``rows + queries``
rows; the last ``queries`` are held out as the pool the traffic draws its
queries from, so queries follow the corpus.

``shards`` (default 1) row-shards the rows over the first ``shards``
devices, for a corpus no one chip holds. Each chip draws only its own
rows, block by block: the noise of row ``r``, column ``c`` is the
threefry block at counter ``r * dim + c``, which is how
``jax.random.normal`` numbers the elements of one ``(rows + queries,
dim)`` draw with ``jax_threefry_partitionable`` (on by default). So the
rows and the pool are the same, bit for bit, whatever ``shards`` is, and
no chip ever holds more than its rows and one block. The shuffled
cluster labels come first, in a program of their own, so that the sort's
scratch is gone before the rows are made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.extend.random import threefry2x32_p
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: Rows a chip draws at a time.
BLOCK_ROWS = 1 << 18


def make(corpus: dict):
    """``(rows, pool)``: the corpus, row-sharded over the first
    ``corpus["shards"]`` devices, and the held-out queries on the host."""
    n, q = int(corpus["rows"]), int(corpus["queries"])
    lo, hi = corpus["center_box"]
    shards = int(corpus.get("shards", 1))
    devices = jax.devices()[:shards]
    if len(devices) < shards or n % shards:
        raise ValueError("corpus.shards %d needs %d devices (found %d) and "
                         "rows divisible by it (%d rows)"
                         % (shards, shards, len(devices), n))
    mesh = Mesh(np.array(devices), ("data",))
    key = jax.device_put(jax.random.PRNGKey(int(corpus["seed"])),
                         NamedSharding(mesh, P()))
    n_clusters = int(corpus["clusters"])
    labels = _labels(key, mesh, n + q, n_clusters)
    rows, pool = _rows(key, labels, mesh, n, q, int(corpus["dim"]),
                       n_clusters, float(corpus["std"]), float(lo),
                       float(hi))
    del labels
    return jax.block_until_ready(rows), np.asarray(jax.device_get(pool))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _labels(key, mesh: Mesh, n: int, n_clusters: int):
    """The shuffled cluster label of every row, on every device."""
    _, kl, _ = jax.random.split(key, 3)
    labels = jax.random.permutation(
        kl, jnp.arange(n, dtype=jnp.int32) % n_clusters)
    return lax.with_sharding_constraint(labels, NamedSharding(mesh, P()))


def _counters(first, count: int, dim: int):
    """``(hi, lo)`` uint32 halves of ``r * dim + c`` for rows ``r`` in
    ``[first, first + count)`` and columns ``c``: the element numbers of
    a ``(rows, dim)`` draw, in 32-bit arithmetic (rows < 2**31)."""
    if dim >= 2 ** 15:
        raise ValueError("dim %d: the counters need dim < 2**15" % dim)
    u32 = jnp.uint32
    r = first.astype(u32) + lax.iota(u32, count)
    x = (r >> 16)[:, None] * u32(dim)                 # < 2**31
    y = ((r & 0xFFFF) * u32(dim))[:, None] + lax.iota(u32, dim)[None, :]
    lo = (x << 16) + y
    hi = (x >> 16) + (lo < (x << 16)).astype(u32)     # the carry
    return hi, lo


def _normal_rows(kn, first, count: int, dim: int):
    """Rows ``[first, first + count)`` of ``jax.random.normal(kn, (rows,
    dim), float32)``, computed without the rows around them: its bits
    (``threefry2x32`` at the element's counter), its uniform on
    ``[nextafter(-1, 0), 1)`` and its ``sqrt(2) * erf_inv``, op for op."""
    f32, u32 = np.float32, np.uint32
    bits1, bits2 = threefry2x32_p.bind(kn[0], kn[1],
                                       *_counters(first, count, dim))
    floats = lax.bitcast_convert_type(
        lax.shift_right_logical(bits1 ^ bits2, u32(32 - 23))
        | np.array(1.0, f32).view(u32), f32) - f32(1.0)
    minval = np.full((1, 1), np.nextafter(f32(-1.0), f32(0.0)), f32)
    maxval = np.full((1, 1), 1.0, f32)
    u = lax.max(minval, floats * (maxval - minval) + minval)
    return lax.mul(np.array(np.sqrt(2), f32), lax.erf_inv(u))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8, 9))
def _rows(key, labels, mesh: Mesh, n: int, q: int, dim: int,
          n_clusters: int, std: float, lo: float, hi: float):
    """``(the first n rows, row-sharded over mesh; the q after them, on
    every device)``."""
    centers_key, _, kn = jax.random.split(key, 3)
    centers = jax.random.uniform(centers_key, (n_clusters, dim), jnp.float32,
                                 minval=lo, maxval=hi)
    m = n // mesh.size

    def draw(first, count):
        lab = lax.dynamic_slice_in_dim(labels, first, count)
        return (jnp.take(centers, lab, axis=0)
                + std * _normal_rows(kn, first, count, dim))

    def shard():
        # Block by block into the shard's rows: a whole shard at once
        # would keep a padded copy of it as scratch.
        first, block = lax.axis_index("data") * m, min(m, BLOCK_ROWS)

        def one(i, out):
            at = jnp.minimum(i * block, m - block)     # the last overlaps
            return lax.dynamic_update_slice_in_dim(
                out, draw(first + at, block), at, axis=0)

        return lax.fori_loop(0, -(-m // block), one,
                             jnp.zeros((m, dim), jnp.float32))

    rows = jax.shard_map(shard, mesh=mesh, in_specs=(), out_specs=P("data"),
                         check_vma=False)()
    pool = lax.with_sharding_constraint(draw(jnp.int32(n), q),
                                        NamedSharding(mesh, P()))
    return rows, pool
