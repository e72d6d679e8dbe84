"""The plain reference: exact L2 search in ``jax.numpy``.

A copy of ``chip_smoke.py``'s exact search, which imports nothing of the
program: a matmul and ``lax.top_k`` per (query tile, corpus tile), merged
with one more ``top_k``, at ``Precision.HIGHEST`` unless a caller asks for
less: the control of ``correct`` computes the same search with the
products split into bfloat16 passes by hand ("high": three passes,
"default": one), so that it reads alike on every backend.
``pair_distances`` gives the squared distance of given (query, row) pairs
in the difference form, which has no cancellation: the yardstick a served
distance is held to. Both pad the last query tile to a whole tile, so
that every number of queries runs the same compiled programs, and both
take a corpus that one device holds or that is row-sharded over several
(one too large for a chip): a shard's rows are read on its own device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _gram(q, x, precision: str):
    """``q @ x.T`` at "highest", or in bfloat16 passes with float32
    accumulation: "high" adds the two cross terms of the split
    ``a = hi + lo`` to the ``hi·hi`` pass of "default"."""
    if precision == "highest":
        return jnp.matmul(q, x.T, precision=lax.Precision.HIGHEST)

    def one_pass(a, b):
        return jnp.matmul(a, b.T, preferred_element_type=jnp.float32)

    def split(a):
        # reduce_precision, not a round trip through astype: XLA may drop
        # the rounding of a bf16 round trip (excess precision), which
        # would leave the low part zero.
        hi = lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)

    (qh, ql), (xh, xl) = split(q), split(x)
    g = one_pass(qh, xh)
    if precision == "high":
        g = g + one_pass(qh, xl) + one_pass(ql, xh)
    elif precision != "default":
        raise ValueError("precision must be highest, high or default")
    return g


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _exact_tile(q, x, base, k, precision):
    d = (jnp.sum(q * q, axis=1)[:, None] + jnp.sum(x * x, axis=1)[None, :]
         - 2.0 * _gram(q, x, precision))
    neg, i = lax.top_k(-d, k)
    return -neg, i + base


def _tile(a: np.ndarray, start: int, size: int) -> np.ndarray:
    """Rows ``[start, start + size)`` of ``a``, zero-padded to ``size``."""
    t = np.asarray(a[start:start + size])
    return np.pad(t, ((0, size - len(t)),) + ((0, 0),) * (t.ndim - 1))


@functools.partial(jax.jit, static_argnames=("k",))
def _merge(d0, i0, d1, i1, k):
    d = jnp.concatenate([d0, d1], axis=1)
    i = jnp.concatenate([i0, i1], axis=1)
    neg, pos = lax.top_k(-d, k)
    return -neg, jnp.take_along_axis(i, pos, axis=1)


def _shards(X) -> list:
    """``[(first row, that device's rows, device)]`` of ``X`` in row
    order: one entry for a one-device array, one per shard for an array
    sharded by rows (a shard held on several devices counts once)."""
    parts = {}
    for sh in X.addressable_shards:
        if sh.data.shape[1:] != X.shape[1:]:
            raise ValueError("the corpus may be sharded by rows only")
        parts.setdefault(sh.index[0].start or 0, (sh.data, sh.device))
    return [(first, x, dev) for first, (x, dev) in sorted(parts.items())]


def exact_knn(X, queries: np.ndarray, k: int, tile: int = 125_000,
              q_tile: int = 1024, precision: str = "highest"):
    """Exact squared-L2 top-k ``(distances, ids)`` of ``queries`` over
    the rows of ``X``, as host arrays, best first. ``X`` is one device's
    array or row-sharded over several: each corpus tile is scored on the
    device that holds it, every tile of every query tile is dispatched
    before a result is read, and the tiles are merged in row order on the
    first shard's device. Where ``tile`` divides each shard's rows the
    tiles are the one-device tiles, and the answer is the one-device
    answer, bit for bit."""
    tiles = [(dev, jnp.int32(first + s), x[s:s + tile])
             for first, x, dev in _shards(X)
             for s in range(0, x.shape[0], tile)]
    home = tiles[0][0]
    out = []
    for qs in range(0, len(queries), q_tile):
        q = _tile(queries, qs, q_tile).astype(np.float32)
        on = {dev: jax.device_put(q, dev) for dev in {t[0] for t in tiles}}
        parts = [_exact_tile(on[dev], x, base, k, precision)
                 for dev, base, x in tiles]
        best = parts[0]
        for d, i in parts[1:]:
            best = _merge(*best, *jax.device_put((d, i), home), k)
        out.append(best)
    n = len(queries)
    return (np.concatenate([np.asarray(d) for d, _ in out])[:n],
            np.concatenate([np.asarray(i) for _, i in out])[:n])


@jax.jit
def _pair_tile(q, rows):
    diff = q[:, None, :] - rows
    return jnp.sum(diff * diff, axis=2)


def pair_distances(X, queries: np.ndarray, ids: np.ndarray,
                   q_tile: int = 1024) -> np.ndarray:
    """Squared L2 distance of each ``queries[r]`` to each row
    ``X[ids[r, j]]`` (ids clipped into range; the caller flags the ones
    that were not). Of a row-sharded ``X``, each row is gathered on the
    device that holds it; every tile is dispatched before one is read."""
    n = X.shape[0]
    shards = _shards(X)
    parts = []
    for s in range(0, len(queries), q_tile):
        q = _tile(queries, s, q_tile).astype(np.float32)
        i = np.clip(_tile(ids, s, q_tile), 0, n - 1).astype(np.int32)
        for first, x, dev in shards:
            own = (i >= first) & (i < first + x.shape[0])
            local = np.where(own, i - first, 0).astype(np.int32)
            parts.append((own, _pair_tile(jax.device_put(q, dev),
                                          x[jax.device_put(local, dev)])))
    out = np.zeros((len(parts) // len(shards) * q_tile,) + ids.shape[1:],
                   np.float32)
    for t, (own, d) in enumerate(parts):
        rows = out[t // len(shards) * q_tile:][:q_tile]
        rows[own] = np.asarray(d)[own]
    return out[:len(queries)]
