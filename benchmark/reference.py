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
that every number of queries runs the same compiled programs.
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


def exact_knn(X, queries: np.ndarray, k: int, tile: int = 125_000,
              q_tile: int = 1024, precision: str = "highest"):
    """Exact squared-L2 top-k ``(distances, ids)`` of ``queries`` over
    the rows of ``X``, as host arrays, best first."""
    dev = next(iter(X.devices()))
    tiles = [(jnp.int32(s), X[s:s + tile])
             for s in range(0, X.shape[0], tile)]
    out_d, out_i = [], []
    for qs in range(0, len(queries), q_tile):
        q = jax.device_put(_tile(queries, qs, q_tile).astype(np.float32),
                           dev)
        best = None
        for base, x in tiles:
            d, i = _exact_tile(q, x, base, k, precision)
            best = (d, i) if best is None else _merge(*best, d, i, k)
        out_d.append(np.asarray(best[0]))
        out_i.append(np.asarray(best[1]))
    n = len(queries)
    return np.concatenate(out_d)[:n], np.concatenate(out_i)[:n]


@jax.jit
def _pair_tile(q, rows):
    diff = q[:, None, :] - rows
    return jnp.sum(diff * diff, axis=2)


def pair_distances(X, queries: np.ndarray, ids: np.ndarray,
                   q_tile: int = 1024) -> np.ndarray:
    """Squared L2 distance of each ``queries[r]`` to each row
    ``X[ids[r, j]]`` (ids clipped into range; the caller flags the ones
    that were not)."""
    dev = next(iter(X.devices()))
    n = X.shape[0]
    out = []
    for s in range(0, len(queries), q_tile):
        q = jax.device_put(_tile(queries, s, q_tile).astype(np.float32), dev)
        i = jax.device_put(np.clip(_tile(ids, s, q_tile), 0, n - 1)
                           .astype(np.int32), dev)
        out.append(np.asarray(_pair_tile(q, X[i])))
    return np.concatenate(out)[:len(queries)]
