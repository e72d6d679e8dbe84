"""The yardstick of the search kernels' roofline share.

``work/<family>.py`` counts the operations and bytes a batch's search
*requires*, from the index's list sizes and the lists the batch's real
(not padded) queries probe, never from a kernel's padded blocks; the least
time is the larger of operations over the chip's bf16 peak and bytes over
its HBM bandwidth (``peaks.json``, keyed by ``device_kind``).
"""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.cells import HERE


def peaks(device_kind: str) -> dict:
    """The peak entry of ``device_kind``; a device not in the table is an
    error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError("no published peaks for device kind %r (known: %s)"
                       % (device_kind, ", ".join(sorted(table))))
    return table[device_kind]


@functools.partial(jax.jit, static_argnames=("n_probes",))
def _probe(q, centers, n_probes):
    d = (jnp.sum(q * q, axis=1)[:, None]
         + jnp.sum(centers * centers, axis=1)[None, :]
         - 2.0 * jnp.matmul(q, centers.T, precision=lax.Precision.HIGHEST))
    return lax.top_k(-d, n_probes)[1]


def probed_lists(centers, batches: list, n_probes: int,
                 tile: int = 1024) -> list:
    """For each batch of queries, the ``n_probes`` lists nearest each of
    its queries (L2 to the centers): ``(n_queries, n_probes)`` arrays.
    All batches go through one program shape, ``tile`` rows at a time."""
    allq = np.concatenate(batches).astype(np.float32)
    pad = (-len(allq)) % tile
    allq = np.concatenate([allq, np.zeros((pad, allq.shape[1]), np.float32)])
    probes = np.concatenate([
        np.asarray(_probe(jnp.asarray(allq[s:s + tile]), centers, n_probes))
        for s in range(0, len(allq), tile)])
    ends = np.cumsum([len(b) for b in batches])
    return np.split(probes[:ends[-1]], ends[:-1])


def least_time(work: dict, peak: dict) -> dict:
    """``{"seconds", "bound"}``: the least time the chip could take for
    ``work`` and which of its two limits sets it."""
    t_ops = work["flops"] / peak["bf16_flops"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_bytes),
            "bound": "flops" if t_ops >= t_bytes else "bytes",
            "flops_s": t_ops, "bytes_s": t_bytes}
