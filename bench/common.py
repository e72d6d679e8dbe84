"""Timing harness.

Ref: cpp/bench/common/benchmark.hpp:93-148 — the reference times with
cudaEvents and flushes L2 between iterations. JAX dispatch is
asynchronous, so every timed region here ends in
``jax.block_until_ready`` on what the work returned (a timing without it
measures the enqueue). Ops too short to time alone run ``iters`` times
inside one jitted ``lax.scan``, and each metric is the median of ≥5
repeats with its spread, the regression-grade contract of the
reference's gbench fixture.
"""

from __future__ import annotations

import json
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (bf16 MXU FLOP/s, HBM bandwidth).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}

# Auto-sized scans run at least this long, so a short op's time is not
# lost in the per-dispatch overhead.
_MIN_SCAN_S = 0.05


def device_peaks(device=None) -> dict:
    """The peak table entry of ``device`` (default: the first device).
    A device that is not in the table is an error, not a default."""
    kind = (device or jax.devices()[0]).device_kind
    if kind not in PEAKS:
        raise KeyError("no published peaks for device kind %r (known: %s)"
                       % (kind, ", ".join(sorted(PEAKS))))
    return PEAKS[kind]


def _arrays(obj) -> list:
    """Every array reachable from ``obj``: pytree leaves, plus one level
    of fields of plain dataclasses such as the IVF Index (not pytrees)."""
    out = []
    for leaf in jax.tree_util.tree_leaves(obj):
        if isinstance(leaf, (jax.Array, np.ndarray)):
            out.append(leaf)
        elif hasattr(leaf, "__dict__"):
            out.extend(v for v in vars(leaf).values()
                       if isinstance(v, (jax.Array, np.ndarray)))
    return out


def block(out) -> None:
    """Wait until the device has finished every array in ``out``."""
    jax.block_until_ready(_arrays(out))


def _checksum(out) -> jax.Array:
    s = jnp.float32(0)
    for leaf in jax.tree_util.tree_leaves(out):
        s = s + jnp.sum(leaf.astype(jnp.float32))
    return s


def _perturb(x: jax.Array, i: jax.Array) -> jax.Array:
    """Make the iteration's input depend on the step index so XLA cannot
    hoist the body out of the scan, without changing the op's character:
    floats get +i·1e-6, ints alternate the low bit."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        return x + i.astype(x.dtype) * jnp.asarray(1e-6, x.dtype)
    return x + (i % 2).astype(x.dtype)


def _make_scan(fn, iters):
    @jax.jit
    def run(x, *extra):
        def body(acc, i):
            xi = jax.tree_util.tree_map(lambda a: _perturb(a, i), x)
            return acc + _checksum(fn(xi, *extra)), None

        acc, _ = lax.scan(body, jnp.float32(0),
                          jnp.arange(iters, dtype=jnp.int32))
        return acc

    return run


def scan_stats(fn: Callable, x, extra: Sequence = (), iters: int = 0,
               repeats: int = 5) -> dict:
    """Median/min/max seconds per application of ``fn(x, *extra)``.
    ``iters=0`` sizes the scan so one repeat runs at least
    ``_MIN_SCAN_S`` (16 to 1024 iterations). The jitted scan is built and
    warmed once per iters value; only the repeats are timed."""

    def timed(run, n):
        t0 = time.perf_counter()
        jax.block_until_ready(run(x, *extra))
        return (time.perf_counter() - t0) / n

    if iters == 0:
        probe_run = _make_scan(fn, 16)
        jax.block_until_ready(probe_run(x, *extra))  # compile + warm
        probe = max(timed(probe_run, 16), 1e-6)
        iters = int(min(1024, max(16, _MIN_SCAN_S / probe)))
    run = _make_scan(fn, iters)
    jax.block_until_ready(run(x, *extra))  # compile + warm once
    times = sorted(timed(run, iters) for _ in range(repeats))
    return {
        "median_s": float(np.median(times)),
        "min_s": times[0],
        "max_s": times[-1],
        "iters": iters,
        "repeats": repeats,
    }


def scan_time(fn: Callable, x, extra: Sequence = (), iters: int = 64,
              repeats: int = 3) -> float:
    """Median seconds per application of ``fn(x, *extra)`` (see
    scan_stats), at a fixed iters for the legacy bench surface."""
    return scan_stats(fn, x, extra, iters=iters, repeats=repeats)["median_s"]


def wall_stats(fn: Callable, repeats: int = 3) -> dict:
    """Wall-clock stats for host-driving functions (index builds, fits)
    that cannot scan; the first call (compile) is excluded."""
    block(fn())
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        block(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return {"median_s": float(np.median(times)), "min_s": times[0],
            "max_s": times[-1], "repeats": repeats}


def wall_time(fn: Callable, repeats: int = 2) -> float:
    return wall_stats(fn, repeats=repeats)["median_s"]


def report(family: str, name: str, seconds: float, items: float = 0.0,
           unit: str = "items/s", **params) -> dict:
    rec = {
        "family": family,
        "bench": name,
        "ms": round(seconds * 1e3, 4),
        **({"throughput": round(items / seconds, 1), "unit": unit}
           if items else {}),
        "params": params,
    }
    print(json.dumps(rec), flush=True)
    return rec
