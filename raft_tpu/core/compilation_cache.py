"""Persistent XLA compilation cache.

Ref: the role of the reference's precompiled ``libraft.so`` instantiation
layer (SURVEY.md §2.13 — cpp/src template instantiations exist precisely
so downstream users do not recompile the kernels). The TPU analog: XLA's
persistent compilation cache makes every jitted raft_tpu program compile
once per (shape, config) *per cache directory* instead of per process —
a cold 1M-row IVF build is mostly XLA compilation, so warm-equivalent
build times survive process restarts.

The directory is part of what a cached entry is found by, so it is
fixed: ``JAX_COMPILATION_CACHE_DIR`` when the deployment sets it (JAX
reads it into ``jax_compilation_cache_dir`` at import), otherwise
``.jax_cache/`` inside the checkout.
"""

from __future__ import annotations

import os

from raft_tpu.core.logger import logger

#: Where the cache lives when nothing outside the program places it.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    A ``jax_compilation_cache_dir`` that is already set — by
    ``JAX_COMPILATION_CACHE_DIR`` or by the application — is left as it
    is; otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`. Safe to
    call repeatedly."""
    import jax

    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = CHECKOUT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache everything non-trivial: raft_tpu's many small jitted engines
    # individually compile fast but number in the dozens per workload.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    logger.debug("persistent XLA compilation cache at %s", path)
    return path
