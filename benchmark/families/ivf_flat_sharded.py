"""IVF-Flat sharded over the first ``index["shards"]`` devices, as a
deployment builds and serves it: ``sharded_ivf_flat_build`` on a 1-axis
mesh and ``Searcher.ivf_flat`` with that mesh. ``index["build"]`` and
``index["searcher"]`` pass the deployment's own choices (placement,
merge engine, ...) through unchanged."""

from __future__ import annotations

import jax
import numpy as np


def _mesh(index: dict):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:int(index["shards"])]), ("data",))


def build(X, index: dict):
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.parallel import sharded_ivf_flat_build

    idx = sharded_ivf_flat_build(
        _mesh(index), ivf_flat.IndexParams(**index["index_params"]), X,
        **index.get("build", {}))
    jax.block_until_ready((idx.data, idx.indices, idx.list_sizes))
    return idx


def searcher(idx, index: dict):
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.serve import Searcher

    return Searcher.ivf_flat(
        idx, ivf_flat.SearchParams(**index["search_params"]),
        mesh=_mesh(index), **index.get("searcher", {}))
