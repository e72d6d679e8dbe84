"""IVF-Flat as a deployment builds and serves it: ``ivf_flat.build`` and
``Searcher.ivf_flat``."""

from __future__ import annotations

import jax


def build(X, index: dict):
    from raft_tpu.neighbors import ivf_flat

    idx = ivf_flat.build(ivf_flat.IndexParams(**index["index_params"]), X)
    jax.block_until_ready((idx.data, idx.indices, idx.list_sizes))
    return idx


def searcher(idx, index: dict):
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.serve import Searcher

    return Searcher.ivf_flat(
        idx, ivf_flat.SearchParams(**index["search_params"]))
