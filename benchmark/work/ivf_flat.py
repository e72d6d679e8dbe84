"""Work an IVF-Flat search requires, per dispatched batch.

Operations: the coarse probe (every real query against every center) and
one distance per (query, row of a probed list), 2·dim each. Bytes: the
centers, the rows and ids of every list some query of the batch probes
(each read once per batch), the queries and the answers.
"""

from __future__ import annotations

import numpy as np

from benchmark.roofline import probed_lists


def count(index, search_params: dict, batches: list, k: int) -> dict:
    sizes = np.asarray(index.list_sizes).astype(np.int64)
    n_lists, dim = index.centers.shape
    n_probes = min(int(search_params["n_probes"]), n_lists)
    row_bytes = dim * index.data.dtype.itemsize + index.indices.dtype.itemsize
    flops = nbytes = 0
    for q, probes in zip(batches,
                         probed_lists(index.centers, batches, n_probes)):
        nq = len(q)
        flops += 2 * nq * n_lists * dim + 2 * dim * int(sizes[probes].sum())
        nbytes += (4 * n_lists * dim
                   + row_bytes * int(sizes[np.unique(probes)].sum())
                   + 4 * nq * dim + 8 * nq * k)
    return {"flops": float(flops), "bytes": float(nbytes)}
