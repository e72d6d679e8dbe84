"""What decides ``correct``: every answer of the window against the reference.

Once the window has closed and the program's state is freed, every
answered request's answer, as the scheduler handed it back after its
split, is held to the exact reference over the same corpus. The
reference searches each distinct query once (the traffic draws its
queries from a fixed pool, so the window repeats them). The numbers
cover batching, padding and the split, the searcher, the coarse probe,
the scan and select:

- ``bad_ids``: served ids outside the corpus or repeated within an answer
  (an exact count, limit 0);
- ``dist_err``: the widest gap between a served distance and the exact
  squared distance of the id served beside it, as a share of the query's
  exact k-th distance. An answer altered or handed to the wrong request
  reads large here;
- ``dist_err_p50``: the median of the same gaps;
- ``recall_short``: 1 - recall@k of the served ids against the exact
  top-k, over every answer.

A configuration's ``correct.limits`` names the numbers compared and the
limit of each; PERF.md gives the readings each limit was set from.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import exact_knn, pair_distances


def reference(X, pool: np.ndarray, rows: np.ndarray, k: int,
              precision: str = "highest"):
    """Exact ``(distances, ids)`` for the query of each answer (``rows``
    index ``pool``), searched once per distinct query."""
    uniq, inv = np.unique(rows, return_inverse=True)
    d, i = exact_knn(X, pool[uniq], k, precision=precision)
    return d[inv], i[inv]


def numbers(X, pool: np.ndarray, rows: np.ndarray, dist: np.ndarray,
            ids: np.ndarray, truth) -> dict:
    """The numbers above for served ``(dist, ids)``, one row per answered
    query ``pool[rows]``; ``truth`` is :func:`reference`'s."""
    ref_d, ref_i = truth
    n = X.shape[0]
    out_of_range = (ids < 0) | (ids >= n)
    srt = np.sort(ids, axis=1)
    repeated = (srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)
    exact = pair_distances(X, pool[rows], ids)
    scale = np.maximum(ref_d[:, -1:], np.finfo(np.float32).tiny)
    gap = np.abs(dist.astype(np.float64) - exact) / scale
    gap = gap[~out_of_range & np.isfinite(gap)]
    hits = (ids[:, :, None] == ref_i[:, None, :]).any(axis=2)
    return {
        "bad_ids": int(out_of_range.sum() + repeated.sum()),
        "dist_err": float(gap.max()) if gap.size else float("nan"),
        "dist_err_p50": float(np.median(gap)) if gap.size else float("nan"),
        "recall_short": float(1.0 - hits.mean()),
    }


def checks(nums: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` for each limited number; a number
    that could not be read (NaN) fails its check."""
    return {name: {"value": nums[name], "limit": limit}
            for name, limit in limits.items()}


def passed(checked: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checked.values())
