"""99th percentile (nearest rank) of every request of the window, from
the time it was due to its answer; a request that failed or was never
answered counts as infinitely late."""

import math


def read(run):
    lat = sorted(r.done - r.due if r.answered else math.inf
                 for r in run.requests)
    if not lat:
        return None
    p99 = lat[max(0, math.ceil(0.99 * len(lat)) - 1)]
    return 1e3 * p99 if math.isfinite(p99) else None
