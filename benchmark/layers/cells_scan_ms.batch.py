"""Device milliseconds per dispatched batch in the operations under the
``ivf_flat.cells_scan`` named scope (the packed-cells Pallas scan),
averaged over the devices that ran any operation in the traced window
(``trace.reduce``'s ``scopes``). None where no operation ran under it."""

from benchmark.spans import batches

SCOPE = "ivf_flat.cells_scan"


def read(run):
    t = run.trace
    per = [s.get(SCOPE, 0.0) for s in (t or {}).get("scopes", {}).values()]
    n = len(batches(run)) if any(per) else 0
    return 1e3 * sum(per) / len(per) / n if n else None
