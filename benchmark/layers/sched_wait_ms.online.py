"""Mean milliseconds a request waited in the scheduler's queue: the
program's ``queue_wait`` spans, one per answered request."""

from benchmark.spans import children, traced


def read(run):
    waits = [children(r)["queue_wait"].duration for r in traced(run)]
    return 1e3 * sum(waits) / len(waits) if waits else None
