"""Mean host turnaround between two batches, in ms: from one batch's
``device_wait`` end to the next batch's ``enqueue`` end."""

from benchmark.turnaround import turnarounds_ms


def read(run):
    t = turnarounds_ms(run)
    return sum(t) / len(t) if t else None
