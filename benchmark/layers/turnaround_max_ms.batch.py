"""The window's longest host turnaround between two batches, in ms (the
stall finder)."""

from benchmark.turnaround import turnarounds_ms


def read(run):
    t = turnarounds_ms(run)
    return max(t) if t else None
