"""Mean milliseconds of garbage-collector pauses per batch: the
program's ``gc`` spans."""

from benchmark.turnaround import gc_ms


def read(run):
    return gc_ms(run)
