"""Mean milliseconds per batch the host takes to launch the searched
program: the program's ``enqueue`` spans (Python glue, the query upload
and the launch)."""

from benchmark.turnaround import enqueue_ms


def read(run):
    return enqueue_ms(run)
