"""Seconds of the index build (``<family>.build``), on the harness clock,
ending in ``block_until_ready`` of the index."""


def read(run):
    return run.timings.get("build_s")
