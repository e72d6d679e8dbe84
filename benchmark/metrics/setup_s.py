"""Seconds from process start to the window's first request: imports,
data, index build and bucket warmup (compiles, or reads from the
persistent compilation cache)."""


def read(run):
    return run.setup_s
