"""Seconds of ``serve.bucketing.warmup`` over the buckets the traffic file
names, on the harness clock."""


def read(run):
    return run.timings.get("warmup_s")
