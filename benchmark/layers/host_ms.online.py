"""Mean host milliseconds per dispatched batch around the device: the
program's ``batch_assembly``, ``device_get`` and ``result_merge`` spans."""

from benchmark.spans import host_ms_per_batch


def read(run):
    return host_ms_per_batch(run)
