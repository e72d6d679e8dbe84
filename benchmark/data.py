"""The corpus and the query pool of a configuration, made on the device.

A configuration's ``corpus.generator`` names a file ``corpus/<name>.py``
whose ``make(corpus)`` returns ``(rows, pool)``: the indexed rows on the
device and the held-out query pool on the host, both from one draw of the
configuration's own ``corpus.seed``, the same in every run.
"""

from __future__ import annotations

from benchmark import cells


def corpus(cfg: dict):
    """``(rows on the device, host query pool)`` of ``cfg["corpus"]``."""
    gen = cells.load_module("corpus", cfg["corpus"]["generator"])
    return gen.make(cfg["corpus"])
