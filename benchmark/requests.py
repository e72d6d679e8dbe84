"""One request of a window, as the load generator saw it.

``due`` is when the request was due to be sent (an open loop's schedule;
a closed-loop client sends it at once), ``sent`` when ``submit`` was
called, ``done`` when the loop saw its ticket complete. All three read
the loop's clock. Latency is ``done - due``.
"""

from __future__ import annotations

import contextlib
import math

import numpy as np


class Request:
    __slots__ = ("rows", "due", "sent", "done", "ticket", "error")

    def __init__(self, rows: np.ndarray, due: float):
        self.rows = rows          # pool rows of its queries
        self.due = due
        self.sent = math.nan
        self.done = math.nan
        self.ticket = None
        self.error = None

    @property
    def answered(self) -> bool:
        return self.error is None and not math.isnan(self.done)


def no_mark(name: str):
    """The annotation hook of an untraced run."""
    return contextlib.nullcontext()


def cyclic_rows(start: int, n: int, pool_size: int) -> np.ndarray:
    return (start + np.arange(n)) % pool_size


def stamp(waiting: list, now: float) -> list:
    """Stamp ``done`` on every request whose ticket completed; returns
    the ones still waiting."""
    still = []
    for r in waiting:
        if r.ticket.done:
            r.done = now
        else:
            still.append(r)
    return still
