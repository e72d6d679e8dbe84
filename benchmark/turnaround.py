"""The host's turnaround between two device programs, read from the
program's span tree per batch.

With a recording tracer the program's ``device_dispatch`` span holds two
measured children: ``enqueue`` (the host launches the searched program)
and ``device_wait`` (the fence until the device finishes it). Each batch
also carries one ``gc`` child per collector pause since the previous
batch finished, and each member request's root a ``batch`` attribute.
A program without those spans reads None here.
"""

from __future__ import annotations

from benchmark.spans import batches


def _child(span, name: str):
    for c in span.children:
        if c.name == name:
            return c
    return None


def boundaries(run) -> list:
    """``[(enqueue, device_wait)]`` spans of each batch, in dispatch
    order."""
    out = []
    for _, ch in batches(run):
        dd = ch.get("device_dispatch")
        if dd is None:
            continue
        enq, wait = _child(dd, "enqueue"), _child(dd, "device_wait")
        if enq is not None and wait is not None:
            out.append((enq, wait))
    return out


def enqueue_ms(run):
    """Mean milliseconds a batch's ``enqueue`` took; None without it."""
    per = [e.duration for e, _ in boundaries(run)]
    return 1e3 * sum(per) / len(per) if per else None


def turnarounds_ms(run) -> list:
    """Milliseconds from each batch's ``device_wait`` end to the next
    batch's ``enqueue`` end: in a closed loop, the host time in which the
    device has nothing queued."""
    b = boundaries(run)
    return [1e3 * (nxt.end - wait.end)
            for (_, wait), (nxt, _) in zip(b, b[1:])]


def gc_ms(run):
    """Mean milliseconds of collector pauses per batch (the first
    member's ``gc`` children), over every batch but the first: a batch
    carries the pauses since the previous one finished, so the first
    one's reach back into set-up. None where the program records none."""
    per = []
    for members, _ in batches(run)[1:]:
        root = members[0].ticket.span
        if "batch" in root.attrs:
            per.append(sum(c.duration for c in root.children
                           if c.name == "gc"))
    return 1e3 * sum(per) / len(per) if per else None
