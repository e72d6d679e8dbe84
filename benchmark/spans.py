"""The program's request spans (``raft_tpu.obs.Tracer``), read per batch.

With a recording tracer every answered request's ticket carries its span
tree: ``queue_wait``, then the batch's ``batch_assembly``,
``device_dispatch``, ``device_get`` and ``result_merge``, measured once
per batch and copied into each member's tree. Members of one batch share
the same ``batch_assembly`` interval, which is how they are grouped here.
"""

from __future__ import annotations


def children(request) -> dict:
    return {c.name: c for c in request.ticket.span.children}


def traced(run) -> list:
    """Answered requests that carry a recorded span tree."""
    return [r for r in run.requests
            if r.answered and r.ticket.span.recording]


def batches(run) -> list:
    """``[(members, spans of the first member by name)]`` in dispatch
    order."""
    groups: dict = {}
    for r in traced(run):
        ch = children(r)
        asm = ch.get("batch_assembly")
        if asm is None:
            continue
        key = (asm.start, asm.end)
        if key not in groups:
            groups[key] = ([], ch)
        groups[key][0].append(r)
    return [groups[key] for key in sorted(groups)]


def host_ms_per_batch(run):
    """Mean host milliseconds per batch around the device: assembly, the
    result pull and the per-request split; None without spans."""
    per = [sum(ch[n].duration for n in
               ("batch_assembly", "device_get", "result_merge") if n in ch)
           for _, ch in batches(run)]
    return 1e3 * sum(per) / len(per) if per else None
