"""The profiler's trace of a window, reduced to device numbers.

``recording`` traces a block with the Python tracer off (it would trace
every call of the host path). ``load`` reads the ``.xplane.pb`` the
profiler wrote into plain events ``{"plane", "line", "name", "start_ns",
"dur_ns"}``, keeping the device planes and the harness's own ``bench.*``
annotations. ``reduce`` turns them into

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices that ran any;
- ``window_s``: the window's length (the ``bench.window`` annotation);
- ``device_ops``: seconds per operation (the ``XLA Ops`` line), named
  ``<program>/<op>`` after the program (``XLA Modules`` line) it ran in,
  most first; ``programs``: the same per program;
- ``idle_gaps``: the longest stretches in which the first device ran
  nothing, each named by the innermost host span open at its middle.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re

#: Lines of a device plane that hold one event per operation, and one
#: event per program.
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@contextlib.contextmanager
def recording(log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:") and not plane.startswith(
        "/device:CPU")


def load(log_dir: str) -> list:
    import jax

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % log_dir)
    events = []
    for path in paths:
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            device = _is_device(plane.name)
            for line in plane.lines:
                for ev in line.events:
                    if device or ev.name.startswith("bench."):
                        events.append({"plane": plane.name,
                                       "line": line.name, "name": ev.name,
                                       "start_ns": float(ev.start_ns),
                                       "dur_ns": float(ev.duration_ns)})
    return events


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def annotation(events: list, name: str) -> tuple:
    """``(start_ns, end_ns)`` of the first host annotation ``name``."""
    for ev in events:
        if ev["name"] == name and not _is_device(ev["plane"]):
            return ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
    raise KeyError("no %r annotation in the trace" % name)


def _module_name(name: str) -> str:
    """A program's name without the run's id suffix ("jit_f(12)")."""
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(text: str) -> str:
    """An operation's name from the HLO text the trace gives it
    ("%fusion.6 = s32[...] fusion(...)" -> "fusion.6")."""
    return text.split(" = ", 1)[0].lstrip("%")


def reduce(events: list, host_spans: list = (), top: int = 10) -> dict:
    """The numbers above; ``host_spans`` adds ``(name, start_ns,
    end_ns)`` intervals on the trace's clock (the program's spans, mapped
    by the harness) to the harness's annotations for naming gaps.
    ``busy_s`` is None when no device ran an operation."""
    w0, w1 = annotation(events, "bench.window")
    ops: dict = {}
    programs: dict = {}
    op_time: dict = {}
    dev = sorted((ev for ev in events if _is_device(ev["plane"])),
                 key=lambda ev: (ev["start_ns"], ev["line"] != MODULES_LINE))
    current: dict = {}       # plane -> (program name, end) running now
    for ev in dev:
        iv = (ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
        inside = _clip([iv], w0, w1)
        if ev["line"] == MODULES_LINE:
            current[ev["plane"]] = (_module_name(ev["name"]), iv[1])
            for s, e in inside:
                key = current[ev["plane"]][0]
                programs[key] = programs.get(key, 0.0) + (e - s) * 1e-9
        elif ev["line"] == OPS_LINE:
            ops.setdefault(ev["plane"], []).append(iv)
            prog, end = current.get(ev["plane"], ("", 0.0))
            key = "%s/%s" % (prog if iv[0] < end else "",
                             _op_name(ev["name"]))
            for s, e in inside:
                op_time[key] = op_time.get(key, 0.0) + (e - s) * 1e-9
    busy = {p: _union(_clip(iv, w0, w1)) for p, iv in ops.items()}
    busy = {p: u for p, u in busy.items() if u}

    def most(d):
        return [list(kv) for kv in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    out = {"window_s": (w1 - w0) * 1e-9, "busy_s": None,
           "device_ops": most(op_time), "programs": most(programs),
           "idle_gaps": []}
    if not busy:
        return out
    out["busy_s"] = sum(sum(e - s for s, e in u) for u in busy.values()) \
        * 1e-9 / len(busy)
    first = busy[sorted(busy)[0]]
    edges = [w0] + [x for s, e in first for x in (s, e)] + [w1]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = list(host_spans) + [
        (ev["name"], ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
        for ev in events if not _is_device(ev["plane"])
        and ev["name"] != "bench.window"]
    for s, e in gaps[:top]:
        mid = 0.5 * (s + e)
        inside = [(he - hs, name) for name, hs, he in spans
                  if hs <= mid <= he]
        out["idle_gaps"].append([min(inside)[1] if inside else "none",
                                 (e - s) * 1e-9])
    return out
