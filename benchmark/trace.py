"""The profiler's trace of a window, reduced to device numbers.

``recording`` traces a block with the Python tracer off (it would trace
every call of the host path). ``load`` reads the ``.xplane.pb`` the
profiler wrote into plain events ``{"plane", "line", "name", "start_ns",
"dur_ns"}``, keeping the device planes and the harness's own ``bench.*``
annotations, and adds one ``{"plane": "/host:metadata", "name":
<program>, "op_names": {<operation>: <op_name>}}`` per program that ran
on a device: the ``op_name`` scope path of each of its operations, read
from the HLO the profiler keeps in its ``/host:metadata`` plane.
``reduce`` turns them into

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices that ran any;
- ``window_s``: the window's length (the ``bench.window`` annotation);
- ``busy_by_device``: the same union per device, unaveraged;
- ``scopes``: per device, the seconds of the operations under each named
  scope (``jax.named_scope``), by its dotted label without the
  ``jit(...)`` parts (``ivf_flat.cells_scan``, ``raft.topk_merge``); an
  operation counts under every label on its path, and one with none
  under ``""``, so the labels that hold no other sum to the device's
  operation seconds (its busy time, but for operations that overlap);
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
#: The plane whose event metadata holds each program's HLO, and the name
#: of the statistic that carries it.
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


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
    ran = {ev["name"] for ev in events if ev["line"] == MODULES_LINE}
    for path in paths:
        with open(path, "rb") as f:
            for name, op_names in hlo_op_names(f.read(), ran).items():
                events.append({"plane": METADATA_PLANE, "line": "op_names",
                               "name": name, "start_ns": 0.0,
                               "dur_ns": 0.0, "op_names": op_names})
    return events


# -- the HLO of the metadata plane ------------------------------------------
# ProfileData does not give the planes' event metadata, so the few fields
# needed are read off the protobuf wire format: XSpace.planes (1);
# XPlane.name (2), .event_metadata (4), .stat_metadata (5), map entries
# key (1) / value (2); XEventMetadata.name (2), .stats (5); XStat
# .metadata_id (1), .bytes_value (6); XStatMetadata.name (2); HloProto
# .hlo_module (1); HloModuleProto.computations (3);
# HloComputationProto.instructions (2); HloInstructionProto.name (1),
# .metadata (7); OpMetadata.op_name (2).

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf, span=None):
    """``(field number, value)`` of each field of the message in
    ``buf[span]``: an int for a varint, a ``(start, end)`` span for a
    length-delimited field (fixed-width fields are skipped)."""
    i, end = span or (0, len(buf))
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError("unsupported protobuf wire type %d" % wire)
        yield key >> 3, value


def _first(buf, span, number: int, default=None):
    for num, value in _fields(buf, span):
        if num == number:
            return value
    return default


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map(buf, span, number: int) -> dict:
    """The entries of map field ``number``: ``{key: value span}``."""
    return {_first(buf, e, 1, 0): _first(buf, e, 2)
            for num, e in _fields(buf, span) if num == number}


def _instruction_op_names(buf, span) -> dict:
    """``{instruction name: op_name}`` of an ``HloProto``."""
    out = {}
    module = _first(buf, span, 1)
    for num, comp in _fields(buf, module or (0, 0)):
        if num != 3:
            continue
        for num2, inst in _fields(buf, comp):
            if num2 != 2:
                continue
            name = meta = None
            for num3, value in _fields(buf, inst):
                if num3 == 1:
                    name = _text(buf, value)
                elif num3 == 7:
                    meta = value
            op_name = meta and _first(buf, meta, 2)
            if name and op_name:
                out[name] = _text(buf, op_name)
    return out


def hlo_op_names(raw: bytes, programs=None) -> dict:
    """``{program: {operation: op_name}}`` from a serialized XSpace's
    metadata plane, for the named ``programs`` (all where None)."""
    buf = memoryview(raw)
    out = {}
    for num, plane in _fields(buf):
        name = num == 1 and _first(buf, plane, 2)
        if not name or _text(buf, name) != METADATA_PLANE:
            continue
        stat_ids = {k for k, v in _map(buf, plane, 5).items()
                    if v and _text(buf, _first(buf, v, 2, (0, 0)))
                    == HLO_STAT}
        for meta in _map(buf, plane, 4).values():
            program = _text(buf, _first(buf, meta, 2, (0, 0)))
            if programs is not None and program not in programs:
                continue
            for num2, stat in _fields(buf, meta):
                if num2 == 5 and _first(buf, stat, 1) in stat_ids:
                    hlo = _first(buf, stat, 6)
                    if hlo:
                        out[program] = _instruction_op_names(buf, hlo)
    return out


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


def _scope_labels(events: list):
    """``labels(program run, operation)``: the dotted named-scope labels
    on the operation's ``op_name`` path, outermost first, or ``("",)``."""
    op_names = {ev["name"]: ev["op_names"] for ev in events
                if ev["plane"] == METADATA_PLANE}

    def labels(run: str, op: str) -> tuple:
        path = op_names.get(run, {}).get(op, "").split("/")
        return tuple(dict.fromkeys(
            part for part in path if "." in part and "(" not in part)) \
            or ("",)

    return labels


def reduce(events: list, host_spans: list = (), top: int = 10) -> dict:
    """The numbers above; ``host_spans`` adds ``(name, start_ns,
    end_ns)`` intervals on the trace's clock (the program's spans, mapped
    by the harness) to the harness's annotations for naming gaps.
    ``busy_s`` is None when no device ran an operation."""
    w0, w1 = annotation(events, "bench.window")
    ops: dict = {}
    programs: dict = {}
    op_time: dict = {}
    scopes: dict = {}
    labels = _scope_labels(events)
    dev = sorted((ev for ev in events if _is_device(ev["plane"])),
                 key=lambda ev: (ev["start_ns"], ev["line"] != MODULES_LINE))
    current: dict = {}       # plane -> (program, its run's name, end)
    for ev in dev:
        iv = (ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
        inside = _clip([iv], w0, w1)
        if ev["line"] == MODULES_LINE:
            current[ev["plane"]] = (_module_name(ev["name"]), ev["name"],
                                    iv[1])
            for s, e in inside:
                key = current[ev["plane"]][0]
                programs[key] = programs.get(key, 0.0) + (e - s) * 1e-9
        elif ev["line"] == OPS_LINE:
            ops.setdefault(ev["plane"], []).append(iv)
            prog, run, end = current.get(ev["plane"], ("", "", 0.0))
            op = _op_name(ev["name"])
            key = "%s/%s" % (prog if iv[0] < end else "", op)
            where = scopes.setdefault(ev["plane"], {})
            for s, e in inside:
                op_time[key] = op_time.get(key, 0.0) + (e - s) * 1e-9
                for label in (labels(run, op) if iv[0] < end else ("",)):
                    where[label] = where.get(label, 0.0) + (e - s) * 1e-9
    busy = {p: _union(_clip(iv, w0, w1)) for p, iv in ops.items()}
    busy = {p: u for p, u in busy.items() if u}

    def most(d):
        return [list(kv) for kv in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    out = {"window_s": (w1 - w0) * 1e-9, "busy_s": None,
           "device_ops": most(op_time), "programs": most(programs),
           "idle_gaps": [],
           "busy_by_device": {p: sum(e - s for s, e in u) * 1e-9
                              for p, u in sorted(busy.items())},
           "scopes": {p: scopes[p] for p in sorted(busy)}}
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
        and ev["plane"] != METADATA_PLANE and ev["name"] != "bench.window"]
    for s, e in gaps[:top]:
        mid = 0.5 * (s + e)
        inside = [(he - hs, name) for name, hs, he in spans
                  if hs <= mid <= he]
        out["idle_gaps"].append([min(inside)[1] if inside else "none",
                                 (e - s) * 1e-9])
    return out
