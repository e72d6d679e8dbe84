"""Deterministic request-span tracer for the serving runtime.

Ref pattern: the reference's only tracing story is NVTX ranges
(core/nvtx.hpp) — host-side annotations a profiler GUI consumes.  An
online serving stack needs the request-scoped analog (the OpenTelemetry
/ Dapper span model): every request yields a tree of timed spans —
queue-wait, batch-assembly, cache-lookup, device dispatch, result
merge, device_get — exportable as JSON or the Chrome trace-event format
(``chrome://tracing`` / Perfetto).

Disciplines (shared with serve/ and core/retry.py):

* **Injectable monotonic clock** — span timestamps are differences of
  the SAME injected clock the scheduler runs on, never wall time, so
  tests assert bit-stable exports (golden files in tests/test_obs.py).
* **Zero-cost when disabled** — a disabled :class:`Tracer` hands out
  the shared :data:`NULL_SPAN` singleton whose every method is a no-op;
  instrumentation sites stay unconditional and pay one attribute check.
  Nothing here ever touches traced code paths: spans are host objects,
  and the device fence (``jax.block_until_ready`` in
  ``Searcher.search``) only runs when a recording span asks for it.
* **Bounded retention** — finished request traces land in a ring buffer
  (``max_traces``) and collector pauses in a bounded deque; a serving
  process must not grow without bound.
* **Collector pauses are spans** — an enabled tracer records every
  garbage-collector pause (start, stop, ``generation``, ``collected``)
  from a ``gc.callbacks`` hook that holds the tracer only weakly; the
  scheduler attaches them to the next batch it finishes.  A disabled
  tracer registers no hook.
* **The profiler's clock** — the batch root (:meth:`Tracer.scoped`),
  every live child of it and every collector pause also hold a host
  range ``raft_tpu::serve.<name>`` (``core/nvtx.push_range``) for
  their lifetime, so under ``jax.profiler`` the program's own spans
  land in the trace beside the device's operations.  The ranges are
  host-only ``TraceAnnotation``s, never a ``named_scope``: they change
  no compiled program.

The device-side counterpart is ``jax.named_scope`` annotations on the
searched programs' stages (neighbors/ivf_flat.py, parallel/knn.py,
parallel/ivf.py) — those tag HLO metadata for ``jax.profiler`` traces
and cost nothing at runtime; this module owns the host-side request
timeline.
"""

from __future__ import annotations

import gc
import json
import threading
import time
import weakref
from collections import deque
from typing import Callable, Dict, List, Optional

from raft_tpu.core.nvtx import pop_range, push_range

__all__ = ["Span", "Tracer", "NULL_SPAN", "NULL_TRACER"]

#: Collector pauses an enabled tracer keeps until a batch takes them.
MAX_PAUSES = 4096


def _range_name(name: str) -> str:
    """``serve.<name>``: the profiler range of a span (``push_range``
    prefixes the ``raft_tpu::`` domain)."""
    return name if name.startswith("serve.") else "serve." + name


class Span:
    """One timed operation: ``name``, start/end on the tracer's clock,
    string-keyed attributes, and child spans.  Create children with
    :meth:`child` (started now, finish later / use as a context
    manager) or :meth:`child_at` (pre-measured interval — the scheduler
    measures one batch once and attaches the interval to every member
    request's tree) or :meth:`copy_child` (a finished span and its
    subtree, copied).

    A ``ranged`` span holds a profiler range from its start until
    :meth:`finish`, and so does every :meth:`child` of it: such spans
    must open and finish on one thread, innermost first."""

    __slots__ = ("name", "start", "end", "attrs", "children", "tid",
                 "_clock", "_sink", "_ranged")

    #: Real spans record; the :data:`NULL_SPAN` singleton reports False —
    #: the one flag instrumentation sites branch on (e.g. whether to pay
    #: the device fence).
    recording = True

    def __init__(self, name: str, clock: Callable[[], float], tid: int = 0,
                 attrs: Optional[dict] = None, sink=None,
                 ranged: bool = False):
        self.name = name
        self._clock = clock
        self.tid = tid
        self._ranged = ranged
        if ranged:
            push_range(_range_name(name))
        self.start = clock()
        self.end: Optional[float] = None
        self.attrs: Dict[str, object] = dict(attrs) if attrs else {}
        self.children: List["Span"] = []
        self._sink = sink

    # -- building the tree -------------------------------------------------
    def child(self, name: str, **attrs) -> "Span":
        """Start a child span now (finish it explicitly or via ``with``)."""
        sp = Span(name, self._clock, tid=self.tid,
                  attrs=attrs if attrs else None, ranged=self._ranged)
        self.children.append(sp)
        return sp

    def child_at(self, name: str, start: float, end: float,
                 **attrs) -> "Span":
        """Attach an already-measured child interval (the scheduler
        measures a batch ONCE and attaches it to every member's tree)."""
        sp = Span(name, self._clock, tid=self.tid,
                  attrs=attrs if attrs else None)
        sp.start = start
        sp.end = end
        self.children.append(sp)
        return sp

    def copy_child(self, span: "Span") -> "Span":
        """Attach a copy of the finished ``span`` and of its subtree
        (:meth:`child_at` all the way down: same names, intervals and
        attributes)."""
        sp = self.child_at(span.name, span.start, span.end, **span.attrs)
        for c in span.children:
            sp.copy_child(c)
        return sp

    def annotate(self, **attrs) -> None:
        self.attrs.update(attrs)

    def finish(self, **attrs) -> None:
        """Stamp the end time (idempotent — the first finish wins) and,
        for request roots, publish into the tracer's ring buffer."""
        if attrs:
            self.attrs.update(attrs)
        if self.end is None:
            self.end = self._clock()
            if self._ranged:
                pop_range()
            if self._sink is not None:
                self._sink(self)

    @property
    def duration(self) -> float:
        return (self.end - self.start) if self.end is not None else 0.0

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.finish()

    # -- export ------------------------------------------------------------
    def tree(self) -> dict:
        """Nested plain-dict form (the JSON export unit)."""
        return {
            "name": self.name,
            "start": self.start,
            "end": self.end,
            "attrs": dict(self.attrs),
            "children": [c.tree() for c in self.children],
        }

    def __repr__(self) -> str:
        return ("Span(%r, start=%s, end=%s, children=%d)"
                % (self.name, self.start, self.end, len(self.children)))


class _NullSpan:
    """Shared do-nothing span: what a disabled tracer hands out so
    instrumentation sites never branch.  Every child is itself."""

    __slots__ = ()
    recording = False
    name = "null"
    children = ()
    attrs: Dict[str, object] = {}
    start = 0.0
    end = 0.0
    duration = 0.0
    tid = 0

    def child(self, name, **attrs):
        return self

    def child_at(self, name, start, end, **attrs):
        return self

    def copy_child(self, span):
        return self

    def annotate(self, **attrs):
        pass

    def finish(self, **attrs):
        pass

    def tree(self) -> dict:
        return {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


#: The process-wide disabled span (see :class:`_NullSpan`).
NULL_SPAN = _NullSpan()


class Tracer:
    """Hands out request root spans and retains finished request traces.

    ``enabled=False`` (or :data:`NULL_TRACER`) turns every
    :meth:`request` into the shared :data:`NULL_SPAN` — the zero-cost
    contract instrumented code relies on.  Thread-safe: request threads
    open roots while a driver thread finishes them and a scraper drains.
    An enabled tracer also records collector pauses
    (:meth:`take_pauses`) until it is closed or collected.
    """

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 enabled: bool = True, max_traces: int = 1024):
        self._clock = clock
        self.enabled = enabled
        self._lock = threading.Lock()
        self._finished: deque = deque(maxlen=max_traces)
        self._dropped = 0
        self._tid = 0
        self._gc_hook = _GcHook(self) if enabled else None

    def request(self, name: str, **attrs):
        """Open one request root span (finished roots land in the ring
        buffer for :meth:`take`); :data:`NULL_SPAN` when disabled."""
        return self._root(name, attrs, ranged=False)

    def scoped(self, name: str, **attrs):
        """Open a root span that lives on the calling thread — the
        scheduler's ``serve.batch``: it and every :meth:`Span.child` of
        it also hold the profiler range ``raft_tpu::serve.<name>`` until
        they finish, innermost first.  Finished like a request root;
        :data:`NULL_SPAN` when disabled."""
        return self._root(name, attrs, ranged=True)

    def _root(self, name: str, attrs: dict, ranged: bool):
        if not self.enabled:
            return NULL_SPAN
        with self._lock:
            self._tid += 1
            tid = self._tid
        return Span(name, self._clock, tid=tid,
                    attrs=attrs if attrs else None, sink=self._publish,
                    ranged=ranged)

    def take_pauses(self) -> List[tuple]:
        """Drain the collector pauses recorded since the last call:
        ``(start, end, generation, collected)`` on the tracer's clock,
        oldest first (at most :data:`MAX_PAUSES`)."""
        hook = self._gc_hook
        return hook.take() if hook is not None else []

    def close(self) -> None:
        """Stop recording collector pauses (unregisters the ``gc``
        hook; also done when the tracer is collected).  Idempotent."""
        hook, self._gc_hook = self._gc_hook, None
        if hook is not None:
            hook.remove()

    def _publish(self, span: Span) -> None:
        with self._lock:
            if len(self._finished) == self._finished.maxlen:
                self._dropped += 1
            self._finished.append(span)

    def take(self) -> List[Span]:
        """Drain the finished request traces (oldest first)."""
        with self._lock:
            out = list(self._finished)
            self._finished.clear()
            return out

    @property
    def pending(self) -> int:
        with self._lock:
            return len(self._finished)

    @property
    def dropped(self) -> int:
        """Finished traces evicted by the ring bound (scrape health)."""
        with self._lock:
            return self._dropped

    # -- export ------------------------------------------------------------
    def to_json(self, spans: Optional[List[Span]] = None, *,
                drain: bool = False) -> str:
        """JSON array of nested span trees (``drain=True`` consumes the
        buffered traces; default peeks without consuming)."""
        if spans is None:
            spans = self.take() if drain else self._peek()
        return json.dumps([s.tree() for s in spans], sort_keys=True,
                          separators=(",", ":"))

    def chrome_trace(self, spans: Optional[List[Span]] = None, *,
                     drain: bool = False) -> dict:
        """Chrome trace-event form: one complete ("ph": "X") event per
        span, timestamps in integer microseconds of the injected clock,
        one ``tid`` row per request — load the JSON in Perfetto /
        ``chrome://tracing``.  Event order is deterministic: requests in
        finish order, spans depth-first in creation order."""
        if spans is None:
            spans = self.take() if drain else self._peek()
        events: List[dict] = []

        def emit(sp: Span) -> None:
            end = sp.end if sp.end is not None else sp.start
            events.append({
                "name": sp.name,
                "ph": "X",
                "ts": int(round(sp.start * 1e6)),
                "dur": int(round((end - sp.start) * 1e6)),
                "pid": 0,
                "tid": sp.tid,
                "cat": "raft_tpu.serve",
                "args": dict(sp.attrs),
            })
            for c in sp.children:
                emit(c)

        for root in spans:
            emit(root)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def chrome_trace_json(self, spans: Optional[List[Span]] = None, *,
                          drain: bool = False) -> str:
        """:meth:`chrome_trace` serialized deterministically (sorted
        keys, no whitespace) — the golden-file export format."""
        return json.dumps(self.chrome_trace(spans, drain=drain),
                          sort_keys=True, separators=(",", ":"))

    def _peek(self) -> List[Span]:
        with self._lock:
            return list(self._finished)

    def __repr__(self) -> str:
        return ("Tracer(enabled=%s, pending=%d)"
                % (self.enabled, self.pending))


class _GcHook:
    """The ``gc.callbacks`` entry of one enabled :class:`Tracer`: times
    each collection on the tracer's clock, holds the profiler range
    ``raft_tpu::serve.gc`` across it, and keeps the pauses in a bounded
    deque.  It takes no lock — a collection can start while any lock of
    the process is held, so the deque's atomic append and popleft are
    all it relies on — and holds the tracer only weakly, removing itself
    when the tracer is collected."""

    def __init__(self, tracer: Tracer):
        self._tracer = weakref.ref(tracer, self._dead)
        self._start: Optional[float] = None
        self._pauses: deque = deque(maxlen=MAX_PAUSES)
        gc.callbacks.append(self)

    def __call__(self, phase: str, info: dict) -> None:
        tracer = self._tracer()
        if tracer is None:
            return
        if phase == "start":
            push_range("serve.gc")
            self._start = tracer._clock()
        elif self._start is not None:
            end = tracer._clock()
            pop_range()
            self._pauses.append((self._start, end, info["generation"],
                                 info["collected"]))
            self._start = None

    def take(self) -> List[tuple]:
        out = []
        while True:
            try:
                out.append(self._pauses.popleft())
            except IndexError:
                return out

    def _dead(self, _ref) -> None:
        # A tracer in a reference cycle dies inside a collection, which
        # then ends without calling this hook: close its range here.
        if self._start is not None:
            pop_range()
            self._start = None
        self.remove()

    def remove(self) -> None:
        try:
            gc.callbacks.remove(self)
        except ValueError:
            pass


#: Shared disabled tracer: the default wired into the scheduler so
#: un-instrumented deployments pay one ``enabled`` check per request.
NULL_TRACER = Tracer(enabled=False)
