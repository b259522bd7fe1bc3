"""Span tracer for the serving stack: monotonic-clock spans, a bounded
ring buffer, Chrome trace-event / Perfetto JSON export.

The ROADMAP's zero-copy-transport item claims "most of the ~226 ms wire
RTT is serialization and socket hops, not compute" — this module is how
that claim gets a measurement.  One ``Tracer`` rides a serving session
(``SessionConfig(trace=True)``) and collects spans from every layer the
critical path crosses:

    edge track    edge.decode, edge.trigger, edge.dispatch, edge.merge,
                  edge.catchup (sync; children catchup.dispatch and
                  catchup.wait), session.policy, edge.stall, scan.run
    wire track    wire.encode (serialize), wire.request (dispatch ->
                  reply), wire.socket (derived: RTT minus the server's
                  reported durations)
    server track  server.queue, server.catchup — SYNTHESIZED client-side
                  from the REPLY frame's duration-only timing payload
                  (protocol v4), so no clock sync between the processes
                  is ever needed; a ``CorrectionServer`` given its own
                  tracer additionally records server.replay spans locally

Correlation: every request-scoped span carries ``req_id`` in its args
(the Dispatcher's monotonically increasing id, echoed by the server), so
a reader can reassemble one request's serialize/socket/queue/compute
breakdown from the flat event list — ``tools/trace_report.py`` does
exactly that.

Cost discipline: the tracer is pay-for-what-you-use.  Sessions default
to ``trace=False`` and every instrumentation site in the engine /
dispatcher / worker goes through ``span(tracer, ...)``, which is a
single ``tracer is None`` check returning a shared no-op span when
disabled — the disabled path never allocates a span, never reads the
clock for tracing, and never constructs a profiler annotation (asserted
by the overhead guard in tests/test_observability.py).

Two sinks, one span: a live span (``Tracer.span``) records its ring
entry on the monotonic clock exactly as ``done`` does, and while it is
open it also holds a ``jax.profiler.TraceAnnotation`` (a TraceMe) with
the span's args as metadata.  When a profiler trace is active the span
therefore lands on the profiler's host plane, on the device trace's
clock, beside the device's operations; when none is active the
annotation costs a few microseconds and records nothing.  Synthesized
spans (``add``: ``wire.request``, ``wire.socket``, ``server.*``) are
placed after the fact and reach the ring only.  The annotation touches
no device array, so instrumentation introduces no host transfer and no
retrace (``tools/check_static.py --strict`` stays green).

Synthesized-span placement: the server reports DURATIONS only
(queue-wait and replay compute).  The client anchors them backwards from
reply arrival — compute ends at arrival, queue precedes compute — which
attributes both socket directions to the gap after dispatch.  Fine for
breakdown totals (durations are exact); only the left edges of the
server spans are approximate.
"""
from __future__ import annotations

import itertools
import json
import os
import time
from collections import deque
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

# stable track -> Chrome tid mapping (one "process" per tracer)
TRACKS = ("edge", "wire", "server")
_TRACK_TID = {name: i for i, name in enumerate(TRACKS)}

_trace_seq = itertools.count(1)


class Span:
    """One completed span: name, category, start (monotonic seconds),
    duration, track, and a small args dict (req_id etc.)."""

    __slots__ = ("name", "cat", "ts", "dur", "track", "args")

    def __init__(self, name: str, cat: str, ts: float, dur: float,
                 track: str, args: Dict[str, Any]):
        self.name = name
        self.cat = cat
        self.ts = ts
        self.dur = dur
        self.track = track
        self.args = args

    def __repr__(self) -> str:  # debugging/test ergonomics
        return (f"Span({self.name!r}, ts={self.ts:.6f}, "
                f"dur={self.dur * 1e3:.3f}ms, track={self.track!r})")


class Tracer:
    """Bounded ring buffer of spans with trace-event export.

    ``capacity`` bounds memory: when full, the OLDEST spans are dropped
    (a long session keeps its tail, which is what a breakdown wants) and
    ``dropped`` counts them.  All methods are cheap enough for the
    reactor tick / per-step hot path when tracing is ON; when tracing is
    OFF the convention is that callers hold ``None`` instead of a
    disabled tracer — one flag check, zero calls into this class.
    """

    def __init__(self, capacity: int = 65536, *,
                 trace_id: Optional[str] = None):
        if capacity <= 0:
            raise ValueError("trace capacity must be >= 1")
        self.capacity = int(capacity)
        self.trace_id = (trace_id if trace_id is not None
                         else f"{os.getpid():x}-{next(_trace_seq):x}")
        self._spans: "deque[Span]" = deque(maxlen=self.capacity)
        self._appended = 0

    # -- recording -----------------------------------------------------------
    @staticmethod
    def clock() -> float:
        """The span clock (monotonic seconds) — callers stamp t0 with
        this so the disabled path can skip the read entirely."""
        return time.monotonic()

    def done(self, name: str, cat: str, t0: float, *, track: str = "edge",
             **args: Any) -> None:
        """Record a span that started at ``t0`` and ends NOW."""
        self.add(name, cat, t0, time.monotonic() - t0, track=track, **args)

    def add(self, name: str, cat: str, ts: float, dur: float, *,
            track: str = "edge", **args: Any) -> None:
        """Record a pre-measured span (synthesized server spans use this
        with durations carried by the REPLY timing payload)."""
        self._appended += 1
        self._spans.append(Span(name, cat, ts, max(float(dur), 0.0),
                                track, args))

    def span(self, name: str, cat: str, *, track: str = "edge",
             **args: Any) -> "LiveSpan":
        """A context manager for a span around live work: the ring span
        (as ``done`` records it, from entry to exit) plus a profiler
        ``TraceAnnotation`` held while it is open.  Args known only
        later are added with ``set`` on the object ``with`` yields."""
        return LiveSpan(self, name, cat, track, args)

    # -- inspection ----------------------------------------------------------
    def spans(self) -> List[Span]:
        return list(self._spans)

    def __len__(self) -> int:
        return len(self._spans)

    @property
    def dropped(self) -> int:
        """Spans evicted by the ring bound (0 unless the session outgrew
        ``capacity``)."""
        return max(0, self._appended - self.capacity)

    def stats(self) -> Dict[str, Any]:
        return {"trace_id": self.trace_id, "spans": len(self._spans),
                "dropped": self.dropped, "capacity": self.capacity}

    # -- export --------------------------------------------------------------
    def to_chrome(self) -> Dict[str, Any]:
        """The Chrome trace-event JSON object (Perfetto loads it as-is):
        ``{"traceEvents": [...], "displayTimeUnit": "ms"}`` with one
        complete ("X") event per span, ts/dur in microseconds, plus
        thread_name metadata naming the tracks."""
        pid = 1
        events: List[Dict[str, Any]] = [
            {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
             "args": {"name": track}}
            for track, tid in _TRACK_TID.items()]
        for s in self._spans:
            events.append({
                "name": s.name, "cat": s.cat, "ph": "X",
                "ts": s.ts * 1e6, "dur": s.dur * 1e6,
                "pid": pid, "tid": _TRACK_TID.get(s.track, 0),
                "args": dict(s.args, trace_id=self.trace_id),
            })
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"trace_id": self.trace_id,
                              "dropped": self.dropped}}

    def export(self, path: str) -> int:
        """Write the Perfetto-loadable JSON; returns the span count."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as fh:
            json.dump(self.to_chrome(), fh)
        return len(self._spans)


class LiveSpan:
    """One open span of ``Tracer.span``.  Not recorded when the body
    raises, as ``done`` is not called then."""

    __slots__ = ("_tracer", "_name", "_cat", "_track", "_args", "_t0",
                 "_tm")

    def __init__(self, tracer: Tracer, name: str, cat: str, track: str,
                 args: Dict[str, Any]):
        self._tracer, self._name, self._cat = tracer, name, cat
        self._track, self._args = track, args

    def __enter__(self) -> "LiveSpan":
        self._tm = TraceAnnotation(self._name, **self._args)
        self._tm.__enter__()
        self._t0 = time.monotonic()
        return self

    def set(self, **args: Any) -> None:
        """Add args to the open span (ring entry and annotation both)."""
        self._args.update(args)
        self._tm.set_metadata(**args)

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            self._tracer.done(self._name, self._cat, self._t0,
                              track=self._track, **self._args)
        self._tm.__exit__(exc_type, exc, tb)


class _NoSpan:
    """The disabled path's span: enters, takes args and exits doing
    nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **args: Any) -> None:
        return None


NO_SPAN = _NoSpan()


def span(tracer: Optional[Tracer], name: str, cat: str, *,
         track: str = "edge", **args: Any):
    """``tracer.span(...)``, or the shared no-op span when tracing is
    off (``tracer is None``): the one flag check of an instrumentation
    site."""
    if tracer is None:
        return NO_SPAN
    return tracer.span(name, cat, track=track, **args)


def validate_chrome_trace(obj: Any) -> int:
    """Validate a loaded trace object against the trace-event schema we
    emit (the CI trace-smoke gate).  Returns the number of duration
    events; raises ``ValueError`` naming the first violation."""
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        raise ValueError("not a trace-event object: missing 'traceEvents'")
    events = obj["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("'traceEvents' is not a list")
    n_x = 0
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"event {i} is not an object")
        for k in ("ph", "pid", "tid", "name"):
            if k not in ev:
                raise ValueError(f"event {i} missing required key {k!r}")
        if ev["ph"] == "X":
            n_x += 1
            for k in ("ts", "dur"):
                if not isinstance(ev.get(k), (int, float)):
                    raise ValueError(f"event {i}: {k!r} is not a number")
                if ev[k] < 0:
                    raise ValueError(f"event {i}: negative {k}")
    if n_x == 0:
        raise ValueError("trace has no duration ('X') events")
    return n_x


def load_trace(path: str) -> Dict[str, Any]:
    """Read + validate a trace file (``tools/trace_report.py``)."""
    with open(path, "r") as fh:
        obj = json.load(fh)
    validate_chrome_trace(obj)
    return obj
