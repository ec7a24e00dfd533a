"""Program spans on the profiler's clock.

``span(name, **attrs)`` is the one span call every instrumented site
makes. A span measures the real time of the block it wraps and reaches up
to three sinks:

* a ``jax.profiler.TraceAnnotation(name)``, always. While a profiler
  session runs (``jax.profiler.trace``) the span lands on the profile's
  host plane, on the same clock as the device's operations; with no
  session it costs about half a microsecond to enter and exit.
* the ambient :class:`~repro.obs.metrics.MetricsRegistry`, always: the
  counters ``span.<name>.calls`` and ``span.<name>.ns`` (the span's
  nanoseconds) accumulate, so a caller that diffs two registry snapshots
  reads each span's time over that stretch with no profiler running.
* the ambient :class:`Tracer`, when one is installed
  (:func:`set_ambient_tracer`; ``KGService(trace=True)`` installs its
  own). It keeps each span in memory with its start and duration on
  ``time.time_ns()`` (CLOCK_REALTIME, the clock the profiler's host plane
  counts from: its events are offsets from the profile's
  ``profile_start_time`` on that clock), the ``seq`` of its parent, a
  request id shared by every span opened under one outermost span (one
  ``serve_window`` call, one adaptation round), and its attributes.
  :meth:`Tracer.export` writes Chrome trace JSON that Perfetto loads.

Attributes are recorded only by a Tracer. A hot site therefore opens its
span by name alone and attaches attributes under ``if sp.recording``, so
with no Tracer installed it builds no attribute dict.

``NULL_TRACER`` is the ambient default: ``enabled`` is False and it
records nothing.
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from repro.obs import metrics as _metrics

__all__ = ["Span", "Tracer", "NullTracer", "NULL_TRACER", "span",
           "set_ambient_tracer", "ambient_tracer"]


def _clean(value):
    """JSON-safe span attribute: numpy scalars to native, containers
    element-wise, everything else passed through for json to reject."""
    if isinstance(value, (bool, int, float, str)) or value is None:
        return value
    if isinstance(value, (list, tuple)):
        return [_clean(v) for v in value]
    if isinstance(value, dict):
        return {str(k): _clean(v) for k, v in value.items()}
    if hasattr(value, "item"):  # numpy scalar
        return value.item()
    return str(value)


class Span:
    """One timed block; context manager returned by :func:`span`."""

    __slots__ = ("name", "attrs", "tracer", "event", "seq", "parent", "req",
                 "depth", "t0", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.tracer: Optional[Tracer] = None
        self.event: Optional[Dict[str, Any]] = None

    @property
    def recording(self) -> bool:
        """True when a Tracer records this span: guard attribute work."""
        return self.tracer is not None

    def annotate(self, **attrs) -> "Span":
        """Attach attributes found out while or after the block ran (a
        span already closed updates its recorded event). Records nothing
        unless a Tracer is recording the span."""
        if self.tracer is not None:
            if self.event is not None:
                self.event["args"].update(
                    {k: _clean(v) for k, v in attrs.items()})
            else:
                self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        tr = _ambient_tracer
        if tr.enabled:
            self.tracer = tr
            tr._open(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.time_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.time_ns()
        self._ann.__exit__(exc_type, exc, tb)
        reg = _metrics.ambient()
        if reg is not None:
            reg.add_span(self.name, t1 - self.t0)
        if self.tracer is not None:
            self.tracer._close(self, t1)


def span(name: str, **attrs) -> Span:
    """Open a program span: ``with span("repro.exec.scan") as sp: ...``."""
    return Span(name, attrs)


class Tracer:
    """In-memory recorder of the spans opened while it is ambient."""

    enabled = True

    def __init__(self):
        self.events: List[Dict[str, Any]] = []
        self._stack: List[Span] = []
        self._seq = 0
        self._req = 0

    def __len__(self) -> int:
        return len(self.events)

    def _open(self, sp: Span) -> None:
        sp.seq = self._seq
        self._seq += 1
        if self._stack:
            top = self._stack[-1]
            sp.parent, sp.req = top.seq, top.req
        else:
            sp.parent, sp.req = None, self._req
            self._req += 1
        sp.depth = len(self._stack)
        self._stack.append(sp)

    def _close(self, sp: Span, t1: int) -> None:
        sp.event = dict(seq=sp.seq, name=sp.name, ts_ns=sp.t0,
                        dur_ns=t1 - sp.t0, parent=sp.parent, req=sp.req,
                        depth=sp.depth,
                        args={k: _clean(v) for k, v in sp.attrs.items()})
        self.events.append(sp.event)
        if self._stack and self._stack[-1] is sp:
            self._stack.pop()

    # -- introspection (tests, smoke checks) ---------------------------
    def structure(self) -> List[Tuple[int, str]]:
        """(depth, name) pairs in span *open* order: the timing-free shape
        of the trace."""
        return [(e["depth"], e["name"])
                for e in sorted(self.events, key=lambda e: e["seq"])]

    def span_counts(self) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        for e in self.events:
            counts[e["name"]] = counts.get(e["name"], 0) + 1
        return counts

    def find(self, name: str) -> List[Dict[str, Any]]:
        return [e for e in self.events if e["name"] == name]

    # -- export --------------------------------------------------------
    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event dict: "X" complete events with microsecond
        timestamps on the wall clock; each event's ``args`` carry its
        ``seq``, ``parent`` and ``req`` besides its attributes."""
        evs: List[Dict[str, Any]] = [
            dict(name="process_name", ph="M", pid=0, tid=0,
                 args=dict(name="repro (wall clock)")),
        ]
        for e in sorted(self.events, key=lambda e: e["seq"]):
            evs.append(dict(name=e["name"], ph="X", ts=e["ts_ns"] / 1e3,
                            dur=e["dur_ns"] / 1e3, pid=0, tid=0,
                            args=dict(e["args"], seq=e["seq"],
                                      parent=e["parent"], req=e["req"])))
        return {"traceEvents": evs, "displayTimeUnit": "ms"}

    def export(self, path: str) -> int:
        """Write Chrome trace JSON to ``path``; returns the span count."""
        with open(path, "w") as fh:
            json.dump(self.chrome_trace(), fh)
        return len(self.events)


class NullTracer:
    """The ambient default: records nothing."""

    enabled = False
    events: List[Dict[str, Any]] = []

    def __len__(self) -> int:
        return 0

    def structure(self):
        return []

    def span_counts(self):
        return {}

    def find(self, name):
        return []


NULL_TRACER = NullTracer()

_ambient_tracer: "Tracer | NullTracer" = NULL_TRACER


def set_ambient_tracer(tracer: "Tracer | NullTracer") -> None:
    """Install the Tracer that records the spans opened from now on
    (``NULL_TRACER`` to record none)."""
    global _ambient_tracer
    _ambient_tracer = tracer


def ambient_tracer() -> "Tracer | NullTracer":
    return _ambient_tracer
