"""Per-request span tracing with Chrome trace-event / Perfetto export.

A :class:`Tracer` records two event shapes:

* **sync spans** (``tracer.span("kernel-execute", ...)``) — complete
  ``"ph": "X"`` events with microsecond ``ts``/``dur``, nested by time
  containment on their track (``tid``).  The serving engine emits
  ``batch`` > ``batch-assembly`` / ``kernel-execute`` / ``post-process`` /
  ``nand-billing`` on the engine track.
* **async spans** (``async_begin``/``async_end``) — ``"ph": "b"/"e"``
  event pairs keyed by ``id``, for intervals that overlap freely across
  requests (``queue-wait`` from ``submit`` to its batch's flush).

``export()`` returns the standard ``{"traceEvents": [...]}`` JSON object
(load it in ``chrome://tracing`` or https://ui.perfetto.dev), with process/
thread metadata events naming the tracks.

Two sinks: every sync span of an enabled tracer also opens a
``jax.profiler.TraceAnnotation`` named ``repro.<span name>`` and closes it
when the span ends, so under an active profiler session the span sits in
the profiler's trace on the same clock as the device's ops (with no session
the annotation costs well under a microsecond).  Async spans and instants
stay in the Chrome export only.

The event buffer is bounded (``MAX_EVENTS``): past it the oldest events are
dropped and counted in ``dropped``, so a long-running server with tracing
on does not grow without limit.

Zero-cost-when-off: a disabled tracer hands back one shared no-op span
object — no allocation, no clock read, no annotation.
"""
from __future__ import annotations

import json
import time
from collections import deque
from typing import List, Optional

ENGINE_TID = 0          # the serving engine's synchronous track
#: events kept per tracer; older ones are dropped (and counted) past it
MAX_EVENTS = 1 << 18
#: prefix of a span's name in the profiler's trace
ANNOTATION_PREFIX = "repro."


class _NullSpan:
    """Shared no-op span for disabled tracers (and a safe ``set`` sink)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass

    def end(self) -> None:
        pass


NULL_SPAN = _NullSpan()


class Span:
    """One in-flight sync span; closes into a complete ("X") trace event."""

    __slots__ = ("_tracer", "name", "cat", "tid", "args", "ts", "_anno")

    def __init__(self, tracer: "Tracer", name: str, cat: str, tid: int,
                 args: dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.tid = tid
        self.args = args
        self._anno = tracer._annotation(ANNOTATION_PREFIX + name)
        self._anno.__enter__()
        self.ts = tracer._now_us()

    def set(self, **args) -> None:
        """Attach (or update) event args after the span opened."""
        self.args.update(args)

    def end(self) -> None:
        t = self._tracer
        dur = max(t._now_us() - self.ts, 0.0)
        self._anno.__exit__(None, None, None)
        t._append({
            "name": self.name, "cat": self.cat, "ph": "X",
            "ts": self.ts, "dur": dur,
            "pid": t.pid, "tid": self.tid, "args": self.args,
        })

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> bool:
        self.end()
        return False


class Tracer:
    def __init__(self, enabled: bool = True, pid: int = 0):
        self.enabled = enabled
        self.pid = pid
        self._epoch = time.perf_counter()
        self._meta_events: List[dict] = []
        self._events: deque = deque(maxlen=MAX_EVENTS)
        self.dropped = 0
        if enabled:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation
            self._meta("process_name", ENGINE_TID, name="repro-serving")
            self._meta("thread_name", ENGINE_TID, name="engine")

    def _now_us(self) -> float:
        return (time.perf_counter() - self._epoch) * 1e6

    def _meta(self, kind: str, tid: int, **args) -> None:
        self._meta_events.append({"name": kind, "ph": "M", "pid": self.pid,
                                  "tid": tid, "args": args})

    def _append(self, event: dict) -> None:
        if len(self._events) == self._events.maxlen:
            self.dropped += 1
        self._events.append(event)

    # ------------------------------------------------------------ sync spans
    def span(self, name: str, cat: str = "serve", tid: int = ENGINE_TID,
             **args):
        """Context manager recording a complete event on track ``tid``."""
        if not self.enabled:
            return NULL_SPAN
        return Span(self, name, cat, tid, args)

    # ----------------------------------------------------------- async spans
    def async_begin(self, name: str, id: int, cat: str = "request",
                    **args) -> None:
        if not self.enabled:
            return
        self._append({
            "name": name, "cat": cat, "ph": "b", "id": id,
            "ts": self._now_us(), "pid": self.pid, "tid": ENGINE_TID,
            "args": args,
        })

    def async_end(self, name: str, id: int, cat: str = "request",
                  **args) -> None:
        if not self.enabled:
            return
        self._append({
            "name": name, "cat": cat, "ph": "e", "id": id,
            "ts": self._now_us(), "pid": self.pid, "tid": ENGINE_TID,
            "args": args,
        })

    def instant(self, name: str, cat: str = "serve", **args) -> None:
        """Zero-duration marker (consolidation points, warnings...)."""
        if not self.enabled:
            return
        self._append({
            "name": name, "cat": cat, "ph": "i", "s": "g",
            "ts": self._now_us(), "pid": self.pid, "tid": ENGINE_TID,
            "args": args,
        })

    # --------------------------------------------------------------- reading
    def events(self) -> List[dict]:
        """Metadata events, then the buffered ones, oldest first."""
        return self._meta_events + list(self._events)

    def export(self, path: Optional[str] = None) -> dict:
        """Chrome trace-event JSON object; written to ``path`` if given."""
        doc = {"traceEvents": self.events(), "displayTimeUnit": "ms"}
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc

    def clear(self) -> None:
        """Drop every recorded event (and the drop count); the track
        metadata stays."""
        self._events.clear()
        self.dropped = 0


#: the shared disabled tracer — every call is a no-op
NULL_TRACER = Tracer(enabled=False)
