"""Span tracer: nested named spans with explicit device-sync boundaries.

JAX dispatches asynchronously: ``commit(block)`` returns as soon as the
computation is enqueued, and the wall time of whichever host line *next*
forces a transfer absorbs all pending device work. A naive ``perf_counter``
pair around one stage therefore mis-attributes latency to a bystander. The
tracer's contract is the opposite: device syncs happen only at span edges,
and only when the caller asks for them —

    with tracer.span("window.steady", sync=outputs):
        outputs = committer.step(...)          # async dispatch inside

``sync=`` (a pytree, callable, or None) is resolved with
``jax.block_until_ready`` at span *exit*, so the span's duration covers
dispatch + device execution of exactly the work it encloses, and code
outside the span keeps overlapping. Spans with ``sync=None`` time pure
host work and never touch the device.

Spans nest per-thread (a ``threading.local`` stack — the storage writer
thread traces its journal appends without corrupting the engine thread's
stack) and carry a depth + parent name so ordering is reconstructible from
the flat event list. Export formats:

  * :meth:`Tracer.dump_jsonl` — one JSON object per line
    (``{"name", "ts", "dur", "depth", "parent", "tid", "args"}``), the
    stable machine-readable form CI asserts against.
  * :meth:`Tracer.dump_chrome` — Chrome ``trace_event`` JSON (``"ph": "X"``
    complete events, microsecond timestamps) loadable in chrome://tracing
    or https://ui.perfetto.dev.

``tracer.event(name, **args)`` records zero-duration structured events
(resize decisions, re-anchor epochs) that appear as instant events in the
Chrome view. ``NULL_TRACER`` is the shared no-op used when obs is off.

Memory is bounded on request: ``Tracer(max_events=N)`` keeps the N most
recent records in a drop-oldest :class:`Ring` (the same ring the flight
recorder uses) and counts evictions in :attr:`Tracer.dropped_events` —
long soak runs stop growing the event list without losing the recent
window that matters for a post-mortem. The default stays unbounded (short
benchmark runs export their complete trace).

A live span is also a ``jax.profiler.TraceAnnotation`` of the same name,
opened on entry and closed after the exit sync: in a ``jax.profiler``
trace the span sits on the host thread that opened it, on the same clock
as the device ops, so an idle gap of the device can be named by the
engine layer the host was in. ``NullTracer`` opens none.

``jax`` is imported lazily (in ``_block`` and ``Span.__enter__``), so the
obs package imports without it and the null tracer never touches it.
"""

from __future__ import annotations

import collections
import json
import threading
import time

__all__ = ["Ring", "Span", "Tracer", "NULL_TRACER", "null_tracer",
           "chrome_events"]


class Ring:
    """Bounded drop-oldest buffer with an exact eviction counter.

    The fixed-memory primitive shared by the bounded tracer and the
    flight recorder: pushes never fail, the oldest item falls out once
    ``capacity`` is reached, and ``dropped`` counts exactly how many
    items the window no longer holds. ``capacity=None`` is unbounded.
    """

    __slots__ = ("capacity", "dropped", "_items")

    def __init__(self, capacity: int | None = None):
        if capacity is not None and capacity < 1:
            raise ValueError(f"ring capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.dropped = 0
        self._items: collections.deque = collections.deque(maxlen=capacity)

    def push(self, item) -> None:
        if self.capacity is not None and len(self._items) == self.capacity:
            self.dropped += 1
        self._items.append(item)

    def items(self) -> list:
        return list(self._items)

    def __len__(self) -> int:
        return len(self._items)

    def clear(self) -> None:
        self._items.clear()
        self.dropped = 0


def _block(obj) -> None:
    """Resolve a sync target: call it if callable, then block on it."""
    if obj is None:
        return
    if callable(obj):
        obj = obj()
    if obj is None:
        return
    import jax

    jax.block_until_ready(obj)


class Span:
    """Context manager for one timed region. Created via Tracer.span."""

    __slots__ = ("tracer", "name", "sync", "args", "t0", "depth", "parent",
                 "annotation")

    def __init__(self, tracer: "Tracer", name: str, sync, args: dict):
        self.tracer = tracer
        self.name = name
        self.sync = sync
        self.args = args
        self.t0 = 0.0
        self.depth = 0
        self.parent = None
        self.annotation = None

    def __enter__(self) -> "Span":
        stack = self.tracer._stack()
        self.depth = len(stack)
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        from jax import profiler

        self.annotation = profiler.TraceAnnotation(self.name)
        self.annotation.__enter__()
        # Sync on entry too, so pending work dispatched *before* the span
        # is not billed to it. Entry sync reuses the same target: by the
        # time the span opens the target usually doesn't exist yet, so
        # callers pass a callable or rely on the default (None = no sync).
        self.t0 = time.perf_counter()
        return self

    def set_sync(self, sync) -> None:
        """Install/replace the exit sync target from inside the span."""
        self.sync = sync

    def __exit__(self, exc_type, exc, tb) -> None:
        try:
            if exc_type is None:
                _block(self.sync)
        finally:
            self.annotation.__exit__(None, None, None)
        t1 = time.perf_counter()
        stack = self.tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self.tracer._emit(self, t1)


class Tracer:
    """Collects spans and instant events; exports JSONL / Chrome JSON.

    ``max_events`` bounds the retained records (drop-oldest);
    ``drop_counter`` is an optional counter-like object (``.inc()``)
    bumped once per evicted record — the ``trace.dropped_events``
    registry counter when wired through :class:`repro.obs.Obs`. Sinks
    registered via :meth:`add_sink` see every record as it completes
    (the flight recorder taps the stream this way) regardless of what
    the ring later evicts.
    """

    def __init__(self, max_events: int | None = None,
                 drop_counter=None) -> None:
        self._events = Ring(max_events)
        self._drop_counter = drop_counter
        self._sinks: list = []
        self._local = threading.local()
        self._lock = threading.Lock()
        self._epoch = time.perf_counter()

    @property
    def dropped_events(self) -> int:
        """Records evicted by the ``max_events`` bound so far."""
        return self._events.dropped

    def add_sink(self, fn) -> None:
        """Register ``fn(record)`` to observe every completed record."""
        self._sinks.append(fn)

    def set_drop_counter(self, counter) -> None:
        self._drop_counter = counter

    def _append(self, rec: dict) -> None:
        with self._lock:
            before = self._events.dropped
            self._events.push(rec)
            if self._events.dropped != before \
                    and self._drop_counter is not None:
                self._drop_counter.inc()
        for fn in self._sinks:
            fn(rec)

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def span(self, name: str, sync=None, **args) -> Span:
        """Open a nested span. ``sync`` is blocked on at exit (see module
        docstring); ``args`` become structured payload on the record."""
        return Span(self, name, sync, args)

    def event(self, name: str, **args) -> None:
        """Zero-duration structured event at the current nesting level."""
        stack = self._stack()
        rec = {
            "name": name,
            "ts": time.perf_counter() - self._epoch,
            "dur": 0.0,
            "depth": len(stack),
            "parent": stack[-1].name if stack else None,
            "tid": threading.get_ident(),
            "args": args,
        }
        self._append(rec)

    def _emit(self, span: Span, t1: float) -> None:
        rec = {
            "name": span.name,
            "ts": span.t0 - self._epoch,
            "dur": t1 - span.t0,
            "depth": span.depth,
            "parent": span.parent,
            "tid": threading.get_ident(),
            "args": span.args,
        }
        self._append(rec)

    # -- export ----------------------------------------------------------

    def records(self) -> list[dict]:
        """Completed records, ordered by start time."""
        with self._lock:
            return sorted(self._events.items(), key=lambda r: r["ts"])

    def dump_jsonl(self, path: str) -> None:
        with open(path, "w") as f:
            for rec in self.records():
                f.write(json.dumps(rec) + "\n")

    def chrome_events(self) -> list[dict]:
        """Chrome trace_event list: "X" complete events (+instants)."""
        return chrome_events(self.records())

    def dump_chrome(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({"traceEvents": self.chrome_events(),
                       "displayTimeUnit": "ms"}, f)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
        self._epoch = time.perf_counter()


def chrome_events(records: list[dict]) -> list[dict]:
    """Tracer-record list -> Chrome trace_event list (shared with the
    flight recorder, whose ring holds records of the same schema)."""
    out = []
    for rec in records:
        ev = {
            "name": rec["name"],
            "cat": rec["parent"] or "root",
            "pid": 1,
            "tid": rec["tid"],
            "ts": rec["ts"] * 1e6,
            "args": rec["args"],
        }
        if rec["dur"] > 0.0:
            ev["ph"] = "X"
            ev["dur"] = rec["dur"] * 1e6
        else:
            ev["ph"] = "i"
            ev["s"] = "t"
        out.append(ev)
    return out


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return None

    def set_sync(self, sync) -> None:
        pass


_NULL_SPAN = _NullSpan()


class NullTracer:
    """No-op tracer. span() skips even the sync (obs-off must not add
    device blocking that obs-on placed deliberately at span edges)."""

    dropped_events = 0

    def span(self, name, sync=None, **args):
        return _NULL_SPAN

    def event(self, name, **args) -> None:
        pass

    def add_sink(self, fn) -> None:
        pass

    def set_drop_counter(self, counter) -> None:
        pass

    def records(self) -> list:
        return []

    def chrome_events(self) -> list:
        return []

    def dump_jsonl(self, path) -> None:
        pass

    def dump_chrome(self, path) -> None:
        pass

    def clear(self) -> None:
        pass


NULL_TRACER = NullTracer()


def null_tracer() -> NullTracer:
    return NULL_TRACER
