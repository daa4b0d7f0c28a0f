"""Host spans of the gate→launch→transport→serve path, on two sinks.

``span(name, step=None, **args)`` is a context manager.  While
observability is on it does two things:

* it stores an in-memory ``Event``: name, thread, monotonic
  (``time.perf_counter_ns``) start and duration, its args, its own id,
  the id of the span that encloses it on the same thread (``parent``)
  and the step it belongs to.  A span opened without ``step`` inherits
  its parent's, so every span of one fleet step shares the step id of
  the step span that encloses it;
* it enters a ``jax.profiler.TraceAnnotation`` of the same name, so a
  profiler session running at the same time records the span on the
  host plane of the device trace, on the device ops' clock.

While observability is off, ``span()`` returns the shared
``NULL_SPAN``: no event is stored and no annotation is made.

``begin(name, track=...)`` returns a handle for work whose completion is
observed later than its start — the async pipeline opens a
``device_compute`` span at dispatch and ends it at the ``collect()``
fence, so host-plan and device spans visibly overlap on separate tracks
without adding a sync point.  Such spans live in memory only.

``STEP_SPANS`` names every span a fleet step opens: the two step spans
of ``fleet.runtime`` and the roles inside them, the same name for the
same role on the single-device and the sharded path.
"""
from __future__ import annotations

import contextvars
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

from repro.obs import state

STEP_SPANS = (
    "fleet_reuse_step", "sharded_fleet_step",  # the step, fleet.runtime
    "stage",           # tables, frames stacked and padded on the device
    "gate",            # enqueue the delta gate
    "gate_readback",   # the gate's stats pulled to the host
    "reuse_plan",      # threshold, dilate, compact, bucket (host numpy)
    "conv_dispatch",   # enqueue the conv chain, cache and canvas updates
    "ref_advance",     # advance the gate's references
    "heads_out",       # hand the head maps out (host pull where sharded)
)


class Event(NamedTuple):
    """One finished span.  ``parent`` is the ``span_id`` of the span
    that enclosed it on its thread (0: none); ``step`` is None outside a
    step."""
    name: str
    tid: int
    t0_ns: int
    dur_ns: int
    args: dict
    span_id: int
    parent: int
    step: Optional[int]


_LOCK = threading.Lock()
_EVENTS: List[Event] = []
_HOST_TIDS: Dict[int, int] = {}     # thread ident -> small stable tid
_TRACK_TIDS: Dict[str, int] = {}    # track name -> tid
TRACK_TID_BASE = 1000               # host tids stay below this
_IDS = itertools.count(1)
_OPEN: contextvars.ContextVar = contextvars.ContextVar("open_span",
                                                       default=None)


class _NullSpan:
    """Shared do-nothing span/handle returned while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass

    def end(self, **args):
        pass


NULL_SPAN = _NullSpan()


def _host_tid() -> int:
    ident = threading.get_ident()
    tid = _HOST_TIDS.get(ident)
    if tid is None:
        with _LOCK:
            tid = _HOST_TIDS.setdefault(ident, len(_HOST_TIDS) + 1)
    return tid


def _track_tid(track: str) -> int:
    tid = _TRACK_TIDS.get(track)
    if tid is None:
        with _LOCK:
            tid = _TRACK_TIDS.setdefault(
                track, TRACK_TID_BASE + len(_TRACK_TIDS))
    return tid


def _store(ev: Event) -> None:
    with _LOCK:
        _EVENTS.append(ev)


class Span:
    """``with span("gate"):`` — closed on the emitting thread."""

    __slots__ = ("name", "args", "step", "span_id", "parent", "_t0",
                 "_token", "_annotation")

    def __init__(self, name: str, step: Optional[int], args: dict):
        self.name = name
        self.args = args
        self.step = step
        self.span_id = next(_IDS)
        self.parent = None
        self._t0 = 0

    def set(self, **args) -> None:
        self.args.update(args)

    def __enter__(self):
        self.parent = _OPEN.get()
        if self.step is None and self.parent is not None:
            self.step = self.parent.step
        self._token = _OPEN.set(self)
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self._t0
        self._annotation.__exit__(*exc)
        _OPEN.reset(self._token)
        _store(Event(self.name, _host_tid(), self._t0, dur, self.args,
                     self.span_id,
                     0 if self.parent is None else self.parent.span_id,
                     self.step))
        return False


class AsyncSpan:
    """begin()/end() span on a named track — for in-flight device work
    whose completion is only observed at an existing fence."""

    __slots__ = ("name", "args", "track", "step", "_t0", "_done")

    def __init__(self, name: str, track: str, step: Optional[int],
                 args: dict):
        self.name = name
        self.track = track
        self.step = step
        self.args = args
        self._done = False
        self._t0 = time.perf_counter_ns()

    def end(self, **args) -> None:
        if self._done:
            return
        self._done = True
        dur = time.perf_counter_ns() - self._t0
        self.args.update(args)
        _store(Event(self.name, _track_tid(self.track), self._t0, dur,
                     self.args, next(_IDS), 0, self.step))


def span(name: str, step: Optional[int] = None, **args):
    """Open a host-thread span; the shared no-op object when disabled."""
    if not state.enabled:
        return NULL_SPAN
    return Span(name, step, args)


def begin(name: str, track: str = "device", step: Optional[int] = None,
          **args):
    """Start an async span on ``track`` NOW; close it with
    ``handle.end()`` wherever the completion is already observed."""
    if not state.enabled:
        return NULL_SPAN
    return AsyncSpan(name, track, step, args)


def events() -> List[Event]:
    with _LOCK:
        return list(_EVENTS)


def span_count() -> int:
    return len(_EVENTS)


def clear() -> None:
    with _LOCK:
        _EVENTS.clear()
