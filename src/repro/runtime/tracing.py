"""Host spans of the serving engine: one in-memory record, and the same
names on the profiler's host plane.

``span(name, req=None, clock=...)`` is a context manager.  It stamps its
start and end on ``clock`` (the engine passes its own, ``Engine._now``),
keeps the id of the span open around it on the same thread as its
parent, and the request id where there is one.  While a profiler session
is active it also enters ``jax.profiler.TraceAnnotation(name)``, so the
span lands on the Python thread's host line, on the device trace's
clock; without one that half costs a flag check.

Finished spans go into a process-wide bounded ring (process-wide in the
same way as ``sampling.TRACE_COUNTS``); ``dropped()`` counts the spans
it pushed out.  ``query(t0, t1, names)`` returns the spans that lie
wholly inside ``[t0, t1)`` and says whether any span of that interval
was dropped.  The span's stamps are also what the engine's request
timings and ``ServeStats`` durations are taken from, so a span reads
the clock even while recording is disabled.

Names start with ``engine.``.  There is no file exporter: a profiler
trace carries the spans.  Nesting is tracked per thread; the ring and
its drop counter assume one thread records at a time, as the engine's
callers (its own loop, the scheduler, the async front end's pump) do.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Callable, Iterable, NamedTuple, Optional

from jax.profiler import TraceAnnotation

#: finished spans the ring holds before it drops the oldest
CAPACITY = 1 << 16


class Record(NamedTuple):
    id: int
    parent: Optional[int]      # id of the span open around it, or None
    name: str
    req: Optional[int]         # request id, where the span has one
    t0: float
    t1: float


_ring: collections.deque = collections.deque(maxlen=CAPACITY)
_ids = itertools.count(1)
_local = threading.local()
_state = {"on": True, "dropped": 0, "dropped_t1": float("-inf")}
_profiling = TraceAnnotation.is_enabled


class span:
    """``with span("engine.admit", req.req_id, clock=now) as s:`` —
    ``s.t0`` is set on entry, ``s.t1`` on exit."""

    __slots__ = ("name", "req", "clock", "t0", "t1", "id", "parent",
                 "_ann")

    def __init__(self, name: str, req: Optional[int] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.name = name
        self.req = req
        self.clock = clock
        self.t1 = None
        self._ann = None

    def __enter__(self) -> "span":
        if _state["on"]:
            stack = getattr(_local, "stack", None)
            if stack is None:
                stack = _local.stack = []
            self.id = next(_ids)
            self.parent = stack[-1] if stack else None
            stack.append(self.id)
            if _profiling():
                self._ann = TraceAnnotation(self.name)
                self._ann.__enter__()
        else:
            self.id = None
        self.t0 = self.clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = self.clock()
        if self.id is None:
            return False
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        _local.stack.pop()
        if len(_ring) == _ring.maxlen:
            _state["dropped"] += 1
            _state["dropped_t1"] = max(_state["dropped_t1"], _ring[0][5])
        _ring.append((self.id, self.parent, self.name, self.req, self.t0,
                      self.t1))
        return False


def enable() -> None:
    _state["on"] = True


def disable() -> None:
    """Record nothing and annotate nothing; spans still read the clock."""
    _state["on"] = False


def dropped() -> int:
    """Spans the ring pushed out since the last ``reset``."""
    return _state["dropped"]


def reset(capacity: int = CAPACITY) -> None:
    """Forget every recorded span; the ring holds ``capacity`` from now
    on."""
    global _ring
    _ring = collections.deque(maxlen=capacity)
    _state["dropped"] = 0
    _state["dropped_t1"] = float("-inf")


def query(t0: float, t1: float, names: Optional[Iterable[str]] = None
          ) -> tuple[list, bool]:
    """The recorded spans with ``t0 <= start`` and ``end < t1``, in the
    order they ended, of the given names (all where None), and whether
    the ring dropped a span that ended at or after ``t0``."""
    want = None if names is None else frozenset(names)
    out = [Record(*r) for r in list(_ring)
           if t0 <= r[4] and r[5] < t1 and (want is None or r[2] in want)]
    return out, bool(_state["dropped"]) and _state["dropped_t1"] >= t0
