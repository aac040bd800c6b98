"""Host spans of the session and the service, in one bounded recorder.

``span(name, **attrs)`` times one step on the calling thread::

    with telemetry.span("session.dispatch", path="mixed", events=256,
                        slots=256):
        ...

It opens a ``jax.profiler.TraceAnnotation`` of the same name, so a
profiler trace shows the step on the device trace's clock beside the
device's operations, and it appends a :class:`Span` to an in-memory ring
when the step ends. ``record(name, start, end, **attrs)`` appends a span
whose stamps were taken elsewhere, possibly on several threads (a served
chunk's life from its due time to its commit); it goes to the ring only.

Stamps are ``time.perf_counter()``: the clock of the service's
``arrival`` stamps. Names are ``<layer>.<step>``. The recorder is always
on and its memory is bounded: the ring keeps the newest ``CAPACITY``
records and counts the ones it evicts (``dropped``). Spans are taken per
feed, per window, per batch and per chunk, never per event.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import NamedTuple

import jax

CAPACITY = 1 << 14


class Span(NamedTuple):
    name: str
    start: float            # time.perf_counter()
    end: float
    thread: str             # name of the thread that recorded it
    parent: str | None      # the span open around it on that thread
    attrs: dict


class _Ring:
    """The bounded record store, shared by every thread of the process."""

    def __init__(self, capacity: int):
        self._ring: collections.deque[Span] = collections.deque(
            maxlen=capacity)
        self._lock = threading.Lock()
        self._dropped = 0
        self._dropped_until = float("-inf")   # latest end evicted
        self._local = threading.local()

    def here(self):
        """The calling thread's name and stack of open span names."""
        try:
            return self._local.here
        except AttributeError:
            self._local.here = (threading.current_thread().name, [])
            return self._local.here

    def add(self, rec: Span) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self._dropped += 1
                self._dropped_until = max(self._dropped_until,
                                          self._ring[0].end)
            self._ring.append(rec)


_RING = _Ring(CAPACITY)


class _Open:
    """An open span: the context manager ``span`` returns."""

    __slots__ = ("name", "attrs", "parent", "start", "_note")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Open":
        _, stack = _RING.here()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self._note = jax.profiler.TraceAnnotation(self.name)
        self._note.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        self._note.__exit__(*exc)
        thread, stack = _RING.here()
        stack.pop()
        _RING.add(Span(self.name, self.start, end, thread, self.parent,
                       self.attrs))


def span(name: str, **attrs) -> _Open:
    """A context manager that records the step it wraps as ``name``, with
    ``attrs``, and marks it in any profiler trace (module docstring)."""
    return _Open(name, attrs)


def record(name: str, start: float, end: float, **attrs) -> None:
    """Record a span from stamps taken elsewhere (``perf_counter`` clock);
    its parent is the span open on the calling thread, if any."""
    thread, stack = _RING.here()
    _RING.add(Span(name, float(start), float(end), thread,
                   stack[-1] if stack else None, attrs))


def spans(since: float | None = None,
          until: float | None = None) -> list[Span]:
    """The records still held that start at or after ``since`` and end at
    or before ``until``, oldest recorded first."""
    lo = float("-inf") if since is None else since
    hi = float("inf") if until is None else until
    with _RING._lock:
        return [s for s in _RING._ring if s.start >= lo and s.end <= hi]


def dropped(since: float | None = None) -> int:
    """How many records the ring has evicted. With ``since``: 0 when every
    evicted record ended before ``since`` (what ``spans(since)`` returns is
    then complete), else that same count."""
    with _RING._lock:
        if since is not None and _RING._dropped_until < since:
            return 0
        return _RING._dropped
