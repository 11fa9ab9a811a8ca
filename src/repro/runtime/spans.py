"""Named host spans and counters: the runtime's one timing mechanism.

    with spans.record("reap.run") as rec:       # opens a run record
        rec.op = "cholesky"
        with spans.span("reap.acquire"):
            ...
        spans.count("h2d_bytes", n)

``span(name)`` opens a ``jax.profiler.TraceAnnotation`` of the same name,
so under the profiler the span lands on the host thread's line, on the
clock of the device's operations; and on exit it adds its duration to the
current **run record**.  The handle it yields carries that duration
(``.seconds``), so a stats key that times the same interval reads the
span instead of keeping a clock pair of its own.

A run record holds per-name totals: inclusive seconds, calls, the seconds
of each name's direct children on the same thread (so self time is
``seconds - child_seconds``), and counters.  ``record(name)`` opens one
with ``name`` as its root span.  A record opened while another is open on
the thread is nested: on exit it adds its sums into the enclosing record
(a solve's matvecs add to the solve); only an outermost record goes to the
ring that ``recent(n)`` reads.  A worker thread counts into the record of
the run that handed it the work: the submitter passes ``current()`` and
the worker runs under ``bind(rec)``.  Spans outside any record time
themselves and are not kept.

Always on: with the profiler off a span costs two clock reads, a no-op
annotation and a dict update under the record's lock.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, Iterator, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

#: Finished outermost records kept for ``recent``.
RING_SIZE = 256

_RING: "collections.deque[Record]" = collections.deque(maxlen=RING_SIZE)
_local = threading.local()
_clock = time.perf_counter


class Record:
    """Per-name sums of one run: ``seconds`` (inclusive), ``calls``,
    ``child_seconds`` (time in direct children on the same thread) and
    ``counters``.  ``op`` is the op tag the run resolved to."""

    __slots__ = ("op", "seconds", "calls", "child_seconds", "counters",
                 "_lock")

    def __init__(self, op: Optional[str] = None):
        self.op = op
        self.seconds: Dict[str, float] = {}
        self.calls: Dict[str, int] = {}
        self.child_seconds: Dict[str, float] = {}
        self.counters: Dict[str, float] = {}
        self._lock = threading.Lock()

    def self_seconds(self, name: str) -> float:
        """Time in ``name`` not spent in its children on the same thread."""
        return self.seconds.get(name, 0.0) - self.child_seconds.get(name, 0.0)

    def _add(self, name: str, dt: float, child: float) -> None:
        with self._lock:
            self.seconds[name] = self.seconds.get(name, 0.0) + dt
            self.calls[name] = self.calls.get(name, 0) + 1
            self.child_seconds[name] = (
                self.child_seconds.get(name, 0.0) + child)

    def _count(self, name: str, n) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def _merge(self, other: "Record") -> None:
        with self._lock:
            for mine, theirs in ((self.seconds, other.seconds),
                                 (self.calls, other.calls),
                                 (self.child_seconds, other.child_seconds),
                                 (self.counters, other.counters)):
                for k, v in theirs.items():
                    mine[k] = mine.get(k, 0) + v


def _state():
    try:
        return _local.frames, _local.records
    except AttributeError:
        _local.frames, _local.records = [], []
        return _local.frames, _local.records


class Span:
    """Handle of one span; ``seconds`` is its duration once it has closed."""

    __slots__ = ("name", "seconds", "child", "_t0", "_rec", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0
        self.child = 0.0

    def __enter__(self) -> "Span":
        frames, records = _state()
        self._rec = records[-1] if records else None
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        frames.append(self)
        self._t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = _clock() - self._t0
        frames, _ = _state()
        frames.pop()
        if frames:
            frames[-1].child += self.seconds
        if self._rec is not None:
            self._rec._add(self.name, self.seconds, self.child)
        self._ann.__exit__(*exc)


def span(name: str) -> Span:
    """Context manager timing ``name``; yields its :class:`Span`."""
    return Span(name)


@contextlib.contextmanager
def record(name: str, op: Optional[str] = None) -> Iterator[Record]:
    """Open a run record whose root span is ``name``; yields the record.

    Nested in another record on this thread, its sums go to that record on
    exit; outermost (or under ``bind(None)``), the record goes to the
    ring."""
    _, records = _state()
    rec = Record(op)
    records.append(rec)
    try:
        with Span(name):
            yield rec
    finally:
        records.pop()
        outer = records[-1] if records else None
        if outer is not None:
            outer._merge(rec)
        else:
            _RING.append(rec)


@contextlib.contextmanager
def bind(rec: Optional[Record]) -> Iterator[None]:
    """Count this thread's spans into ``rec`` (a record opened on another
    thread) until exit; ``None`` keeps them out of every record."""
    _, records = _state()
    records.append(rec)
    try:
        yield
    finally:
        records.pop()


def current() -> Optional[Record]:
    """The record this thread's spans count into, or None."""
    _, records = _state()
    return records[-1] if records else None


def count(name: str, n=1) -> None:
    """Add ``n`` to counter ``name`` of the current record (if any)."""
    _, records = _state()
    if records and records[-1] is not None:
        records[-1]._count(name, n)


def recent(n: int) -> List[Record]:
    """The last ``n`` finished outermost records, oldest first."""
    if n <= 0:
        return []
    ring = list(_RING)
    return ring[-n:]


def clear() -> None:
    """Drop every finished record."""
    _RING.clear()


def to_host(x) -> np.ndarray:
    """``np.asarray(x)`` under ``reap.fetch``: the wait for the device and
    the copy back, its bytes counted as ``d2h_bytes``."""
    with span("reap.fetch"):
        out = np.asarray(x)
        count("d2h_bytes", out.nbytes)
    return out
