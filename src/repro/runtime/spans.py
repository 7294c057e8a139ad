"""Named host spans and counters on the query and write paths.

``with span("p2h.search"):`` does two things:

* it enters a ``jax.profiler.TraceAnnotation`` of the same name, so when
  a profiler session is on the interval lands on the trace's host plane,
  on the clock of the device ops (none is made without a session);
* on exit it adds the interval's host duration to a per-name record:
  count, total seconds, self seconds (the duration less the time of the
  spans nested inside it on the same thread) and a ring of the last
  :data:`RING` durations, for percentiles.  The span keeps its duration
  as ``duration_s``.

``count(name, n)`` adds to a counter.  The recorder is process-wide.
Each thread records into its own table, with no lock on the way;
:func:`snapshot` merges the tables (the background compactor records
too) and :func:`reset` starts a new generation of them.  The serving
engine surfaces it in ``P2HEngine.stats()`` and clears it in
``P2HEngine.reset_stats()``.
"""
from __future__ import annotations

import collections
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["RING", "count", "reset", "snapshot", "span"]

#: durations kept per span name and thread for the percentiles
RING = 4096


class _Record:
    __slots__ = ("count", "total_s", "self_s", "ring")

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.self_s = 0.0
        self.ring = collections.deque(maxlen=RING)


class _Table:
    """One thread's records and counters of one generation."""

    __slots__ = ("gen", "thread", "records", "counters")

    def __init__(self, gen: int, thread):
        self.gen = gen
        self.thread = thread
        self.records: dict[str, _Record] = {}
        self.counters: dict[str, int] = {}


_lock = threading.Lock()  # guards _gen, _tables and _retired
_gen = 0
_tables: list[_Table] = []  # this generation's, one per live thread
_retired = _Table(0, None)  # the tables of threads that have ended
_local = threading.local()  # .stack: this thread's open spans; .table
_tracing = TraceAnnotation.is_enabled
_clock = time.perf_counter


def _fold(into: _Table, table: _Table) -> None:
    for name, rec in table.records.items():
        acc = into.records.get(name)
        if acc is None:
            acc = into.records[name] = _Record()
        acc.count += rec.count
        acc.total_s += rec.total_s
        acc.self_s += rec.self_s
        acc.ring.extend(rec.ring)
    for name, n in table.counters.items():
        into.counters[name] = into.counters.get(name, 0) + n


def _table() -> _Table:
    """This thread's table of the current generation."""
    table = getattr(_local, "table", None)
    if table is None or table.gen != _gen:
        with _lock:
            # a thread that ended records no more: fold its table, so
            # the list holds the live threads only
            for old in [t for t in _tables if not t.thread.is_alive()]:
                _fold(_retired, old)
                _tables.remove(old)
            table = _local.table = _Table(_gen, threading.current_thread())
            _tables.append(table)
    return table


class span:
    """Context manager: one named span (``attrs`` go to the profiler
    annotation only)."""

    __slots__ = ("name", "duration_s", "_attrs", "_ann", "_t0", "_child_s")

    def __init__(self, name: str, **attrs):
        self.name = name
        self._attrs = attrs

    def __enter__(self):
        try:
            stack = _local.stack
        except AttributeError:
            stack = _local.stack = []
        stack.append(self)
        self._child_s = 0.0
        if _tracing():
            self._ann = TraceAnnotation(self.name, **self._attrs)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = _clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = self.duration_s = _clock() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = _local.stack
        stack.pop()
        if stack:
            stack[-1]._child_s += dt
        records = _table().records
        rec = records.get(self.name)
        if rec is None:
            rec = records[self.name] = _Record()
        rec.count += 1
        rec.total_s += dt
        rec.self_s += dt - self._child_s
        rec.ring.append(dt)
        return False


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    counters = _table().counters
    counters[name] = counters.get(name, 0) + n


def _percentile(ordered, p: float) -> float:
    """Nearest-rank percentile of a sorted, non-empty sequence."""
    return ordered[min(len(ordered) - 1,
                       int(round(p / 100 * (len(ordered) - 1))))]


def snapshot() -> dict:
    """``{"spans": {name: {count, total_s, self_s, p50_ms, p95_ms}},
    "counters": {name: total}}`` over every thread since the last
    :func:`reset`; a counter never added to is absent.  The percentiles
    read the last :data:`RING` durations of each thread."""
    with _lock:
        # copies, taken whole: a thread may add a name meanwhile
        tables = [_copy(t) for t in (_retired, *_tables)]
    merged: dict[str, list] = {}  # name -> [count, total, self, durations]
    counters: dict[str, int] = {}
    for table in tables:
        for name, rec in table.records.items():
            acc = merged.setdefault(name, [0, 0.0, 0.0, []])
            acc[0] += rec.count
            acc[1] += rec.total_s
            acc[2] += rec.self_s
            acc[3] += rec.ring
        for name, n in table.counters.items():
            counters[name] = counters.get(name, 0) + n
    spans = {}
    for name, (n, total, self_s, ring) in merged.items():
        ring.sort()
        spans[name] = {"count": n, "total_s": total, "self_s": self_s,
                       "p50_ms": _percentile(ring, 50) * 1e3,
                       "p95_ms": _percentile(ring, 95) * 1e3}
    return {"spans": spans, "counters": counters}


def _copy(table: _Table) -> _Table:
    out = _Table(table.gen, None)
    for name, rec in list(table.records.items()):
        r = out.records[name] = _Record()
        r.count, r.total_s, r.self_s = rec.count, rec.total_s, rec.self_s
        r.ring.extend(list(rec.ring))
    out.counters = dict(table.counters)
    return out


def reset() -> None:
    """Clear every record and counter: each thread starts a new table
    at its next update (a span open across the reset records into the
    old one, which nothing reads)."""
    global _gen, _retired
    with _lock:
        _gen += 1
        _tables.clear()
        _retired = _Table(_gen, None)
