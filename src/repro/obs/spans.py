"""Host spans and counters: what the grid driver spent its time on.

The simulator's drivers (`repro.core.mess.sweep`,
`repro.traces.replay.replay_suite` / `replay_mixes`) mark their own
layers here: routing, each compiled launch, the wait for its results,
the dense re-run of saturated rows, the merge.  Two kinds of entry:

* a **span**, ``span(name)``: a context manager (or decorator) that
  records ``(name, parent, start, end)`` on the host's
  `time.perf_counter` clock, the parent taken from a per-thread stack
  of open spans.  It also opens a `jax.profiler.TraceAnnotation`, so
  under an active profiler session the span lands on the trace's host
  plane, on the same clock as the device ops;
* a **count**, ``count(name, n)``: a timestamped increment (rows
  launched per engine, scan steps launched, event-budget steps used).

Both go into one bounded ring (`RING` entries; the oldest drop first),
so recording is always on and never grows without limit.  A span costs
a `perf_counter` pair, an append and a TraceMe that does nothing while
no profiler runs.  Read an interval back with `spans_between` and
`counts_between`; `self_seconds` is the host time of the outermost
spans less their waits on the device.

(Not to be confused with memory *access* traces, `repro.traces`, or
the simulated-time timeline of `repro.obs.export.to_perfetto`.)
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import NamedTuple

import jax

#: entries the ring holds; a driver call records about fifteen
RING = 32768


class Span(NamedTuple):
    name: str
    parent: str | None     # the enclosing span on the same thread
    start: float           # `time.perf_counter` seconds
    end: float


class Count(NamedTuple):
    name: str
    t: float               # `time.perf_counter` seconds
    n: int


_ring: collections.deque = collections.deque(maxlen=RING)
_open = threading.local()


@contextlib.contextmanager
def span(name: str):
    """Record the enclosed block as a span named ``name``."""
    stack = _open.__dict__.setdefault("stack", [])
    parent = stack[-1] if stack else None
    stack.append(name)
    start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        end = time.perf_counter()
        stack.pop()
        _ring.append(Span(name, parent, start, end))


def count(name: str, n) -> None:
    """Record an increment of ``n`` to the counter ``name``."""
    _ring.append(Count(name, time.perf_counter(), int(n)))


def spans_between(t0: float, t1: float) -> list:
    """The spans that started and ended within ``[t0, t1]``."""
    return [e for e in list(_ring) if isinstance(e, Span)
            and t0 <= e.start and e.end <= t1]


def counts_between(t0: float, t1: float) -> dict:
    """``{name: total}`` of the counts recorded within ``[t0, t1]``."""
    totals: dict = {}
    for e in list(_ring):
        if isinstance(e, Count) and t0 <= e.t <= t1:
            totals[e.name] = totals.get(e.name, 0) + e.n
    return totals


def self_seconds(spans, leaf: str = ".fetch") -> float:
    """Seconds of the outermost ``spans`` less the ``leaf`` spans in them.

    The outermost spans are those with no parent; a span whose name
    ends in ``leaf`` and lies inside one of them is the host waiting
    for the device, so what is left is the driver's own host work.
    """
    roots = [s for s in spans if s.parent is None]
    waits = [s for s in spans if s.name.endswith(leaf) and any(
        r.start <= s.start and s.end <= r.end for r in roots)]
    return (sum(r.end - r.start for r in roots)
            - sum(s.end - s.start for s in waits))
