"""Timing of the served path: the one place where it is taken.

`span(name, **attrs)` marks an interval in two sinks:

* a `jax.profiler.TraceAnnotation`, so the span lands on the host plane
  of any profiler trace, on the same clock as the device's lines; that
  shared clock is what lets a reader put a device idle gap down to the
  phase of the program the host was in;
* a record in a bounded in-process ring (`Ring`), stamped with `clock()`,
  the clock of `Request.created_at`; the benchmark's readers and the
  admin snapshot read it.

`record(name, t_start, t_end, **attrs)` writes to the ring alone: for
intervals that cross threads (a request's queue wait) or would enclose
other spans in the trace (a whole engine step).

Nothing switches this on or off.  The profiler's own start and stop is
the switch for the trace sink; an idle annotation and a ring append cost
about a microsecond each.  The ring is one per process, as the profiler
is: the serving threads write it and readers in the same process read
it.
"""
from __future__ import annotations

import bisect
import collections
import math
import threading
import time
from typing import Any, Dict, Iterable, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

# phases of one engine step, back to back on the serving thread (none
# nests in another, so a trace gap's label names the phase); attrs
# `node` and `step`
ENGINE_SCHEDULE = "engine.schedule"          # pick, page budget, host batch
PREFILL_DISPATCH = "engine.prefill.dispatch"
PREFILL_SYNC = "engine.prefill.sync"         # first tokens to the host
DECODE_PAGES = "engine.decode.pages"         # page growth, page tables
DECODE_DISPATCH = "engine.decode.dispatch"
DECODE_SYNC = "engine.decode.sync"           # the block to the host
ENGINE_EMIT = "engine.emit"                  # stream callbacks, releases
# a node's pump waiting for work; attr `node`
RUNTIME_IDLE = "runtime.idle"
# one routing attempt: pick plus the node's submit; attrs `request`,
# then `node`, `replica` and `ok` once a replica was picked
FRONTEND_ROUTE = "frontend.route"
# ring only: a whole engine step (attrs `node`, `step`), and a request's
# wait from creation to its first admission (attrs `node`, `request`)
ENGINE_STEP = "engine.step"
QUEUE_WAIT = "scheduler.queue_wait"

SYNCS = (PREFILL_SYNC, DECODE_SYNC)
RING_RECORDS = 1 << 16


def clock() -> float:
    """The ring's clock (seconds, `time.monotonic`)."""
    return time.monotonic()


class Record(NamedTuple):
    name: str
    t_start: float
    t_end: float
    thread: str
    attrs: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start


class Ring:
    """The newest `maxlen` records.  `dropped` counts those pushed out;
    `records` answers None for a window that may have lost some."""

    def __init__(self, maxlen: int = RING_RECORDS):
        self._buf: collections.deque = collections.deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self._dropped = 0
        self._lost_until = -math.inf    # latest end among those dropped

    def add(self, rec: Record) -> None:
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self._dropped += 1
                self._lost_until = max(self._lost_until, self._buf[0].t_end)
            self._buf.append(rec)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def records(self, name: str, since: float,
                until: float) -> Optional[List[Record]]:
        """Records of `name` that ended in [since, until]; None where a
        record that ended at or after `since` was pushed out."""
        with self._lock:
            if self._dropped and self._lost_until >= since:
                return None
            return [r for r in self._buf
                    if r.name == name and since <= r.t_end <= until]


RING = Ring()


def record(name: str, t_start: float, t_end: float, **attrs) -> None:
    RING.add(Record(name, t_start, t_end,
                    threading.current_thread().name, attrs))


def records(name: str, since: float,
            until: float) -> Optional[List[Record]]:
    return RING.records(name, since, until)


class _Span:
    __slots__ = ("name", "attrs", "_note", "_t0")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name, self.attrs = name, attrs

    def __enter__(self) -> Dict[str, Any]:
        self._note = TraceAnnotation(self.name, **self.attrs)
        self._note.__enter__()
        self._t0 = clock()
        return self.attrs

    def __exit__(self, *exc) -> None:
        t1 = clock()
        self._note.__exit__(*exc)
        record(self.name, self._t0, t1, **self.attrs)


def span(name: str, **attrs) -> _Span:
    """Context manager marking its body in both sinks.  It yields the
    attrs dict: what the body adds to it reaches the ring record (the
    trace keeps the attrs given here)."""
    return _Span(name, attrs)


class Phases:
    """Back-to-back spans on one thread: `to(name)` ends the open span
    and begins `name` (it keeps a span of that name open); `end()` ends
    the last.  Each span carries this object's attrs."""

    def __init__(self, **attrs):
        self.attrs = attrs
        self._open: Optional[_Span] = None

    def to(self, name: str) -> None:
        if self._open is not None and self._open.name == name:
            return
        self.end()
        self._open = span(name, **self.attrs)
        self._open.__enter__()

    def end(self) -> None:
        if self._open is not None:
            s, self._open = self._open, None
            s.__exit__(None, None, None)


# ---- what an operator reads: the recent past of one node ---------- #
def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank q-th percentile; None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    return xs[max(math.ceil(q / 100.0 * len(xs)), 1) - 1]


def host_seconds(steps: List[Record], syncs: List[Record]) -> List[float]:
    """Per engine step, its time less the device waits (`SYNCS` spans)
    inside it: a step's spans run on its thread, within its interval."""
    rows: Dict[str, List] = {}
    for i, st in enumerate(steps):
        rows.setdefault(st.thread, []).append((st.t_start, i))
    for row in rows.values():
        row.sort()
    waits = [0.0] * len(steps)
    for s in syncs:
        row = rows.get(s.thread, [])
        k = bisect.bisect_right(row, (s.t_start, math.inf)) - 1
        if k >= 0 and s.t_end <= steps[row[k][1]].t_end:
            waits[row[k][1]] += s.seconds
    return [st.seconds - w for st, w in zip(steps, waits)]
