"""Client side of a run: sends the mix through `Gateway.submit` and
records, on the benchmark's own clock, when each request was due, when
it was sent, and when each of its tokens arrived.

Open loop: requests go out on the mix's schedule whether or not earlier
ones have finished; each is timed from when it was due.  Closed loop:
one client per slot sends its next request as the last completes; each
is timed from when it was sent.  One thread per in-flight request reads
its stream, as a caller of the Gateway would.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, Iterator, List, Optional, Sequence

from traffic import RequestSpec

STREAM_TIMEOUT_S = 300.0
SAMPLE_PERIOD_S = 0.05
# open loop: how long past the window's close the measured requests may
# take to finish before the run gives up on them (they then count as
# failed)
DRAIN_LIMIT_S = 120.0


@dataclasses.dataclass
class Record:
    spec: RequestSpec
    due: float
    sent: float = 0.0
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    error: str = ""
    node: str = ""
    finished: bool = False
    cancelled: bool = False        # by the benchmark, after the window

    @property
    def phase(self) -> str:
        return self.spec.phase

    @property
    def ok(self) -> bool:
        return (self.finished and not self.error
                and len(self.tokens) == self.spec.max_tokens)


@dataclasses.dataclass
class Window:
    loop: str
    t0: float
    t1: float
    records: List[Record]
    samples: List[Dict]                  # periodic program readings
    trace_window: Optional[tuple] = None  # (start, end) on the same clock


class _Tracker:
    """In-flight handles, and a condition on finished window requests."""

    def __init__(self):
        self.cv = threading.Condition()
        self.live: Dict[int, object] = {}
        self.window_left = 0

    def add(self, rec: Record, handle) -> None:
        with self.cv:
            self.live[rec.spec.idx] = (rec, handle)
            if rec.phase == "window":
                self.window_left += 1

    def done(self, rec: Record) -> None:
        with self.cv:
            self.live.pop(rec.spec.idx, None)
            if rec.phase == "window":
                self.window_left -= 1
            self.cv.notify_all()

    def cancel_all(self) -> None:
        with self.cv:
            live = list(self.live.values())
        for rec, h in live:
            rec.cancelled = True
            h.cancel()


def _consume(handle, rec: Record, tracker: _Tracker) -> None:
    from repro.api import StreamEventType
    try:
        for ev in handle.stream(timeout_s=STREAM_TIMEOUT_S):
            if ev.type is StreamEventType.TOKEN:
                rec.times.append(time.perf_counter())
                rec.tokens.append(ev.token)
            elif ev.type is StreamEventType.ERROR:
                rec.error = str(ev.error)
        rec.node = handle.internal.node or ""
    except Exception as e:              # a broken stream is a failed request
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        rec.finished = True
        tracker.done(rec)


def _sampler(read: Callable[[], Dict], t0: float, t1: float,
             out: List[Dict], stop: threading.Event) -> None:
    nxt = t0
    while not stop.is_set():
        now = time.perf_counter()
        if now >= t1:
            return
        if now >= nxt:
            out.append(dict(read(), t=now))
            nxt += SAMPLE_PERIOD_S
        stop.wait(max(nxt - time.perf_counter(), 0.0))


def _sleep_until(t: float) -> None:
    while True:
        d = t - time.perf_counter()
        if d <= 0:
            return
        time.sleep(min(d, 0.05) if d > 0.002 else d)


class Tracer:
    """Starts and stops the profiler around a sub-window, off the
    dispatching thread."""

    def __init__(self, start: float, seconds: float, out_dir: str):
        self.start, self.seconds, self.dir = start, seconds, out_dir
        self.window = None
        self._t = threading.Thread(target=self._run, name="bench-tracer",
                                   daemon=True)

    def _run(self):
        import jax
        # host events from JAX and the runtime only: the Python tracer
        # would slow every thread of the process it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        _sleep_until(self.start)
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        a = time.perf_counter()
        _sleep_until(a + self.seconds)
        b = time.perf_counter()
        jax.profiler.stop_trace()
        self.window = (a, b)

    def begin(self):
        self._t.start()

    def join(self):
        self._t.join()


def run_open(submit: Callable, schedule: Iterator[RequestSpec],
             lead_s: float, seconds: float, read: Callable[[], Dict],
             tracer: Optional[Tracer] = None) -> Window:
    """Drive an open-loop schedule.  Tail traffic keeps the load on until
    every window request has finished; then in-flight tail requests are
    cancelled."""
    tracker = _Tracker()
    records: List[Record] = []
    samples: List[Dict] = []
    stop = threading.Event()
    start = time.perf_counter()
    t0, t1 = start + lead_s, start + lead_s + seconds
    sampler = threading.Thread(target=_sampler, name="bench-sampler",
                               args=(read, t0, t1, samples, stop),
                               daemon=True)
    sampler.start()
    if tracer is not None:
        tracer.begin()
    pool = ThreadPoolExecutor(max_workers=1024,
                              thread_name_prefix="bench-client")
    try:
        for spec in schedule:
            if spec.phase == "tail":
                with tracker.cv:
                    if tracker.window_left == 0:
                        break
                if time.perf_counter() > t1 + DRAIN_LIMIT_S:
                    break
            due = start + spec.due
            _sleep_until(due)
            rec = Record(spec, due)
            rec.sent = time.perf_counter()
            handle = submit(spec)
            records.append(rec)
            tracker.add(rec, handle)
            pool.submit(_consume, handle, rec, tracker)
        tracker.cancel_all()
    finally:
        pool.shutdown(wait=True)
        stop.set()
        sampler.join()
        if tracer is not None:
            tracer.join()
    return Window("open", t0, t1, records, samples,
                  tracer.window if tracer else None)


def run_closed(submit: Callable, streams: Sequence[Iterator[RequestSpec]],
               lead_s: float, seconds: float, read: Callable[[], Dict],
               tracer: Optional[Tracer] = None) -> Window:
    """Drive one client per stream until the window closes; requests in
    flight at the close are cancelled (their tokens before it count)."""
    tracker = _Tracker()
    records: List[Record] = []
    lock = threading.Lock()
    samples: List[Dict] = []
    stop, closed = threading.Event(), threading.Event()
    start = time.perf_counter()
    t0, t1 = start + lead_s, start + lead_s + seconds

    def client(it: Iterator[RequestSpec]):
        for spec in it:
            if closed.is_set():
                return
            rec = Record(spec, 0.0)
            rec.sent = rec.due = time.perf_counter()
            handle = submit(spec)
            with lock:
                records.append(rec)
            tracker.add(rec, handle)
            _consume(handle, rec, tracker)

    sampler = threading.Thread(target=_sampler, name="bench-sampler",
                               args=(read, t0, t1, samples, stop),
                               daemon=True)
    sampler.start()
    if tracer is not None:
        tracer.begin()
    clients = [threading.Thread(target=client, args=(it,), daemon=True,
                                name=f"bench-client-{i}")
               for i, it in enumerate(streams)]
    for c in clients:
        c.start()
    try:
        _sleep_until(t1)
    finally:
        closed.set()
        tracker.cancel_all()
        for c in clients:
            c.join(STREAM_TIMEOUT_S)
        stop.set()
        sampler.join()
        if tracer is not None:
            tracer.join()
    return Window("closed", t0, t1, records, samples,
                  tracer.window if tracer else None)
