"""The readers of the program's own span records (`spans.py` and the
`program_counter` metrics): their arithmetic on hand-written records,
the window and traced sub-window they keep to, and None where the ring
lost the window or the program keeps none."""
import sys
import time
import types

import pytest
import spec

import repro
from repro import obs


@pytest.fixture
def ring(monkeypatch):
    fresh = obs.Ring()
    monkeypatch.setattr(obs, "RING", fresh)
    return fresh


def _ctx(t0, t1, trace=None, replicas=("n0", "n1", "n2", "n3")):
    window = types.SimpleNamespace(t0=t0, t1=t1, trace_window=trace)
    return types.SimpleNamespace(window=window, replicas=list(replicas))


def _at(t):
    """A time on the window's clock, moved onto the ring's."""
    return t + obs.clock() - time.perf_counter()


def _read(name, ctx):
    return spec.load_reader(name).read(ctx)


def _step(node, step, start, ms, sync_ms=(), thread="pump-n0"):
    """One engine step at `start` (window clock) of `ms`, with device
    waits of `sync_ms` inside it."""
    a = _at(start)
    t = a
    for i, w in enumerate(sync_ms):
        name = obs.DECODE_SYNC if i % 2 == 0 else obs.PREFILL_SYNC
        obs.RING.add(obs.Record(name, t, t + w / 1e3, thread,
                                {"node": node, "step": step}))
        t += w / 1e3
    obs.RING.add(obs.Record(obs.ENGINE_STEP, a, a + ms / 1e3, thread,
                            {"node": node, "step": step}))


def test_host_ms_per_step_is_step_less_its_syncs_outside_the_trace(ring):
    t0 = time.perf_counter()
    ctx = _ctx(t0, t0 + 10.0, trace=(t0 + 4.0, t0 + 6.0))
    _step("n0", 1, t0 + 1.0, 100.0, (90.0,))            # host 10 ms
    _step("n0", 2, t0 + 2.0, 100.0, (94.0, 2.0))        # host 4 ms
    _step("n1", 2, t0 + 2.0, 50.0, (40.0,), thread="pump-n1")   # 10 ms
    _step("n0", 3, t0 + 5.0, 100.0, (50.0,))            # traced: left out
    _step("n0", 4, t0 + 3.95, 100.0, ())                 # ends in the trace
    _step("n0", 0, t0 - 0.05, 100.0, ())                 # starts before
    _step("n0", 5, t0 + 10.5, 100.0, ())                 # ends after
    assert _read("engine.host_ms_per_step", ctx) == pytest.approx(8.0)
    untraced = _ctx(t0, t0 + 10.0)
    # with no traced sub-window every step inside the window counts
    assert _read("engine.host_ms_per_step", untraced) == pytest.approx(
        (10 + 4 + 10 + 50 + 100) / 5)


def test_queue_wait_is_the_mean_of_requests_admitted_in_the_window(ring):
    t0 = time.perf_counter()
    ctx = _ctx(t0, t0 + 10.0)
    for i, (created, admitted) in enumerate(
            [(-1.0, 0.02), (1.0, 1.03), (2.0, 2.09), (9.9, 10.2)]):
        obs.record(obs.QUEUE_WAIT, _at(t0 + created), _at(t0 + admitted),
                   node="n0", request=i)
    # admitted before the window's start or after its end: left out
    assert _read("scheduler.queue_wait_ms", ctx) == pytest.approx(
        (1020 + 30 + 90) / 3)
    assert _read("scheduler.queue_wait_ms", _ctx(t0 + 20, t0 + 30)) is None


def test_replica_imbalance_counts_every_replica_and_accepted_routes(ring):
    t0 = time.perf_counter()
    ctx = _ctx(t0, t0 + 10.0)
    routes = ["n0"] * 5 + ["n1"] * 3 + ["n2"] * 4      # n3 sent nothing
    for i, node in enumerate(routes):
        t = _at(t0 + 1.0 + i * 0.1)
        obs.record(obs.FRONTEND_ROUTE, t, t + 1e-4, request=i, node=node,
                   replica=f"{node}/{i}", ok=True)
    t = _at(t0 + 5.0)
    obs.record(obs.FRONTEND_ROUTE, t, t + 1e-4, request=99, node="n3",
               replica="n3/9", ok=False)               # refused attempt
    obs.record(obs.FRONTEND_ROUTE, t, t + 1e-4, request=98)   # no backend
    assert _read("frontend.replica_imbalance", ctx) == pytest.approx(
        5 / (12 / 4))
    assert _read("frontend.replica_imbalance", _ctx(t0 + 20, t0 + 30)) \
        is None


def _write(t0):
    for i in range(5):
        t = _at(t0 + 1.0 + i)
        _step("n0", i, t0 + 1.0 + i, 10.0, (5.0,))
        obs.record(obs.QUEUE_WAIT, t - 0.01, t, node="n0", request=i)
        obs.record(obs.FRONTEND_ROUTE, t, t, request=i, node="n0", ok=True)


@pytest.mark.parametrize("name", ["engine.host_ms_per_step",
                                  "scheduler.queue_wait_ms",
                                  "frontend.replica_imbalance"])
def test_a_lost_window_or_no_ring_reads_none(monkeypatch, name):
    small = obs.Ring(maxlen=3)
    monkeypatch.setattr(obs, "RING", small)
    t0 = time.perf_counter()
    ctx = _ctx(t0, t0 + 10.0)
    _write(t0)
    assert small.dropped > 0
    assert _read(name, ctx) is None
    # the same records whole read a number; from a program without the
    # span ring they read None
    monkeypatch.setattr(obs, "RING", obs.Ring())
    _write(t0)
    assert _read(name, ctx) is not None
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs")
    assert _read(name, ctx) is None
