"""The serving loop's spans (`repro.obs`): each engine phase once per
phase it ran, back to back; one queue-wait record per request, never
again on a preempted resume; a bounded ring that says what it lost; the
spans on a profiler trace's host plane, where `tracing.reduce` puts a
device gap down to one of them; and the program names the benchmark's
trace readers match."""
import json
import pathlib
import time

import jax
import pytest

from repro import obs
from repro.configs import ARCHS
from repro.serving import (EngineConfig, InferenceEngine, Request,
                           SamplingParams)

ENGINE_PHASES = (obs.ENGINE_SCHEDULE, obs.PREFILL_DISPATCH,
                 obs.PREFILL_SYNC, obs.DECODE_PAGES, obs.DECODE_DISPATCH,
                 obs.DECODE_SYNC, obs.ENGINE_EMIT)


@pytest.fixture(scope="module")
def cfg():
    return ARCHS["olmo-1b"].reduced()


@pytest.fixture(scope="module")
def params(cfg, param_store):
    return param_store(cfg)


@pytest.fixture
def ring(monkeypatch):
    fresh = obs.Ring()
    monkeypatch.setattr(obs, "RING", fresh)
    return fresh


def _engine(cfg, params, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", 48)
    return InferenceEngine(cfg, params, EngineConfig(**kw), node="n7")


def _req(n_prompt, max_tokens, **kw):
    return Request(model="m", prompt=list(range(1, n_prompt + 1)),
                   sampling=SamplingParams(max_tokens=max_tokens, **kw))


def _all(name):
    return obs.records(name, -float("inf"), float("inf"))


def _counts(step):
    return {n: sum(1 for r in _all(n) if r.attrs["step"] == step)
            for n in ENGINE_PHASES}


def test_a_step_writes_each_phase_once_back_to_back(cfg, params, ring):
    eng = _engine(cfg, params)
    assert eng.submit(_req(5, 12))
    eng.step()                           # admit + first decode block
    eng.step()                           # decode only
    assert _counts(0) == {obs.ENGINE_SCHEDULE: 1, obs.PREFILL_DISPATCH: 1,
                          obs.PREFILL_SYNC: 1, obs.ENGINE_EMIT: 2,
                          obs.DECODE_PAGES: 1, obs.DECODE_DISPATCH: 1,
                          obs.DECODE_SYNC: 1}
    assert _counts(1) == {obs.ENGINE_SCHEDULE: 1, obs.PREFILL_DISPATCH: 0,
                          obs.PREFILL_SYNC: 0, obs.ENGINE_EMIT: 1,
                          obs.DECODE_PAGES: 1, obs.DECODE_DISPATCH: 1,
                          obs.DECODE_SYNC: 1}
    steps = _all(obs.ENGINE_STEP)
    assert [s.attrs for s in steps] == [{"node": "n7", "step": 0},
                                        {"node": "n7", "step": 1}]
    # phases never nest: each starts where the last ended, inside its step
    phases = sorted((r for n in ENGINE_PHASES for r in _all(n)),
                    key=lambda r: r.t_start)
    for a, b in zip(phases, phases[1:]):
        assert a.t_end <= b.t_start
    for st in steps:
        mine = [p for p in phases if p.attrs["step"] == st.attrs["step"]]
        assert all(st.t_start <= p.t_start and p.t_end <= st.t_end
                   for p in mine)
        assert {p.attrs["node"] for p in mine} == {"n7"}
    host = obs.host_seconds(steps, _all(obs.PREFILL_SYNC)
                            + _all(obs.DECODE_SYNC))
    assert all(0 < h < s.seconds for h, s in zip(host, steps))


def test_queue_wait_once_per_request_not_on_preempted_resume(cfg, params,
                                                             ring):
    """Six slots against a ~3-sequence page budget: the engine preempts
    and resumes, and each request still records one queue wait, from
    its creation to its first admission."""
    eng = _engine(cfg, params, n_slots=6, page_size=8, kv_pages=18)
    reqs = [_req(2 + i, 20) for i in range(6)]
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_done()
    assert eng.preemptions >= 1
    waits = _all(obs.QUEUE_WAIT)
    assert sorted(w.attrs["request"] for w in waits) == \
        sorted(r.request_id for r in reqs)
    for r in reqs:
        (w,) = [w for w in waits if w.attrs["request"] == r.request_id]
        assert (w.t_start, w.t_end) == (r.created_at, r.admitted_at)
        assert w.attrs["node"] == "n7"
        assert r.created_at <= r.admitted_at <= r.first_token_at


def test_full_ring_counts_dropped_and_refuses_a_lost_window(ring):
    small = obs.Ring(maxlen=4)
    for i in range(6):
        small.add(obs.Record("x", i, i + 0.5, "t", {}))
    assert small.dropped == 2
    assert small.records("x", 1.0, 10.0) is None      # 1.5 was pushed out
    assert [r.t_start for r in small.records("x", 1.6, 10.0)] == [2, 3, 4, 5]
    # the module's ring keeps its own count
    obs.record("y", 0.0, 1.0)
    assert obs.RING.dropped == 0 and len(obs.records("y", 0.0, 1.0)) == 1


def test_span_yields_attrs_the_ring_keeps(ring):
    with obs.span("z", request=3) as attrs:
        attrs.update(node="n1", ok=True)
    (rec,) = _all("z")
    assert rec.attrs == {"request": 3, "node": "n1", "ok": True}
    ph = obs.Phases(node="n2")
    ph.to("a")
    ph.to("a")                           # same name: the span stays open
    ph.to("b")
    ph.end()
    assert len(_all("a")) == 1 and len(_all("b")) == 1


def test_profiler_trace_holds_the_spans_and_labels_a_gap(cfg, params, ring,
                                                        tmp_path,
                                                        monkeypatch):
    """A CPU trace around hand-pumped steps holds every engine phase on
    the host plane.  A device timeline idle across the longest
    `engine.emit` (a slow stream callback) is put down to that span."""
    monkeypatch.syspath_prepend(str(
        pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
        / "onchip"))
    import tracing
    from jax.profiler import ProfileData

    eng = _engine(cfg, params)
    r = _req(5, 8)
    r.on_token = lambda _r, _t: time.sleep(0.005)
    assert eng.submit(r)
    eng.run_until_done()                 # compiles outside the trace
    r2 = _req(6, 8)
    r2.on_token = lambda _r, _t: time.sleep(0.005)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert eng.submit(r2)
        eng.run_until_done()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    host = [(int(ev.start_ns), int(ev.end_ns), ev.name)
            for plane in data.planes if plane.name == tracing.HOST
            for ln in plane.lines for ev in ln.events]
    assert set(ENGINE_PHASES) <= {n for _, _, n in host}
    emit = max((h for h in host if h[2] == obs.ENGINE_EMIT),
               key=lambda h: h[1] - h[0])
    assert emit[1] - emit[0] > 5e6       # several 5 ms callbacks
    base = min(s for s, _, _ in host)
    a, b = emit[0] - base, emit[1] - base
    ev = "events {{ metadata_id: {} offset_ps: {} duration_ps: {} }}"
    host_events = " ".join(ev.format(i + 1, (s - base) * 1000,
                                     (e - s) * 1000)
                           for i, (s, e, _) in enumerate(host))
    host_meta = " ".join(
        f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
        f'name: {json.dumps(n)} }} }}' for i, (_, _, n) in enumerate(host))
    text = f"""
    planes {{ id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
        {ev.format(1, (a - 1000) * 1000, 2000 * 1000)}
        {ev.format(1, (b - 1000) * 1000, 2000 * 1000)} }}
      event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }} }}
    planes {{ id: 2 name: "/host:CPU"
      lines {{ id: 1 name: "pump" timestamp_ns: 0 {host_events} }}
      {host_meta} }}"""
    summary = tracing.reduce(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)), 1.0, [0])
    assert summary.idle_gaps[0][0] == obs.ENGINE_EMIT


def test_compiled_programs_keep_the_names_the_trace_readers_match(cfg,
                                                                  params):
    """The benchmark finds device time by the jit names `prefill_admit`
    and `fused_decode` (its `readers.PREFILL`, `readers.DECODE`), which
    name the lowered modules (`jit_fused_decode` on the device)."""
    names = []

    def on_compile(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            names.append(kw.get("fun_name", ""))

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        eng = _engine(cfg, params)
        assert eng.submit(_req(5, 6))
        eng.run_until_done()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert any("prefill_admit" in n for n in names), names
    assert any("fused_decode" in n for n in names), names
