"""Device-resident decode hot path: fused K-step dispatch parity, bucketed
prefill compile counts, on-device done masks, cancel state hygiene, and the
dispatch/sync reduction the benchmark reports."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS
from repro.serving import (EngineConfig, InferenceEngine, Request,
                           RequestState, SamplingParams, Scheduler,
                           SchedulerConfig, sample_batched)
from repro.serving.request import CODE_INVALID_REQUEST
from repro.serving.sampler import sample


@pytest.fixture(scope="module")
def cfg():
    return ARCHS["olmo-1b"].reduced()


@pytest.fixture(scope="module")
def params(cfg, param_store):
    return param_store(cfg)


def _engine(cfg, params, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("max_len", 48)
    return InferenceEngine(cfg, params, EngineConfig(**kw))


def _run(eng, reqs):
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_done()
    return [tuple(r.output) for r in reqs]


# ------------------- fused multi-token parity ---------------------- #
def test_fused_greedy_parity_k1_vs_k8(cfg, params):
    """Greedy decode must be bit-identical whether the engine dispatches
    1 or 8 steps per fused block (the K quantum is a scheduling choice,
    never a numerics choice)."""
    def work():
        return [Request(model="m", prompt=list(range(1, 2 + i)),
                        sampling=SamplingParams(max_tokens=6 + i))
                for i in range(5)]
    outs = {k: _run(_engine(cfg, params, decode_block=k), work())
            for k in (1, 4, 8)}
    assert outs[1] == outs[4] == outs[8]
    assert all(len(o) == 6 + i for i, o in enumerate(outs[8]))


def test_done_mask_stops_slots_mid_block(cfg, params):
    """Slots hitting max_tokens mid-scan stop advancing on device: exact
    budgets even when they are not multiples of the K quantum."""
    eng = _engine(cfg, params, decode_block=8)
    reqs = [Request(model="m", prompt=[2, 3],
                    sampling=SamplingParams(max_tokens=m))
            for m in (1, 3, 11)]
    _run(eng, reqs)
    assert [len(r.output) for r in reqs] == [1, 3, 11]
    assert all(r.state == RequestState.FINISHED for r in reqs)


def _first_new_token(out):
    """Index of the first decoded token (past the prefill-sampled one)
    that has not appeared earlier in the stream, or None."""
    for i in range(1, len(out)):
        if out[i] not in out[:i]:
            return i
    return None


def test_eos_stops_mid_block(cfg, params):
    eng = _engine(cfg, params, decode_block=8)
    # a greedy stream may repeat one token forever; probe prompts until
    # one yields a token unseen before it, so EOS can't fire earlier
    for prompt in ([5, 6], [1, 2, 3], [7], [11, 13, 17, 19], [40, 41]):
        probe = Request(model="m", prompt=prompt,
                        sampling=SamplingParams(max_tokens=10))
        _run(eng, [probe])
        idx = _first_new_token(probe.output)
        if idx is not None:
            break
    assert idx is not None, "no probe prompt yields a fresh token"
    r = Request(model="m", prompt=prompt,
                sampling=SamplingParams(max_tokens=10,
                                        eos_id=probe.output[idx]))
    _run(eng, [r])
    assert r.output == probe.output[:idx + 1]    # stopped exactly at EOS
    assert r.state == RequestState.FINISHED


# ------------------- bucketed prefill ------------------------------ #
def test_bucketed_prefill_compiles_once_per_bucket(cfg, params):
    """Every prompt length inside one power-of-two bucket shares a single
    trace; a new bucket costs exactly one more compile."""
    eng = _engine(cfg, params)
    for ln in (3, 4, 5, 6, 7, 8):
        _run(eng, [Request(model="m", prompt=list(range(ln)),
                           sampling=SamplingParams(max_tokens=2))])
    assert eng.prefill_traces == 1          # lengths 3..8 -> bucket 8
    _run(eng, [Request(model="m", prompt=list(range(9)),
                       sampling=SamplingParams(max_tokens=2))])
    assert eng.prefill_traces == 2          # length 9 -> bucket 16
    assert eng.decode_traces == 1           # decode compiled exactly once


def test_bucketed_prefill_matches_unpadded_outputs(cfg, params):
    """Padding to the bucket must not change any row's tokens: greedy
    outputs for different lengths equal the same prompts run alone (which
    also pad, but to a batch of one — cross-checks row independence)."""
    solo = [_run(_engine(cfg, params),
                 [Request(model="m", prompt=list(range(1, 2 + i)),
                          sampling=SamplingParams(max_tokens=5))])[0]
            for i in range(3)]
    batched = _run(_engine(cfg, params),
                   [Request(model="m", prompt=list(range(1, 2 + i)),
                            sampling=SamplingParams(max_tokens=5))
                    for i in range(3)])
    assert batched == solo


def test_scheduler_groups_same_bucket():
    sched = Scheduler(SchedulerConfig(max_prefill_per_step=3))
    lens = [3, 20, 5, 6, 18]               # buckets: 8, 32, 8, 8, 32
    reqs = [Request(model="m", prompt=list(range(n))) for n in lens]
    for r in reqs:
        sched.submit(r)

    def bucket_of(n):
        b = 8
        while b < n:
            b <<= 1
        return b
    group = sched.next_prefill_bucket(4, bucket_of)
    assert [len(r.prompt) for r in group] == [3, 5, 6]
    # skipped requests keep FCFS order for the next step
    group = sched.next_prefill_bucket(4, bucket_of)
    assert [len(r.prompt) for r in group] == [20, 18]
    assert sched.depth == 0


# ------------------- dispatch / sync discipline -------------------- #
def test_fused_block_cuts_dispatches_and_syncs(cfg, params):
    """The acceptance bar: K=8 issues >= 5x fewer device dispatches AND
    host syncs per generated token than K=1 on a decode-heavy workload.
    Deterministic counters — no timing flakiness."""
    stats = {}
    for k in (1, 8):
        eng = _engine(cfg, params, n_slots=4, decode_block=k)
        reqs = [Request(model="m", prompt=[1, 2, 3 + i],
                        sampling=SamplingParams(max_tokens=33))
                for i in range(6)]
        _run(eng, reqs)
        stats[k] = eng.perf_stats()
    assert stats[1]["tokens"] == stats[8]["tokens"]
    for metric in ("dispatches_per_token", "host_syncs_per_token"):
        assert stats[1][metric] / stats[8][metric] >= 5.0, (metric, stats)


# ------------------- cancel / release hygiene ---------------------- #
def test_cancel_clears_device_slot_state(cfg, params):
    """Cancelling an in-flight request zeroes its slot's persistent
    device arrays, so the freed slot can't be decoded or sampled with
    stale temperature/budget on the next fused dispatch."""
    eng = _engine(cfg, params, decode_block=4)
    a = Request(model="m", prompt=[1, 2],
                sampling=SamplingParams(max_tokens=1000, temperature=0.9,
                                        top_k=7))
    b = Request(model="m", prompt=[3, 4],
                sampling=SamplingParams(max_tokens=13))
    eng.submit(a), eng.submit(b)
    eng.step()
    slot_a = next(s for s, r in eng.slot_req.items() if r is a)
    assert eng.cancel(a.request_id)
    assert not bool(eng.active[slot_a])
    assert float(eng.temps[slot_a]) == 0.0
    assert int(eng.remaining[slot_a]) == 0
    eng.run_until_done()
    assert b.state == RequestState.FINISHED and len(b.output) == 13
    # the freed slot is reusable and produces a clean stream
    c = Request(model="m", prompt=[9], sampling=SamplingParams(max_tokens=4))
    _run(eng, [c])
    assert len(c.output) == 4


def test_decode_stops_at_cache_capacity(cfg, params):
    """A budget larger than the remaining cache stops cleanly at the
    cache edge (on-device capacity mask) instead of clamp-writing past
    max_len and emitting garbage forever."""
    eng = _engine(cfg, params, n_slots=2, max_len=16, decode_block=8)
    r = Request(model="m", prompt=list(range(1, 13)),   # 12 prompt tokens
                sampling=SamplingParams(max_tokens=100))
    _run(eng, [r])
    # first token + one decode per remaining cache slot (pos 12..15)
    assert len(r.output) == 16 - 12 + 1
    assert r.state == RequestState.FINISHED


def test_vision_prefix_prompt_near_max_len(param_store):
    """Prefix tokens count against the cache: a prompt that only fits
    without its vision prefix is rejected as invalid, and one that fits
    decodes fine even when bucket rounding would otherwise overflow."""
    vcfg = ARCHS["internvl2-76b"].reduced()
    eng = InferenceEngine(vcfg, param_store(vcfg),
                          EngineConfig(n_slots=2, max_len=24,
                                       decode_block=4))
    prefix = eng._prefix_tokens
    assert prefix > 0
    ok = Request(model="v", prompt=list(range(24 - prefix)),
                 sampling=SamplingParams(max_tokens=2))
    _run(eng, [ok])
    assert ok.state == RequestState.FINISHED and len(ok.output) >= 1
    bad = Request(model="v", prompt=list(range(24 - prefix + 1)),
                  sampling=SamplingParams(max_tokens=2))
    assert not eng.submit(bad)
    assert bad.error_code == CODE_INVALID_REQUEST


# ------------------- long-prompt classification -------------------- #
def test_long_prompt_is_invalid_at_submit(cfg, params):
    """A prompt no slot can ever hold is a 400, not a 429 — rejected at
    submit time, never enqueued."""
    eng = _engine(cfg, params)
    bad = Request(model="m", prompt=list(range(eng.ecfg.max_len + 1)),
                  sampling=SamplingParams(max_tokens=2))
    assert not eng.submit(bad)
    assert bad.state == RequestState.FAILED
    assert bad.error_code == CODE_INVALID_REQUEST
    assert eng.scheduler.depth == 0        # never reached the queue


def test_gateway_rejects_oversized_prompt_as_invalid(param_store):
    from repro.api import ErrorCode, Gateway
    from repro.cluster import BackendNode, Fleet
    from repro.core import (ModelCatalog, ReplicaInfo, ReplicaKey,
                            SDAIController)
    cfg = ARCHS["olmo-1b"].reduced()
    fleet = Fleet([BackendNode("n0", "v5e-1", param_store=param_store)])
    catalog = ModelCatalog()
    catalog.register(cfg)
    ctrl = SDAIController(fleet, catalog)
    ctrl.discover()
    inst = fleet.nodes["n0"].deploy(cfg, n_slots=2, max_len=32)
    ctrl.replicas.add(ReplicaInfo(ReplicaKey("n0", inst.instance_id),
                                  cfg.name, "", 2, 32, inst.bytes))
    gw = Gateway(ctrl)
    resp = gw.generate(cfg.name, list(range(33)),
                       SamplingParams(max_tokens=2))
    assert resp.error.code is ErrorCode.INVALID_REQUEST
    assert not resp.error.retryable
    assert inst.engine.scheduler.depth == 0    # rejected before routing
    assert gw.generate(cfg.name, [1, 2], SamplingParams(max_tokens=2)).ok


def test_gateway_counts_prefix_tokens_against_context(param_store):
    """Vision/meta prefix tokens occupy cache slots: a prompt that only
    fits without the prefix must be a 400 at the gateway (not a
    retryable NO_BACKEND after every replica refuses it)."""
    from repro.api import ErrorCode, Gateway
    from repro.cluster import BackendNode, Fleet
    from repro.core import (ModelCatalog, ReplicaInfo, ReplicaKey,
                            SDAIController)
    cfg = ARCHS["internvl2-76b"].reduced()
    fleet = Fleet([BackendNode("n0", "v5e-1", param_store=param_store)])
    catalog = ModelCatalog()
    catalog.register(cfg)
    ctrl = SDAIController(fleet, catalog)
    ctrl.discover()
    inst = fleet.nodes["n0"].deploy(cfg, n_slots=2, max_len=24)
    ctrl.replicas.add(ReplicaInfo(ReplicaKey("n0", inst.instance_id),
                                  cfg.name, "", 2, 24, inst.bytes))
    gw = Gateway(ctrl)
    prefix = inst.engine._prefix_tokens
    assert prefix > 0
    resp = gw.generate(cfg.name, list(range(24 - prefix + 1)),
                       SamplingParams(max_tokens=2))
    assert resp.error.code is ErrorCode.INVALID_REQUEST
    assert not resp.error.retryable
    assert gw.generate(cfg.name, list(range(24 - prefix)),
                       SamplingParams(max_tokens=2)).ok


# ------------------- batched sampler parity ------------------------ #
def test_sample_batched_matches_single_params():
    key = jax.random.PRNGKey(3)
    logits = jax.random.normal(key, (4, 64)) * 3.0
    for p in (SamplingParams(temperature=0.0),
              SamplingParams(temperature=0.8),
              SamplingParams(temperature=0.8, top_k=5),
              SamplingParams(temperature=0.8, top_k=5, top_p=0.7)):
        want = sample(logits, key, p)
        got = sample_batched(
            logits, key,
            jnp.full((4,), p.temperature, jnp.float32),
            jnp.full((4,), p.top_k, jnp.int32),
            jnp.full((4,), p.top_p, jnp.float32))
        assert want.tolist() == got.tolist(), p


def test_emit_many_preserves_streaming_contract():
    seen = []
    r = Request(model="m", prompt=[1],
                on_token=lambda req, t: seen.append(t))
    r.emit_many([7, 8, 9])
    assert seen == [7, 8, 9] and r.output == [7, 8, 9]
    assert r.first_token_at is not None
