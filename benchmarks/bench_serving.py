"""Live serving throughput/latency on CPU (tiny model) through Gateway API
v1, plus four studies:

* device-resident hot path — fused K-step decode vs single-step dispatch
  (dispatches/token, host syncs/token, tok/s, p50/p95 step time),
* paged KV cache — paged pool with oversubscribed slots vs contiguous
  per-slot strips at the *same KV VRAM budget*: concurrent-slot
  occupancy, kv-page utilization, preemptions, tok/s,
* paged attention — the decode hot path reading the page pool directly
  through the device page table vs gather/scatter logical views: KV
  bytes moved per token, token-identical outputs, equal dispatches,
* speculative decoding — on-device n-gram propose + single-dispatch
  greedy verify vs per-token decode: accepted tokens per verify
  dispatch, dispatch/sync reduction, token-identical outputs,
* continuous runtime — >= 4 concurrent tenants across >= 2 nodes driven
  entirely by background pump threads (zero caller-side pumps), with
  per-tenant token-bucket rejections and load-driven controller scale-up,
* prefix cache — an 80%-shared-prefix workload (one system prompt, many
  private tails) with the hierarchical KV cache on vs off: cache-hit
  rate, prefill dispatch tokens, TTFT, token-identical outputs,
* http wire — requests/s and p95 TTFT through the OpenAI-compatible
  socket service vs the in-process Gateway (informational).

Writes ``BENCH_serving.json``; CI gates ``dispatches_per_token`` /
``host_syncs_per_token`` (lower is better), the paged study's
``kv_page_utilization`` and the prefix study's ``prefix_hit_rate``
(higher is better) against ``benchmarks/baseline_serving.json`` (soft
20% regression budget — wall-clock numbers stay informational).
"""
from __future__ import annotations

import json
import threading
import time
from pathlib import Path

import jax

from repro.api import (Gateway, GenerationRequest, RuntimeConfig,
                       TenantQuota)
from repro.cluster import BackendNode, Fleet
from repro.configs import ARCHS
from repro.core import (ModelCatalog, ModelDemand, ReplicaInfo, ReplicaKey,
                        SDAIController)
from repro.models import build
from repro.serving import (EngineConfig, InferenceEngine, Request,
                           SamplingParams)

_cache = {}


def _store(cfg):
    if "p" not in _cache:
        _cache["p"] = build(cfg).init(jax.random.PRNGKey(0))
    return _cache["p"]


def _stack(quantize=""):
    """One-node fleet serving one (optionally quantized) live engine,
    fronted by the unified gateway."""
    cfg = ARCHS["olmo-1b"].reduced()
    fleet = Fleet([BackendNode("n0", "v5e-1", param_store=_store)])
    catalog = ModelCatalog()
    catalog.register(cfg)
    ctrl = SDAIController(fleet, catalog)
    ctrl.discover()
    node = fleet.nodes["n0"]
    inst = node.deploy(cfg, quantize=quantize, n_slots=4, max_len=64)
    ctrl.replicas.add(ReplicaInfo(ReplicaKey("n0", inst.instance_id),
                                  cfg.name, quantize, 4, 64, inst.bytes))
    return cfg, inst, Gateway(ctrl)


def _pct(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    i = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[i]


def _fused_study(n_requests: int = 8, max_tokens: int = 32,
                 ks=(1, 8)) -> dict:
    """Engine-level dispatch-discipline comparison: same workload, same
    params, K=1 (the per-token legacy loop) vs fused K-step blocks.
    Counters are deterministic; timings are informational."""
    cfg = ARCHS["olmo-1b"].reduced()
    params = _store(cfg)
    out = {}
    for k in ks:
        eng = InferenceEngine(cfg, params,
                              EngineConfig(n_slots=4, max_len=64,
                                           decode_block=k))
        # compile outside the clock: 5 warmups cover both admission batch
        # shapes the run will see (a full group of 4 and a tail of 1)
        for i in range(5):
            eng.submit(Request(model=cfg.name, prompt=[1, 2, 3],
                               sampling=SamplingParams(max_tokens=2)))
        eng.run_until_done()
        base = eng.perf_stats()
        reqs = [Request(model=cfg.name, prompt=[1, 2, 3 + (i % 5)],
                        sampling=SamplingParams(max_tokens=max_tokens))
                for i in range(n_requests)]
        for r in reqs:
            eng.submit(r)
        step_s = []
        t0 = time.perf_counter()
        while eng.slot_req or eng.scheduler.depth:
            s0 = time.perf_counter()
            eng.step()
            step_s.append(time.perf_counter() - s0)
        wall = time.perf_counter() - t0
        stats = eng.perf_stats()
        toks = stats["tokens"] - base["tokens"]
        disp = stats["dispatches"] - base["dispatches"]
        syncs = stats["host_syncs"] - base["host_syncs"]
        step_s.sort()
        out[f"k{k}"] = {
            "decode_block": k,
            "tokens": toks,
            "dispatches": disp,
            "host_syncs": syncs,
            "dispatches_per_token": disp / max(toks, 1),
            "host_syncs_per_token": syncs / max(toks, 1),
            "tok_per_s": toks / wall if wall > 0 else 0.0,
            "p50_step_ms": _pct(step_s, 0.50) * 1e3,
            "p95_step_ms": _pct(step_s, 0.95) * 1e3,
            "prefill_traces": stats["prefill_traces"],
        }
    lo, hi = f"k{ks[0]}", f"k{ks[-1]}"
    out["reduction"] = {
        "dispatches_per_token":
            out[lo]["dispatches_per_token"] /
            max(out[hi]["dispatches_per_token"], 1e-12),
        "host_syncs_per_token":
            out[lo]["host_syncs_per_token"] /
            max(out[hi]["host_syncs_per_token"], 1e-12),
    }
    return out


def _paged_study(n_requests: int = 12, max_tokens: int = 24) -> dict:
    """The VRAM story, measured: a paged engine whose 8 slots share the
    *same 32-page KV budget* as a 4-slot contiguous engine admits more
    concurrent requests (higher peak slot occupancy, higher kv-page
    utilization) and drains the same workload in fewer engine steps.
    Counters are deterministic; timings are informational."""
    cfg = ARCHS["olmo-1b"].reduced()
    params = _store(cfg)
    variants = {
        # 4 slots x 64 tokens / 8-token pages = 32 pages, fully reserved
        "contiguous": EngineConfig(n_slots=4, max_len=64, page_size=8,
                                   paged=False),
        # same 32-page budget, slots oversubscribed 2x; admission is
        # page-aware and the engine preempts on exhaustion
        "paged": EngineConfig(n_slots=8, max_len=64, page_size=8,
                              kv_pages=32),
    }
    out = {}
    for name, ecfg in variants.items():
        eng = InferenceEngine(cfg, params, ecfg)
        for _ in range(4):            # compile outside the clock
            eng.submit(Request(model=cfg.name, prompt=[1, 2, 3],
                               sampling=SamplingParams(max_tokens=2)))
        eng.run_until_done()
        base = eng.perf_stats()
        reqs = [Request(model=cfg.name, prompt=[1, 2, 3 + (i % 5)],
                        sampling=SamplingParams(max_tokens=max_tokens))
                for i in range(n_requests)]
        for r in reqs:
            eng.submit(r)
        peak_active, peak_occ, util_sum, steps = 0, 0.0, 0.0, 0
        t0 = time.perf_counter()
        while eng.slot_req or eng.scheduler.depth:
            eng.step()
            steps += 1
            peak_active = max(peak_active, eng.pool.n_active)
            peak_occ = max(peak_occ, eng.pool.page_occupancy())
            util_sum += eng.pool.utilization()
        wall = time.perf_counter() - t0
        stats = eng.perf_stats()
        toks = stats["tokens"] - base["tokens"]
        assert all(len(r.output) == max_tokens for r in reqs), name
        out[name] = {
            "n_slots": ecfg.n_slots,
            "kv_pages": eng.pool.n_pages,
            "peak_active_slots": peak_active,
            "kv_page_utilization": util_sum / max(steps, 1),
            "peak_page_occupancy": peak_occ,
            "steps_to_drain": steps,
            "preemptions": stats["preemptions"],
            "tokens": toks,
            "tok_per_s": toks / wall if wall > 0 else 0.0,
            "dispatches_per_token":
                (stats["dispatches"] - base["dispatches"])
                / max(toks, 1),
        }
    # acceptance: same VRAM, more admitted work
    assert out["paged"]["peak_active_slots"] > \
        out["contiguous"]["peak_active_slots"], out
    out["gain"] = {
        "peak_active_slots":
            out["paged"]["peak_active_slots"]
            / max(out["contiguous"]["peak_active_slots"], 1),
        "kv_page_utilization":
            out["paged"]["kv_page_utilization"]
            / max(out["contiguous"]["kv_page_utilization"], 1e-9),
    }
    return out


def _paged_attn_study(n_requests: int = 8, max_tokens: int = 24) -> dict:
    """Paged-attention study: same paged engine, same workload, with the
    decode hot path either materializing every slot's logical KV view
    (gather + scatter per dispatch) or reading the page pool directly
    through the device page table.  Dispatch/sync discipline must be
    identical and greedy outputs token-identical; the win is logical KV
    bytes moved per token.  Counters are deterministic."""
    cfg = ARCHS["olmo-1b"].reduced()
    params = _store(cfg)
    out, outputs = {}, {}
    for name, on in (("gather", False), ("paged_attn", True)):
        eng = InferenceEngine(cfg, params,
                              EngineConfig(n_slots=4, max_len=64,
                                           decode_block=4, page_size=8,
                                           paged_attention=on))
        for _ in range(4):            # compile outside the clock
            eng.submit(Request(model=cfg.name, prompt=[1, 2, 3],
                               sampling=SamplingParams(max_tokens=2)))
        eng.run_until_done()
        base = eng.perf_stats()
        reqs = [Request(model=cfg.name, prompt=[1, 2, 3 + (i % 5)],
                        sampling=SamplingParams(max_tokens=max_tokens))
                for i in range(n_requests)]
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        eng.run_until_done()
        wall = time.perf_counter() - t0
        stats = eng.perf_stats()
        toks = stats["tokens"] - base["tokens"]
        bytes_moved = (stats["logical_bytes_moved"]
                       - base["logical_bytes_moved"])
        outputs[name] = [tuple(r.output) for r in reqs]
        out[name] = {
            "paged_attention": on,
            "tokens": toks,
            "dispatches": stats["dispatches"] - base["dispatches"],
            "host_syncs": stats["host_syncs"] - base["host_syncs"],
            "logical_bytes_moved": bytes_moved,
            "logical_bytes_moved_per_token": bytes_moved / max(toks, 1),
            "tok_per_s": toks / wall if wall > 0 else 0.0,
        }
    # the kernel is a memory optimization, never a numerics or
    # scheduling change
    assert outputs["paged_attn"] == outputs["gather"], \
        "paged attention changed greedy outputs"
    assert out["paged_attn"]["dispatches"] == out["gather"]["dispatches"]
    assert out["paged_attn"]["host_syncs"] == out["gather"]["host_syncs"]
    ratio = (out["gather"]["logical_bytes_moved_per_token"]
             / max(out["paged_attn"]["logical_bytes_moved_per_token"], 1))
    assert ratio >= 2.0, out
    out["gain"] = {"logical_bytes_moved_per_token": ratio}
    return out


def _spec_study(n_requests: int = 6, max_tokens: int = 24) -> dict:
    """Speculative-decoding study: greedy decode with the on-device
    n-gram proposer + single-dispatch verify vs plain per-token decode
    (decode_block=1) on a repetition-heavy workload.  Outputs must be
    token-identical (greedy verify); the win is accepted tokens per
    verify dispatch > 1, i.e. fewer dispatches and host syncs per
    token.  Counters are deterministic."""
    cfg = ARCHS["olmo-1b"].reduced()
    params = _store(cfg)
    out, outputs = {}, {}
    for name, on in (("spec_off", False), ("spec_on", True)):
        eng = InferenceEngine(cfg, params,
                              EngineConfig(n_slots=4, max_len=64,
                                           decode_block=1, page_size=8,
                                           paged_attention=True,
                                           speculative=on))
        for _ in range(4):            # compile outside the clock
            eng.submit(Request(model=cfg.name, prompt=[1, 2, 3],
                               sampling=SamplingParams(max_tokens=2)))
        eng.run_until_done()
        base = eng.perf_stats()
        reqs = [Request(model=cfg.name, prompt=[1, 2, 3 + (i % 5)],
                        sampling=SamplingParams(max_tokens=max_tokens))
                for i in range(n_requests)]
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        eng.run_until_done()
        wall = time.perf_counter() - t0
        stats = eng.perf_stats()
        toks = stats["tokens"] - base["tokens"]
        outputs[name] = [tuple(r.output) for r in reqs]
        out[name] = {
            "speculative": on,
            "tokens": toks,
            "dispatches_per_token":
                (stats["dispatches"] - base["dispatches"])
                / max(toks, 1),
            "host_syncs_per_token":
                (stats["host_syncs"] - base["host_syncs"])
                / max(toks, 1),
            "spec_dispatches":
                stats["spec_dispatches"] - base["spec_dispatches"],
            "spec_emitted": stats["spec_emitted"] - base["spec_emitted"],
            "tok_per_s": toks / wall if wall > 0 else 0.0,
        }
        if on:
            d = out[name]["spec_dispatches"]
            out[name]["spec_accepted_per_dispatch"] = \
                out[name]["spec_emitted"] / max(d, 1)
    # greedy verify is provably lossless
    assert outputs["spec_on"] == outputs["spec_off"], \
        "speculative decoding changed greedy outputs"
    assert out["spec_on"]["spec_accepted_per_dispatch"] > 1.0, out
    out["gain"] = {
        "dispatches_per_token":
            out["spec_off"]["dispatches_per_token"]
            / max(out["spec_on"]["dispatches_per_token"], 1e-12),
    }
    return out


def _prefix_study(n_requests: int = 10, max_tokens: int = 12) -> dict:
    """Prefix-cache study: every request carries the same 32-token
    system prefix plus a private 8-token tail (80% shared).  With the
    hierarchical KV cache on, only the first request prefills the full
    prompt — the rest map the cached prefix pages and prefill their
    suffix bucket only, so prefill dispatch tokens and TTFT drop while
    greedy outputs stay token-identical.  Counters are deterministic;
    timings are informational."""
    cfg = ARCHS["olmo-1b"].reduced()
    params = _store(cfg)
    shared = list(range(1, 33))                 # 32 tokens = 4 pages
    prompts = [shared + [40 + i, 50 + i, 60 + i, 70 + i,
                         40 + i, 50 + i, 60 + i, 71 + i]
               for i in range(n_requests)]      # 40 tokens, 80% shared
    out, outputs = {}, {}
    for name, on in (("cache_off", False), ("cache_on", True)):
        eng = InferenceEngine(cfg, params,
                              EngineConfig(n_slots=4, max_len=64,
                                           decode_block=4, page_size=8,
                                           prefix_cache=on))
        # compile outside the clock: full-prompt prefill, and (cache on)
        # the suffix-admission trace; flush so the bench window starts
        # cache-cold and the hit rate reflects the workload, not warmup
        for p in ([99] * 40, [99] * 32 + [98] * 8):
            eng.submit(Request(model=cfg.name, prompt=list(p),
                               sampling=SamplingParams(max_tokens=2)))
            eng.run_until_done()
        if on:
            eng.flush_prefix_cache()
            cache_base = eng.prefix_cache.stats()
        base = eng.perf_stats()
        ttfts, outs = [], []
        t0 = time.perf_counter()
        for p in prompts:
            r = Request(model=cfg.name, prompt=list(p),
                        sampling=SamplingParams(max_tokens=max_tokens))
            eng.submit(r)
            eng.run_until_done()
            ttfts.append(r.ttft)
            outs.append(tuple(r.output))
        wall = time.perf_counter() - t0
        stats = eng.perf_stats()
        outputs[name] = outs
        ttfts.sort()
        toks = stats["tokens"] - base["tokens"]
        out[name] = {
            "requests": n_requests,
            "prefill_dispatch_tokens":
                stats["prefill_dispatch_tokens"]
                - base["prefill_dispatch_tokens"],
            "suffix_prefills":
                stats["suffix_prefills"] - base["suffix_prefills"],
            "mean_ttft_ms": (sum(ttfts) / len(ttfts) * 1e3
                             if ttfts else 0.0),
            "p95_ttft_ms": _pct(ttfts, 0.95) * 1e3,
            "tok_per_s": toks / wall if wall > 0 else 0.0,
        }
        if on:
            cs = eng.prefix_cache.stats()
            lookups = cs["lookups"] - cache_base["lookups"]
            hits = cs["hits"] - cache_base["hits"]
            out[name]["prefix_hit_rate"] = hits / max(lookups, 1)
    # caching is a memory optimization, never a numerics change
    assert outputs["cache_on"] == outputs["cache_off"], \
        "prefix cache changed greedy outputs"
    on, off = out["cache_on"], out["cache_off"]
    assert on["prefix_hit_rate"] >= 0.8, out
    assert on["prefill_dispatch_tokens"] < off["prefill_dispatch_tokens"]
    out["gain"] = {
        "prefill_dispatch_tokens":
            off["prefill_dispatch_tokens"]
            / max(on["prefill_dispatch_tokens"], 1),
        "mean_ttft": off["mean_ttft_ms"] / max(on["mean_ttft_ms"], 1e-9),
    }
    return out


def _runtime_study(n_tenants: int = 4, n_nodes: int = 2,
                   reqs_per_tenant: int = 10, max_tokens: int = 12) -> dict:
    """Multi-tenant continuous serving: background pumps drive >= 2 nodes
    while >= 4 tenants submit concurrently — zero caller-side `_pump()`
    calls — with one rate-capped tenant (structured RATE_LIMITED) and a
    deliberately under-replicated model that the controller scales up
    under sustained queue pressure."""
    cfg = ARCHS["olmo-1b"].reduced()
    params = _store(cfg)
    fleet = Fleet([BackendNode(f"n{i}", "v5e-1",
                               param_store=lambda c: params)
                   for i in range(n_nodes)])
    catalog = ModelCatalog()
    catalog.register(cfg)
    ctrl = SDAIController(fleet, catalog)
    ctrl.cfg.fill_vram = False           # leave free VRAM for scale-up
    ctrl.discover()
    # anti-affinity spreads the two seed replicas across nodes; the
    # flood below still queues ~10 deep per replica, so the autoscaler
    # has headroom (and free VRAM) to grow toward the cap
    plan = ctrl.deploy([ModelDemand(cfg, min_replicas=2, max_replicas=4,
                                    n_slots=2, max_len=48)])
    assert not plan.unplaced
    gw = Gateway(ctrl)
    # tenant 0 gets a hard bucket: 2 requests then (effectively) no refill
    gw.admin.set_tenant_quota("tenant0", TenantQuota(requests_per_s=0.01,
                                                     burst_requests=2))
    rt = gw.start(RuntimeConfig(tick_interval_s=0.02))
    gw.generate(cfg.name, [1, 2, 3], SamplingParams(max_tokens=2),
                timeout_s=120)           # warm the first replica's traces
    results = []
    lock = threading.Lock()

    def worker(t):
        tenant = f"tenant{t}"
        # flood-submit, then collect: ~40 queued requests over 2x2 slots
        # keep backlog-per-replica far above AutoscaleConfig.queue_high
        # for many sustain windows (seconds of decode vs a 60 ms streak),
        # so the scale-up assertion below is timing-robust in CI
        handles = [gw.submit(cfg.name, [1, 2, (i % 5) + 1],
                             SamplingParams(max_tokens=max_tokens),
                             tenant=tenant)
                   for i in range(reqs_per_tenant)]
        for h in handles:
            r = h.result(timeout_s=120)
            with lock:
                results.append((tenant, r))

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_tenants)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0
    gw.stop(timeout_s=60)

    ok = [r for _, r in results if r.ok]
    limited = [r for _, r in results
               if not r.ok and r.error.code.value == "rate_limited"]
    # acceptance invariants, enforced here so CI's smoke run catches
    # regressions in the runtime contract itself
    assert gw.stats.caller_pumps == 0, "caller pumped despite runtime"
    assert limited, "capped tenant never saw RATE_LIMITED"
    assert len(ok) >= n_tenants, "fleet stopped serving"
    assert ctrl.scale_ups >= 1, "sustained pressure never scaled up"
    nodes_used = {r.node for r in ok}
    assert len(nodes_used) >= 2, "traffic never spanned multiple nodes"
    return {
        "tenants": n_tenants,
        "nodes": n_nodes,
        "nodes_serving": sorted(nodes_used),
        "requests": len(results),
        "completed": len(ok),
        "rate_limited": len(limited),
        "caller_pumps": gw.stats.caller_pumps,
        "scale_ups": ctrl.scale_ups,
        "replicas_final": len(ctrl.replicas.for_model(cfg.name)),
        "pump_wakeups": rt.stats.pump_wakeups,
        "ticks": rt.stats.ticks,
        "tok_per_s": sum(len(r.tokens) for r in ok) / wall
        if wall > 0 else 0.0,
    }


def _http_study(n_tenants: int = 2, reqs_per_tenant: int = 8,
                max_tokens: int = 8) -> dict:
    """The wire tax, informational: requests/s and TTFT through the
    OpenAI-compatible socket service vs the in-process Gateway for the
    same workload (2 tenants on keep-alive connections, SSE streaming
    so TTFT is measured at the first token frame)."""
    from repro.api.http import GatewayHTTPServer, HTTPClient, HTTPConfig
    cfg = ARCHS["olmo-1b"].reduced()
    params = _store(cfg)
    fleet = Fleet([BackendNode(f"n{i}", "v5e-1",
                               param_store=lambda c: params)
                   for i in range(2)])
    catalog = ModelCatalog()
    catalog.register(cfg)
    ctrl = SDAIController(fleet, catalog)
    ctrl.cfg.fill_vram = False
    ctrl.discover()
    plan = ctrl.deploy([ModelDemand(cfg, min_replicas=2, max_replicas=2,
                                    n_slots=2, max_len=64)])
    assert not plan.unplaced
    gw = Gateway(ctrl)
    srv = GatewayHTTPServer(gw, HTTPConfig(port=0)).start()
    n_total = n_tenants * reqs_per_tenant
    prompts = [(1, 2, (i % 5) + 1) for i in range(n_total)]
    sampling = SamplingParams(max_tokens=max_tokens)
    # warm every replica's traces for the admission shapes both legs
    # will see (prefill batches of 1, 2, and 4) so neither pays compiles
    warm = SamplingParams(max_tokens=2)
    for _ in range(2):
        gw.generate(cfg.name, [1, 2, 3], warm, timeout_s=120)
    for n in (2, 2, 4, 4):
        gw.generate_batch(
            [GenerationRequest(model=cfg.name, prompt=(1, 2, 3),
                               sampling=warm) for _ in range(n)],
            timeout_s=120)
    # in-process reference: identical workload through the Gateway
    t0 = time.perf_counter()
    resps = gw.generate_batch(
        [GenerationRequest(model=cfg.name, prompt=p, sampling=sampling)
         for p in prompts], timeout_s=120)
    dt_inproc = time.perf_counter() - t0
    assert all(r.ok for r in resps)
    inproc_ttfts = sorted(r.ttft for r in resps if r.ttft is not None)
    # over the wire: one keep-alive streaming client per tenant.
    # Workers only collect; every correctness assert runs on the main
    # thread after join (an assert inside a Thread would be swallowed
    # and the study would report fabricated metrics)
    outcomes = []                       # (ttft, n_toks) per request
    lock = threading.Lock()

    def worker(t):
        client = HTTPClient(srv.url(), tenant=f"bench{t}")
        for i in range(reqs_per_tenant):
            s0 = time.perf_counter()
            first = None
            n_toks = 0
            for ch in client.complete(cfg.name,
                                      list(prompts[t * reqs_per_tenant
                                                   + i]),
                                      max_tokens=max_tokens, stream=True,
                                      timeout_s=120):
                if ch["choices"][0].get("token") is not None:
                    n_toks += 1
                    if first is None:
                        first = time.perf_counter() - s0
            with lock:
                outcomes.append((first, n_toks))
        client.close()

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_tenants)]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0
    srv.stop(timeout_s=60)
    assert len(outcomes) == n_total, "a worker died mid-study"
    assert all(n == max_tokens for _, n in outcomes), \
        "a stream lost tokens over the wire"
    ttfts = sorted(f for f, _ in outcomes)
    return {
        "tenants": n_tenants,
        "requests": n_total,
        "http_req_per_s": n_total / wall if wall > 0 else 0.0,
        "http_p95_ttft_ms": _pct(ttfts, 0.95) * 1e3,
        "http_mean_ttft_ms": (sum(ttfts) / len(ttfts) * 1e3
                              if ttfts else 0.0),
        "inproc_req_per_s": (n_total / dt_inproc
                             if dt_inproc > 0 else 0.0),
        "inproc_p95_ttft_ms": _pct(inproc_ttfts, 0.95) * 1e3,
        "inproc_mean_ttft_ms": (sum(inproc_ttfts) / len(inproc_ttfts)
                                * 1e3 if inproc_ttfts else 0.0),
    }


def run(n_requests: int = 12, max_tokens: int = 24,
        json_path: str = "BENCH_serving.json"):
    rows = []
    report = {"gateway": {}}
    for quant in ("", "int8"):
        cfg, inst, gw = _stack(quant)
        # warm-up/compile
        gw.generate(cfg.name, [1, 2, 3],
                    SamplingParams(max_tokens=2))
        reqs = [GenerationRequest(model=cfg.name, prompt=(1, 2, 3, i),
                                  sampling=SamplingParams(
                                      max_tokens=max_tokens))
                for i in range(n_requests)]
        t0 = time.perf_counter()
        resps = gw.generate_batch(reqs)
        dt = time.perf_counter() - t0
        assert all(r.ok for r in resps), [r.error for r in resps if not r.ok]
        toks = sum(len(r.tokens) for r in resps)
        ttfts = [r.ttft for r in resps if r.ttft]
        tag = quant or "bf16"
        rows.append((f"serving_decode_{tag}", dt / toks * 1e6,
                     f"tok_per_s={toks/dt:.1f}"))
        rows.append((f"serving_ttft_{tag}",
                     sum(ttfts) / len(ttfts) * 1e6,
                     f"n={len(ttfts)}"))
        mem = inst.engine.memory_report()
        rows.append((f"serving_mem_{tag}", 0.0,
                     f"params={mem['param_bytes']};"
                     f"cache={mem['cache_bytes']}"))
        report["gateway"][tag] = {
            "tok_per_s": toks / dt,
            "mean_ttft_s": sum(ttfts) / len(ttfts) if ttfts else None,
            "engine": inst.engine.perf_stats(),
        }
        if not quant:
            # streaming path: per-event overhead vs blocking batch
            t0 = time.perf_counter()
            n_events = sum(
                1 for _ in gw.stream(cfg.name, [1, 2, 3],
                                     SamplingParams(
                                         max_tokens=max_tokens)))
            dt = time.perf_counter() - t0
            rows.append(("serving_stream_event", dt / n_events * 1e6,
                         f"events={n_events}"))
    ks = (1, 8)
    fused = _fused_study(ks=ks)
    report["fused"] = fused
    paged = _paged_study()
    report["paged"] = paged
    rows.append(("serving_paged_occupancy", 0.0,
                 f"peak_active_paged={paged['paged']['peak_active_slots']};"
                 f"peak_active_contig="
                 f"{paged['contiguous']['peak_active_slots']};"
                 f"kv_page_util={paged['paged']['kv_page_utilization']:.3f};"
                 f"preemptions={paged['paged']['preemptions']};"
                 f"tok_per_s={paged['paged']['tok_per_s']:.1f}"))
    pattn = _paged_attn_study()
    report["paged_attn"] = pattn
    rows.append(("serving_paged_attention", 0.0,
                 f"bytes_per_token_gather="
                 f"{pattn['gather']['logical_bytes_moved_per_token']:.0f};"
                 f"bytes_per_token_paged="
                 f"{pattn['paged_attn']['logical_bytes_moved_per_token']:.0f};"
                 f"reduction_x"
                 f"{pattn['gain']['logical_bytes_moved_per_token']:.1f}"))
    spec = _spec_study()
    report["spec"] = spec
    rows.append(("serving_spec_decode", 0.0,
                 f"accepted_per_dispatch="
                 f"{spec['spec_on']['spec_accepted_per_dispatch']:.2f};"
                 f"dispatch_reduction_x"
                 f"{spec['gain']['dispatches_per_token']:.1f}"))
    prefix = _prefix_study()
    report["prefix"] = prefix
    rows.append(("serving_prefix_cache", 0.0,
                 f"hit_rate={prefix['cache_on']['prefix_hit_rate']:.2f};"
                 f"prefill_tokens_on="
                 f"{prefix['cache_on']['prefill_dispatch_tokens']};"
                 f"prefill_tokens_off="
                 f"{prefix['cache_off']['prefill_dispatch_tokens']};"
                 f"mean_ttft_on_ms="
                 f"{prefix['cache_on']['mean_ttft_ms']:.2f};"
                 f"mean_ttft_off_ms="
                 f"{prefix['cache_off']['mean_ttft_ms']:.2f}"))
    runtime = _runtime_study()
    report["runtime"] = runtime
    http = _http_study()
    report["http"] = http
    rows.append(("serving_http_wire", 0.0,
                 f"req_per_s={http['http_req_per_s']:.1f};"
                 f"p95_ttft_ms={http['http_p95_ttft_ms']:.1f};"
                 f"inproc_req_per_s={http['inproc_req_per_s']:.1f};"
                 f"inproc_p95_ttft_ms={http['inproc_p95_ttft_ms']:.1f}"))
    rows.append(("serving_runtime_multitenant", 0.0,
                 f"tenants={runtime['tenants']};"
                 f"completed={runtime['completed']};"
                 f"rate_limited={runtime['rate_limited']};"
                 f"caller_pumps={runtime['caller_pumps']};"
                 f"scale_ups={runtime['scale_ups']};"
                 f"tok_per_s={runtime['tok_per_s']:.1f}"))
    red = fused["reduction"]
    hi = f"k{ks[-1]}"
    rows.append((f"serving_fused_{hi}_tok_per_s", 0.0,
                 f"tok_per_s={fused[hi]['tok_per_s']:.1f};"
                 f"p50_step_ms={fused[hi]['p50_step_ms']:.2f};"
                 f"p95_step_ms={fused[hi]['p95_step_ms']:.2f}"))
    rows.append(("serving_fused_dispatch_reduction", 0.0,
                 f"dispatches_per_token_x{red['dispatches_per_token']:.1f};"
                 f"host_syncs_per_token_x{red['host_syncs_per_token']:.1f}"))
    Path(json_path).write_text(json.dumps(report, indent=2))
    return rows


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    for name, us, derived in run():
        print(f"{name},{us:.2f},{derived}")
