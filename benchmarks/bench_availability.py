"""Availability under failure injection — quantifies the paper's central
HA claim (it gave no numbers; we do).

Two scenarios:

1. **Fleet survival** (paper testbed + zoo): kill k nodes mid-workload,
   measure request success rate, failover overhead (extra retries), the
   controller's reallocation latency, and the no-HA static-table
   baseline the paper's HAProxy replaces.
2. **Survivable streams** (real engines, seeded chaos): N greedy
   streams run through the continuous runtime while a seeded
   `FaultInjector` kill schedule takes out a node mid-decode.  Reports
   `tokens_lost` and `tokens_duplicated` versus the fault-free
   reference (both MUST be 0 — mid-stream migration replays nothing and
   drops nothing) plus recovery latency (crash -> first resumed token)
   and the migration count.  The `availability` section is merged into
   ``BENCH_serving.json`` so `check_regression.py` can gate on it.
"""
from __future__ import annotations

import json
import random
import threading
import time
from pathlib import Path

import jax

from repro.api import Gateway, RuntimeConfig, StreamEventType
from repro.cluster import (BackendNode, FaultInjector, Fleet,
                           paper_testbed)
from repro.configs import ARCHS, ZOO
from repro.core import (ControllerConfig, ModelCatalog, ModelDemand,
                        ReplicaInfo, ReplicaKey, SDAIController)
from repro.models import build
from repro.serving import SamplingParams

_params = {}


def _store(cfg):
    if cfg.name not in _params:
        _params[cfg.name] = build(cfg).init(jax.random.PRNGKey(0))
    return _params[cfg.name]


# ------------------- scenario 1: fleet survival --------------------- #
def _fleet_survival(n_requests: int, kills: int, seed: int):
    rng = random.Random(seed)
    fleet = paper_testbed(param_store=_store)
    catalog = ModelCatalog()
    models = ["deepseek-r1-7b", "qwen3-8b", "deepseek-r1-1.5b",
              "llama3.2-1b", "gemma3-1b", "nomic-embed-text"]
    for m in models:
        catalog.register(ZOO[m])
    ctrl = SDAIController(fleet, catalog, ControllerConfig())
    ctrl.discover()
    ctrl.deploy([ModelDemand(ZOO[m], min_replicas=2) for m in models])

    gw = Gateway(ctrl)
    ok = fail = retries = 0
    realloc_us = []
    kill_at = {n_requests * (i + 1) // (kills + 1) for i in range(kills)}
    for i in range(n_requests):
        if i in kill_at:
            alive = [n for n, node in fleet.nodes.items() if node.alive]
            if len(alive) > 1:
                fleet.fail_node(rng.choice(alive))
                t0 = time.perf_counter()
                ctrl.tick()
                realloc_us.append((time.perf_counter() - t0) * 1e6)
        resp = gw.generate(rng.choice(models),
                           [rng.randrange(64) for _ in range(4)],
                           SamplingParams(max_tokens=4))
        retries += resp.retries
        if resp.ok:
            ok += 1
        else:
            fail += 1
    rows = [
        ("availability_success_rate", 0.0, f"{ok/(ok+fail):.4f}"),
        ("availability_failovers", 0.0, str(retries)),
        ("availability_realloc",
         sum(realloc_us) / max(len(realloc_us), 1),
         f"kills={len(realloc_us)}"),
    ]
    # baseline: NO health-checked frontend — clients pin to a static
    # deploy-time routing table (round-robin, no liveness, no retries),
    # the setup the paper's HAProxy replaces
    fleet2 = paper_testbed(param_store=_store)
    ctrl2 = SDAIController(fleet2, catalog, ControllerConfig())
    ctrl2.discover()
    ctrl2.deploy([ModelDemand(ZOO[m], min_replicas=2) for m in models])
    static_table = {m: [r.key for r in ctrl2.replicas.for_model(m)]
                    for m in models}
    rr = {m: 0 for m in models}
    from repro.serving.request import Request
    rng2 = random.Random(seed)
    ok2 = fail2 = 0
    for i in range(n_requests):
        if i in kill_at:
            alive = [n for n, node in fleet2.nodes.items() if node.alive]
            if len(alive) > 1:
                fleet2.fail_node(rng2.choice(alive))
        m = rng2.choice(models)
        keys = static_table[m]
        key = keys[rr[m] % len(keys)]
        rr[m] += 1
        req = Request(model=m, prompt=[rng2.randrange(64)
                                       for _ in range(4)],
                      sampling=SamplingParams(max_tokens=4))
        node = fleet2.nodes[key.node_id]
        sent = node.submit(key.instance_id, req)
        if sent and not req.error:
            ok2 += 1
        else:
            fail2 += 1
    rows.append(("availability_no_ha_baseline", 0.0,
                 f"{ok2/(ok2+fail2):.4f}"))
    return rows


# ------------------- scenario 2: survivable streams ----------------- #
def _survivable_streams(n_streams: int = 6, max_tokens: int = 24,
                        seed: int = 1234):
    """Seeded kill-a-node-mid-decode chaos soak on real engines."""
    cfg = ARCHS["olmo-1b"].reduced()
    fleet = Fleet([BackendNode(f"n{i}", "v5e-1", param_store=_store)
                   for i in range(3)])
    catalog = ModelCatalog()
    catalog.register(cfg)
    ctrl = SDAIController(fleet, catalog)
    ctrl.discover()
    for node in fleet.nodes.values():
        inst = node.deploy(cfg, n_slots=2, max_len=48)
        ctrl.replicas.add(ReplicaInfo(
            ReplicaKey(node.node_id, inst.instance_id),
            cfg.name, "", 2, 48, inst.bytes))
    gw = Gateway(ctrl)
    prompts = [[1, 2, i + 1] for i in range(n_streams)]

    # fault-free reference (greedy => per-prompt deterministic)
    reference = {}
    for p in prompts:
        r = gw.generate(cfg.name, p, SamplingParams(max_tokens=max_tokens),
                        timeout_s=120)
        assert r.ok, r.error
        reference[tuple(p)] = list(r.tokens)

    inj = FaultInjector.kill_schedule(
        seed=seed, node_ids=list(fleet.nodes), n_kills=1,
        first_step=3).install(fleet, bus=ctrl.bus)
    gw.start(RuntimeConfig(tick_interval_s=0.02))
    streams = {}            # request_id -> [(t, index, token), ...]
    lock = threading.Lock()

    def consume(rid, handle):
        got = []
        for ev in handle.stream(timeout_s=120):
            if ev.type is StreamEventType.TOKEN:
                got.append((time.monotonic(), ev.index, ev.token))
        with lock:
            streams[rid] = got

    try:
        handles = [(p, gw.submit(cfg.name, p,
                                 SamplingParams(max_tokens=max_tokens)))
                   for p in prompts]
        threads = [threading.Thread(target=consume,
                                    args=(h.internal.request_id, h))
                   for _, h in handles]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
    finally:
        gw.stop(timeout_s=60)
        inj.uninstall()

    crash_ts = sorted(e.ts for e in ctrl.bus.of_kind("fault_injected")
                      if e.data.get("fault") == "crash")
    tokens_lost = tokens_dup = 0
    for p, h in handles:
        got = streams.get(h.internal.request_id, [])
        ref = reference[tuple(p)]
        seen = [i for _, i, _ in got]
        tokens_dup += len(seen) - len(set(seen))
        delivered = [tok for _, _, tok in got]
        # lost = reference tokens the stream never delivered in order
        tokens_lost += sum(1 for a, b in zip(ref, delivered) if a != b)
        tokens_lost += max(0, len(ref) - len(delivered))
    # recovery latency: crash -> first token the migrated stream
    # delivered after its resume on the survivor
    recovery_us = []
    for ev in ctrl.bus.of_kind("request_migrated"):
        got = streams.get(ev.data.get("request_id"), [])
        killed_at = max((t for t in crash_ts if t <= ev.ts), default=None)
        after = [t for t, _, _ in got if t > ev.ts]
        if killed_at is not None and after:
            recovery_us.append((min(after) - killed_at) * 1e6)
    recovery_us.sort()
    mean_us = sum(recovery_us) / max(len(recovery_us), 1)
    p95_us = recovery_us[int(0.95 * (len(recovery_us) - 1))] \
        if recovery_us else 0.0
    max_us = recovery_us[-1] if recovery_us else 0.0
    migrations = gw.stats.migrations
    report = {
        "streams": n_streams,
        "max_tokens": max_tokens,
        "seed": seed,
        "tokens_lost": tokens_lost,
        "tokens_duplicated": tokens_dup,
        "migrations": migrations,
        "stream_retries": gw.stats.stream_retries,
        "recovery_mean_us": mean_us,
        "recovery_p95_us": p95_us,
        "recovery_max_us": max_us,
        "faults_fired": len(inj.fired),
    }
    rows = [
        ("chaos_tokens_lost", 0.0, str(tokens_lost)),
        ("chaos_tokens_duplicated", 0.0, str(tokens_dup)),
        ("chaos_migrations", 0.0, str(migrations)),
        ("chaos_recovery", mean_us,
         f"p95={p95_us:.0f}us max={max_us:.0f}us n={len(recovery_us)}"),
    ]
    return rows, report


def _merge_report(report: dict, json_path: str = "BENCH_serving.json"):
    """Merge the availability section into the serving bench report —
    creating the file when the chaos soak runs standalone (its own CI
    job), augmenting it when run after bench_serving."""
    path = Path(json_path)
    try:
        merged = json.loads(path.read_text())
    except (FileNotFoundError, ValueError):
        merged = {}
    merged["availability"] = report
    path.write_text(json.dumps(merged, indent=2))


def run(n_requests: int = 120, kills: int = 2, seed: int = 0):
    rows = _fleet_survival(n_requests, kills, seed)
    chaos_rows, report = _survivable_streams()
    rows.extend(chaos_rows)
    _merge_report(report)
    return rows


if __name__ == "__main__":
    import sys

    from repro import compile_cache
    compile_cache.enable()
    if "--chaos-only" in sys.argv:     # CI chaos-soak job: scenario 2
        rows, report = _survivable_streams()
        _merge_report(report)
    else:
        rows = run()
    for name, us, derived in rows:
        print(f"{name:36s} {us:12.1f} us/call   {derived}")
