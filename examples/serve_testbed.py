"""End-to-end serving driver (the paper's kind of workload): the 6-node
heterogeneous testbed serves a batched request stream across the full zoo
while nodes fail and recover mid-flight.

Demonstrates every architectural claim at once:
  * unified client interface (one endpoint, many models/nodes),
  * VRAM-aware placement with int8/int4 fallback on legacy nodes,
  * health-checked least-connection load balancing,
  * replica failover + controller-driven reallocation on node death,
  * elastic re-fill when a node recovers.

    PYTHONPATH=src python examples/serve_testbed.py [--requests 60]
"""
import argparse
import dataclasses
import random

import jax

from repro import compile_cache
from repro.api import Gateway
from repro.cluster import paper_testbed
from repro.configs import ZOO
from repro.core import (ControllerConfig, ModelCatalog, ModelDemand,
                        SDAIController)
from repro.models import build
from repro.serving import SamplingParams

_params = {}


def param_store(cfg):
    if cfg.name not in _params:
        _params[cfg.name] = build(cfg).init(jax.random.PRNGKey(0))
    return _params[cfg.name]


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=60)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    rng = random.Random(args.seed)

    fleet = paper_testbed(param_store=param_store)
    catalog = ModelCatalog()
    # two live (tiny) models + the big accounted zoo from paper Table 1
    live = {}
    for name in ("llama3.2-1b", "gemma3-1b"):
        cfg = dataclasses.replace(ZOO[name].reduced(), name=name)
        live[name] = cfg
        catalog.register(cfg)
    for name in ("deepseek-r1-7b", "qwen3-8b", "deepseek-r1-1.5b",
                 "nomic-embed-text"):
        catalog.register(ZOO[name])

    ctrl = SDAIController(fleet, catalog, ControllerConfig())
    ctrl.discover()
    plan = ctrl.deploy(
        [ModelDemand(c, min_replicas=2, n_slots=2, max_len=48)
         for c in live.values()] +
        [ModelDemand(ZOO["deepseek-r1-7b"], min_replicas=2),
         ModelDemand(ZOO["qwen3-8b"], min_replicas=1),
         ModelDemand(ZOO["deepseek-r1-1.5b"], min_replicas=2),
         ModelDemand(ZOO["nomic-embed-text"], min_replicas=2)])
    print(f"placed {len(plan.assignments)} instances "
          f"(util {ctrl.fleet_utilization():.1%}); quantized: "
          f"{sum(1 for a in plan.assignments if a.quantize)}")

    gw = Gateway(ctrl)
    models = gw.models()
    ok = fail = 0
    failed_at = recovered_at = None
    victim = None
    for i in range(args.requests):
        # failure injection at 1/3, recovery at 2/3 of the workload
        if i == args.requests // 3:
            victim = rng.choice([n for n in fleet.nodes
                                 if fleet.nodes[n].alive])
            fleet.fail_node(victim)
            ctrl.tick()
            failed_at = i
            print(f"[{i}] !! node {victim} DIED -> controller "
                  f"reallocated; routing now "
                  f"{ {m: len(r) for m, r in ctrl.frontend.routing_table().items()} }")
        if i == 2 * args.requests // 3 and victim:
            fleet.recover_node(victim)
            ctrl.tick()
            recovered_at = i
            print(f"[{i}] node {victim} RECOVERED -> re-filled")
        model = rng.choice(models)
        resp = gw.generate(model, [rng.randrange(64) for _ in range(4)],
                           SamplingParams(max_tokens=4))
        if resp.ok:
            ok += 1
        else:
            fail += 1
    print(f"\navailability: {ok}/{ok+fail} = {ok/(ok+fail):.1%} "
          f"(node died at req {failed_at}, recovered at {recovered_at})")
    print("frontend stats:", ctrl.frontend.stats)
    ev = [e.kind for e in ctrl.bus.events]
    print("controller events:", {k: ev.count(k) for k in sorted(set(ev))})


if __name__ == "__main__":
    main()
