"""Placement studies: VRAM utilization AND heterogeneous cost.

Study 1 (utilization): quantifies the paper's 'fully exploit each node's
VRAM' objective: smart (BFD + quant fallback + fill) vs naive first-fit,
at testbed and 100/1000-node scales; plus placement latency.  Each
variant reports its *own* measured latency (naive used to claim
``dt_us=0.0``) and utilization is a structured derived value, not packed
into an info string.

Study 2 (cost): the heterogeneity story — cost-optimal placement
(`place_cost_optimal`, ranking candidate nodes by modeled cost-per-token
from the per-class perf model) vs the class-blind VRAM-only `place()`,
on the paper testbed and the mixed 100-node fleet.  Both solvers place
the same demand set with fill disabled, so equal assignment counts make
the cost-per-token comparison apples-to-apples.  Results land in the
``placement`` section of ``BENCH_serving.json`` and are gated in CI via
``check_regression.py --only placement``.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

from repro.cluster import paper_testbed, scale_fleet
from repro.configs import ZOO
from repro.core.perfmodel import PerfModel
from repro.core.placement import (ModelDemand, NodeSpec, as_vram_nodes,
                                  place, place_cost_optimal, place_naive,
                                  plan_cost_per_token, plan_utilization)

DEMANDS = [
    ("deepseek-r1-7b", 2, 6), ("qwen3-8b", 1, 4),
    ("deepseek-r1-8b", 1, 4), ("llama3.2-3b", 2, 8),
    ("llama3.2-1b", 2, 12), ("gemma3-1b", 2, 12),
    ("qwen3-4b", 1, 6), ("nomic-embed-text", 2, 12),
]

# heterogeneous cost study: a mixed workload — high-traffic short-chat
# models plus long-context tails (the long-bucket demands are what a
# class-blind packer mis-places onto slow-BW or overpriced nodes)
COST_DEMANDS = [
    ("llama3.2-1b", 2, 30, 2048, 3.0, (("short", 0.7), ("medium", 0.3))),
    ("qwen3-1.7b", 2, 30, 2048, 2.0,
     (("short", 0.5), ("medium", 0.3), ("long", 0.2))),
    ("llama3.2-3b", 2, 20, 4096, 1.0, (("medium", 0.3), ("long", 0.7))),
    ("deepseek-r1-7b", 1, 10, 4096, 1.0, (("long", 1.0),)),
]


def _nodes_of(fleet):
    return {nid: (n.hbm_budget, n.klass.legacy)
            for nid, n in fleet.nodes.items()}


def _specs_of(fleet):
    return {nid: NodeSpec(n.hbm_budget, n.klass)
            for nid, n in fleet.nodes.items()}


def _merge_report(report: dict, json_path: str = "BENCH_serving.json"):
    """Merge the placement section into the serving bench report —
    creating the file when this study runs standalone (its own CI job),
    augmenting it when run after bench_serving."""
    path = Path(json_path)
    try:
        merged = json.loads(path.read_text())
    except (FileNotFoundError, ValueError):
        merged = {}
    merged["placement"] = report
    path.write_text(json.dumps(merged, indent=2))


def _cost_study():
    """Cost-optimal vs VRAM-only on heterogeneous fleets -> rows +
    structured report (the CI-gated artifact)."""
    perf = PerfModel()
    demands = [ModelDemand(ZOO[m], min_replicas=r, max_replicas=cap,
                           max_len=ml, weight=w, bucket_mix=mix)
               for m, r, cap, ml, w, mix in COST_DEMANDS]
    rows, report = [], {}
    for label, fleet in [("testbed6", paper_testbed()),
                         ("fleet100", scale_fleet(100, seed=1))]:
        specs = _specs_of(fleet)
        vram_nodes = as_vram_nodes(specs)
        # fill=False on both sides: identical demand floors, so both
        # solvers place the same replica count and the comparison is at
        # equal placed demand
        t0 = time.perf_counter()
        p_vram = place(vram_nodes, demands, fill=False)
        dt_vram_us = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        p_cost = place_cost_optimal(specs, demands, perf, fill=False)
        dt_cost_us = (time.perf_counter() - t0) * 1e6
        cpt_vram = plan_cost_per_token(p_vram, specs, demands, perf)
        cpt_cost = plan_cost_per_token(p_cost, specs, demands, perf)
        advantage = 1.0 - cpt_cost / cpt_vram if cpt_vram > 0 else 0.0
        equal = (len(p_vram.assignments) == len(p_cost.assignments)
                 and not p_vram.unplaced and not p_cost.unplaced)
        report[label] = {
            "cost_per_token_vram": cpt_vram,
            "cost_per_token_cost_optimal": cpt_cost,
            "cost_advantage": advantage,
            "placed_vram": len(p_vram.assignments),
            "placed_cost_optimal": len(p_cost.assignments),
            "equal_demand": equal,
            "utilization_vram": plan_utilization(p_vram, vram_nodes),
            "utilization_cost_optimal":
                plan_utilization(p_cost, vram_nodes),
            "dt_vram_us": dt_vram_us,
            "dt_cost_optimal_us": dt_cost_us,
        }
        rows.append((f"placement_cpt_vram_{label}", dt_vram_us,
                     f"{cpt_vram:.4e}"))
        rows.append((f"placement_cpt_cost_{label}", dt_cost_us,
                     f"{cpt_cost:.4e}"))
        rows.append((f"placement_cost_advantage_{label}", 0.0,
                     f"{advantage:.4f}"))
    return rows, report


def run():
    rows = []
    for label, fleet, scale in [
            ("testbed6", paper_testbed(), 1),
            ("fleet100", scale_fleet(100, seed=1), 8),
            ("fleet1000", scale_fleet(1000, seed=2), 60)]:
        nodes = _nodes_of(fleet)
        demands = [ModelDemand(ZOO[m], min_replicas=min(r * scale,
                                                        len(nodes)),
                               max_replicas=cap * scale)
                   for m, r, cap in DEMANDS]
        t0 = time.perf_counter()
        smart = place(nodes, demands)
        dt_smart_us = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        naive = place_naive(nodes, demands)
        dt_naive_us = (time.perf_counter() - t0) * 1e6
        u_s = plan_utilization(smart, nodes)
        u_n = plan_utilization(naive, nodes)
        rows.append((f"placement_util_smart_{label}", dt_smart_us,
                     f"{u_s:.4f}"))
        rows.append((f"placement_util_naive_{label}", dt_naive_us,
                     f"{u_n:.4f}"))
        rows.append((f"placement_unplaced_{label}", 0.0,
                     f"smart={len(smart.unplaced)};"
                     f"naive={len(naive.unplaced)}"))
    cost_rows, report = _cost_study()
    rows.extend(cost_rows)
    _merge_report(report)
    return rows


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    for name, us, derived in run():
        print(f"{name:36s} {us:12.1f} us/call   {derived}")
