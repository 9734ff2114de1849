"""Benchmark harness — one bench per paper claim/figure (the paper gives no
quantitative tables; §6 names the claims we quantify):

  availability  — HA under failure injection (+ no-HA baseline)
  placement     — VRAM utilization vs naive first-fit, 6/100/1000 nodes
  lb            — frontend fairness + straggler mitigation
  serving       — live engine tokens/s + TTFT (bf16 vs int8-at-rest)
  kernels       — hot-spot kernels: portable-path timing + VMEM budgets
  compression   — gradient wire-byte ratio + convergence parity
  roofline      — per (arch x shape x mesh) dry-run roofline table

Prints ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    from benchmarks import (bench_availability, bench_placement, bench_lb,
                            bench_serving, bench_kernels,
                            bench_compression, bench_roofline)
    suites = [
        ("availability", bench_availability.run),
        ("placement", bench_placement.run),
        ("lb", bench_lb.run),
        ("serving", bench_serving.run),
        ("kernels", bench_kernels.run),
        ("compression", bench_compression.run),
        ("roofline", bench_roofline.run),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        try:
            for row in fn():
                n, us, derived = row
                print(f"{n},{us:.2f},{derived}", flush=True)
        except Exception as e:  # pragma: no cover
            failures += 1
            traceback.print_exc()
            print(f"{name},0.00,SUITE_ERROR:{type(e).__name__}",
                  flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
