#!/usr/bin/env python3
"""Readings that set a cell's limits and rates, made on the chip and not
by the benchmark's own runs.

    python3 benchmarks/onchip/calibrate.py --workload olmo-1b.chat \
        --seeds 1,2,3 --seconds 20 --control --out cal.jsonl

runs the cell once per seed in one process (set-up is paid once in
compiles; each seed makes its own weights and traffic) and writes one
JSON line per run: the end-to-end metrics and the widest logit gap of
the served sample against the float32 reference.  With `--control` a
second line follows: the same run with the fp8 control put in the
program's place (its tokens at each position of the same prompts and
served tokens), judged by the same checks; its `correct` has to read
false.
`--rates` overrides an open-loop mix's arrival rate, one run per rate:
the sweep that finds a cell's knee.
"""
import argparse
import gc
import json
import pathlib
import statistics
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402
import readers  # noqa: E402
import spec as spec_lib  # noqa: E402


def backlog(ctx) -> dict:
    """Whether the queue grew through the window: mean time to first
    token of the last quarter of the window's requests against the
    first quarter's."""
    reqs = sorted(readers.window_requests(ctx), key=lambda r: r.due)
    q = max(len(reqs) // 4, 1)
    ttft = [(r.times[0] - r.due) * 1e3 if r.times else float("inf")
            for r in reqs]
    return {"ttft_first_quarter_ms": statistics.fmean(ttft[:q]),
            "ttft_last_quarter_ms": statistics.fmean(ttft[-q:]),
            "ttft_p50_ms": readers.percentile(ttft, 50)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    harness.enable_compile_cache()
    bench = spec_lib.load_benchmark()
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates \
        else [None]
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with out.open("a") as f:
        for rate in rates:
            for seed in seeds:
                t = time.perf_counter()
                res = harness.run_cell(
                    args.workload, seed, args.seconds, False,
                    started=t, bench=bench, control=args.control,
                    mix_over={"rate_per_s": rate} if rate else None)
                ctx = res.context
                queue = backlog(ctx) if ctx.window.loop == "open" else None
                head = readers.percentile(
                    [v * 1e3 for s in ctx.window.samples
                     for v in s["head_wait"]], 95)
                row = dict(res.line, workload=args.workload, rate=rate,
                           wall_s=time.perf_counter() - t, backlog=queue,
                           head_wait_p95_ms=head)
                f.write(json.dumps(row) + "\n")
                cv = res.verdict.control
                if cv is not None:
                    f.write(json.dumps(dict(row, role="control",
                                            correct=cv.correct,
                                            checks=cv.checks)) + "\n")
                f.flush()
                brief = {k: round(v["value"], 3)
                         for k, v in res.line["metrics"].items()}
                print(json.dumps({
                    "seed": seed, "rate": rate, "correct": res.line["correct"],
                    "attempted": res.line["attempted"],
                    "failed": res.line["failed"], "metrics": brief,
                    "gap": res.verdict.checks["widest_logit_gap"]["value"],
                    "control_gap": cv and
                    cv.checks["widest_logit_gap"]["value"],
                    "control_correct": cv and cv.correct,
                    "backlog": queue, "head_wait_p95_ms": head,
                    "compiled_in_window":
                        res.line["run"]["compiled_in_window"]["programs"],
                    "peak": res.line["device"]["memory_peak_bytes"],
                    "wall_s": round(time.perf_counter() - t, 1)}),
                    flush=True)
                del res
                gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
