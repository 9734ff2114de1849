"""Arithmetic shared by the metric readers (`metrics/<name>.py`).  Each
reader takes a `harness.Context` and returns a number or None: None
where the run gave it nothing to read, never 0 for a share of a peak.

Tails are nearest-rank percentiles over every sample: a request that
failed counts as infinitely late in each latency tail it belongs to.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence

import work

INF = float("inf")


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank q-th percentile; None for no samples."""
    xs = sorted(values)
    if not xs:
        return None
    k = max(int(math.ceil(q / 100.0 * len(xs))), 1)
    return xs[k - 1]


def window_requests(ctx) -> List:
    w = ctx.window
    if w.loop == "open":
        return [r for r in w.records if r.phase == "window"]
    return [r for r in w.records if not r.cancelled]


def ttfts_ms(ctx) -> List[float]:
    """Open loop: first token - due; closed loop: first token - sent."""
    out = []
    for r in window_requests(ctx):
        if r.times and not (r.error and not r.cancelled):
            out.append((r.times[0] - r.due) * 1e3)
        elif not r.cancelled:
            out.append(INF)
    return out


def itls_ms(ctx) -> List[float]:
    """Every gap between consecutive tokens of the window's requests.
    Open loop: all gaps of the requests due in the window; closed loop:
    gaps that end inside the window."""
    w = ctx.window
    out = []
    for r in w.records:
        if w.loop == "open" and r.phase != "window":
            continue
        t = r.times
        for a, b in zip(t, t[1:]):
            if w.loop == "closed" and not (w.t0 <= b <= w.t1):
                continue
            out.append((b - a) * 1e3)
        if w.loop == "open" and not r.ok:
            out.append(INF)
    return out


def tokens_in(ctx, a: float, b: float) -> int:
    return sum(1 for r in ctx.window.records for t in r.times
               if a <= t <= b)


# ---- trace readers: tokens and prompts inside the traced sub-window -- #
def _traced(ctx):
    if ctx.trace is None or ctx.window.trace_window is None:
        return None
    return ctx.window.trace_window


def decode_contexts(ctx) -> Optional[List[int]]:
    """Context length of every decode token that arrived in the traced
    sub-window: token i >= 1 of a prompt of L attends to L + i
    positions."""
    tw = _traced(ctx)
    if tw is None:
        return None
    out = []
    for r in ctx.window.records:
        n = len(r.spec.prompt)
        out.extend(n + i for i, t in enumerate(r.times)
                   if i >= 1 and tw[0] <= t <= tw[1])
    return out


def prefill_prompts(ctx) -> Optional[List[int]]:
    """Prompt lengths of the requests whose first token arrived in the
    traced sub-window."""
    tw = _traced(ctx)
    if tw is None:
        return None
    return [len(r.spec.prompt) for r in ctx.window.records
            if r.times and tw[0] <= r.times[0] <= tw[1]]


def program_time(ctx, fragment: str):
    """(device seconds, runs) of the program, or None untraced/absent."""
    if _traced(ctx) is None:
        return None
    secs, runs = ctx.trace.program(fragment)
    return (secs, runs) if runs else None


DECODE, PREFILL = "fused_decode", "prefill_admit"


def decode_step_ms(ctx) -> Optional[float]:
    got = program_time(ctx, DECODE)
    if got is None:
        return None
    return got[0] / (got[1] * ctx.engine["decode_block"]) * 1e3


def prefill_ms_per_1k(ctx) -> Optional[float]:
    got, prompts = program_time(ctx, PREFILL), prefill_prompts(ctx)
    if got is None or not prompts:
        return None
    return got[0] * 1e3 / (sum(prompts) / 1e3)


def decode_roofline(ctx) -> Optional[float]:
    got, contexts = program_time(ctx, DECODE), decode_contexts(ctx)
    if got is None or not contexts:
        return None
    a, p = ctx.arch, ctx.peaks
    steps = got[1] * ctx.engine["decode_block"]
    least = max(work.decode_bytes(a, steps, contexts)
                / p["hbm_bytes_per_s"],
                work.decode_flops(a, contexts) / p["bf16_flops_per_s"])
    return least / got[0] * 100.0


def mfu_decode(ctx) -> Optional[float]:
    got, contexts = program_time(ctx, DECODE), decode_contexts(ctx)
    if got is None or not contexts:
        return None
    flops = work.decode_flops(ctx.arch, contexts)
    return flops / got[0] / ctx.peaks["bf16_flops_per_s"] * 100.0


def mfu_prefill(ctx) -> Optional[float]:
    got, prompts = program_time(ctx, PREFILL), prefill_prompts(ctx)
    if got is None or not prompts:
        return None
    flops = work.prefill_flops(ctx.arch, prompts)
    return flops / got[0] / ctx.peaks["bf16_flops_per_s"] * 100.0


def device_idle(ctx) -> Optional[float]:
    if _traced(ctx) is None or ctx.trace.window_s <= 0:
        return None
    return (1.0 - ctx.trace.busy_s / ctx.trace.window_s) * 100.0
