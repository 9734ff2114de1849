"""The one general traffic generator.  A mix is a data file
(`traffic/<name>.json`); this module turns it and a seed into requests.

Every seed gets the same multiset of sizes, in another order: prompt
and output lengths are the quantiles (i + 1/2)/n of their distributions,
shuffled by the seed, so seeds differ in which requests meet, not in how
much work a window holds.  Open-loop arrivals are a Poisson process
conditioned on its count: the n arrivals of a span of T seconds are n
independent uniform times in it, sorted (unconditioned, the same
process has independent exponential gaps of mean T/n).  So every seed
sends as many requests in a span, and each seed bunches them at random.

Mix parameters (all lengths in tokens):

    loop          "open" (arrivals on a schedule) or "closed" (each
                  client sends its next request when the last completes)
    rate_per_s    open: mean arrivals per second into the whole cell
    clients_per_slot  closed: clients per engine slot of the cell
    lead_s        traffic sent before the measured window opens, so the
                  window starts in steady state
    prompt, output  {"median", "sigma", "min", "max"}: a log-normal of
                  that median and shape, clipped to [min, max]
    block         closed: requests per shuffled block of each client
    temperature   0 for greedy (the comparison with the reference needs
                  greedy tokens)
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, Iterator, List, Sequence

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    idx: int              # unique within one run
    phase: str            # "lead", "window" or "tail"
    due: float            # open loop: seconds after the lead-in starts
    prompt: np.ndarray    # int32 token ids
    max_tokens: int


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2 ** 64, *salt])


def lengths(dist: Dict, n: int) -> List[int]:
    """The n quantiles (i + 1/2)/n of a clipped log-normal, ascending."""
    mu, sigma = math.log(dist["median"]), float(dist["sigma"])
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        v = int(round(math.exp(mu + sigma * z)))
        out.append(min(max(v, int(dist["min"])), int(dist["max"])))
    return out


def _block(mix: Dict, seed: int, salt: int, n: int, vocab: int,
           first_idx: int, phase: str, start: float, span: float,
           open_loop: bool) -> List[RequestSpec]:
    """n requests: the length quantiles in a seeded order, due at n
    sorted uniform times in [start, start + span) (open loop) or all at
    once (closed loop)."""
    rng = _rng(seed, salt)
    p_lens = rng.permutation(lengths(mix["prompt"], n))
    o_lens = rng.permutation(lengths(mix["output"], n))
    if open_loop:
        dues = np.sort(start + span * rng.random(n)).tolist()
    else:
        dues = [start] * n
    out = []
    for i in range(n):
        toks = _rng(seed, salt, i).integers(1, vocab, size=int(p_lens[i]),
                                            dtype=np.int32)
        out.append(RequestSpec(first_idx + i, phase, float(dues[i]), toks,
                               int(o_lens[i])))
    return out


def open_schedule(mix: Dict, seed: int, seconds: float,
                  vocab: int) -> Iterator[RequestSpec]:
    """Lead-in, then exactly round(rate * seconds) window requests due
    in [lead_s, lead_s + seconds), then tail blocks for as long as the
    caller keeps reading (load stays on while the window drains)."""
    rate, lead = float(mix["rate_per_s"]), float(mix.get("lead_s", 0))
    n_lead = int(round(rate * lead))
    n_win = max(int(round(rate * seconds)), 1)
    idx = 0
    if n_lead:
        yield from _block(mix, seed, 1, n_lead, vocab, idx, "lead", 0.0,
                          lead, True)
        idx += n_lead
    yield from _block(mix, seed, 2, n_win, vocab, idx, "window", lead,
                      seconds, True)
    idx += n_win
    start, salt = lead + seconds, 3
    while True:
        yield from _block(mix, seed, salt, n_win, vocab, idx, "tail",
                          start, seconds, True)
        idx, start, salt = idx + n_win, start + seconds, salt + 1


def closed_streams(mix: Dict, seed: int, n_clients: int,
                   vocab: int) -> List[Iterator[RequestSpec]]:
    """One endless request stream per client; each block of
    `mix["block"]` requests is the same quantile set, shuffled."""
    n = int(mix["block"])

    def stream(c: int) -> Iterator[RequestSpec]:
        b = 0
        while True:
            yield from _block(mix, seed, 1000 * (c + 1) + b, n, vocab,
                              (c * 1_000_000) + b * n, "window", 0.0, 0.0,
                              False)
            b += 1
    return [stream(c) for c in range(n_clients)]


def prompt_range(mix: Dict) -> Sequence[int]:
    """Every prompt length the mix can send: its clipped support."""
    return range(int(mix["prompt"]["min"]), int(mix["prompt"]["max"]) + 1)
