"""The comparison that decides `correct`.

Once the window has closed and the program's state is freed, a sample
of the finished requests, drawn from the seed and always holding the
longest, is run through the configuration's family's plain float32
reference over prompt + served tokens.  The number compared is the
widest gap by which a served token's reference logit lies below the
reference's best at its position: a greedy token from a sound bf16
path is the argmax or a near-tie; one from a broken path lies anywhere
in the vocabulary.  Every request of the window must also have come
back whole: an answer that never comes fails the run as a wrong one
does.

The control is the reference computed in fp8 and put in the program's
place: at each position of the same prompts and served tokens, the
token the fp8 control puts first, judged by the same checks and limits.
A limit that the control does not fail separates nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

import reference


@dataclasses.dataclass
class Verdict:
    correct: bool
    checks: Dict[str, Dict[str, float]]     # name -> {"value", "limit"}
    control: Optional["Verdict"] = None     # the control's, if read
    sample: int = 0
    sample_tokens: int = 0


def pick_sample(records: Sequence, seed: int, target_tokens: int,
                max_requests: int) -> List:
    """The longest finished request, one from every node that served
    any, then others in a seeded order until `target_tokens` served
    tokens or `max_requests` requests."""
    done = [r for r in records if r.ok]
    if not done:
        return []
    rng = np.random.default_rng([seed % 2 ** 64, 77])
    order = [done[i] for i in rng.permutation(len(done))]
    longest = max(done, key=lambda r: (len(r.tokens), len(r.spec.prompt),
                                       -r.spec.idx))
    out = [longest]
    for node in sorted({r.node for r in done}):
        if all(r.node != node for r in out):
            out.append(next(r for r in order if r.node == node))
    for r in order:
        if len(out) >= max_requests or \
                sum(len(x.tokens) for x in out) >= target_tokens:
            break
        if r not in out:
            out.append(r)
    return out


def pad_len(max_len: int) -> int:
    q = reference.Q_BLOCK
    return -(-max_len // q) * q


def judge(window_records: Sequence, sample: Sequence, family,
          weights: Dict, arch: Dict, cfg: Dict, *,
          control: bool = False) -> Verdict:
    """Compare the sample with `family`'s reference; count what never
    came.  With `control`, judge the fp8 control's tokens the same
    way."""
    limit = cfg["limits"]["widest_logit_gap"]
    # a configuration whose limit is not yet set judges nothing correct
    limit = float("nan") if limit is None else float(limit)
    short = sum(1 for r in window_records if not r.ok)
    pad = pad_len(int(cfg["engine"]["max_len"]))
    widest = ctrl = 0.0 if sample else float("inf")
    for r in sample:
        g, c = reference.gaps(family, weights, arch,
                              r.spec.prompt.tolist(), r.tokens, pad,
                              control=control)
        widest = max(widest, float(np.max(g)))
        if control:
            ctrl = max(ctrl, float(np.max(c)))
    n_tokens = sum(len(r.tokens) for r in sample)
    theirs = _verdict(ctrl, short, limit, len(sample), n_tokens) \
        if control else None
    return dataclasses.replace(
        _verdict(widest, short, limit, len(sample), n_tokens),
        control=theirs)


def _verdict(widest: float, short: int, limit: float, n_sample: int,
             n_tokens: int) -> Verdict:
    checks = {
        "widest_logit_gap": {"value": widest, "limit": limit},
        "unfinished_requests": {"value": float(short), "limit": 0.0},
    }
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return Verdict(ok, checks, None, n_sample, n_tokens)
