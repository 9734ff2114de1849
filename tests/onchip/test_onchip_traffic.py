"""The one traffic generator: deterministic per seed, the same sizes for
every seed in another order, Poisson arrivals, and the stated
distributions."""
import itertools
import statistics

import numpy as np
import pytest
import spec
import traffic

CHAT = spec.load_traffic("chat")
LONGDOC = spec.load_traffic("longdoc")
SEED = 2 ** 31 + 12345          # seeds exceed 32 signed bits


def _window(mix, seed, seconds=30):
    out = []
    for r in traffic.open_schedule(mix, seed, seconds, vocab=50304):
        if r.phase == "tail":
            break
        out.append(r)
    return out


def test_open_schedule_is_deterministic_per_seed():
    a, b = _window(CHAT, SEED), _window(CHAT, SEED)
    assert [(r.due, r.max_tokens, r.prompt.tolist()) for r in a] == \
        [(r.due, r.max_tokens, r.prompt.tolist()) for r in b]
    c = _window(CHAT, SEED + 1)
    assert [r.max_tokens for r in a] != [r.max_tokens for r in c]


def test_every_seed_gets_the_same_sizes_in_another_order():
    a = [r for r in _window(CHAT, 1) if r.phase == "window"]
    b = [r for r in _window(CHAT, SEED) if r.phase == "window"]
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in b)
    assert sorted(r.max_tokens for r in a) == sorted(r.max_tokens for r in b)
    assert len(a) == len(b)
    assert [r.due for r in a] != [r.due for r in b]


@pytest.mark.parametrize("seconds", [10, 30, 51])
def test_window_holds_rate_times_seconds_arrivals(seconds):
    win = [r for r in _window(CHAT, SEED, seconds) if r.phase == "window"]
    lead = float(CHAT["lead_s"])
    assert len(win) == round(CHAT["rate_per_s"] * seconds)
    dues = [r.due for r in win]
    assert dues == sorted(dues)
    assert lead <= dues[0] and dues[-1] < lead + seconds


@pytest.mark.parametrize("mix", [CHAT, LONGDOC], ids=["chat", "longdoc"])
def test_lengths_follow_the_stated_distribution(mix):
    for key in ("prompt", "output"):
        d = mix[key]
        ls = traffic.lengths(d, 1001)
        assert ls == sorted(ls)
        assert min(ls) >= d["min"] and max(ls) <= d["max"]
        if d["min"] < d["median"] < d["max"]:
            assert ls[500] == pytest.approx(d["median"], abs=1)


def test_tokens_lie_in_the_vocabulary_and_prompts_fit_the_cell():
    for cfg, mix in (("olmo-1b", CHAT), ("phi4-mini", LONGDOC)):
        c = spec.load_config(cfg)
        assert mix["prompt"]["max"] + mix["output"]["max"] \
            <= c["engine"]["max_len"]
    for r in _window(CHAT, SEED, 10)[:20]:
        assert r.prompt.min() >= 1 and r.prompt.max() < 50304


def test_closed_streams_repeat_one_block_per_client():
    streams = traffic.closed_streams(LONGDOC, SEED, 2, vocab=200064)
    n = LONGDOC["block"]
    first = list(itertools.islice(streams[0], 2 * n))
    again = list(itertools.islice(
        traffic.closed_streams(LONGDOC, SEED, 2, vocab=200064)[0], 2 * n))
    assert [r.prompt.tolist() for r in first] == \
        [r.prompt.tolist() for r in again]
    want = traffic.lengths(LONGDOC["prompt"], n)
    assert sorted(len(r.prompt) for r in first[:n]) == want
    assert sorted(len(r.prompt) for r in first[n:]) == want
    other = list(itertools.islice(streams[1], n))
    assert {r.idx for r in other}.isdisjoint({r.idx for r in first})


def test_open_loop_arrivals_are_poisson():
    """Pooled over seeds, the gaps between arrivals are exponential of
    mean 1/rate: as many short gaps, and as long a tail, as a Poisson
    process has."""
    rate = CHAT["rate_per_s"]
    gaps = []
    for seed in range(SEED, SEED + 200):
        win = [r.due for r in _window(CHAT, seed, 51)
               if r.phase == "window"]
        gaps.extend(np.diff(win))
    gaps = np.asarray(gaps) * rate
    assert statistics.fmean(gaps) == pytest.approx(1.0, rel=0.05)
    assert statistics.pstdev(gaps) == pytest.approx(1.0, rel=0.08)
    for x in (0.1, 0.5, 1.0, 2.0, 3.0):
        assert np.mean(gaps < x) == pytest.approx(1 - np.exp(-x), abs=0.02)


def test_seeds_bunch_arrivals_differently():
    """No seed's order is smoothed: the busiest 5 s of a window hold
    more than their share of its arrivals, by a different amount from
    seed to seed."""
    busiest = set()
    for seed in (1, 2, 3, SEED):
        dues = np.asarray([r.due for r in _window(CHAT, seed, 51)
                           if r.phase == "window"])
        counts = [np.sum((dues >= t) & (dues < t + 5))
                  for t in np.arange(dues[0], dues[-1] - 5, 0.5)]
        busiest.add(int(max(counts)))
        assert max(counts) > 5 * CHAT["rate_per_s"]
    assert len(busiest) > 1
