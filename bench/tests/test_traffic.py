"""The traffic generator: same seed, same schedule; every seed, the same
fixed trace from another start."""
import math

import numpy as np
import pytest

from bench.traffic import (load_mix, n_requests, schedule, seed_words,
                           sizes, trace)

BIG = 2**31 + 12345          # seeds run past 32 bits


def _shape(reqs):
    return [(len(r.prompt), r.n_out) for r in reqs]


def test_same_seed_same_schedule():
    mix = load_mix("azure_code")
    assert schedule(mix, BIG, 1000, 10) == schedule(mix, BIG, 1000, 10)


def test_seeds_share_sizes_and_gaps_in_another_order():
    mix = load_mix("azure_code")
    a, b = schedule(mix, BIG, 1000, 10), schedule(mix, BIG + 1, 1000, 10)
    assert len(a) == len(b) == n_requests(mix, 10)
    k = mix["prompt"]["sizes"]
    tr = trace(mix, 51)
    # every run of ``sizes`` consecutive requests of the trace holds each
    # size once
    assert sorted(p for _, p, _ in tr[:k]) == sorted(sizes(mix["prompt"]))
    assert sorted(o for _, _, o in tr[:k]) == sorted(sizes(mix["output"]))
    assert _shape(a) != _shape(b)
    gaps = lambda s: sorted(np.diff([0.0] + [r.arrival for r in s]))
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-9, atol=1e-12)
    assert a[0].prompt != b[0].prompt
    assert all(1 <= t < 1000 for r in a for t in r.prompt)


@pytest.mark.parametrize("seed", [7, BIG])
def test_every_window_holds_the_trace_once(seed):
    """Whenever a window of ``seconds`` opens, it holds each request of
    the trace once, each after the same gap."""
    mix = load_mix("azure_code")
    s = schedule(mix, seed, 1000, 51)
    tr = trace(mix, 51)
    for t0 in (mix["warm_s"], mix["warm_s"] + 0.37):
        held = [(round(r.arrival - p.arrival, 9), len(r.prompt), r.n_out)
                for p, r in zip(s, s[1:]) if t0 <= r.arrival < t0 + 51]
        assert sorted(held) == sorted((round(g, 9), p, o) for g, p, o in tr)


def test_sizes_follow_the_fit():
    spec = {"median": 1500, "mean": 2048, "sizes": 32, "min": 64,
            "max": 6144}
    s = sizes(spec)
    assert s == sorted(s) and s[-1] == 6144 and s[0] >= 64
    # the middle quantiles sit either side of the median
    assert s[15] < 1500 < s[16]
    sigma = math.sqrt(2 * math.log(2048 / 1500))
    assert abs(math.log(s[20] / 1500) / sigma - 0.3601) < 0.01


def test_arrivals_hold_the_rate():
    mix = load_mix("azure_code")
    s = schedule(mix, 7, 1000, 51)
    span = s[-1].arrival
    assert abs(len(s) / span - mix["rate_per_s"]) < 0.05 * mix["rate_per_s"]


def test_seed_words():
    assert seed_words(5) == [5]
    assert seed_words(2**32 + 3) == [3, 1]
