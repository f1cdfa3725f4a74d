"""One general traffic generator; each mix is a data file
``traffic/<name>.json`` of its parameters.

A mix is an open loop of single requests: arrivals on a schedule fixed
before the run, whether or not earlier requests have finished.  Its
lengths come from a published trace: ``prompt`` and ``output`` each give
the median and mean that the source reports, and the generator fits a
log-normal to the two (sigma = sqrt(2 ln(mean / median))), takes
``sizes`` of its quantiles at token granularity, and clips them to
``[min, max]``.

The schedule replays one fixed trace: ``round(rate_per_s * seconds)``
requests, a window's worth, whose lengths are dealt from those sizes
(each run of ``sizes`` consecutive requests holds every size once) and
whose gaps are the quantiles of an exponential at ``rate_per_s``,
scaled to last exactly the window, both in an order fixed by the mix's
``trace_seed``.  A run's seed picks the request it starts
from and draws its own token ids; the trace repeats end to end from
there.  So every window holds each request of the trace once, with the
same neighbours: the seed changes the order of the work (where the
trace wraps) and the tokens, not the work's amount, its queueing or its
programs.

Mix parameters:

  source       where the lengths come from
  rate_per_s   offered requests per second
  trace_seed   fixes the trace's order of lengths and gaps
  warm_s       seconds of the schedule served before the window opens
  tail_s       seconds of schedule past the window, so the load holds
               while the window's last requests finish
  prompt       {"median", "mean", "sizes", "min", "max"}: prompt tokens
  output       the same, for decoded tokens
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List, Tuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def seed_words(seed: int) -> List[int]:
    """A seed of any size as 32-bit words, for numpy's seeding."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


@dataclasses.dataclass
class Request:
    rid: str
    arrival: float               # seconds after the schedule's start
    prompt: List[int]
    n_out: int


def sizes(spec: dict) -> List[int]:
    """The mix's set of lengths for one of ``prompt`` or ``output``."""
    median, mean, k = float(spec["median"]), float(spec["mean"]), \
        int(spec["sizes"])
    sigma = math.sqrt(2.0 * math.log(mean / median))
    z = NormalDist()
    return [min(int(spec["max"]), max(int(spec["min"]), round(
        median * math.exp(sigma * z.inv_cdf((i + 0.5) / k)))))
        for i in range(k)]


def n_requests(mix: dict, seconds: float) -> int:
    return math.ceil(float(mix["rate_per_s"])
                     * (float(mix["warm_s"]) + seconds + float(mix["tail_s"])))


def _dealt(values: List[int], n: int, rng: np.random.Generator) -> List[int]:
    out: List[int] = []
    while len(out) < n:
        out += [values[i] for i in rng.permutation(len(values))]
    return out[:n]


def trace(mix: dict, seconds: float) -> List[Tuple[float, int, int]]:
    """The mix's fixed trace for a window of ``seconds``: (gap before,
    prompt tokens, decoded tokens) per request."""
    rate = float(mix["rate_per_s"])
    count = max(1, round(rate * seconds))
    rng = np.random.default_rng(int(mix["trace_seed"]))
    prompts = _dealt(sizes(mix["prompt"]), count, rng)
    outs = _dealt(sizes(mix["output"]), count, rng)
    gaps = np.array([-math.log(1.0 - (i + 0.5) / count)
                     for i in range(count)])
    gaps *= seconds / gaps.sum()
    gaps = gaps[rng.permutation(count)]
    return [(float(gaps[i]), prompts[i], outs[i]) for i in range(count)]


def schedule(mix: dict, seed: int, vocab: int, seconds: float,
             prefix: str = "r") -> List[Request]:
    """The seed's requests, in arrival order, for a window of
    ``seconds``."""
    reqs = trace(mix, seconds)
    r_start, r_ids = (np.random.default_rng(s) for s in
                      np.random.SeedSequence(seed_words(seed)).spawn(2))
    start = int(r_start.integers(len(reqs)))
    out, t = [], 0.0
    for i in range(n_requests(mix, seconds)):
        gap, n_prompt, n_out = reqs[(start + i) % len(reqs)]
        t += gap
        out.append(Request(f"{prefix}{i}", t,
                           [int(x) for x in r_ids.integers(1, vocab,
                                                           size=n_prompt)],
                           n_out))
    return out
