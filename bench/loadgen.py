"""Open-loop load generator: a traffic file's parameters -> arrivals.

A traffic mix is data (``bench/traffic/<mix>.json``); this is the one
generator that reads it. Arrivals are Poisson at ``rate_rps`` over three
stretches: the warm-up (``warmup_s``, so the queue is at steady state
when timing starts), the measured window and the drain (``drain_s``,
arrivals go on while the window's requests finish). Each stretch gets
its expected number of arrivals, placed as a Poisson process with that
count places them: uniformly at random, from the run's seed.

Every seed sees the same work in the window, in another order:

* the *catalog*, ``catalog.requests`` (prompt, output) length pairs, is
  drawn once from the mix's clipped lognormals with ``catalog.seed``;
* the window's arrivals take the catalog's first entries, one each, in an
  order drawn from the run's seed; the warm-up and drain stretches cycle
  through the same entries, each cycle in an order of its own;
* prompt token ids are drawn from the run's seed, so no two requests share
  a document.

Fixing the catalog fixes the set of program shapes a run can reach
(warm-up compiles exactly those), and keeps runs of different seeds as
alike in work as two runs of one seed.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float        # seconds after the traffic starts
    prompt_len: int
    output_len: int     # tokens the client reads before it stops
    catalog_idx: int


def seed_sequence(seed: int, *salt: int) -> np.random.SeedSequence:
    """Any whole number, 64-bit and beyond, keys its own stream."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.SeedSequence([seed, *salt])


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def catalog(traffic: dict) -> List[tuple]:
    """The mix's fixed (prompt_len, output_len) pairs."""
    cat = traffic["catalog"]
    rng = np.random.default_rng(seed_sequence(cat["seed"], 1))
    n = cat["requests"]
    return list(zip(_lengths(rng, traffic["prompt_tokens"], n).tolist(),
                    _lengths(rng, traffic["output_tokens"], n).tolist()))


def horizon_s(traffic: dict, seconds: float) -> float:
    """How long arrivals keep coming: warm-up, window and drain."""
    return traffic["warmup_s"] + seconds + traffic["drain_s"]


def window_count(traffic: dict, seconds: float) -> int:
    """Arrivals in the measured window: its expected number."""
    return int(round(float(traffic["rate_rps"]) * seconds))


def schedule(traffic: dict, seed: int, seconds: float) -> List[Arrival]:
    """Arrivals for one run, ordered by due time."""
    if traffic["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrival']!r}")
    n = window_count(traffic, seconds)
    cat = catalog(traffic)
    if not 0 < n <= len(cat):
        raise ValueError(f"the window holds {n} arrivals, the catalog "
                         f"{len(cat)}")
    rate = float(traffic["rate_rps"])
    w0, w1 = traffic["warmup_s"], traffic["warmup_s"] + seconds
    rng = np.random.default_rng(seed_sequence(seed, 3))
    out: List[Arrival] = []
    for lo, hi in ((0.0, w0), (w0, w1), (w1, horizon_s(traffic, seconds))):
        k = int(round(rate * (hi - lo)))
        times = np.sort(rng.uniform(lo, hi, size=k))
        # the catalog's first n entries, each cycle in its own order: the
        # window takes exactly one cycle
        idx = np.concatenate([rng.permutation(n)
                              for _ in range(-(-k // n))])[:k]
        out += [Arrival(float(t), cat[i][0], cat[i][1], int(i))
                for t, i in zip(times, idx)]
    return out


def prompts(arrivals: List[Arrival], vocab: int, seed: int):
    """One token-id array per arrival, from the run's seed."""
    rng = np.random.default_rng(seed_sequence(seed, 4))
    return [rng.integers(0, vocab, size=a.prompt_len, dtype=np.int32)
            for a in arrivals]
