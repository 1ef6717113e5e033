"""Serving traffic from a mix's parameters and the seed.

Lengths and arrival gaps are stratified: for n draws the generator takes
the distribution's quantiles at (i + 1/2) / n, in an order drawn from the
mix's own ``schedule_seed``. Every run of a mix therefore offers the same
schedule of sizes and arrivals; ``--seed`` draws what the requests say
(prompt tokens, uniform over the vocabulary) and who sends them (tenants,
with a Zipf popularity). Queueing at a fixed rate depends on the order of
arrivals (a burst of long requests fills every slot), so a schedule that
changed with the seed would change the work a run holds.

Distributions, by ``kind``:
  lognormal: median, sigma, min, max (clipped)
  uniform:   min, max (integers, inclusive)
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np

from chipbench.harness import seeds


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """The n stratified values of ``dist`` (sorted)."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["kind"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        v = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
        v = np.clip(np.rint(v), dist["min"], dist["max"])
    elif kind == "uniform":
        v = np.floor(dist["min"] + u * (dist["max"] - dist["min"] + 1))
    elif kind == "exponential":                 # arrival gaps, seconds
        v = -np.log1p(-u) / float(dist["rate"])
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return v


@dataclasses.dataclass
class Draw:
    uid: int
    due: float            # seconds after the window opens
    prompt_len: int
    output_len: int
    tenant: int
    prompt: np.ndarray


def _zipf_tenants(rng, n_tenants: int, s: float, n: int) -> np.ndarray:
    w = 1.0 / np.arange(1, n_tenants + 1) ** s
    return rng.choice(n_tenants, size=n, p=w / w.sum())


def open_loop(mix: Dict, seed: int, seconds: float, vocab: int
              ) -> List[Draw]:
    """Poisson arrivals at ``mix['rate']`` per second over the window:
    round(rate * seconds) requests whose gaps are the stratified
    exponential quantiles in the schedule's order."""
    n = max(1, int(round(float(mix["rate"]) * seconds)))
    sched = seeds.stream(int(mix["schedule_seed"]), 11)
    gaps = sched.permutation(quantiles(
        {"kind": "exponential", "rate": mix["rate"]}, n))
    due = np.cumsum(gaps) - gaps[0]
    due *= seconds / max(due[-1] + gaps[0], 1e-9)
    return _draws(mix, sched, seeds.stream(seed, 11), due, vocab)


def backlog(mix: Dict, seed: int, vocab: int) -> Iterator[Draw]:
    """An endless queue (all due at 0): rounds of ``mix['stratum']``
    stratified requests, each round in the schedule's next order."""
    sched = seeds.stream(int(mix["schedule_seed"]), 12)
    rng = seeds.stream(seed, 12)
    uid = 0
    while True:
        n = int(mix["stratum"])
        for d in _draws(mix, sched, rng, np.zeros(n), vocab, uid0=uid):
            yield d
        uid += n


def _draws(mix: Dict, sched, rng, due: np.ndarray, vocab: int,
           uid0: int = 0) -> List[Draw]:
    """Sizes in the schedule's order; tenants and tokens from ``rng``."""
    n = len(due)
    plen = sched.permutation(quantiles(mix["prompt"], n)).astype(int)
    olen = sched.permutation(quantiles(mix["output"], n)).astype(int)
    tenants = _zipf_tenants(rng, int(mix["tenants"]),
                            float(mix["zipf"]), n)
    return [Draw(uid=uid0 + i, due=float(due[i]), prompt_len=int(plen[i]),
                 output_len=int(olen[i]), tenant=int(tenants[i]),
                 prompt=rng.integers(0, vocab, int(plen[i]),
                                     dtype=np.int32))
            for i in range(n)]
