"""Alg. 1 — genetic submodel search, latency-bounded, predictor-filtered.

Submodels are generated by a genetic algorithm in the family's spec space
(the paper CNN's depth × width grid, or the transformer/SSM zoo's
(d_ff, experts, SSD heads, depth-gate) genes), then filtered through the
search helper = online accuracy predictor (Alg. 2) + offline latency LUT.
Per worker k we keep the submodel maximising predicted accuracy subject
to ``g(ω, p_k) < l_k``. All workers' GAs run in lockstep, so each
generation scores the whole cohort's candidates in one predictor call.

Family-agnostic: mutation, crossover, and random sampling are the
``ElasticFamily`` spec-space surface; this module never touches a
concrete config class or spec type.
"""
from __future__ import annotations

import dataclasses
import random
from typing import List, Optional, Sequence

import numpy as np

from repro import obs
from repro.core.elastic import family_for
from repro.core.latency import LatencyTable
from repro.core.predictor import AccuracyPredictor


@dataclasses.dataclass
class SearchConfig:
    population: int = 24
    generations: int = 8         # the paper's S search iterations
    mutate_prob: float = 0.3
    crossover_prob: float = 0.5
    elite: int = 4


def random_spec(cfg, rng: random.Random):
    """Random feasible spec for any family config (back-compat shim)."""
    return family_for(cfg).random_spec(rng)


def search_submodel(cfg, predictor: AccuracyPredictor,
                    latency: LatencyTable, *, device: str, quality: int,
                    latency_bound: float,
                    search_cfg: SearchConfig = SearchConfig(),
                    seed: int = 0):
    """GA for one worker (Alg. 1 inner loop); ``cfg`` may be any family
    config or an ElasticFamily instance."""
    return _lockstep(family_for(cfg), predictor, latency, [device],
                     [quality], [latency_bound], [seed], search_cfg)[0]


def search_all_workers(cfg, predictor: AccuracyPredictor,
                       latency: LatencyTable, *, devices: Sequence[str],
                       qualities: Sequence[int],
                       latency_bounds: Sequence[float],
                       search_cfg: SearchConfig = SearchConfig(),
                       seed: int = 0) -> List:
    """Alg. 1 outer loop: one submodel per worker."""
    seeds = [seed * 977 + k for k in range(len(devices))]
    return _lockstep(family_for(cfg), predictor, latency, devices,
                     qualities, latency_bounds, seeds, search_cfg)


def _lockstep(fam, predictor: AccuracyPredictor, latency: LatencyTable,
              devices: Sequence[str], qualities: Sequence[int],
              latency_bounds: Sequence[float], seeds: Sequence[int],
              search_cfg: SearchConfig) -> List:
    """K workers' GAs advanced one generation at a time.

    Each worker keeps its own RNG, population, elites and best; only the
    scoring is shared: every worker's feasible candidates of a generation
    are scored in one predictor call, padded to K x population rows so a
    cohort size compiles one shape. The predictor draws nothing from the
    RNGs, so each worker makes the same draws, in the same order, as a GA
    run on its own, and keeps the same best."""
    n_pop = search_cfg.population
    rngs = [random.Random(s) for s in seeds]
    pops = [[fam.random_spec(rng) for _ in range(n_pop)] for rng in rngs]
    best: List = [None] * len(rngs)
    best_acc = [-1.0] * len(rngs)

    for gen in range(search_cfg.generations):
        with obs.span("cfl.search.generation"):
            feasible = [[s for s in pop if latency.lookup(s, d) < lb]
                        for pop, d, lb in zip(pops, devices,
                                              latency_bounds)]
            rows = [s for f in feasible for s in f]
            if rows:
                scores = predictor.predict_rows(
                    rows, [q for f, q in zip(feasible, qualities)
                           for _ in f], pad_to=len(rngs) * n_pop)
            at = 0
            for k, (rng, pop, feas) in enumerate(zip(rngs, pops, feasible)):
                if feas:
                    accs = scores[at:at + len(feas)]
                    at += len(feas)
                    order = np.argsort(-accs)
                    if accs[order[0]] > best_acc[k]:
                        best_acc[k] = float(accs[order[0]])
                        best[k] = feas[order[0]]
                    elites = [feas[i] for i in order[:search_cfg.elite]]
                else:
                    elites = []
                if gen == search_cfg.generations - 1:
                    continue        # a last brood would never be scored
                nxt = list(elites)
                while len(nxt) < n_pop:
                    if elites and rng.random() < search_cfg.crossover_prob:
                        child = fam.crossover(rng.choice(elites),
                                              rng.choice(pop), rng)
                    else:
                        child = fam.random_spec(rng)
                    nxt.append(fam.mutate(child, rng,
                                          search_cfg.mutate_prob))
                pops[k] = nxt

    # nothing feasible: fall back to the smallest submodel
    return [fam.minimal_spec() if b is None else b for b in best]
