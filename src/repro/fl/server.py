"""CFL server (Alg. 4): submodel sampling -> local training -> alignment +
aggregation -> search-helper update, with per-round latency/fairness
accounting from the device profiles.

Family-agnostic: the server consumes only the ``ElasticFamily`` protocol
(spec-space surface for Alg. 1–2, mask algebra for the batched engine, the
extract/pad reference for the sequential loop), so one ``CFLServer`` runs
the paper CNN and every transformer/SSM zoo parent alike.

Two round engines share the same algorithm:

* **batched** (default) — every client trains in parent coordinates with a
  per-client mask; one jitted vmap/scan program covers the whole cohort
  regardless of spec diversity (fl.engine.BatchedRoundEngine).
* **sequential** — the extract → per-client jit → pad loop
  (fl.engine.SequentialFamilyTrainer), kept for A/B verification (one
  compile per distinct submodel config).
"""
from __future__ import annotations

import dataclasses
import random
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from repro import obs
from repro.core.elastic import ElasticFamily, family_for
from repro.core.fairness import accuracy_fairness, round_time_fairness
from repro.core.latency import LatencyTable
from repro.core.predictor import AccuracyPredictor
from repro.core.search import SearchConfig, search_all_workers
from repro.fl.client import ClientInfo
from repro.fl.engine import BatchedRoundEngine, SequentialFamilyTrainer
from repro.fl.selection import (FleetTracker, Selection, SelectionPolicy,
                                predict_full_round_times)


@dataclasses.dataclass
class CFLConfig:
    n_workers: int = 8
    local_epochs: int = 1
    batch_size: int = 32
    lr: float = 0.05
    momentum: float = 0.9
    search: SearchConfig = dataclasses.field(default_factory=SearchConfig)
    coverage_norm: bool = False     # beyond-paper aggregation variant
    # l_k = frac * min(own, fleet-median) full-model step latency; >1 lets
    # devices at/below the median train the full parent model.
    latency_bound_frac: float = 1.05
    batched_rounds: bool = True     # parent-space cohort engine vs seq loop
    # shard the engine's stacked client axis over this many devices
    # (sharding.cohort; clamped to a divisor of the cohort / device count)
    cohort_shards: int = 1
    # route the batched engine's masked compute through tile-skipping
    # kernels (kernels.dispatch): False = dense masked XLA; True = 'auto'
    # backend (Pallas-TPU on TPU hosts, Pallas-interpret elsewhere); or an
    # explicit backend name ('tpu' | 'interpret' | 'xla')
    elastic_kernels: Union[bool, str] = False
    # client-selection policy for partial-participation rounds
    # (fl.selection): 'full' (every client, the paper's regime and the
    # default) | 'uniform' | 'fairness' | 'latency', or a SelectionPolicy
    # instance for custom fractions/knobs
    selection: Union[None, str, SelectionPolicy] = "full"
    # round scheduling (fl.runtime): 'sync' = the paper's barrier rounds;
    # 'async' = event-driven buffered rounds (FedBuff-style) driven by the
    # simulated latency clock
    mode: str = "sync"
    # double-buffered host pipeline (fl.engine prefetch ring): while round
    # r's fused train+eval runs on device, the host packs + H2D-stages
    # round r+1's cohort, keyed off the policy's already-drawn next
    # selection. Value-validated at consume time, so overlap is bit-exact
    # vs eager — a stale staged cohort falls back to eager packing.
    overlap: bool = False
    # how many future cohorts the prefetch ring may hold (>= 1); only
    # meaningful with overlap=True
    prefetch_depth: int = 1
    # async buffer size B: apply the server step whenever B deltas have
    # arrived; None = the dispatch cohort size (i.e. the sync barrier,
    # which with staleness_decay=0 reproduces sync numerics exactly)
    async_buffer: Optional[int] = None
    # staleness discount exponent a in (1+s)^-a for async deltas trained
    # against an s-versions-old snapshot; 0.5 = FedBuff's 1/sqrt(1+s),
    # 0 disables discounting
    staleness_decay: float = 0.5
    # cohort RNG derivation: 'seedseq' (SeedSequence spawn keys,
    # collision-free across nearby seeds) | 'legacy' (the pre-runtime
    # modular mixing, kept so recorded benches stay reproducible)
    selection_rng: str = "seedseq"
    # deterministic fault injection (fl.faults): None disables; a
    # FaultPlan / dict / "drop=0.2,corrupt=0.05" shorthand enables the
    # chaos harness in both modes (resolve_fault_plan coerces)
    faults: object = None
    # async quorum: the server step fires when ceil(quorum_frac × cohort)
    # deltas have arrived (async_buffer, when set, overrides); 1.0 is the
    # sync barrier. Sync mode sheds stragglers via deadline_factor
    # instead (a barrier round has no partial-wait semantics).
    quorum_frac: float = 1.0
    # per-dispatch time budget as a multiple of the cohort's median
    # predicted round time; slots not arrived by then are failed
    # (miss + retry). None = no deadline, except when faults are on
    # (defaults to 4× so dropped clients fail in bounded sim-time)
    deadline_factor: Optional[float] = None
    # failed clients re-enqueue with exponential backoff
    # (retry_backoff × 2^attempt sim-seconds), up to max_retries
    # consecutive failures, then they give up until re-selected
    max_retries: int = 2
    retry_backoff: float = 0.5
    # quarantine gate: reject deltas with non-finite entries or norm >
    # norm_clip_factor × the cohort's median finite norm (<= 0 keeps the
    # finite check only). Active when faults are on or
    # validate_deltas=True.
    norm_clip_factor: float = 6.0
    validate_deltas: bool = False
    # round-granular checkpointing (checkpoint.fleet): save a resumable
    # snapshot every N applied server steps into checkpoint_dir
    checkpoint_every: Optional[int] = None
    checkpoint_dir: str = "checkpoints/fleet"
    seed: int = 0


class CFLServer:
    """One CFL control plane for any elastic family. ``cfg`` may be a
    family config (CNNConfig / zoo ModelConfig) or an ElasticFamily
    instance — existing CNN call sites work unchanged."""

    def __init__(self, cfg, params, clients: List[ClientInfo],
                 client_data: List[Dict], test_data: List[Dict],
                 fl_cfg: CFLConfig):
        self.family: ElasticFamily = family_for(cfg)
        self.cfg = self.family.cfg
        self.params = params
        self.clients = clients
        self.client_data = client_data
        self.test_data = test_data
        self.fl = fl_cfg
        self.predictor = AccuracyPredictor(self.family, seed=fl_cfg.seed)
        self.latency = LatencyTable(self.family,
                                    batch_size=fl_cfg.batch_size)
        self.tracker = FleetTracker(
            clients, fl_cfg.selection, seed=fl_cfg.seed,
            predicted_times_fn=self._predict_round_times,
            rng_mode=getattr(fl_cfg, "selection_rng", "seedseq"))
        self.round_idx = 0
        self.history: List[Dict] = []
        self._runtime = None            # built lazily on first async round
        self._sim_clock = 0.0
        if fl_cfg.batched_rounds:
            self.engine = BatchedRoundEngine(
                self.family, lr=fl_cfg.lr, momentum=fl_cfg.momentum,
                cohort_shards=fl_cfg.cohort_shards,
                elastic_kernels=fl_cfg.elastic_kernels)
            self._seq = None
            # staged cohorts drawn under an old policy/fleet must never
            # be consumed: any tracker invalidation flushes the ring
            self.tracker.add_invalidate_hook(
                lambda: self.engine.flush_prefetch("fleet-invalidate"))
            if getattr(fl_cfg, "overlap", False):
                self.engine.enable_prefetch(
                    getattr(fl_cfg, "prefetch_depth", 1))
        else:
            self.engine = None
            self._seq = SequentialFamilyTrainer(
                self.family, lr=fl_cfg.lr, momentum=fl_cfg.momentum)

    # ------------------------------------------------------------------
    def set_selection(self, selection) -> None:
        """Swap the client-selection policy ('full' | 'uniform' |
        'fairness' | 'latency' or a SelectionPolicy instance) for the
        rounds that follow — the engine's compiled programs survive the
        swap as long as the padded cohort size does. Any cohort the
        prefetch ring staged under the old policy is flushed (via the
        tracker's invalidate hook)."""
        self.tracker.set_policy(selection)

    def set_mode(self, mode: str) -> None:
        """Switch round scheduling for the rounds that follow: 'sync'
        (barrier rounds) | 'async' (event-driven buffered rounds,
        fl.runtime). Switching to sync with deltas still in flight
        drains the runtime first — remaining completions are aggregated
        (each a server step, recorded in ``history``) before the first
        sync round, so no arrived update is dropped and no client stays
        flagged pending. Staged prefetch state is flushed either way:
        the two modes predict different next cohorts."""
        if mode not in ("sync", "async"):
            raise ValueError(f"mode must be 'sync' or 'async', "
                             f"got {mode!r}")
        if mode == "sync" and self._runtime is not None:
            self._runtime.drain()
        if self.engine is not None:
            self.engine.flush_prefetch("set_mode")
        self.fl.mode = mode

    def set_overlap(self, overlap: bool) -> None:
        """Toggle the double-buffered host pipeline for the rounds that
        follow (``CFLConfig.overlap`` / ``prefetch_depth``). Disabling
        flushes whatever is staged; numerics are identical either way."""
        if self.engine is None:
            if overlap:
                raise ValueError("overlap requires the batched engine "
                                 "(batched_rounds=True)")
            return
        self.fl.overlap = bool(overlap)
        self.engine.enable_prefetch(
            getattr(self.fl, "prefetch_depth", 1) if overlap else 0)

    @property
    def runtime(self):
        """The event-driven fleet runtime (fl.runtime.FleetRuntime),
        built on first use; async rounds are driven through it."""
        if self._runtime is None:
            from repro.fl.runtime import FleetRuntime
            self._runtime = FleetRuntime(
                self, buffer_size=getattr(self.fl, "async_buffer", None),
                staleness_decay=getattr(self.fl, "staleness_decay", 0.5))
        return self._runtime

    def _predict_round_times(self) -> List[float]:
        return predict_full_round_times(
            self.family, self.clients, self.latency,
            batch_size=self.fl.batch_size, epochs=self.fl.local_epochs)

    def sample_submodels(self, client_ids: Optional[Sequence[int]] = None
                         ) -> List:
        """Alg. 1 + helper filtering; round 0 uses random feasible specs
        (predictor untrained). ``client_ids`` restricts the search to a
        selected cohort (partial participation) — per-client randomness is
        keyed by fleet id, so a client's round-0 spec does not depend on
        who else was selected."""
        ids = list(range(len(self.clients))) if client_ids is None \
            else [int(i) for i in client_ids]
        cohort = [self.clients[i] for i in ids]
        if self.round_idx == 0:
            fallback = self.family.minimal_spec()
            specs = []
            for i, c in zip(ids, cohort):
                rng = random.Random(self.fl.seed * 131 + i)
                cand = [self.family.random_spec(rng) for _ in range(32)]
                feas = [s for s in cand
                        if self.latency.lookup(s, c.device) < c.latency_bound]
                # deterministic fallback: the minimal spec is the cheapest
                # expressible submodel, so if even it is infeasible nothing
                # else would be either — take it and let the timing model
                # surface the violation.
                specs.append(feas[0] if feas else fallback)
            return specs
        return search_all_workers(
            self.family, self.predictor, self.latency,
            devices=[c.device for c in cohort],
            qualities=[c.quality for c in cohort],
            latency_bounds=[c.latency_bound for c in cohort],
            search_cfg=self.fl.search,
            seed=self.fl.seed + self.round_idx)

    # ------------------------------------------------------------------
    def _client_seed(self, k: int, round_idx: Optional[int] = None) -> int:
        r = self.round_idx if round_idx is None else int(round_idx)
        return self.fl.seed * 7 + r * 131 + k

    def _stage_next_round(self, round_idx: Optional[int] = None) -> None:
        """Prefetch hook (the double-buffering seam): called by the
        engine after round r's fused program is dispatched but before
        its results are materialised — draw round r+1's cohort from the
        derivational selection RNG (side-effect-free for any round) and
        stage its packs/H2D while r still runs on device. Only fires for
        state-independent policies (a fairness draw depends on this
        round's ``record``, so an early draw would never match); the
        staged entry is value-validated at consume time either way, so
        a wrong prediction costs a re-pack, never numerics. Mirrors the
        exact ``train_cohort`` call ``run_round`` will make, including
        the faults path's always-subset participation."""
        engine = self.engine
        if engine is None or not engine.prefetch_enabled:
            return
        if getattr(self.tracker.policy, "state_dependent", True):
            return
        r = (self.round_idx + 1) if round_idx is None else int(round_idx)
        sel = self.tracker.select(r)
        faulty = getattr(self.fl, "faults", None) is not None
        if not faulty and self.tracker.is_full:
            seeds = [self._client_seed(k, r)
                     for k in range(len(self.clients))]
            participation = None
        else:
            seeds = [self._client_seed(int(i), r) for i in sel.idx]
            participation = sel
        engine.stage_cohort(
            r, self.client_data, batch_size=self.fl.batch_size,
            epochs=self.fl.local_epochs, seeds=seeds,
            eval_datasets=self.test_data, participation=participation)

    def _simulated_times(self, specs, n_steps,
                         client_ids: Optional[Sequence[int]] = None
                         ) -> List[float]:
        """Simulated wall-clock per client: compute + update exchange."""
        clients = self.clients if client_ids is None \
            else [self.clients[int(i)] for i in client_ids]
        times = []
        with obs.span("cfl.bookkeep"):
            for client, spec, n in zip(clients, specs, n_steps):
                prof = self.latency.fleet[client.device]
                t = n * self.latency.lookup(spec, client.device) + \
                    prof.comm_latency(2 * self.family.param_bytes(spec))
                times.append(float(t))
        return times

    def cohort_specs(self, participants: Optional[Sequence[int]] = None
                     ) -> List:
        """Runtime hook: specs for a dispatch cohort (None = full fleet).
        CFL's policy is the Alg. 1 search (``sample_submodels``)."""
        return self.sample_submodels(participants)

    def post_aggregate(self, specs, participants: Sequence[int],
                       accs: Sequence[float]) -> Dict:
        """Runtime hook, called once per applied server step: the
        search-helper update (Alg. 2) over the deltas that were just
        aggregated — participants only: absentees reported nothing."""
        with obs.span("cfl.post_aggregate"):
            with obs.span("predictor.add"):
                self.predictor.add_profiles(
                    [(spec, self.clients[i].quality, acc)
                     for spec, i, acc in zip(specs, participants, accs)])
            with obs.span("predictor.train"):
                mae = self.predictor.train_round(epochs=4)
            return {"specs": [self.family.genes(s) for s in specs],
                    "predictor_mae": mae}

    def run_round(self) -> Dict:
        if getattr(self.fl, "mode", "sync") == "async":
            return self.runtime.run_until_aggregate()
        with obs.span("cfl.round", round=self.round_idx):
            return self._sync_round()

    def _sync_round(self) -> Dict:
        with obs.span("cfl.select"):
            sel = self.tracker.select(self.round_idx)
            participants = [int(i) for i in sel.participants]
        with obs.span("cfl.search"):
            specs = self.sample_submodels(
                None if self.tracker.is_full else participants)
        stats = None
        faulty = getattr(self.fl, "faults", None) is not None
        if faulty:
            from repro.fl.faults import faulty_sync_round
            accs, times, participants, specs_kept, stats = \
                faulty_sync_round(self, specs, sel)
            extras = self.post_aggregate(specs_kept, participants, accs) \
                if participants else {}
        else:
            if self.fl.batched_rounds:
                accs, times = self._train_round_batched(specs, sel)
            else:
                accs, times = self._train_round_sequential(specs, sel)
            extras = self.post_aggregate(specs, participants, accs)
        with obs.span("cfl.bookkeep"):
            if not faulty:
                self.tracker.record(participants, accs)
            rec = {
                "round": self.round_idx,
                "participants": participants,
                "selection": self.tracker.policy.name,
                "accs": accs,
                "fairness": accuracy_fairness(accs if accs
                                              else [float("nan")]),
                "timing": round_time_fairness(times if times else [0.0]),
            }
            rec.update(extras)
            rec.update(self._sync_clock_columns(times))
            if stats is not None:
                rec.update(stats)
            self.history.append(rec)
            self.round_idx += 1
        return rec

    def _sync_clock_columns(self, times: Sequence[float]) -> Dict:
        """Sync rows carry the same scheduling columns as async ones:
        staleness is 0 by construction, aggregate_lag is the barrier wait
        (how long each delta sat before the straggler arrived), and
        sim_clock accumulates the barrier round times. Failure stats are
        the honest zeros for a fault-free barrier round (the fault path
        overrides them)."""
        barrier = max(times) if times else 0.0
        self._sim_clock += barrier
        return {"staleness": 0.0,
                "aggregate_lag": float(np.mean([barrier - t
                                                for t in times]))
                if times else 0.0,
                "sim_clock": self._sim_clock,
                "mode": "sync",
                "dropped": 0, "retried": 0, "quarantined": 0,
                "quorum_waited_ms": barrier * 1e3}

    # ------------------------------------------------------------------
    def _train_round_batched(self, specs, sel: Optional[Selection] = None):
        """Whole cohort's local train + eval in one compiled program, then
        one fused aggregate+apply program (fl.engine). Full participation
        (or no selection, for direct callers) takes the legacy path —
        bit-identical to pre-selection rounds; otherwise the engine runs
        the fixed-size padded subset."""
        if sel is None or self.tracker.is_full:
            seeds = [self._client_seed(k) for k in range(len(self.clients))]
            self.params, accs, n_steps = self.engine.run_fl_round(
                self.params, specs, self.client_data, self.test_data,
                [c.n_samples for c in self.clients],
                batch_size=self.fl.batch_size, epochs=self.fl.local_epochs,
                seeds=seeds, coverage_norm=self.fl.coverage_norm,
                prefetch_hook=self._stage_next_round)
            return accs, self._simulated_times(specs, n_steps)
        # pad per-slot specs with a repeat of slot 0 (weight 0, no steps —
        # only its mask-table entry is reused, never its update)
        m = len(sel.idx)
        specs_pad = list(specs) + [specs[0]] * (m - len(specs))
        seeds = [self._client_seed(int(i)) for i in sel.idx]
        self.params, accs_pad, n_steps_pad = self.engine.run_fl_round(
            self.params, specs_pad, self.client_data, self.test_data,
            None, batch_size=self.fl.batch_size,
            epochs=self.fl.local_epochs, seeds=seeds,
            coverage_norm=self.fl.coverage_norm, participation=sel,
            prefetch_hook=self._stage_next_round)
        accs = sel.take_valid(accs_pad)
        n_steps = [int(n) for n in sel.take_valid(n_steps_pad)]
        participants = [int(i) for i in sel.participants]
        return accs, self._simulated_times(specs, n_steps, participants)

    def _train_round_sequential(self, specs,
                                sel: Optional[Selection] = None):
        """Per-client extract → train → pad loop (A/B reference) via the
        family-agnostic SequentialFamilyTrainer; a partial cohort is just
        the participant sub-lists with the selection's aggregation
        weights."""
        if sel is None or self.tracker.is_full:
            ids = list(range(len(self.clients)))
            sizes = [c.n_samples for c in self.clients]
        else:
            ids = [int(i) for i in sel.participants]
            sizes = [float(w) for w, v in zip(sel.weights, sel.valid)
                     if v > 0]
        seeds = [self._client_seed(i) for i in ids]
        self.params, accs, n_steps = self._seq.run_fl_round(
            self.params, specs, [self.client_data[i] for i in ids],
            [self.test_data[i] for i in ids], sizes,
            batch_size=self.fl.batch_size, epochs=self.fl.local_epochs,
            seeds=seeds, coverage_norm=self.fl.coverage_norm)
        return accs, self._simulated_times(
            specs, n_steps, None if self.tracker.is_full else ids)

    def global_accuracy(self, data: Dict) -> float:
        return self.family.evaluate(self.params, data)
