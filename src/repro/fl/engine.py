"""Batched parent-space FL round engine — family-agnostic.

The sequential round loop (extract → per-client jit → pad) compiles one
program per *distinct submodel config* and re-runs Python orchestration per
client. This engine instead trains every client in **parent coordinates**:
each client gets a 0/1 mask bundle from its ``core.elastic.ElasticFamily``
(the same prefix-channel / prefix-depth semantics as
``kernels/elastic_matmul.py``'s ``k_active`` tiles), and a single jitted
``vmap``-over-clients / ``lax.scan``-over-steps program runs the whole
cohort's local epochs — regardless of how many different specs the search
helper emits, and for the CNN parent *and* the transformer/SSM zoo alike.

Exactness contract (verified in tests/test_fl_engine.py and
tests/test_elastic_family.py): for every spec, masked parent-space
forward/backward computes the same math as the extract→train→pad path —
see ``core.elastic`` for the per-family mask algebra. Gradients are
masked, so momentum/updates on uncovered entries stay 0 and
``Δ = mask * (ω_0 − ω_E)`` equals the zero-padded submodel update.

Clients with fewer local steps than the cohort max are handled with step
validity flags (invalid steps are no-ops on the carry), partial batches
with sample validity weights — bitwise-faithful to the per-client loader.

**Partial participation** (``fl.selection``): a round may train only a
subset of the fleet. The engine keeps its shapes stable by running a
**fixed-size padded cohort** — ``run_fl_round(..., participation=sel)``
takes a ``Selection`` whose (M,) ``idx``/``valid``/``weights`` arrays
gather the selected clients out of the fleet-resident data pack on
device; padding slots carry no valid steps (their local train is an exact
no-op) and weight 0 (they drop out of the fused aggregate+apply). M and
the fleet-wide step/eval paddings are round-invariant, so the selected
subset can churn every round without adding compiled programs — the
2-programs/round invariant survives partial participation.

**Cohort sharding**: with ``cohort_shards > 1`` the stacked leading client
axis is committed to a 1-D ``cohort`` mesh (``sharding.cohort``) before
dispatch; jit propagates the layout so the whole round — local train, local
eval, and the fused aggregate+apply reduction — scales across devices with
one collective per round.

**Double-buffered prefetch** (``enable_prefetch``): while round r's fused
train+eval program runs on device, the host can already pack round r+1's
batch streams and stage its gathers/H2D — ``stage_cohort`` builds exactly
the tensors the next ``train_cohort`` call would, into a bounded ring of
:class:`StagedCohort` entries. Consumption is **value-validated**: a
staged entry is used only when the eventual call's selection triple,
seeds, batch/epoch geometry and resident-data identity all match, so the
staged tensors are bit-identical to what the eager path would have built
(jax async dispatch provides the actual wall-clock overlap; staging adds
zero compiled programs — it reuses the same pack/gather/device_put calls).
A mismatch silently falls back to eager packing and flushes the ring:
overlap can only ever cost a re-pack, never numerics. Callers flush on
policy/fleet/mode changes, drain, quorum misses, and checkpoint restore.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.aggregate import (aggregate, aggregate_coverage,
                                  apply_server_update)
# re-exported for API compatibility with the PR-1 CNN-specific engine
from repro.core.elastic import (CohortMasks, ElasticFamily, SpecLRU,
                                build_cohort_masks, family_for,
                                masked_forward)
from repro.data.loader import index_batches
from repro.optim import apply_updates, clip_by_global_norm, sgd
from repro.sharding.cohort import (cohort_axis_sharding, cohort_mesh,
                                   effective_cohort_shards, shard_cohort)


# ---------------------------------------------------------------------------
# host-side packing: data (family-agnostic — x is images or token rows)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class CohortBatches:
    x: jax.Array            # (K, N, ...) each client's data, once
    y: jax.Array            # (K, N) int32
    idx: jax.Array          # (K, S, B) int32 gather indices per step
    sample_valid: jax.Array  # (K, S, B) float32
    step_valid: jax.Array   # (K, S) bool
    n_steps: np.ndarray     # (K,) host ints (timing model)


def pack_cohort_data(datasets: Sequence[Dict[str, np.ndarray]]
                     ) -> Tuple[jax.Array, jax.Array]:
    """Stack every client's (round-invariant) data once: (K, N, ...)."""
    K = len(datasets)
    N = max(len(d["y"]) for d in datasets)
    sample_shape = datasets[0]["x"].shape[1:]
    x = np.zeros((K, N) + sample_shape, datasets[0]["x"].dtype)
    y = np.zeros((K, N), np.int32)
    for k, d in enumerate(datasets):
        n = len(d["y"])
        x[k, :n] = d["x"]
        y[k, :n] = d["y"]
    return jnp.asarray(x), jnp.asarray(y)


def n_stream_steps(n: int, batch_size: int, epochs: int) -> int:
    """Steps ``index_batches(n, batch_size, epochs=epochs)`` will yield
    (drop-remainder semantics; a dataset smaller than one batch still
    yields one partial batch per epoch). The fleet-wide max of this is the
    round-invariant step padding partial-participation packing uses."""
    per_epoch = n // batch_size if n >= batch_size else 1
    return per_epoch * epochs


def _pack_streams(lengths: Sequence[int], batch_size: int, *, epochs: int,
                  seeds: Sequence[int], n_steps_pad: Optional[int] = None):
    """Build the (K, S, B) index / validity tensors for per-client batch
    streams; ``lengths[k] == 0`` marks a padding slot (no valid steps).
    ``n_steps_pad`` pins S to a caller-chosen (fleet-wide) value so the
    packed shapes stay round-invariant under cohort churn."""
    streams = [list(index_batches(n, batch_size, seed=s, epochs=epochs))
               if n > 0 else []
               for n, s in zip(lengths, seeds)]
    K = len(streams)
    S = max(len(st) for st in streams) if n_steps_pad is None \
        else int(n_steps_pad)
    idx = np.zeros((K, S, batch_size), np.int32)
    sv = np.zeros((K, S, batch_size), np.float32)
    stv = np.zeros((K, S), bool)
    for k, stream in enumerate(streams):
        assert len(stream) <= S, (k, len(stream), S)
        for t, b_idx in enumerate(stream):
            idx[k, t, :len(b_idx)] = b_idx
            sv[k, t, :len(b_idx)] = 1.0
            stv[k, t] = True
    return (jnp.asarray(idx), jnp.asarray(sv), jnp.asarray(stv),
            np.array([len(st) for st in streams]))


def pack_cohort(datasets: Sequence[Dict[str, np.ndarray]], batch_size: int,
                *, epochs: int, seeds: Sequence[int],
                data: Optional[Tuple[jax.Array, jax.Array]] = None
                ) -> CohortBatches:
    """Pack every client's epoch-shuffled batch stream (same index stream
    as the sequential loader) into one rectangular block. Each client's
    data is resident exactly once — local epochs are an int32 index tensor
    gathered per scan step, not extra data copies — and a cached
    ``pack_cohort_data`` result can be reused across rounds (only the
    index/validity tensors depend on the round seeds)."""
    x, y = pack_cohort_data(datasets) if data is None else data
    idx, sv, stv, n_steps = _pack_streams(
        [len(d["y"]) for d in datasets], batch_size, epochs=epochs,
        seeds=seeds)
    return CohortBatches(x, y, idx, sv, stv, n_steps)


@dataclasses.dataclass
class EvalPack:
    x: jax.Array        # (K, T, ...)
    y: jax.Array        # (K, T) int32
    valid: jax.Array    # (K, T) float32


def pack_eval(datasets: Sequence[Dict[str, np.ndarray]]) -> EvalPack:
    K = len(datasets)
    T = max(len(d["y"]) for d in datasets)
    sample_shape = datasets[0]["x"].shape[1:]
    x = np.zeros((K, T) + sample_shape, datasets[0]["x"].dtype)
    y = np.zeros((K, T), np.int32)
    v = np.zeros((K, T), np.float32)
    for k, d in enumerate(datasets):
        n = len(d["y"])
        x[k, :n] = d["x"]
        y[k, :n] = d["y"]
        v[k, :n] = 1.0
    return EvalPack(jnp.asarray(x), jnp.asarray(y), jnp.asarray(v))


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class StagedCohort:
    """One prefetched cohort: host-packed + H2D-staged tensors for a round
    that has not started yet. Entries are pure functions of their key
    fields (selection triple, seeds, geometry, resident-pack identity), so
    a hit hands ``train_cohort`` bit-identical inputs and a stale
    prediction can only cost a re-pack, never numerics."""
    round_idx: int                   # staged-for round (observability/ckpt)
    batch_size: int
    epochs: int
    seeds: Tuple[int, ...]
    data_ref: object                 # strong ref: id identity can't recycle
    eval_ref: object
    has_eval: bool
    stream: Tuple                    # (idx, sv, stv) device, cohort-sharded
    n_steps: np.ndarray
    sel_idx: Optional[np.ndarray] = None      # None = full-cohort entry
    sel_valid: Optional[np.ndarray] = None
    sel_weights: Optional[np.ndarray] = None
    x: Optional[jax.Array] = None             # subset path: staged gathers
    y: Optional[jax.Array] = None
    ex: Optional[jax.Array] = None
    ey: Optional[jax.Array] = None
    ev: Optional[jax.Array] = None


@dataclasses.dataclass
class CohortResult:
    deltas: Dict            # stacked (K, ...) masked updates ω_0 − ω_E
    trained: Dict           # stacked (K, ...) locally-trained parent params
    masks: CohortMasks
    n_steps: np.ndarray
    accs: Optional[np.ndarray] = None   # fused local-eval accuracies


class BatchedRoundEngine:
    """One compiled train program + one eval program shared by every
    submodel spec in the cohort (and across rounds, while shapes hold).

    ``cfg`` may be a CNNConfig, a transformer-zoo ModelConfig, or an
    ElasticFamily instance (``core.elastic.family_for`` resolves configs).
    ``cohort_shards`` > 1 shards the stacked client axis over that many
    devices (clamped to a divisor of the cohort / available devices).

    ``elastic_kernels`` routes masked compute through the tile-skipping
    kernel path (``kernels.dispatch``): masked width / expert / head /
    channel tiles are *skipped*, not zeroed. Truthy values: True ('auto'
    backend) or a backend name. The per-client prefix scalars are
    derived inside the jitted program from the mask inputs, so the
    2-programs/round invariant holds under spec churn. The resolved op
    table is **engine-owned** (passed to ``masked_loss``/``masked_metric``
    per call, never stored on the family), so engines sharing one family
    instance each keep the path their own flag selected — the dense A/B
    baseline can never silently run the kernel path or vice versa.
    """

    def __init__(self, cfg, *, lr: float, momentum: float,
                 grad_clip: float = 5.0, cohort_shards: int = 1,
                 elastic_kernels=False):
        from repro.kernels.dispatch import kernel_dispatch
        self.family: ElasticFamily = family_for(cfg)
        # resolve_backend maps True -> 'auto'; falsy -> 'xla' (= no table)
        self._elastic_kernels = kernel_dispatch(
            elastic_kernels or "xla").table(self.family.name)
        self.cfg = self.family.cfg
        self._opt = sgd(lr, momentum=momentum)
        self._grad_clip = grad_clip
        self._train = jax.jit(jax.vmap(self._client_train))
        self._eval = jax.jit(jax.vmap(self._client_eval))
        # fused local-train + local-eval: a full CFL round is two compiled
        # programs total (this + aggregate_apply), whatever the spec mix
        self._train_eval = jax.jit(jax.vmap(self._client_train_eval))
        # bounded caches; data entries hold a strong ref to the keying
        # datasets object so its id() cannot be recycled while cached
        self._eval_cache: "OrderedDict[int, Tuple[object, EvalPack]]" = \
            OrderedDict()
        self._data_cache: "OrderedDict[int, Tuple[object, Tuple]]" = \
            OrderedDict()
        # stacked cohort masks, keyed by the spec-table genes of the mix
        self._masks_cache: "OrderedDict[Tuple, CohortMasks]" = OrderedDict()
        self._requested_shards = int(cohort_shards)
        self._cohort_meshes: Dict[int, jax.sharding.Mesh] = {}
        # double-buffered prefetch ring (enable_prefetch); 0 = disabled
        self._prefetch_depth = 0
        self._prefetch_ring: List[StagedCohort] = []
        self._prefetch_stats = {"staged": 0, "hits": 0, "misses": 0,
                                "flushes": 0}

    @property
    def kernel_path(self) -> str:
        """'tile-skipping' | 'dense-masked' — the BENCH-row label."""
        return "tile-skipping" if self._elastic_kernels else "dense-masked"

    # -- cohort sharding ---------------------------------------------------
    def cohort_sharding(self, n_clients: int):
        """NamedSharding for the stacked client axis, or None when the
        engine runs unsharded (cohort_shards == 1)."""
        if self._requested_shards <= 1:
            return None
        s = effective_cohort_shards(n_clients, self._requested_shards)
        mesh = self._cohort_meshes.get(s)
        if mesh is None:
            mesh = self._cohort_meshes.setdefault(s, cohort_mesh(s))
        return cohort_axis_sharding(mesh)

    # -- double-buffered prefetch ring -------------------------------------
    @property
    def prefetch_enabled(self) -> bool:
        return self._prefetch_depth > 0

    def enable_prefetch(self, depth: int = 1) -> None:
        """Turn the double-buffered host pipeline on: up to ``depth``
        future cohorts may be staged at once. ``depth <= 0`` disables
        and flushes whatever is staged."""
        depth = int(depth)
        if depth <= 0:
            self.flush_prefetch("disabled")
            self._prefetch_depth = 0
            return
        self._prefetch_depth = depth
        while len(self._prefetch_ring) > depth:
            self._prefetch_ring.pop(0)

    def flush_prefetch(self, reason: str = "") -> None:
        """Drop every staged cohort — the buffer refs are released (the
        'donation' side of the ring) and the next round packs eagerly.
        Called on policy/fleet/mode changes, drain, quorum misses and
        checkpoint restore; a flush can only forfeit overlap, never
        change numerics."""
        del reason      # observability hook; kept out of the stats key
        if self._prefetch_ring:
            self._prefetch_stats["flushes"] += 1
            self._prefetch_ring.clear()

    def prefetch_stats(self) -> Dict[str, int]:
        """Copy of the ring counters: staged / hits / misses / flushes."""
        return dict(self._prefetch_stats)

    def stage_cohort(self, round_idx: int, datasets: Sequence[Dict], *,
                     batch_size: int, epochs: int, seeds: Sequence[int],
                     eval_datasets: Optional[Sequence[Dict]] = None,
                     participation=None) -> None:
        """Pack + H2D-stage a *future* round's cohort while the current
        round's fused program still runs on device. Builds exactly the
        tensors the matching ``train_cohort`` call would (same
        ``_pack_streams`` / gather / ``shard_cohort`` code paths, so a
        hit is bit-identical by construction) and appends them to the
        ring. No-op unless ``enable_prefetch`` was called."""
        if not self.prefetch_enabled:
            return
        seeds = tuple(int(s) for s in seeds)
        if participation is None:
            # only the streams depend on the round; warm the resident
            # packs so first-round H2D doesn't land on the hot path
            self._cohort_data(datasets)
            if eval_datasets is not None:
                self._eval_pack(eval_datasets)
            stream, n_steps = self._full_stream(datasets, batch_size,
                                                epochs, seeds)
            entry = StagedCohort(
                round_idx=int(round_idx), batch_size=int(batch_size),
                epochs=int(epochs), seeds=seeds, data_ref=datasets,
                eval_ref=eval_datasets,
                has_eval=eval_datasets is not None, stream=stream,
                n_steps=n_steps)
        else:
            t = self._subset_tensors(datasets, participation, batch_size,
                                     epochs, seeds, eval_datasets)
            entry = StagedCohort(
                round_idx=int(round_idx), batch_size=int(batch_size),
                epochs=int(epochs), seeds=seeds, data_ref=datasets,
                eval_ref=eval_datasets,
                has_eval=eval_datasets is not None, stream=t["stream"],
                n_steps=t["n_steps"],
                sel_idx=np.array(participation.idx, copy=True),
                sel_valid=np.array(participation.valid, copy=True),
                sel_weights=np.array(participation.weights, copy=True),
                x=t["x"], y=t["y"], ex=t["ex"], ey=t["ey"], ev=t["ev"])
        self._prefetch_ring.append(entry)
        self._prefetch_stats["staged"] += 1
        while len(self._prefetch_ring) > self._prefetch_depth:
            self._prefetch_ring.pop(0)

    def _take_staged(self, datasets, eval_datasets, participation,
                     batch_size: int, epochs: int, seeds):
        """Pop the staged entry matching this exact call, if any.
        Matching is by value — selection triple, seeds, geometry, and
        resident-pack identity — so a hit cannot change what the compiled
        program sees. On a hit the entry leaves the ring (its buffers are
        donated to the round) along with anything staged before it; on a
        miss the whole ring is flushed (a wrong prediction means the
        pipeline desynced — stale tensors must not linger)."""
        if not self.prefetch_enabled or not self._prefetch_ring:
            return None
        seeds = tuple(int(s) for s in seeds)
        for pos, e in enumerate(self._prefetch_ring):
            if (e.batch_size == int(batch_size)
                    and e.epochs == int(epochs) and e.seeds == seeds
                    and e.data_ref is datasets
                    and e.has_eval == (eval_datasets is not None)
                    and (not e.has_eval or e.eval_ref is eval_datasets)
                    and self._sel_match(e, participation)):
                del self._prefetch_ring[:pos + 1]
                self._prefetch_stats["hits"] += 1
                if e.sel_idx is None:
                    return {"stream": e.stream, "n_steps": e.n_steps}
                return {"x": e.x, "y": e.y, "stream": e.stream,
                        "n_steps": e.n_steps, "ex": e.ex, "ey": e.ey,
                        "ev": e.ev}
        self._prefetch_stats["misses"] += 1
        self.flush_prefetch("stale")
        return None

    @staticmethod
    def _sel_match(e: StagedCohort, part) -> bool:
        if (e.sel_idx is None) != (part is None):
            return False
        if part is None:
            return True
        return (np.array_equal(e.sel_idx, np.asarray(part.idx))
                and np.array_equal(e.sel_valid, np.asarray(part.valid))
                and np.array_equal(e.sel_weights,
                                   np.asarray(part.weights)))

    def prefetch_snapshot(self) -> Dict:
        """Host-side ring snapshot for ``checkpoint.fleet``: each entry's
        *derivation* (round, selection triple, seeds, geometry) rather
        than its device tensors — staging is a pure function of the
        resident packs, so restore re-stages bit-exactly."""
        entries = []
        for e in self._prefetch_ring:
            entries.append({
                "round_idx": int(e.round_idx),
                "batch_size": int(e.batch_size),
                "epochs": int(e.epochs),
                "seeds": [int(s) for s in e.seeds],
                "has_eval": bool(e.has_eval),
                "sel": None if e.sel_idx is None else (
                    np.asarray(e.sel_idx), np.asarray(e.sel_valid),
                    np.asarray(e.sel_weights)),
            })
        return {"depth": int(self._prefetch_depth), "entries": entries,
                "stats": dict(self._prefetch_stats)}

    def prefetch_restore(self, snap: Dict, datasets,
                         eval_datasets=None) -> None:
        """Rebuild the staged ring from :meth:`prefetch_snapshot` against
        the (restored) resident packs."""
        from repro.fl.selection import Selection
        self.flush_prefetch("restore")
        self._prefetch_depth = int(snap.get("depth", self._prefetch_depth))
        for es in snap.get("entries", []):
            sel = es.get("sel")
            part = None if sel is None else Selection(
                np.asarray(sel[0]), np.asarray(sel[1]),
                np.asarray(sel[2]))
            self.stage_cohort(
                es["round_idx"], datasets, batch_size=es["batch_size"],
                epochs=es["epochs"], seeds=es["seeds"],
                eval_datasets=eval_datasets if es.get("has_eval")
                else None,
                participation=part)
        if snap.get("stats"):
            self._prefetch_stats = {k: int(v)
                                    for k, v in snap["stats"].items()}

    def _full_stream(self, datasets, batch_size: int, epochs: int, seeds):
        """The full-cohort stream tensors (the only round-dependent part
        of ``pack_cohort`` — x/y come from the cached resident pack)."""
        idx, sv, stv, n_steps = _pack_streams(
            [len(d["y"]) for d in datasets], batch_size, epochs=epochs,
            seeds=seeds)
        sh = self.cohort_sharding(len(datasets))
        return shard_cohort((idx, sv, stv), sh), n_steps

    def _subset_tensors(self, datasets, part, batch_size: int, epochs: int,
                        seeds, eval_datasets) -> Dict:
        """Everything ``_train_cohort_subset`` feeds the compiled program
        beyond params/masks: the device gathers of the selected clients'
        packs and the fleet-padded stream tensors. Shared by the eager
        path and ``stage_cohort`` so staged == eager bit-for-bit."""
        m = len(part.idx)
        sh = self.cohort_sharding(m)
        gidx = jnp.asarray(np.asarray(part.idx, np.int32))
        x_full, y_full = self._cohort_data(datasets)
        x = shard_cohort(jnp.take(x_full, gidx, 0), sh)
        y = shard_cohort(jnp.take(y_full, gidx, 0), sh)
        # step padding is the *fleet-wide* max so S never depends on which
        # subset was selected (shape churn would mean program churn)
        s_fleet = max(n_stream_steps(len(d["y"]), batch_size, epochs)
                      for d in datasets)
        lengths = [len(datasets[i]["y"]) if v > 0 else 0
                   for i, v in zip(part.idx, part.valid)]
        idx, sv, stv, n_steps = _pack_streams(
            lengths, batch_size, epochs=epochs, seeds=seeds,
            n_steps_pad=s_fleet)
        out = {"x": x, "y": y, "stream": shard_cohort((idx, sv, stv), sh),
               "n_steps": n_steps, "ex": None, "ey": None, "ev": None}
        if eval_datasets is not None:
            pack = self._eval_pack(eval_datasets)
            valid_col = jnp.asarray(
                np.asarray(part.valid, np.float32))[:, None]
            out["ex"] = shard_cohort(jnp.take(pack.x, gidx, 0), sh)
            out["ey"] = shard_cohort(jnp.take(pack.y, gidx, 0), sh)
            out["ev"] = shard_cohort(
                jnp.take(pack.valid, gidx, 0) * valid_col, sh)
        return out

    # -- single-client programs (vmapped over the cohort) ------------------
    def _client_train(self, theta0, pmask, fwd, data_x, data_y, idx, svalid,
                      stvalid):
        opt_state = self._opt.init(theta0)

        def step(carry, inp):
            p, ostate = carry
            ix, sv, valid = inp
            x, yb = data_x[ix], data_y[ix]

            def loss_fn(pp):
                return self.family.masked_loss(
                    pp, fwd, x, yb, sv, kernels=self._elastic_kernels)

            grad = jax.grad(loss_fn)(p)
            grad = jax.tree.map(lambda gg, mm: gg * mm, grad, pmask)
            grad, _ = clip_by_global_norm(grad, self._grad_clip)
            upd, ostate2 = self._opt.update(grad, ostate, p)
            new = (apply_updates(p, upd), ostate2)
            # padded steps leave the carry untouched
            carry2 = jax.tree.map(lambda a, b: jnp.where(valid, a, b),
                                  new, carry)
            return carry2, ()

        (theta_e, _), _ = jax.lax.scan(step, (theta0, opt_state),
                                       (idx, svalid, stvalid))
        delta = jax.tree.map(lambda a, b, mm: (a - b) * mm, theta0, theta_e,
                             pmask)
        return delta, theta_e

    def _client_eval(self, params, fwd, x, y, valid):
        return self.family.masked_metric(params, fwd, x, y, valid,
                                         kernels=self._elastic_kernels)

    def _client_train_eval(self, theta0, pmask, fwd, data_x, data_y, idx,
                           svalid, stvalid, ex, ey, evalid):
        delta, theta_e = self._client_train(
            theta0, pmask, fwd, data_x, data_y, idx, svalid, stvalid)
        acc = self._client_eval(theta_e, fwd, ex, ey, evalid)
        return delta, theta_e, acc

    # -- cohort API --------------------------------------------------------
    def broadcast_params(self, params, n_clients: int):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_clients,) + a.shape), params)

    def train_cohort(self, theta0_stacked, specs: Sequence,
                     datasets: Sequence[Dict], *, batch_size: int,
                     epochs: int, seeds: Sequence[int],
                     eval_datasets: Optional[Sequence[Dict]] = None,
                     participation=None, prefetch_hook=None
                     ) -> CohortResult:
        """Run every client's local epochs (and, when eval_datasets is
        given, its local test pass) as one compiled program.

        With ``participation`` (an ``fl.selection.Selection``) the cohort
        is the fixed-size padded subset it names: ``specs`` and ``seeds``
        are per-slot (length M == len(participation.idx)), ``datasets`` /
        ``eval_datasets`` stay the full fleet lists (their resident packs
        are cached across rounds; the subset is gathered on device), and
        padding slots train zero steps. Step padding is the fleet-wide
        max, so the packed shapes — and therefore the compiled programs —
        are invariant under subset churn.

        ``prefetch_hook`` (no-arg callable) runs after the fused program
        is *dispatched* but before its results are materialised — the
        double-buffering seam: the hook stages the next cohort's packs
        (``stage_cohort``) while this cohort still runs on device. When
        the prefetch ring already holds a matching staged entry for
        *this* call, its tensors are consumed instead of re-packing."""
        if participation is not None:
            return self._train_cohort_subset(
                theta0_stacked, specs, datasets, participation,
                batch_size=batch_size, epochs=epochs, seeds=seeds,
                eval_datasets=eval_datasets, prefetch_hook=prefetch_hook)
        sh = self.cohort_sharding(len(specs))
        with obs.span("engine.pack"):
            with obs.span("engine.masks"):
                masks = self._cohort_masks(specs)
            with obs.span("engine.data"):
                x, y = self._cohort_data(datasets)
                pack = None if eval_datasets is None \
                    else self._eval_pack(eval_datasets)
            with obs.span("engine.stream"):
                staged = self._take_staged(datasets, eval_datasets, None,
                                           batch_size, epochs, seeds)
                if staged is not None:
                    stream, n_steps = staged["stream"], staged["n_steps"]
                else:
                    stream, n_steps = self._full_stream(
                        datasets, batch_size, epochs, seeds)
            theta0_stacked = shard_cohort(theta0_stacked, sh)
        if pack is None:
            with obs.span("engine.dispatch"):
                deltas, trained = self._train(
                    theta0_stacked, masks.param_mask, masks.fwd, x, y,
                    *stream)
            if prefetch_hook is not None:
                prefetch_hook()
            return CohortResult(deltas, trained, masks, n_steps)
        with obs.span("engine.dispatch"):
            deltas, trained, accs = self._train_eval(
                theta0_stacked, masks.param_mask, masks.fwd, x, y,
                *stream, pack.x, pack.y, pack.valid)
        if prefetch_hook is not None:
            prefetch_hook()     # overlaps with the in-flight fused program
        with obs.span("engine.wait"):
            accs = np.asarray(accs)
        return CohortResult(deltas, trained, masks, n_steps, accs)

    def _train_cohort_subset(self, theta0_stacked, specs: Sequence,
                             datasets: Sequence[Dict], participation, *,
                             batch_size: int, epochs: int,
                             seeds: Sequence[int],
                             eval_datasets: Optional[Sequence[Dict]] = None,
                             prefetch_hook=None) -> CohortResult:
        """Fixed-size padded subset round: gather the selected clients out
        of the fleet-resident packs on device, pad streams to the
        fleet-wide step count, and run the same compiled programs."""
        part = participation
        m = len(part.idx)
        if not (len(specs) == len(seeds) == m):
            raise ValueError(
                f"per-slot specs/seeds must match the padded cohort size "
                f"{m}, got {len(specs)}/{len(seeds)}")
        sh = self.cohort_sharding(m)
        with obs.span("engine.pack"):
            with obs.span("engine.masks"):
                masks = self._cohort_masks(specs)
            with obs.span("engine.stream"):
                t = self._take_staged(datasets, eval_datasets, part,
                                      batch_size, epochs, seeds)
                if t is None:
                    t = self._subset_tensors(datasets, part, batch_size,
                                             epochs, seeds, eval_datasets)
            theta0_stacked = shard_cohort(theta0_stacked, sh)
        if eval_datasets is None:
            with obs.span("engine.dispatch"):
                deltas, trained = self._train(
                    theta0_stacked, masks.param_mask, masks.fwd, t["x"],
                    t["y"], *t["stream"])
            if prefetch_hook is not None:
                prefetch_hook()
            return CohortResult(deltas, trained, masks, t["n_steps"])
        with obs.span("engine.dispatch"):
            deltas, trained, accs = self._train_eval(
                theta0_stacked, masks.param_mask, masks.fwd, t["x"],
                t["y"], *t["stream"], t["ex"], t["ey"], t["ev"])
        if prefetch_hook is not None:
            prefetch_hook()     # overlaps with the in-flight fused program
        with obs.span("engine.wait"):
            accs = np.asarray(accs)
        return CohortResult(deltas, trained, masks, t["n_steps"], accs)

    def _cohort_masks(self, specs: Sequence) -> CohortMasks:
        key = tuple(self.family.genes(s) for s in specs)
        masks = self._masks_cache.get(key)
        if masks is None:
            masks = self.family.cohort_masks(specs)
            sh = self.cohort_sharding(len(specs))
            if sh is not None:
                masks = CohortMasks(shard_cohort(masks.param_mask, sh),
                                    shard_cohort(masks.fwd, sh))
            self._masks_cache[key] = masks
            while len(self._masks_cache) > 8:
                self._masks_cache.popitem(last=False)
        return masks

    def _eval_pack(self, datasets: Sequence[Dict]) -> EvalPack:
        def build(d):
            p = pack_eval(d)
            sh = self.cohort_sharding(len(d))
            if sh is not None:
                p = EvalPack(*shard_cohort((p.x, p.y, p.valid), sh))
            return p
        return self._cached(self._eval_cache, datasets, build)

    def _cohort_data(self, datasets: Sequence[Dict]):
        def build(d):
            return shard_cohort(pack_cohort_data(d),
                                self.cohort_sharding(len(d)))
        return self._cached(self._data_cache, datasets, build)

    @staticmethod
    def _cached(cache: OrderedDict, datasets, build, bound: int = 4):
        key = id(datasets)
        hit = cache.get(key)
        if hit is not None and hit[0] is datasets:
            return hit[1]
        val = build(datasets)
        cache[key] = (datasets, val)
        while len(cache) > bound:
            cache.popitem(last=False)
        return val

    def run_fl_round(self, params, specs: Sequence,
                     datasets: Sequence[Dict], test_datasets: Sequence[Dict],
                     sizes: Sequence[float], *, batch_size: int, epochs: int,
                     seeds: Sequence[int], coverage_norm: bool = False,
                     participation=None, prefetch_hook=None):
        """One full FL round — cohort local train + eval fused, then fused
        aggregate+apply. The single dispatch contract shared by CFLServer
        and FedAvgServer (FedAvg is specs=[full_spec]*K, coverage off).

        With ``participation`` (an ``fl.selection.Selection``) the round
        trains only its fixed-size padded cohort: ``specs``/``seeds`` are
        per-slot, ``sizes`` is ignored in favour of the selection's
        aggregation weights, and padding slots contribute neither updates
        nor coverage. Returns (new_params, accs, n_steps) — with
        participation these are per-slot; filter by ``participation.valid``
        for the real cohort members.

        When the engine runs cohort-sharded the reduction routes through
        ``aggregate_apply_hierarchical``: per-shard partial sums + one
        explicit pytree collective over the 'cohort' axis, instead of
        relying on GSPMD to split the flat mean (≤1e-5 vs the flat path —
        same fp32 partial sums, different reduction order)."""
        from repro.core.aggregate import (aggregate_apply,
                                          aggregate_apply_hierarchical)
        with obs.span("engine.broadcast"):
            theta0 = self.broadcast_params(params, len(specs))
        res = self.train_cohort(theta0, specs, datasets,
                                batch_size=batch_size, epochs=epochs,
                                seeds=seeds, eval_datasets=test_datasets,
                                participation=participation,
                                prefetch_hook=prefetch_hook)
        covs = res.masks.param_mask if coverage_norm else None
        sh = self.cohort_sharding(len(specs))
        with obs.span("engine.aggregate"):
            if participation is None:
                weights = jnp.asarray(sizes, jnp.float32)
                part = None
            else:
                weights = jnp.asarray(
                    np.asarray(participation.weights, np.float32))
                part = jnp.asarray(
                    np.asarray(participation.valid, np.float32))
            if sh is not None:
                new_params = aggregate_apply_hierarchical(
                    params, res.deltas, covs, weights, mesh=sh.mesh,
                    coverage_norm=coverage_norm, participation=part)
            else:
                new_params = aggregate_apply(
                    params, res.deltas, covs, weights,
                    coverage_norm=coverage_norm, participation=part)
        return new_params, [float(a) for a in res.accs], res.n_steps

    def eval_cohort(self, params_stacked, specs: Sequence,
                    datasets: Sequence[Dict],
                    masks: Optional[CohortMasks] = None) -> np.ndarray:
        if masks is None:
            masks = self._cohort_masks(specs)
        pack = self._eval_pack(datasets)
        accs = self._eval(params_stacked, masks.fwd, pack.x, pack.y,
                          pack.valid)
        return np.asarray(accs)


# ---------------------------------------------------------------------------
# sequential reference: extract → jit-per-spec → pad, for any family
# ---------------------------------------------------------------------------
class SequentialFamilyTrainer:
    """The original per-client loop, generalised over ElasticFamily — the
    A/B reference the batched engine is verified against, and the baseline
    the round-engine benchmark measures (one compiled train-step + eval
    program per *distinct submodel config*; caches are split and bounded
    exactly like ``fl.client``'s)."""

    def __init__(self, cfg, *, lr: float, momentum: float,
                 grad_clip: float = 5.0, cache_size: int = 64):
        self.family: ElasticFamily = family_for(cfg)
        self._opt = sgd(lr, momentum=momentum)
        self._grad_clip = grad_clip
        self._train_cache = SpecLRU(cache_size)
        self._eval_cache = SpecLRU(cache_size)

    def n_programs(self) -> int:
        """Compiled entry points so far (the benchmark's compile counter)."""
        return len(self._train_cache) + len(self._eval_cache)

    def _train_step(self, spec, ctx):
        def build():
            @jax.jit
            def step(p, o, x, yb, sw):
                def loss(pp):
                    return self.family.sub_loss(pp, ctx, x, yb, sw)
                g = jax.grad(loss)(p)
                g, _ = clip_by_global_norm(g, self._grad_clip)
                upd, o2 = self._opt.update(g, o, p)
                return apply_updates(p, upd), o2
            return step
        return self._train_cache.get_or_build(self.family.genes(spec), build)

    def _eval_fn(self, spec, ctx):
        def build():
            @jax.jit
            def ev(p, x, y, valid):
                return self.family.sub_metric(p, ctx, x, y, valid)
            return ev
        return self._eval_cache.get_or_build(self.family.genes(spec), build)

    def client_update(self, params, spec, data, *, batch_size: int,
                      epochs: int, seed: int):
        """E local epochs on the extracted submodel; returns
        (delta, trained_sub, sub_ctx, n_steps) with delta in sub coords."""
        sub0, ctx = self.family.extract(params, spec)
        step = self._train_step(spec, ctx)
        o = self._opt.init(sub0)
        p = sub0
        n_steps = 0
        for b_idx in index_batches(len(data["y"]), batch_size, seed=seed,
                                   epochs=epochs):
            x = jnp.asarray(data["x"][b_idx])
            yb = jnp.asarray(data["y"][b_idx])
            sw = jnp.ones((len(b_idx),), jnp.float32)
            p, o = step(p, o, x, yb, sw)
            n_steps += 1
        delta = jax.tree.map(lambda a, b: a - b, sub0, p)
        return delta, p, ctx, n_steps

    def run_fl_round(self, params, specs: Sequence,
                     datasets: Sequence[Dict], test_datasets: Sequence[Dict],
                     sizes: Sequence[float], *, batch_size: int, epochs: int,
                     seeds: Sequence[int], coverage_norm: bool = False):
        """Same contract as BatchedRoundEngine.run_fl_round."""
        deltas, covs, accs, n_steps_all = [], [], [], []
        for spec, data, tdata, seed in zip(specs, datasets, test_datasets,
                                           seeds):
            delta, trained, ctx, n = self.client_update(
                params, spec, data, batch_size=batch_size, epochs=epochs,
                seed=seed)
            ev = self._eval_fn(spec, ctx)
            acc = float(ev(trained, jnp.asarray(tdata["x"]),
                           jnp.asarray(tdata["y"]),
                           jnp.ones((len(tdata["y"]),), jnp.float32)))
            deltas.append(self.family.pad_delta(delta, params, spec))
            if coverage_norm:
                covs.append(jax.tree.map(
                    jnp.asarray, self.family.spec_masks(spec).param_mask))
            accs.append(acc)
            n_steps_all.append(n)
        if coverage_norm:
            delta_t = aggregate_coverage(deltas, covs, list(sizes))
        else:
            delta_t = aggregate(deltas, list(sizes))
        params = apply_server_update(params, delta_t)
        return params, accs, np.array(n_steps_all)
