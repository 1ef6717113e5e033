"""Alg. 3 — submodel alignment + aggregation.

``aggregate``: the paper's rule — zero-pad every client update to parent
coordinates, then data-size-weighted average  Δ_t = Σ_k (n_k/n) Δ_k.

``aggregate_coverage``: beyond-paper variant — normalise each parent entry
by the total weight of clients that actually *covered* it (HeteroFL-style),
so rarely-sampled deep layers / late channels are not diluted toward zero.
Falls back to the paper's rule where coverage is full. Controlled by the
`coverage` flag so experiments can compare both (EXPERIMENTS.md §Perf).

On a pod, this whole operation is jit-able: the padded updates are a pytree
sum — under `data`-axis sharding it lowers to reduce-scatter/all-reduce.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp


def weighted_sum(trees: Sequence, weights: Sequence[float]):
    total = sum(weights)
    out = jax.tree.map(lambda a: a * (weights[0] / total), trees[0])
    for t, w in zip(trees[1:], weights[1:]):
        out = jax.tree.map(lambda acc, a, w=w: acc + a * (w / total), out, t)
    return out


def aggregate(padded_deltas: Sequence, data_sizes: Sequence[float]):
    """Paper rule (Alg. 3 last line): Δ = Σ (n_k/n) Δ_k over *aligned*
    (already padded) updates."""
    return weighted_sum(padded_deltas, list(data_sizes))


def aggregate_coverage(padded_deltas: Sequence, coverages: Sequence,
                       data_sizes: Sequence[float], eps: float = 1e-8):
    """Entry-wise: Δ[i] = Σ_k n_k c_k[i] Δ_k[i] / max(Σ_k n_k c_k[i], eps).

    coverages: 0/1 trees of the same structure (core.submodel.coverage_*).
    Partial-participation rounds on the sequential path pass participant
    sub-lists here; the batched engine's fused analogue
    (``aggregate_apply``) takes an explicit ``participation`` mask
    instead, because its stacked cohort keeps padding slots resident.
    """
    n = list(data_sizes)
    num = jax.tree.map(lambda a: a * n[0], padded_deltas[0])
    den = jax.tree.map(lambda c: c * n[0], coverages[0])
    for t, c, w in zip(padded_deltas[1:], coverages[1:], n[1:]):
        num = jax.tree.map(lambda acc, a, w=w: acc + a * w, num, t)
        den = jax.tree.map(lambda acc, a, w=w: acc + a * w, den, c)
    return jax.tree.map(lambda nu, de: nu / jnp.maximum(de, eps), num, den)


def apply_server_update(params, delta, server_lr: float = 1.0):
    """ω_{t+1} = ω_t − Δ_t (Alg. 4); Δ already carries the client-side sign
    convention (ω_0 − ω_E)."""
    return jax.tree.map(lambda p, d: (p - server_lr * d).astype(p.dtype),
                        params, delta)


# ---------------------------------------------------------------------------
# fused batched path (round engine): stacked client axis, one jitted program
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("coverage_norm", "sanitize"))
def aggregate_apply(params, stacked_deltas, stacked_coverages, weights, *,
                    coverage_norm: bool = False, eps: float = 1e-8,
                    participation=None, sanitize: bool = False):
    """Fused Alg. 3 + Alg. 4 server step over a *stacked* cohort.

    stacked_deltas / stacked_coverages: pytrees whose leaves carry a
    leading client axis (K, ...) — the batched engine's native layout, so
    aggregation + apply is a single compiled program instead of 2K
    tree_maps. Weighted sums reduce in fp32 regardless of param dtype.
    stacked_coverages may be None when coverage_norm is False (the paper
    rule never reads it — don't pay the device transfer).

    participation: optional (K,) 0/1 flags for partial-participation
    rounds (the engine's fixed-size padded cohort): padding slots drop out
    of both the update numerator and the coverage denominator, so the
    average runs over the *participating* mass only and entries covered
    solely by padding slots stay exactly 0 under coverage_norm. A runtime
    input, not a static one — subset churn never recompiles this program.

    sanitize: zero non-finite delta entries *inside* the weighted sum.
    Zeroing a quarantined client's weight is not enough on its own —
    ``0 * NaN`` is NaN, so one poisoned slot would NaN the whole fused
    sum; with ``sanitize`` the masked entries drop out exactly. Finite
    deltas pass through bit-identically (``where`` on an all-true mask),
    so the fault-free numerics are unchanged. The participating mass is
    also floored at ``eps`` so a fully-quarantined cohort applies a
    no-op step instead of 0/0.
    """
    w = weights.astype(jnp.float32)
    if participation is not None:
        w = w * participation.astype(jnp.float32)

    def clean(d):
        d = d.astype(jnp.float32)
        return jnp.where(jnp.isfinite(d), d, 0.0) if sanitize else d

    def plain(d):
        wd = w.reshape((-1,) + (1,) * (d.ndim - 1))
        return jnp.sum(clean(d) * wd, 0) / jnp.maximum(jnp.sum(w), eps)

    def covnorm(d, c):
        wd = w.reshape((-1,) + (1,) * (d.ndim - 1))
        num = jnp.sum(clean(d) * wd, 0)
        den = jnp.sum(c.astype(jnp.float32) * wd, 0)
        return num / jnp.maximum(den, eps)

    if coverage_norm:
        delta_t = jax.tree.map(covnorm, stacked_deltas, stacked_coverages)
    else:
        delta_t = jax.tree.map(plain, stacked_deltas)
    return jax.tree.map(lambda p, d: (p - d).astype(p.dtype), params,
                        delta_t)


# ---------------------------------------------------------------------------
# buffered (FedBuff-style) aggregation: partial sums a server can hold
# ---------------------------------------------------------------------------
def staleness_scale(staleness: float, decay: float) -> float:
    """FedBuff staleness discount ``(1+s)^-decay`` for a delta trained
    against a server snapshot ``s`` versions old. ``decay=0.5`` is the
    paper-standard ``1/sqrt(1+s)``; ``decay=0`` disables discounting
    (async with a full buffer then reproduces sync exactly). Host-side
    scalar: staleness is uniform per dispatch group (every slot trained
    against the same snapshot), so the discount never enters the
    per-leaf program shape."""
    return float((1.0 + float(staleness)) ** (-float(decay)))


@functools.partial(jax.jit, static_argnames=("coverage_norm", "sanitize"))
def cohort_reduce(stacked_deltas, stacked_coverages, weights, *,
                  coverage_norm: bool = False, participation=None,
                  scale=1.0, sanitize: bool = False):
    """Reduce one completed dispatch group to its aggregation partial
    sums: ``(num, den)`` where ``num`` is the fp32 weighted delta sum per
    leaf and ``den`` is the matching coverage-weight sum per leaf
    (``coverage_norm``) or the scalar participating weight mass. ``scale``
    is the group's staleness discount (:func:`staleness_scale`) — a
    runtime input, so staleness churn never recompiles.

    Partial sums are what a buffered-async server can *hold*: groups
    completing at different sim-times tree-add (:func:`buffer_add`) into
    one running buffer, and :func:`buffer_apply` turns the buffer into a
    server step whenever B deltas have arrived. The compiled-program
    count stays bounded (reduce/add/apply — one each per family) no
    matter how completion order interleaves.

    ``sanitize`` zeroes non-finite delta entries inside the sum (see
    :func:`aggregate_apply`): a quarantined slot's 0 weight would still
    poison the partial sum via ``0 * NaN`` without it. Coverage masks
    are 0/1 and never sanitised.
    """
    w = weights.astype(jnp.float32)
    if participation is not None:
        w = w * participation.astype(jnp.float32)
    w = w * scale

    def num_leaf(d):
        d = d.astype(jnp.float32)
        if sanitize:
            d = jnp.where(jnp.isfinite(d), d, 0.0)
        wd = w.reshape((-1,) + (1,) * (d.ndim - 1))
        return jnp.sum(d * wd, 0)

    num = jax.tree.map(num_leaf, stacked_deltas)
    if coverage_norm:
        den = jax.tree.map(num_leaf, stacked_coverages)
    else:
        den = jnp.sum(w)
    return num, den


@jax.jit
def buffer_add(acc, update):
    """Fold a group's ``(num, den)`` partial sums into the running
    buffer (leafwise add — works for both den variants)."""
    return jax.tree.map(jnp.add, acc, update)


@functools.partial(jax.jit, static_argnames=("coverage_norm",))
def buffer_apply(params, num, den, *, coverage_norm: bool = False,
                 eps: float = 1e-8):
    """Serve the buffered update: Δ = num/max(den, eps) (leafwise under
    coverage_norm, scalar mass otherwise), then ω ← ω − Δ. With a single
    group holding the full cohort this reproduces ``aggregate_apply``."""
    if coverage_norm:
        delta_t = jax.tree.map(lambda n, d: n / jnp.maximum(d, eps),
                               num, den)
    else:
        delta_t = jax.tree.map(lambda n: n / jnp.maximum(den, eps), num)
    return jax.tree.map(lambda p, d: (p - d).astype(p.dtype), params,
                        delta_t)


# ---------------------------------------------------------------------------
# delta validation: the quarantine gate in front of every aggregate
# ---------------------------------------------------------------------------
@jax.jit
def delta_validity(stacked_deltas, participation, clip_factor):
    """Per-client validity gate over a stacked ``(K, ...)`` delta tree:
    returns ``(valid, norms)`` — (K,) float32 0/1 flags and the (K,)
    fp32 global L2 norms.

    A slot is valid iff every entry of its delta is finite **and** its
    norm is within ``clip_factor ×`` the median norm of the finite
    participating slots (robust to <50% outliers — exactly the poisoned
    minority the gate exists for). ``clip_factor <= 0`` disables the
    norm test (finite check only). ``participation`` masks which slots
    vote in the median (padding/failed slots don't drag it); everything
    is runtime data, so fault churn never recompiles this program.

    Compose the result into :func:`cohort_reduce` /
    :func:`aggregate_apply` by multiplying it into ``participation``
    (with ``sanitize=True`` so the rejected entries also vanish from the
    sums): quarantined deltas drop out of the numerator *and* the
    coverage denominator without a recompile.
    """
    part = participation.astype(jnp.float32) > 0

    def leaf_stats(d):
        d32 = d.astype(jnp.float32)
        axes = tuple(range(1, d32.ndim))
        fin = jnp.isfinite(d32)
        sq = jnp.sum(jnp.where(fin, d32 * d32, 0.0), axis=axes)
        return sq, jnp.all(fin, axis=axes)

    stats = [leaf_stats(d) for d in jax.tree.leaves(stacked_deltas)]
    sq = functools.reduce(jnp.add, [s for s, _ in stats])
    finite = functools.reduce(jnp.logical_and, [f for _, f in stats])
    norm = jnp.sqrt(sq)
    ref = jnp.where(part & finite, norm, jnp.nan)
    limit = clip_factor * jnp.maximum(jnp.nanmedian(ref), 1e-12)
    norm_ok = jnp.where(jnp.isnan(limit), True, norm <= limit)
    ok = finite & ((clip_factor <= 0) | norm_ok)
    return ok.astype(jnp.float32), norm


# ---------------------------------------------------------------------------
# hierarchical aggregation: per-shard partial sums + one collective
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _hierarchical_program(mesh, coverage_norm: bool, has_participation: bool,
                          sanitize: bool = False):
    """Compile the sharded aggregate+apply for one (mesh, flags) combo.

    Each cohort shard reduces its resident clients to local partial sums
    (never materialising the full stacked tree on one device), then a
    single ``psum`` over the whole ``(num, den)`` pytree crosses the
    'cohort' axis once — the flat mean's reduce-scatter/all-gather pair
    becomes one explicit collective, which is the shape that scales to
    the multi-host fleet (ROADMAP item 1).
    """
    from jax.sharding import PartitionSpec as P
    rep, sh = P(), P("cohort")

    def local(params, stacked_deltas, stacked_coverages, w):
        def num_leaf(d):
            d = d.astype(jnp.float32)
            if sanitize:
                d = jnp.where(jnp.isfinite(d), d, 0.0)
            wd = w.reshape((-1,) + (1,) * (d.ndim - 1))
            return jnp.sum(d * wd, 0)
        num = jax.tree.map(num_leaf, stacked_deltas)
        den = jax.tree.map(num_leaf, stacked_coverages) if coverage_norm \
            else jnp.sum(w)
        num, den = jax.lax.psum((num, den), "cohort")
        if coverage_norm:
            delta_t = jax.tree.map(lambda n, d: n / jnp.maximum(d, 1e-8),
                                   num, den)
        else:
            delta_t = jax.tree.map(lambda n: n / jnp.maximum(den, 1e-8),
                                   num)
        return jax.tree.map(lambda p, d: (p - d).astype(p.dtype), params,
                            delta_t)

    inner = jax.shard_map(local, mesh=mesh, in_specs=(rep, sh, sh, sh),
                          out_specs=rep)

    def run(params, stacked_deltas, stacked_coverages, weights,
            participation):
        w = weights.astype(jnp.float32)
        if has_participation:
            w = w * participation.astype(jnp.float32)
        return inner(params, stacked_deltas, stacked_coverages, w)

    return jax.jit(run)


def aggregate_apply_hierarchical(params, stacked_deltas, stacked_coverages,
                                 weights, *, mesh,
                                 coverage_norm: bool = False,
                                 participation=None,
                                 sanitize: bool = False):
    """Sharded twin of :func:`aggregate_apply`: same signature plus the
    cohort ``mesh``; numerics match the flat mean ≤1e-5 (same fp32
    partial sums, different reduction order). Requires the stacked client
    axis to divide the mesh (``sharding.cohort.effective_cohort_shards``
    guarantees it)."""
    fn = _hierarchical_program(mesh, bool(coverage_norm),
                               participation is not None, bool(sanitize))
    if not coverage_norm:
        stacked_coverages = jax.tree.map(
            lambda d: jnp.zeros((d.shape[0], 1), jnp.float32),
            stacked_deltas)
    if participation is None:
        participation = jnp.ones_like(weights)
    return fn(params, stacked_deltas, stacked_coverages, weights,
              participation)
