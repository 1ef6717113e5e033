"""Pallas MoE token dispatch/combine: gather-reduce row movement.

Replaces the XLA gather/scatter pair around the grouped expert matmul
(``models.moe._dispatch_compute_combine``) with two tiny row-movement
kernels driven by scalar-prefetched router indices:

  * ``gather_rows``   — out[r] = x[idx[r]] (or zeros when invalid): the
    *dispatch* direction, one grid cell per capacity slot. The row index
    lives in the BlockSpec index map, so the copy is pure DMA — invalid
    slots clamp to row 0 (a resident block: no fresh DMA) and write
    zeros.
  * ``gather_reduce`` — out[t] = Σ_j gates[t,j] · y[dest[t,j]]: the
    *combine* direction, one grid cell per token with k statically
    unrolled gathered operands (the maxtext gather-reduce pattern).
    Dropped/invalid assignments carry gate 0, so clamped indices
    contribute nothing.

``moe_dispatch`` / ``moe_combine`` wrap them in custom VJPs that are
closed under each other: the cotangent of a gather is a gather-reduce
and vice versa (token→slot assignment is injective over valid slots), so
the backward issues the same per-row DMA volume as the forward — token
movement stays proportional to what the router actually routed, per
cohort, in both passes. Expert-prefix elasticity rides the validity
vectors: slots of masked experts are invalid and their (t,j) gates are
zero, so a narrow cohort moves (and back-propagates) only its own rows.

All *narrow* int32 bookkeeping (argsort, searchsorted, slot tables) stays
XLA in ``models.moe`` — only the wide (·,d) row traffic runs here.

Rows move as (R, 1, d) arrays with (1, d) blocks: a block whose last two
dims equal the array's is one the TPU lowering accepts, where a (1, d)
block of an (R, d) array is not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.backend import default_interpret
from repro.kernels.elastic_matmul import _int_zero


def _gather_kernel(s_ref, x_ref, o_ref, *, n_rows):
    r = pl.program_id(0)
    ok = s_ref[n_rows + r] > 0

    @pl.when(ok)
    def _copy():
        o_ref[...] = x_ref[...]

    @pl.when(jnp.logical_not(ok))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)


def gather_index_map(n_src, n_rows):
    """Row index map of ``gather_rows``: valid rows fetch x[idx[r]],
    invalid rows clamp to row 0 (resident — no DMA). Exported for the
    roofline gate's DMA accounting."""
    def m(r, s):
        return (jnp.where(s[n_rows + r] > 0,
                          jnp.minimum(s[r], n_src - 1), 0), 0, 0)
    return m


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(x, idx, valid, *, interpret=None):
    """x: (R_src, d); idx/valid: (R,) int32 -> (R, d) with
    out[r] = x[idx[r]] where valid[r] else 0."""
    interpret = default_interpret(interpret)
    n_src, d = x.shape
    R = idx.shape[0]
    s = jnp.concatenate([jnp.asarray(idx, jnp.int32),
                         jnp.asarray(valid, jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R,),
        in_specs=[pl.BlockSpec((None, 1, d), gather_index_map(n_src, R))],
        out_specs=pl.BlockSpec((None, 1, d), lambda r, s: (r, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, n_rows=R),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, 1, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(s, x.reshape(n_src, 1, d))
    return out.reshape(R, d)


def _gather_reduce_kernel(s_ref, g_ref, *refs, k):
    y_refs, o_ref = refs[:-1], refs[-1]
    g = g_ref[...].astype(jnp.float32)                  # (1, k)
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for j in range(k):
        acc = acc + g[:, j:j + 1] * y_refs[j][...].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def gather_reduce_index_maps(n_src, k):
    """The k row index maps of ``gather_reduce`` (one per unrolled
    operand), each clamping its dest slot into range."""
    def mk(j):
        def m(t, s):
            return (jnp.minimum(s[t * k + j], n_src - 1), 0, 0)
        return m
    return [mk(j) for j in range(k)]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_reduce(y, dest, gates, *, interpret=None):
    """y: (R_src, d); dest: (T, k) int32; gates: (T, k) ->
    (T, d) with out[t] = Σ_j gates[t,j] · y[dest[t,j]]. Out-of-range
    dest entries must carry gate 0 (they clamp to the last row)."""
    interpret = default_interpret(interpret)
    n_src, d = y.shape
    T, k = dest.shape
    s = jnp.asarray(dest, jnp.int32).reshape(-1)
    maps = gather_reduce_index_maps(n_src, k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T,),
        in_specs=[pl.BlockSpec((None, 1, k), lambda t, s: (t, 0, 0))] +
                 [pl.BlockSpec((None, 1, d), m) for m in maps],
        out_specs=pl.BlockSpec((None, 1, d), lambda t, s: (t, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_gather_reduce_kernel, k=k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, 1, d), y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(s, gates.reshape(T, 1, k), *([y.reshape(n_src, 1, d)] * k))
    return out.reshape(T, d)


# ---------------------------------------------------------------------------
# differentiable dispatch / combine (the model-facing pair)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _make_dispatch(n_experts: int, cap: int, interpret: bool):
    @jax.custom_vjp
    def f(xt, slot_src, slot_valid, dest_tj, kept_tj):
        eb = gather_rows(xt, slot_src, slot_valid, interpret=interpret)
        return eb.reshape(n_experts, cap, xt.shape[-1])

    def fwd(xt, slot_src, slot_valid, dest_tj, kept_tj):
        return f(xt, slot_src, slot_valid, dest_tj, kept_tj), \
            (xt, slot_src, slot_valid, dest_tj, kept_tj)

    def bwd(res, deb):
        xt, slot_src, slot_valid, dest_tj, kept_tj = res
        (T, d), dt_ = xt.shape, xt.dtype
        k = dest_tj.shape[0] // T
        dxt = gather_reduce(deb.reshape(n_experts * cap, d).astype(dt_),
                            dest_tj.reshape(T, k),
                            kept_tj.reshape(T, k).astype(dt_),
                            interpret=interpret)
        return (dxt, _int_zero(slot_src), _int_zero(slot_valid),
                _int_zero(dest_tj), _int_zero(kept_tj))

    f.defvjp(fwd, bwd)
    return f


def moe_dispatch(xt, slot_src, slot_valid, dest_tj, kept_tj, *,
                 n_experts: int, cap: int, interpret=None):
    """Pallas token dispatch: (T,d) -> (E, cap, d) expert buffer.

    slot_src/slot_valid: (E*cap,) per-slot source token + validity;
    dest_tj/kept_tj: (T*k,) per-assignment dest slot + kept flag (the
    transpose of the slot tables — the VJP's gather-reduce uses them).
    """
    return _make_dispatch(n_experts, cap,
                          default_interpret(interpret))(
        xt, jnp.asarray(slot_src, jnp.int32),
        jnp.asarray(slot_valid, jnp.int32),
        jnp.asarray(dest_tj, jnp.int32), jnp.asarray(kept_tj, jnp.int32))


@functools.lru_cache(maxsize=None)
def _make_combine(interpret: bool):
    @jax.custom_vjp
    def f(y_flat, gate_eff, dest_tj, slot_src, slot_valid, slot_gate):
        T, k = gate_eff.shape
        return gather_reduce(y_flat, dest_tj.reshape(T, k), gate_eff,
                             interpret=interpret)

    def fwd(y_flat, gate_eff, dest_tj, slot_src, slot_valid, slot_gate):
        return f(y_flat, gate_eff, dest_tj, slot_src, slot_valid,
                 slot_gate), \
            (y_flat, gate_eff, dest_tj, slot_src, slot_valid, slot_gate)

    def bwd(res, dout):
        y_flat, gate_eff, dest_tj, slot_src, slot_valid, slot_gate = res
        T, k = gate_eff.shape
        # slot ← token: each valid slot reads its owner token's cotangent
        dy = gather_rows(dout, slot_src, slot_valid,
                         interpret=interpret) * slot_gate[:, None]
        # gate cotangent: re-gather the slot rows this (t,j) pointed at
        yg = gather_rows(y_flat, dest_tj,
                         (gate_eff.reshape(-1) != 0).astype(jnp.int32),
                         interpret=interpret).reshape(T, k, -1)
        dgate = jnp.einsum("td,tjd->tj", dout.astype(jnp.float32),
                           yg.astype(jnp.float32)).astype(gate_eff.dtype)
        return (dy.astype(y_flat.dtype), dgate, _int_zero(dest_tj),
                _int_zero(slot_src), _int_zero(slot_valid),
                jnp.zeros_like(slot_gate))

    f.defvjp(fwd, bwd)
    return f


def moe_combine(y_flat, gate_eff, dest_tj, slot_src, slot_valid,
                slot_gate, *, interpret=None):
    """Pallas token combine: (E*cap, d) expert outputs -> (T, d).

    gate_eff: (T,k) per-assignment effective gates (0 for dropped /
    masked-expert assignments); slot_gate: (E*cap,) the same values in
    slot order (the VJP's dispatch-direction weights). Differentiable in
    ``y_flat`` and ``gate_eff``.
    """
    return _make_combine(default_interpret(interpret))(
        y_flat, gate_eff, jnp.asarray(dest_tj, jnp.int32),
        jnp.asarray(slot_src, jnp.int32),
        jnp.asarray(slot_valid, jnp.int32), slot_gate)
