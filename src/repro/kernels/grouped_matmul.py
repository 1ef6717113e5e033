"""Grouped expert-prefix matmul — the MoE leg of the tile-skipping path.

The sort-dispatch MoE (models.moe) batches expert compute as
``(E, cap, d) @ (E, d, f)`` einsums over *all* parent experts. A CFL
submodel keeps a prefix of routed experts (router logits for the suffix
are masked to -inf, so no token is ever dispatched past ``e_active``) —
the parent-space masked forward still paid full-E FLOPs. This kernel
skips whole expert blocks at ``g >= g_active``:

* grid (G, M/BM, N/BN, K/BK) with a runtime ``g_active`` scalar-prefetch
  operand; skipped experts issue no matmul and write zeros;
* the BlockSpec index maps clamp ``g`` to the last active expert, so
  skipped grid steps re-request a resident block — no DMA for the
  inactive expert suffix;
* ``grouped_elastic_matmul`` is differentiable and closed under its own
  VJP: ``dxs = g(dy, wsᵀ, g_active)``, ``dws = g(xsᵀ, dy, g_active)`` —
  backward skips the same experts.

Semantics: ``y[g] = xs[g] @ ws[g] if g < g_active else 0``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.backend import default_interpret
from repro.kernels.elastic_matmul import _int_zero, _last_block, _round_up


def _kernel(s_ref, xs_ref, ws_ref, o_ref, acc_ref, *, nk):
    g, kk = pl.program_id(0), pl.program_id(3)
    ga = s_ref[0]

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(g < ga)
    def _accum():
        acc_ref[...] += jax.lax.dot_general(
            xs_ref[0], ws_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _write():
        o_ref[0] = jnp.where(g < ga, acc_ref[...], 0.0).astype(o_ref.dtype)


def grouped_index_maps():
    """BlockSpec index maps of one grouped launch, in operand order
    (xs, ws). A dead expert (g >= g_active) freezes the whole block
    request — group clamped to the last active expert *and* the (i, kk) /
    (kk, j) stream coordinates pinned to 0 — so skipped expert blocks
    issue no DMA at all. Exported for the roofline gate's DMA
    accounting."""
    def gcl(g, s):
        return jnp.minimum(g, _last_block(s[0], 1))

    def xs_map(g, i, j, kk, s):
        live = g < s[0]
        return (gcl(g, s), jnp.where(live, i, 0), jnp.where(live, kk, 0))

    def ws_map(g, i, j, kk, s):
        live = g < s[0]
        return (gcl(g, s), jnp.where(live, kk, 0), jnp.where(live, j, 0))

    return xs_map, ws_map


def _grouped_call(xs, ws, ga, *, bm, bn, bk, interpret):
    G, M, K = xs.shape
    G2, K2, N = ws.shape
    assert G == G2 and K == K2, (xs.shape, ws.shape)
    bm = min(bm, _round_up(M, 8))
    bn = min(bn, _round_up(N, 128))
    bk = min(bk, _round_up(K, 128))
    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    if (Mp, Kp) != (M, K):
        xs = jnp.pad(xs, ((0, 0), (0, Mp - M), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        ws = jnp.pad(ws, ((0, 0), (0, Kp - K), (0, Np - N)))
    nk = Kp // bk
    scalars = jnp.asarray(ga, jnp.int32).reshape(1)

    xs_map, ws_map = grouped_index_maps()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, Mp // bm, Np // bn, nk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), xs_map),
            pl.BlockSpec((1, bk, bn), ws_map),
        ],
        out_specs=pl.BlockSpec((1, bm, bn),
                               lambda g, i, j, kk, s: (g, i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    y = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, Mp, Np), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(scalars, xs, ws)
    if (Mp, Np) != (M, N):
        y = y[:, :M, :N]
    return y


@functools.lru_cache(maxsize=None)
def _make_grouped(bm, bn, bk, interpret):
    call = functools.partial(_grouped_call, bm=bm, bn=bn, bk=bk,
                             interpret=interpret)

    @jax.custom_vjp
    def f(xs, ws, ga):
        return call(xs, ws, ga)

    def fwd(xs, ws, ga):
        return f(xs, ws, ga), (xs, ws, ga)

    def bwd(res, dy):
        xs, ws, ga = res
        dxs = call(dy, jnp.swapaxes(ws, 1, 2), ga)
        dws = call(jnp.swapaxes(xs, 1, 2), dy, ga)
        return dxs, dws, _int_zero(ga)

    f.defvjp(fwd, bwd)
    return f


def grouped_elastic_matmul(xs, ws, g_active=None, *, bm=128, bn=128,
                           bk=128, interpret=None):
    """Differentiable grouped matmul with an expert-prefix skip.

    xs: (G, M, K); ws: (G, K, N); g_active: runtime int32 (None = all
    groups). Returns (G, M, N) with groups >= g_active exactly zero.
    """
    ga = jnp.asarray(xs.shape[0] if g_active is None else g_active,
                     jnp.int32)
    return _make_grouped(int(bm), int(bn), int(bk),
                         default_interpret(interpret))(
        xs, ws, ga)
