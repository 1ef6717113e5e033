"""Elastic matmul — CFL submodel compute that is *skipped*, not zeroed.

CFL submodels keep a *prefix* of output channels (DESIGN.md §5). On GPU
the paper slices channels (a gather); on TPU arbitrary slicing breaks MXU
tiling, so we adapt: every dimension of ``y = x @ w`` is blocked in MXU
tiles and the kernel skips whole tiles outside the active prefixes —

* ``n_active`` — output-column prefix (the up/gate projection of an
  elastic MLP, conv output channels): tiles with ``col0 >= n_active``
  issue no matmul and write zeros;
* ``k_active`` — **contraction prefix** (the down projection
  ``(…, d_ff_active) @ (d_ff, d_model)``, conv input channels): K-tiles
  past the active prefix are skipped entirely, so the second MLP matmul
  costs ``k_active/K`` of the parent, not just the first;
* ``m_active`` — row prefix (used by the transposed calls of the VJP so
  the backward is tile-skipping too).

All three are **runtime scalars** (SMEM scalar-prefetch operands):
submodel switches per FL round need no re-layout and no recompile, which
is what keeps the batched engine at 2 compiled programs/round under spec
churn. The scalars also feed the BlockSpec index maps: a skipped tile's
block index is *clamped* to the last active block, so consecutive grid
steps see an unchanged index and Pallas issues **no new DMA** for skipped
tiles — skipping saves both MXU issue slots and HBM bandwidth.

``elastic_dense`` is the differentiable wrapper (fused bias + activation
variants included). Its VJP is closed under the same kernel: with masks
``R_m, C_n, P_k`` for the three prefixes and ``y = R_m C_n · act((x·P_k)
@ w + b)``,

    dx = edense(dpre, wᵀ, k_active=n, n_active=k, m_active=m)
    dw = edense(xᵀ, dpre, k_active=m, n_active=n, m_active=k)

so backward matmuls skip the same tiles the forward skipped (``dpre`` is
``dy`` times the recomputed activation derivative; recompute is itself an
elastic matmul).

Grid: (M/BM, N/BN, K/BK), K innermost (sequential accumulation in VMEM
scratch). dims (i, j) are parallel. Non-multiple shapes are zero-padded
to tile multiples (the padding rides the masked region, so ``k_active ==
K`` and ``K % bk != 0`` are both exact).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.backend import default_interpret

# the single activation table — fused acts must match the dense paths
from repro.models.layers import ACTIVATIONS as _ACTS  # noqa: E402


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _last_block(active, b):
    """Index of the last block intersecting the active prefix (>= 0 so a
    0-active prefix still maps to a valid — already resident — block)."""
    return jnp.maximum((active + b - 1) // b - 1, 0)


def edense_index_maps(bm, bn, bk):
    """The (x, w, bias) BlockSpec index maps of one elastic_dense launch
    — exported for the roofline gate's DMA accounting.

    Scalars: s[0]=k_active, s[1]=n_active, s[2]=m_active. Live tiles
    clamp each axis to its last active block; *dead* output tiles
    (row/col past the m/n prefixes) freeze the whole request at K-block
    0, so a skipped tile re-requests the resident block and Pallas
    issues no DMA at all — skipping saves HBM bandwidth, not just MXU
    issue slots."""
    def dead(i, j, s):
        return (i * bm >= s[2]) | (j * bn >= s[1])

    def kcl(i, j, kk, s):
        return jnp.where(dead(i, j, s), 0,
                         jnp.minimum(kk, _last_block(s[0], bk)))

    def xmap(i, j, kk, s):
        return (jnp.minimum(i, _last_block(s[2], bm)), kcl(i, j, kk, s))

    def wmap(i, j, kk, s):
        return (kcl(i, j, kk, s), jnp.minimum(j, _last_block(s[1], bn)))

    def bmap(i, j, kk, s):
        return (0, jnp.minimum(j, _last_block(s[1], bn)))

    return xmap, wmap, bmap


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------
def _edense_kernel(s_ref, *refs, bm, bn, bk, nk, act, has_bias):
    if has_bias:
        x_ref, w_ref, b_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, o_ref, acc_ref = refs
    i, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ka, na, ma = s_ref[0], s_ref[1], s_ref[2]
    row0, col0, k0 = i * bm, j * bn, kk * bk

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = (row0 < ma) & (col0 < na)

    # interior K tile: full MXU issue, no masking
    @pl.when(live & (k0 + bk <= ka))
    def _accum():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # boundary K tile: mask the partial contraction columns
    @pl.when(live & (k0 < ka) & (k0 + bk > ka))
    def _accum_edge():
        kidx = k0 + jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 1)
        xm = jnp.where(kidx < ka, x_ref[...], jnp.zeros_like(x_ref[...]))
        acc_ref[...] += jax.lax.dot_general(
            xm, w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _write():
        y = acc_ref[...]
        if has_bias:
            y = y + b_ref[...].astype(jnp.float32)
        if act is not None:
            y = _ACTS[act](y)
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
        y = jnp.where((rows < ma) & (cols < na), y, 0.0)
        o_ref[...] = y.astype(o_ref.dtype)


def _edense_call(x, w, bias, ka, na, ma, *, act, bm, bn, bk, interpret):
    """Raw (non-differentiable) launcher. x: (M, K); w: (K, N);
    bias: (N,) or None; ka/na/ma: int32 runtime scalars."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    bm = min(bm, _round_up(M, 8))
    bn = min(bn, _round_up(N, 128))
    bk = min(bk, _round_up(K, 128))
    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    if (Mp, Kp) != (M, K):
        x = jnp.pad(x, ((0, Mp - M), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        w = jnp.pad(w, ((0, Kp - K), (0, Np - N)))
    has_bias = bias is not None
    if has_bias and Np != N:
        bias = jnp.pad(bias, (0, Np - N))
    nk = Kp // bk
    scalars = jnp.stack([jnp.asarray(ka, jnp.int32),
                         jnp.asarray(na, jnp.int32),
                         jnp.asarray(ma, jnp.int32)])

    # clamped index maps: tiles outside the active prefixes re-request the
    # resident block — an unchanged index between consecutive grid steps,
    # i.e. no DMA is issued for skipped tiles (see edense_index_maps)
    xmap, wmap, bmap = edense_index_maps(bm, bn, bk)
    in_specs = [
        pl.BlockSpec((bm, bk), xmap),
        pl.BlockSpec((bk, bn), wmap),
    ]
    args = [x, w]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), bmap))
        args.append(bias.reshape(1, Np))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Mp // bm, Np // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, s: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    y = pl.pallas_call(
        functools.partial(_edense_kernel, bm=bm, bn=bn, bk=bk, nk=nk,
                          act=act, has_bias=has_bias),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(scalars, *args)
    if (Mp, Np) != (M, N):
        y = y[:M, :N]
    return y


# ---------------------------------------------------------------------------
# differentiable wrapper (closed under its own VJP)
# ---------------------------------------------------------------------------
def _int_zero(v):
    """float0 cotangent for an integer primal (jax's non-diff convention)."""
    return np.zeros(np.shape(v), jax.dtypes.float0)


@functools.lru_cache(maxsize=None)
def _make_edense(act, has_bias, bm, bn, bk, interpret):
    call = functools.partial(_edense_call, act=act, bm=bm, bn=bn, bk=bk,
                             interpret=interpret)
    noact = functools.partial(_edense_call, act=None, bm=bm, bn=bn, bk=bk,
                              interpret=interpret)

    def _dpre(x, w, bias, ka, na, ma, dy):
        """dy through the fused activation (recomputes the pre-activation
        with the same tile-skipping kernel). Positions outside the active
        prefixes may hold garbage — the downstream kernels' contraction /
        output masks drop them."""
        if act is None:
            return dy
        pre = noact(x, w, bias, ka, na, ma)
        _, vjp = jax.vjp(_ACTS[act], pre)
        return vjp(dy.astype(pre.dtype))[0].astype(dy.dtype)

    def _grads(x, w, bias, ka, na, ma, dy):
        dpre = _dpre(x, w, bias, ka, na, ma, dy)
        dx = noact(dpre, w.T, None, na, ka, ma)
        dw = noact(x.T, dpre, None, ma, na, ka)
        return dpre, dx, dw

    if has_bias:
        @jax.custom_vjp
        def f(x, w, bias, ka, na, ma):
            return call(x, w, bias, ka, na, ma)

        def fwd(x, w, bias, ka, na, ma):
            return f(x, w, bias, ka, na, ma), (x, w, bias, ka, na, ma)

        def bwd(res, dy):
            x, w, bias, ka, na, ma = res
            dpre, dx, dw = _grads(x, w, bias, ka, na, ma, dy)
            rows = jnp.arange(x.shape[0]) < ma
            cols = jnp.arange(w.shape[1]) < na
            db = jnp.sum(
                dpre.astype(jnp.float32) *
                rows[:, None].astype(jnp.float32) *
                cols[None, :].astype(jnp.float32), axis=0)
            return (dx, dw, db.astype(bias.dtype),
                    _int_zero(ka), _int_zero(na), _int_zero(ma))
    else:
        @jax.custom_vjp
        def f(x, w, ka, na, ma):
            return call(x, w, None, ka, na, ma)

        def fwd(x, w, ka, na, ma):
            return f(x, w, ka, na, ma), (x, w, ka, na, ma)

        def bwd(res, dy):
            x, w, ka, na, ma = res
            _, dx, dw = _grads(x, w, None, ka, na, ma, dy)
            return dx, dw, _int_zero(ka), _int_zero(na), _int_zero(ma)

    f.defvjp(fwd, bwd)
    return f


def elastic_dense(x, w, bias=None, *, k_active=None, n_active=None,
                  m_active=None, act=None, bm=128, bn=128, bk=128,
                  interpret=None):
    """Differentiable tile-skipping dense layer.

    ``y = act((x ⊙ [k < k_active]) @ w + bias) ⊙ [n < n_active]
    ⊙ [m < m_active]`` with runtime int32 prefixes (None = full). x may
    carry leading batch dims (flattened to M); masks on M apply to the
    flattened axis. act in {None, "silu", "gelu", "relu"} (static).
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    M, K = x2.shape
    N = w.shape[-1]
    ka = jnp.asarray(K if k_active is None else k_active, jnp.int32)
    na = jnp.asarray(N if n_active is None else n_active, jnp.int32)
    ma = jnp.asarray(M if m_active is None else m_active, jnp.int32)
    f = _make_edense(act, bias is not None, int(bm), int(bn), int(bk),
                     default_interpret(interpret))
    if bias is None:
        y = f(x2, w, ka, na, ma)
    else:
        y = f(x2, w, bias, ka, na, ma)
    return y.reshape(*lead, N)


# ---------------------------------------------------------------------------
# back-compat: the PR-1 output-prefix-only entry point
# ---------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def elastic_matmul(x, w, k_active, *, bm=128, bn=128, bk=128,
                   interpret=None):
    """y[m, n] = sum_k x[m,k] w[k,n] for n < k_active else 0.

    x: (M, K), w: (K, N), k_active: int32 scalar (dynamic). Kept with the
    PR-1 signature (``k_active`` here is the *output-column* prefix);
    ``elastic_dense`` is the general/differentiable entry point.
    """
    return _edense_call(x, w, None, jnp.asarray(x.shape[-1], jnp.int32),
                        jnp.asarray(k_active, jnp.int32),
                        jnp.asarray(x.shape[0], jnp.int32),
                        act=None, bm=bm, bn=bn, bk=bk,
                        interpret=default_interpret(interpret))
