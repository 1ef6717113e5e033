"""Flash attention (Pallas TPU): causal / sliding-window / softcap / GQA,
elastic over a runtime head prefix, forward *and* backward.

TPU adaptation of the standard flash algorithm:
  * forward grid (B*H, Sq/BQ, Sk/BK), KV innermost (sequential);
    online-softmax accumulators (m, l, acc) live in VMEM scratch across
    KV steps, and the log-sum-exp per row is emitted alongside o so the
    backward can rebuild p = exp(s - lse) without a second softmax pass;
  * causal and sliding-window *whole-block skipping* via `pl.when` — for a
    window `w`, compute is O(S·w) instead of O(S²) (this is what makes
    gemma2 local layers and zamba2@500k affordable);
  * BQ/BK default 128/256: (BQ,D)+(BK,D)+(BQ,BK) fp32 tiles stay well
    under VMEM (~16 MB) for D ≤ 256 while filling the 128-lane MXU.
  * logit softcap (gemma2) folded into the score tile before masking.

CFL elasticity (the ``ssd_scan`` pattern): a submodel keeps a *prefix*
of attention heads. ``h_active`` is a runtime int32 scalar-prefetch
operand — grid cells whose head index is past the prefix issue no
compute and write zeros, and their Q/K/V index maps clamp to the last
active head (for K/V: its GQA group), so the inactive suffix costs no
MXU work and no DMA. The scalar is traced, so per-client head prefixes
in the vmapped cohort never recompile.

The backward runs as two kernels under the same prefix: a dQ kernel
(KV innermost, dq accumulator in scratch) and a dK/dV kernel (Q
innermost, per-head dk/dv accumulators; the host group-sums the H-sized
result onto the KV heads). Both rebuild the score tile from the saved
lse and ``delta = Σ_d do·o``, flash-v2 style.

Layout: the kernels run head-major — q/k/v/o as (B, H, S, D), lse and
delta as (B, H, 1, S) rows — so every block's last two dims are a
(seq-block, D) or (1, seq-block) tile that the TPU lowering accepts (a
block of one head out of a (.., S, H, D) array is not). The public
(B, S, H, D) layout is transposed at the custom-vjp boundary.

A subtlety the forward guards against: a row can be *fully masked inside
a contributing block* (``bk < bq`` under causal, or a sliding-window
block edge). Its running max then stays NEG_INF and ``exp(s - m)`` would
be exp(0)=1 — ``bk`` units of garbage mass in l/acc — so the
probability tile is zeroed whenever the running max is still NEG_INF.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.backend import default_interpret

NEG_INF = -2.0 ** 30


def attn_block_contributes(qi: int, ki: int, *, bq: int, bk: int,
                           causal: bool, window: Optional[int]):
    """The whole-block skip predicate, on host ints — exported so the
    roofline bench counts executed tiles from the kernel's own rule."""
    ok = True
    if causal:
        ok = ok and (ki * bk <= qi * bq + bq - 1)
    if window is not None:
        ok = ok and (ki * bk + bk - 1 >= qi * bq - (window - 1))
    return ok


def _contributes(qi, ki, *, bq, bk, causal, window):
    q0, k0 = qi * bq, ki * bk
    ok = True
    if causal:
        ok = k0 <= q0 + bq - 1
    if window is not None:
        ok = jnp.logical_and(ok, k0 + bk - 1 >= q0 - (window - 1))
    return ok


def _head_clamp(H):
    def hcl(bh, s):
        # clamp to the last active head: skipped cells re-request a
        # resident block (no DMA)
        return jnp.minimum(jax.lax.rem(bh, H),
                           jnp.maximum(s[0] - 1, 0))
    return hcl


def _masked_scores(q, k, q0, k0, bq, bk, causal, window, cap, scale):
    """(s, mask, dcap) — scores after scale/softcap, the validity mask,
    and the softcap derivative factor (None when cap is off)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    dcap = None
    if cap is not None:
        t = jnp.tanh(s / cap)
        s = cap * t
        dcap = 1.0 - t * t
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask = jnp.logical_and(mask, kpos <= qpos)
    if window is not None:
        mask = jnp.logical_and(mask, qpos - kpos < window)
    return s, mask, dcap


def _fwd_kernel(s_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *,
                bq, bk, nk, causal, window, cap, scale, n_heads):
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    head = jax.lax.rem(bh, n_heads)
    ha = s_ref[0]
    q0 = qi * bq
    k0 = ki * bk

    @pl.when((head >= ha) & (ki == nk - 1))
    def _skip():
        o_ref[...] = jnp.zeros_like(o_ref)
        lse_ref[...] = jnp.full_like(lse_ref, NEG_INF)

    @pl.when(head < ha)
    def _live():
        @pl.when(ki == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(_contributes(qi, ki, bq=bq, bk=bk, causal=causal,
                              window=window))
        def _step():
            q = q_ref[...].astype(jnp.float32)
            k = k_ref[...].astype(jnp.float32)
            v = v_ref[...].astype(jnp.float32)
            s, mask, _ = _masked_scores(q, k, q0, k0, bq, bk, causal,
                                        window, cap, scale)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # rows fully masked so far: m_new is still NEG_INF and
            # exp(s - m_new) would be 1 — zero the tile instead.
            p = jnp.where(m_new > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, 1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        @pl.when(ki == nk - 1)
        def _write():
            l = l_ref[...]
            o_ref[...] = (acc_ref[...] /
                          jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
            lse = jnp.where(l > 0.0,
                            m_ref[...] + jnp.log(jnp.maximum(l, 1e-30)),
                            NEG_INF)                     # (bq, 1) column
            lse_ref[...] = lse.T.astype(lse_ref.dtype)


def _kv_block_range(*, bq, bk, nk, causal, window):
    """Contributing K/V block range [lo, hi] for a q row-block: dead
    (qi, ki) cells clamp ki into it, so the causal upper triangle and the
    out-of-window band re-request resident blocks — no DMA."""
    def rng(qi):
        lo = 0
        hi = nk - 1
        if window is not None:
            lo = jnp.maximum((qi * bq - (window - 1)) // bk, 0)
        if causal:
            hi = jnp.minimum((qi * bq + bq - 1) // bk, nk - 1)
        return lo, hi
    return rng


def _q_block_range(*, bq, bk, nq, causal, window):
    """Contributing q block range [lo, hi] for a K/V block (the dK/dV
    kernel's sequential axis)."""
    def rng(ki):
        lo = (ki * bk) // bq if causal else 0
        hi = nq - 1
        if window is not None:
            hi = jnp.minimum((ki * bk + bk - 1 + window - 1) // bq, nq - 1)
        return lo, hi
    return rng


def attn_fwd_index_maps(H, G, *, bq, bk, nk, causal, window):
    """Forward input index maps (q, k, v) — exported for the roofline
    gate's DMA accounting. Skipped heads freeze the whole request; dead
    (qi, ki) cells clamp ki into the contributing range."""
    hcl = _head_clamp(H)
    krng = _kv_block_range(bq=bq, bk=bk, nk=nk, causal=causal,
                           window=window)

    def live(bh, s):
        return jax.lax.rem(bh, H) < s[0]

    def qm(bh, qi, ki, s):
        return (bh // H, hcl(bh, s), jnp.where(live(bh, s), qi, 0), 0)

    def km(bh, qi, ki, s):
        lo, hi = krng(qi)
        kc = jnp.clip(ki, lo, hi)
        return (bh // H, hcl(bh, s) // G,
                jnp.where(live(bh, s), kc, 0), 0)

    return [qm, km, km]


def _hm(x):
    """(B, S, H, D) <-> (B, H, S, D)."""
    return jnp.swapaxes(x, 1, 2)


def _fwd_call(q, k, v, ha, *, causal, window, cap, scale, bq, bk,
              interpret):
    """Head-major forward. q: (B,H,Sq,D) k,v: (B,KV,Sk,D) ->
    o (B,H,Sq,D), lse (B,H,1,Sq)."""
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    nk = Sk // bk
    grid = (B * H, Sq // bq, nk)
    maps = attn_fwd_index_maps(H, G, bq=bq, bk=bk, nk=nk, causal=causal,
                               window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, bq, D), maps[0]),
            pl.BlockSpec((None, None, bk, D), maps[1]),
            pl.BlockSpec((None, None, bk, D), maps[2]),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bq, D),
                         lambda bh, qi, ki, s: (bh // H, bh % H, qi, 0)),
            pl.BlockSpec((None, None, 1, bq),
                         lambda bh, qi, ki, s: (bh // H, bh % H, 0, qi)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
                          window=window, cap=cap, scale=scale, n_heads=H),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ha, q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_tile(q, k, v, do, lse_ref, d_ref, q0, k0, *,
              bq, bk, causal, window, cap, scale):
    """Rebuild p from lse and return (p, ds) for one (bq, bk) tile.
    ``lse_ref``/``d_ref`` hold (1, bq) rows; they are used as columns."""
    s, mask, dcap = _masked_scores(q, k, q0, k0, bq, bk, causal, window,
                                   cap, scale)
    lse = lse_ref[...].T                               # (bq, 1)
    delta = d_ref[...].T                               # (bq, 1)
    p = jnp.where(mask & (lse > NEG_INF * 0.5), jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    if dcap is not None:
        ds = ds * dcap
    return p, ds * scale


def _dq_kernel(s_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
               dq_ref, dq_acc, *,
               bq, bk, nk, causal, window, cap, scale, n_heads):
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    head = jax.lax.rem(bh, n_heads)
    ha = s_ref[0]

    @pl.when((head >= ha) & (ki == nk - 1))
    def _skip():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(head < ha)
    def _live():
        @pl.when(ki == 0)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)

        @pl.when(_contributes(qi, ki, bq=bq, bk=bk, causal=causal,
                              window=window))
        def _step():
            q = q_ref[...].astype(jnp.float32)
            k = k_ref[...].astype(jnp.float32)
            v = v_ref[...].astype(jnp.float32)
            do = do_ref[...].astype(jnp.float32)
            _, ds = _bwd_tile(q, k, v, do, lse_ref, d_ref,
                              qi * bq, ki * bk, bq=bq, bk=bk, causal=causal,
                              window=window, cap=cap, scale=scale)
            dq_acc[...] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(ki == nk - 1)
        def _write():
            dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(s_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                bq, bk, nq, causal, window, cap, scale, n_heads):
    bh, ki, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    head = jax.lax.rem(bh, n_heads)
    ha = s_ref[0]

    @pl.when((head >= ha) & (qi == nq - 1))
    def _skip():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(head < ha)
    def _live():
        @pl.when(qi == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        @pl.when(_contributes(qi, ki, bq=bq, bk=bk, causal=causal,
                              window=window))
        def _step():
            q = q_ref[...].astype(jnp.float32)
            k = k_ref[...].astype(jnp.float32)
            v = v_ref[...].astype(jnp.float32)
            do = do_ref[...].astype(jnp.float32)
            p, ds = _bwd_tile(q, k, v, do, lse_ref, d_ref,
                              qi * bq, ki * bk,
                              bq=bq, bk=bk, causal=causal, window=window,
                              cap=cap, scale=scale)
            dv_acc[...] += jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[...] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(qi == nq - 1)
        def _write():
            dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def attn_dq_index_maps(H, G, *, bq, bk, nk, causal, window):
    """dQ-kernel input index maps (q, k, v, do, lse, delta). Same grid
    and skip geometry as the forward (K/V the sequential axis)."""
    hcl = _head_clamp(H)
    krng = _kv_block_range(bq=bq, bk=bk, nk=nk, causal=causal,
                           window=window)

    def live(bh, s):
        return jax.lax.rem(bh, H) < s[0]

    def qm(bh, qi, ki, s):
        return (bh // H, hcl(bh, s), jnp.where(live(bh, s), qi, 0), 0)

    def km(bh, qi, ki, s):
        lo, hi = krng(qi)
        kc = jnp.clip(ki, lo, hi)
        return (bh // H, hcl(bh, s) // G,
                jnp.where(live(bh, s), kc, 0), 0)

    def lm(bh, qi, ki, s):
        return (bh // H, hcl(bh, s), 0, jnp.where(live(bh, s), qi, 0))

    return [qm, km, km, qm, lm, lm]


def attn_dkv_index_maps(H, G, *, bq, bk, nq, causal, window):
    """dK/dV-kernel input index maps (q, k, v, do, lse, delta) — note the
    grid is (B*H, Sk/bk, Sq/bq): Q is the sequential axis, so dead cells
    clamp qi into the contributing range instead."""
    hcl = _head_clamp(H)
    qrng = _q_block_range(bq=bq, bk=bk, nq=nq, causal=causal,
                          window=window)

    def live(bh, s):
        return jax.lax.rem(bh, H) < s[0]

    def qc(bh, ki, qi, s):
        lo, hi = qrng(ki)
        return jnp.where(live(bh, s), jnp.clip(qi, lo, hi), 0)

    def qm(bh, ki, qi, s):
        return (bh // H, hcl(bh, s), qc(bh, ki, qi, s), 0)

    def km(bh, ki, qi, s):
        return (bh // H, hcl(bh, s) // G,
                jnp.where(live(bh, s), ki, 0), 0)

    def lm(bh, ki, qi, s):
        return (bh // H, hcl(bh, s), 0, qc(bh, ki, qi, s))

    return [qm, km, km, qm, lm, lm]


def _bwd_call(q, k, v, do, o, lse, ha, *, causal, window, cap, scale,
              bq, bk, interpret):
    """Head-major backward: (q, do, o) (B,H,Sq,D), (k, v) (B,KV,Sk,D),
    lse (B,H,1,Sq) -> (dq, dk, dv) in the same layouts."""
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    nq, nk = Sq // bq, Sk // bk
    delta = jnp.einsum("bhsd,bhsd->bhs", do.astype(jnp.float32),
                       o.astype(jnp.float32))[:, :, None, :]

    common = dict(causal=causal, window=window, cap=cap, scale=scale,
                  n_heads=H)
    maps = attn_dq_index_maps(H, G, bq=bq, bk=bk, nk=nk, causal=causal,
                              window=window)
    in_specs = [
        pl.BlockSpec((None, None, bq, D), maps[0]),
        pl.BlockSpec((None, None, bk, D), maps[1]),
        pl.BlockSpec((None, None, bk, D), maps[2]),
        pl.BlockSpec((None, None, bq, D), maps[3]),
        pl.BlockSpec((None, None, 1, bq), maps[4]),
        pl.BlockSpec((None, None, 1, bq), maps[5]),
    ]
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, nk=nk, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, nq, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (None, None, bq, D),
                lambda bh, qi, ki, s: (bh // H, bh % H, qi, 0)),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ha, q, k, v, do, lse, delta)

    kmaps = attn_dkv_index_maps(H, G, bq=bq, bk=bk, nq=nq, causal=causal,
                                window=window)
    kv_out = lambda bh, ki, qi, s: (bh // H, bh % H, ki, 0)
    dkf, dvf = pl.pallas_call(
        functools.partial(_dkv_kernel, bq=bq, bk=bk, nq=nq, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, nk, nq),
            in_specs=[
                pl.BlockSpec((None, None, bq, D), kmaps[0]),
                pl.BlockSpec((None, None, bk, D), kmaps[1]),
                pl.BlockSpec((None, None, bk, D), kmaps[2]),
                pl.BlockSpec((None, None, bq, D), kmaps[3]),
                pl.BlockSpec((None, None, 1, bq), kmaps[4]),
                pl.BlockSpec((None, None, 1, bq), kmaps[5]),
            ],
            out_specs=[pl.BlockSpec((None, None, bk, D), kv_out),
                       pl.BlockSpec((None, None, bk, D), kv_out)],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, Sk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Sk, D), v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ha, q, k, v, do, lse, delta)
    # GQA: every query head wrote its own dk/dv; sum the groups back onto
    # the KV heads (skipped heads wrote zeros, so the prefix is free).
    if G != 1:
        dkf = dkf.reshape(B, KV, G, Sk, D).sum(axis=2)
        dvf = dvf.reshape(B, KV, G, Sk, D).sum(axis=2)
    return dq, dkf.astype(k.dtype), dvf.astype(v.dtype)


def _active_len(mask):
    return jnp.sum(mask > 0).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _make_flash(causal, window, cap, scale, bq, bk, interpret, has_mask):
    """custom-vjp flash op closed under the runtime head prefix: Pallas
    forward (o + lse), Pallas dq/dkv backward; the backward reruns the
    forward for (o, lse) instead of saving them (flash-style recompute,
    cheap next to the O(S²) tiles and remat-friendly)."""
    kw = dict(causal=causal, window=window, cap=cap, scale=scale,
              bq=bq, bk=bk, interpret=interpret)

    def _ha(head_mask, H):
        if head_mask is None:
            return jnp.asarray(H, jnp.int32).reshape(1)
        return _active_len(head_mask).reshape(1)

    def _out(q, k, v, head_mask):
        ha = _ha(head_mask, q.shape[2])
        return _hm(_fwd_call(_hm(q), _hm(k), _hm(v), ha, **kw)[0])

    def _grads(q, k, v, head_mask, dy):
        ha = _ha(head_mask, q.shape[2])
        qh, kh, vh = _hm(q), _hm(k), _hm(v)
        o, lse = _fwd_call(qh, kh, vh, ha, **kw)
        return tuple(_hm(g) for g in
                     _bwd_call(qh, kh, vh, _hm(dy), o, lse, ha, **kw))

    if has_mask:
        @jax.custom_vjp
        def f(q, k, v, head_mask):
            return _out(q, k, v, head_mask)

        def fwd(q, k, v, head_mask):
            return f(q, k, v, head_mask), (q, k, v, head_mask)

        def bwd(res, dy):
            q, k, v, head_mask = res
            return _grads(q, k, v, head_mask, dy) + \
                (jnp.zeros_like(head_mask),)
    else:
        @jax.custom_vjp
        def f(q, k, v):
            return _out(q, k, v, None)

        def fwd(q, k, v):
            return f(q, k, v), (q, k, v)

        def bwd(res, dy):
            return _grads(*res, None, dy)

    f.defvjp(fwd, bwd)
    return f


def _block_sizes(Sq, Sk, bq, bk):
    """Clamp block sizes to the sequence and fall back to a gcd when the
    sequence is not a multiple — non-tile-multiple shapes stay legal."""
    bq = min(bq, Sq)
    if Sq % bq:
        bq = math.gcd(Sq, bq)
    bk = min(bk, Sk)
    if Sk % bk:
        bk = math.gcd(Sk, bk)
    return bq, bk


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "cap", "scale", "bq", "bk",
                              "interpret"))
def flash_attention(q, k, v, head_mask=None, *, causal: bool = True,
                    window: Optional[int] = None,
                    cap: Optional[float] = None,
                    scale: Optional[float] = None,
                    bq: int = 128, bk: int = 256,
                    interpret: Optional[bool] = None):
    """q: (B,Sq,H,D) k,v: (B,Sk,KV,D) -> (B,Sq,H,D).

    head_mask: optional (H,) 0/1 prefix mask — heads past
    ``sum(head_mask)`` are skipped (zero output, no matmul, no DMA) in
    forward and backward; the scalar is traced, so churn never
    recompiles. Differentiable via the Pallas dq/dkv kernels.
    """
    interpret = default_interpret(interpret)
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bq, bk = _block_sizes(Sq, Sk, bq, bk)
    f = _make_flash(causal, window, cap, float(scale), bq, bk,
                    bool(interpret), head_mask is not None)
    if head_mask is None:
        return f(q, k, v)
    return f(q, k, v, head_mask)
