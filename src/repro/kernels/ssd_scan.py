"""Mamba2 SSD chunk scan (Pallas TPU), forward *and* backward, with a
head-prefix skip.

One grid cell = one (batch, head) × one chunk; the chunk axis is the
innermost *sequential* grid dimension and the SSM state h (P×N, fp32)
persists in VMEM scratch across chunks — the TPU-native formulation of
SSD: intra-chunk compute is dense (Q×Q decay-masked score matmul on the
MXU), inter-chunk is a rank-preserving state pass, no HBM round-trip for
the state.

The backward (``ssd_scan_bwd``) is the transposed scan: chunks are
visited in *reverse* order (the index maps flip the chunk axis, the grid
itself stays forward-ordered), and the decay-weighted state cotangent
``dh`` (P×N, fp32) persists in VMEM scratch exactly like ``h`` does in
the forward. Each chunk needs the state the forward *entered* it with,
so ``ssd_scan(..., return_states=True)`` also emits the per-chunk
initial states — the backward caller reruns the forward once (flash
style) instead of saving O(S·P) activations.

CFL elasticity: a submodel keeps a *prefix* of SSD heads
(``core.submodel.extract_transformer``). ``h_active`` is a runtime int32
scalar-prefetch operand — grid cells whose head index is past the prefix
issue no compute and write zeros, and their BlockSpec index maps clamp to
the last active head so no DMA is spent on the inactive suffix. Masked
compute is therefore *skipped*, not zeroed, in both passes, and spec
churn never recompiles (the scalar is traced).

Layout: the kernels run head-major — x (B,H,S,P), B/C (B,H,S,N), dt as
(B,H,1,S) rows, A as (H,1,1) — so each block's last two dims are a
(chunk, P|N) or (1, chunk) tile the TPU lowering accepts (one head out of
a (.., S, H, P) array is not). The public (B,S,H,·) layout is transposed
in the wrappers. Block shapes: x (Q,P), B/C (Q,N), dt (1,Q) with
Q=chunk (≤256), P=head_dim (64..128), N=d_state (64..128) — everything
fits VMEM with room for double buffering. The TPU lowering has no
cumsum, so the in-chunk prefix sums are triangular masked reductions
(O(Q²) VPU work, next to the Q×Q score matmuls already there).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.backend import default_interpret

def _hm(x):
    """(B, S, H, ·) <-> (B, H, S, ·)."""
    return jnp.swapaxes(x, 1, 2)


def _tri(q):
    """(Q, Q) lower-triangular (i >= j) mask."""
    return jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)


def _cumsum(v, tri):
    """Inclusive prefix sum of a (Q, 1) column, as a (Q, 1) column."""
    return jnp.sum(jnp.where(tri, v.T, 0.0), axis=1, keepdims=True)


def _total(v):
    """Sum of a 2-D tile as a (1, 1) array."""
    return jnp.sum(jnp.sum(v, axis=1, keepdims=True), axis=0, keepdims=True)


def _kernel(s_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, *refs, q, n_heads,
            with_states):
    if with_states:
        y_ref, st_ref, h_ref = refs
    else:
        y_ref, h_ref = refs
    bh, ci = pl.program_id(0), pl.program_id(1)
    head = jax.lax.rem(bh, n_heads)
    ha = s_ref[0]

    @pl.when(head >= ha)
    def _skip():
        y_ref[...] = jnp.zeros_like(y_ref)
        if with_states:
            st_ref[...] = jnp.zeros_like(st_ref)

    @pl.when(head < ha)
    def _compute():
        @pl.when(ci == 0)
        def _init():
            h_ref[...] = jnp.zeros_like(h_ref)

        x = x_ref[...].astype(jnp.float32)               # (Q,P)
        dt = dt_ref[...].astype(jnp.float32).T           # (Q,1)
        A = a_ref[...]                                   # (1,1)
        Bm = b_ref[...].astype(jnp.float32)              # (Q,N)
        Cm = c_ref[...].astype(jnp.float32)              # (Q,N)

        tri = _tri(q)
        cum = _cumsum(dt * A, tri)                       # (Q,1) negative
        cum_end = cum[q - 1:, :]                         # (1,1)
        M = jnp.where(tri, jnp.exp(cum - cum.T), 0.0)
        CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        xdt = x * dt
        y_intra = jax.lax.dot_general(CB * M, xdt, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        h = h_ref[...]                                   # (P,N)
        if with_states:
            st_ref[...] = h.astype(st_ref.dtype)         # chunk-initial state
        y_inter = jnp.exp(cum) * jax.lax.dot_general(
            Cm, h, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        y_ref[...] = (y_intra + y_inter).astype(y_ref.dtype)

        decay_end = jnp.exp(cum_end - cum)               # (Q,1)
        S_c = jax.lax.dot_general(xdt * decay_end, Bm,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        h_ref[...] = h * jnp.exp(cum_end) + S_c


def _head_clamp(H):
    def hcl(bh, s):
        # clamp the head index to the last active head: skipped cells
        # re-request a resident block (no DMA)
        return jnp.minimum(jax.lax.rem(bh, H),
                           jnp.maximum(s[0] - 1, 0))
    return hcl


def _chunk_clamp(H):
    def cc(bh, ci, s):
        # skipped heads also freeze the chunk stream: a dead (bh, ci)
        # cell re-requests chunk 0 of the clamped head — resident, no DMA
        return jnp.where(jax.lax.rem(bh, H) < s[0], ci, 0)
    return cc


def ssd_fwd_index_maps(H):
    """The forward kernel's input index maps, in ``pallas_call`` order
    (x, dt, A, B, C) — exported so the roofline gate can measure DMA
    block requests from the *actual* maps the kernel runs with."""
    hcl, cc = _head_clamp(H), _chunk_clamp(H)
    xm = lambda bh, ci, s: (bh // H, hcl(bh, s), cc(bh, ci, s), 0)
    return [xm,
            lambda bh, ci, s: (bh // H, hcl(bh, s), 0, cc(bh, ci, s)),
            lambda bh, ci, s: (hcl(bh, s), 0, 0),
            xm, xm]


def ssd_bwd_index_maps(H, nc):
    """The backward kernel's input index maps (x, dt, A, B, C, states,
    dy): the chunk axis is flipped (``nc-1-ci``) — the transposed scan
    walks chunks in reverse while the grid stays forward-ordered."""
    hcl, cc = _head_clamp(H), _chunk_clamp(H)
    rc = lambda bh, ci, s: cc(bh, nc - 1 - ci, s)
    xm = lambda bh, ci, s: (bh // H, hcl(bh, s), rc(bh, ci, s), 0)
    return [xm,
            lambda bh, ci, s: (bh // H, hcl(bh, s), 0, rc(bh, ci, s)),
            lambda bh, ci, s: (hcl(bh, s), 0, 0),
            xm, xm,
            lambda bh, ci, s: (bh // H, rc(bh, ci, s), hcl(bh, s), 0, 0),
            xm]


def _head_major(xh, dt, A, Bm, Cm):
    """Kernel operands: x/B/C (B,H,S,·), dt (B,H,1,S), A (H,1,1); B/C
    repeated from G groups onto the H heads."""
    H = xh.shape[2]
    rep = H // Bm.shape[2]
    if rep != 1:
        Bm = jnp.repeat(Bm, rep, axis=2)
        Cm = jnp.repeat(Cm, rep, axis=2)
    return (_hm(xh), _hm(dt)[:, :, None, :], A.reshape(H, 1, 1), _hm(Bm),
            _hm(Cm))


def _seq_specs(maps, chunk, P, N):
    """BlockSpecs of (x, dt, A, B, C) under ``maps``."""
    return [pl.BlockSpec((None, None, chunk, P), maps[0]),
            pl.BlockSpec((None, None, 1, chunk), maps[1]),
            pl.BlockSpec((None, 1, 1), maps[2]),
            pl.BlockSpec((None, None, chunk, N), maps[3]),
            pl.BlockSpec((None, None, chunk, N), maps[4])]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret", "return_states"))
def ssd_scan(xh, dt, A, Bm, Cm, chunk: int = 128, *, h_active=None,
             interpret: bool | None = None, return_states: bool = False):
    """xh: (B,S,H,P)  dt: (B,S,H)  A: (H,)  Bm/Cm: (B,S,G,N).

    h_active: runtime int32 head prefix (None = all heads); heads past it
    are skipped (zero output, no matmul, no DMA). Returns y (B,S,H,P); with
    ``return_states=True`` also the per-chunk *initial* states
    (B, S/chunk, H, P, N) — the residual ``ssd_scan_bwd`` consumes.
    (Decode uses ssm.mamba_decode.)
    """
    interpret = default_interpret(interpret)
    B, S, H, P = xh.shape
    N = Bm.shape[3]
    assert S % chunk == 0
    nc = S // chunk
    ha = jnp.asarray(H if h_active is None else h_active,
                     jnp.int32).reshape(1)

    y_spec = pl.BlockSpec((None, None, chunk, P),
                          lambda bh, ci, s: (bh // H, bh % H, ci, 0))
    out_specs = y_spec
    out_shape = jax.ShapeDtypeStruct((B, H, S, P), xh.dtype)
    if return_states:
        st_spec = pl.BlockSpec(
            (None, None, None, P, N),
            lambda bh, ci, s: (bh // H, ci, bh % H, 0, 0))
        out_specs = [y_spec, st_spec]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B, nc, H, P, N), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H, nc),
        in_specs=_seq_specs(ssd_fwd_index_maps(H), chunk, P, N),
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, q=chunk, n_heads=H,
                          with_states=return_states),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ha, *_head_major(xh, dt, A, Bm, Cm))
    if return_states:
        return _hm(out[0]), out[1]
    return _hm(out)


def _bwd_kernel(s_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, st_ref, dy_ref,
                dx_ref, ddt_ref, du_ref, db_ref, dc_ref, dh_ref, *,
                q, n_heads):
    """One reverse-order chunk of the transposed SSD scan.

    dh (the cotangent of the state *entering* the next-later chunk) lives
    in VMEM scratch; each step consumes the incoming dh, emits this
    chunk's dx/ddt/du/dB/dC blocks, and leaves ``dh = E_Q·dh + dh_y`` for
    the chunk before it. ``du`` is the cotangent of ``u = dt·A`` — the
    host reduces it to dA (and folds it into ddt) so the kernel never
    needs a cross-chunk reduction. Per-position vectors are (Q,1)
    columns; ddt/du leave as (1,Q) rows.
    """
    bh, ci = pl.program_id(0), pl.program_id(1)
    head = jax.lax.rem(bh, n_heads)
    ha = s_ref[0]

    @pl.when(head >= ha)
    def _skip():
        dx_ref[...] = jnp.zeros_like(dx_ref)
        ddt_ref[...] = jnp.zeros_like(ddt_ref)
        du_ref[...] = jnp.zeros_like(du_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    @pl.when(head < ha)
    def _compute():
        @pl.when(ci == 0)
        def _init():
            dh_ref[...] = jnp.zeros_like(dh_ref)

        x = x_ref[...].astype(jnp.float32)               # (Q,P)
        dt = dt_ref[...].astype(jnp.float32).T           # (Q,1)
        A = a_ref[...]                                   # (1,1)
        Bm = b_ref[...].astype(jnp.float32)              # (Q,N)
        Cm = c_ref[...].astype(jnp.float32)              # (Q,N)
        h_in = st_ref[...].astype(jnp.float32)           # (P,N)
        dy = dy_ref[...].astype(jnp.float32)             # (Q,P)

        tri = _tri(q)
        cum = _cumsum(dt * A, tri)                       # (Q,1)
        cum_end = cum[q - 1:, :]                         # (1,1)
        L = jnp.where(tri, jnp.exp(cum - cum.T), 0.0)
        CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        xdt = x * dt
        e = jnp.exp(cum)                                 # (Q,1)
        E_Q = jnp.exp(cum_end)                           # (1,1)
        w_end = jnp.exp(cum_end - cum)                   # (Q,1)

        dh_out = dh_ref[...]                             # (P,N)

        # intra-chunk: y_intra = (CB∘L) @ xdt
        dG = jax.lax.dot_general(dy, xdt, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dCB = dG * L
        DL = dCB * CB                                    # dG∘CB∘L
        dxdt = jax.lax.dot_general(CB * L, dy, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        dC = jax.lax.dot_general(dCB, Bm, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dB = jax.lax.dot_general(dCB, Cm, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

        # inter-chunk read: y_inter = e ∘ (C @ h_inᵀ)
        CH = jax.lax.dot_general(Cm, h_in, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dcum = (jnp.sum(DL, axis=1, keepdims=True) -
                jnp.sum(DL, axis=0, keepdims=True).T +
                jnp.sum(dy * CH, axis=1, keepdims=True) * e)
        dC = dC + e * jax.lax.dot_general(
            dy, h_in, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dh_y = jax.lax.dot_general(dy * e, Cm,
                                   (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

        # state write: h_out = E_Q·h_in + Σ_s w_s·(xdt_s ⊗ B_s)
        XD = jax.lax.dot_general(xdt, dh_out, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        T = jnp.sum(XD * Bm, axis=1, keepdims=True)      # (Q,1)
        dxdt = dxdt + w_end * jax.lax.dot_general(
            Bm, dh_out, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dB = dB + w_end * XD
        dcum = dcum - T * w_end
        last = E_Q * _total(dh_out * h_in) + _total(T * w_end)   # (1,1)

        # cum = cumsum(u): du_s = Σ_{t≥s} dcum_t; `last` is the cum[-1]
        # term (decay-to-end + carried state), which lands on every s.
        du = (_total(dcum) + last) - _cumsum(dcum, tri) + dcum

        dh_ref[...] = dh_out * E_Q + dh_y

        dx_ref[...] = (dxdt * dt).astype(dx_ref.dtype)
        ddt_ref[...] = (jnp.sum(dxdt * x, axis=1, keepdims=True) +
                        du * A).T.astype(ddt_ref.dtype)
        du_ref[...] = du.T.astype(du_ref.dtype)
        db_ref[...] = dB.astype(db_ref.dtype)
        dc_ref[...] = dC.astype(dc_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_bwd(xh, dt, A, Bm, Cm, states, dy, chunk: int = 128, *,
                 h_active=None, interpret: bool | None = None):
    """VJP of ``ssd_scan`` w.r.t. (xh, dt, A, Bm, Cm).

    ``states`` is the (B, S/chunk, H, P, N) per-chunk initial-state array
    from ``ssd_scan(..., return_states=True)``; ``dy`` the output
    cotangent. Heads past ``h_active`` produce exactly-zero cotangents
    (and clamp their DMA like the forward). GQA (G < H) group-sums dB/dC
    on the host. Returns (dxh, ddt, dA, dBm, dCm).
    """
    interpret = default_interpret(interpret)
    B, S, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert S % chunk == 0
    nc = S // chunk
    rep = H // G
    ha = jnp.asarray(H if h_active is None else h_active,
                     jnp.int32).reshape(1)

    maps = ssd_bwd_index_maps(H, nc)
    flip = lambda bh, ci, s: (bh // H, bh % H, nc - 1 - ci, 0)
    row_flip = lambda bh, ci, s: (bh // H, bh % H, 0, nc - 1 - ci)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H, nc),
        in_specs=_seq_specs(maps, chunk, P, N) + [
            pl.BlockSpec((None, None, None, P, N), maps[5]),
            pl.BlockSpec((None, None, chunk, P), maps[6]),
        ],
        out_specs=[
            pl.BlockSpec((None, None, chunk, P), flip),
            pl.BlockSpec((None, None, 1, chunk), row_flip),
            pl.BlockSpec((None, None, 1, chunk), row_flip),
            pl.BlockSpec((None, None, chunk, N), flip),
            pl.BlockSpec((None, None, chunk, N), flip),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
    )
    dxh, ddt, du, dBf, dCf = pl.pallas_call(
        functools.partial(_bwd_kernel, q=chunk, n_heads=H),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), xh.dtype),
            jax.ShapeDtypeStruct((B, H, 1, S), dt.dtype),
            jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32),
            jax.ShapeDtypeStruct((B, H, S, N), Bm.dtype),
            jax.ShapeDtypeStruct((B, H, S, N), Cm.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ha, *_head_major(xh, dt, A, Bm, Cm), states, _hm(dy))
    ddt, du = _hm(ddt[:, :, 0, :]), _hm(du[:, :, 0, :])
    dBf, dCf = _hm(dBf), _hm(dCf)
    # u = dt·A: the A cotangent is a host-side reduction of du (zero for
    # skipped heads, so dA inherits the prefix for free).
    dA = jnp.einsum("bsh,bsh->h", du,
                    dt.astype(jnp.float32)).astype(A.dtype)
    if rep != 1:
        dBf = dBf.reshape(B, S, G, rep, N).sum(axis=3)
        dCf = dCf.reshape(B, S, G, rep, N).sum(axis=3)
    return _hm(dxh), ddt, dA, dBf, dCf
