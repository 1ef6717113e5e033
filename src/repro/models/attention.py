"""Attention: GQA/MQA/MLA, sliding windows, qk-norm, softcap.

Two execution paths:
  * `chunked_attention` — memory-efficient blockwise attention (online
    softmax, lax.scan over KV blocks) used for train/prefill. This is the
    XLA reference path used by the dry-run; the Pallas flash kernel in
    `repro.kernels.flash_attention` implements the same contract for TPU.
  * `*_decode` — single-token attention against a KV cache (ring-buffer
    cache for sliding-window layers, compressed-latent cache for MLA).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import _he, apply_rope, rmsnorm, softcap

NEG_INF = -2.0 ** 30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def gqa_init(key, d_model, n_heads, n_kv, head_dim, qk_norm=False):
    ks = jax.random.split(key, 4)
    p = {
        "wq": _he(ks[0], (d_model, n_heads, head_dim), d_model),
        "wk": _he(ks[1], (d_model, n_kv, head_dim), d_model),
        "wv": _he(ks[2], (d_model, n_kv, head_dim), d_model),
        "wo": _he(ks[3], (n_heads, head_dim, d_model), n_heads * head_dim),
    }
    if qk_norm:
        p["q_norm"] = {"scale": jnp.zeros((head_dim,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.zeros((head_dim,), jnp.float32)}
    return p


def mla_init(key, d_model, n_heads, mla):
    ks = jax.random.split(key, 5)
    qk = mla.qk_nope_dim + mla.qk_rope_dim
    return {
        "wq": _he(ks[0], (d_model, n_heads, qk), d_model),
        "w_dkv": _he(ks[1], (d_model, mla.kv_lora_rank + mla.qk_rope_dim),
                     d_model),
        "kv_norm": {"scale": jnp.zeros((mla.kv_lora_rank,), jnp.float32)},
        "w_uk": _he(ks[2], (mla.kv_lora_rank, n_heads, mla.qk_nope_dim),
                    mla.kv_lora_rank),
        "w_uv": _he(ks[3], (mla.kv_lora_rank, n_heads, mla.v_head_dim),
                    mla.kv_lora_rank),
        "wo": _he(ks[4], (n_heads, mla.v_head_dim, d_model),
                  n_heads * mla.v_head_dim),
    }


# ---------------------------------------------------------------------------
# blockwise attention (reference/XLA path)
# ---------------------------------------------------------------------------
def _band_count(nq: int, target: int = 8) -> int:
    """Largest divisor of nq not exceeding target."""
    best = 1
    for b in range(1, min(target, nq) + 1):
        if nq % b == 0:
            best = b
    return best


def chunked_attention(q, k, v, *, causal: bool, window: Optional[int] = None,
                      cap: Optional[float] = None, q_chunk: int = 512,
                      kv_chunk: int = 1024, scale: Optional[float] = None,
                      head_mask=None):
    """q: (B,Sq,H,D) k,v: (B,Sk,KV,D). Returns (B,Sq,H,D).

    GQA is handled by *expanding* K/V to the full H heads (a per-shard
    slice-broadcast) rather than reshaping H into (KV, G): splitting a
    TP-sharded head dim makes GSPMD give up and replicate the whole
    attention computation across the 'model' axis.

    head_mask: optional (H,) 0/1 — CFL elastic attention width.
    """
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    vD = v.shape[-1]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    assert Sq % q_chunk == 0 and Sk % kv_chunk == 0
    # the left-sliced local branch assumes causality; non-causal windows
    # (unused by any arch) fall through to the masked global branch
    use_local = window is not None and causal and (window + q_chunk) <= Sk

    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)

    qr = q.reshape(B, Sq // q_chunk, q_chunk, H, D)

    def one_q_chunk(qi, qblk, n_kv):
        # qblk: (B, qc, H, D); absolute q positions:
        q_pos = qi * q_chunk + jnp.arange(q_chunk)

        def scores(kblk):
            s = jnp.einsum("bqhd,bshd->bhqs", qblk.astype(jnp.float32),
                           kblk.astype(jnp.float32)) * scale
            return softcap(s, cap)

        if use_local:
            # local attention: only the KV slice [q_start-window, q_end)
            span = window + q_chunk
            start = jnp.clip(qi * q_chunk + q_chunk - span, 0, Sk - span)
            kblk = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
            vblk = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            k_pos = start + jnp.arange(span)
            s = scores(kblk)
            mask = (k_pos[None, :] <= q_pos[:, None]) if causal else (
                jnp.ones((q_chunk, span), bool))
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
            s = jnp.where(mask[None, None], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqs,bshd->bqhd", p, vblk.astype(jnp.float32))
            return o

        # global attention: online softmax over kv chunks
        def body(carry, kv_i):
            m, l, o = carry
            kblk = jax.lax.dynamic_slice_in_dim(k, kv_i * kv_chunk,
                                                kv_chunk, 1)
            vblk = jax.lax.dynamic_slice_in_dim(v, kv_i * kv_chunk,
                                                kv_chunk, 1)
            k_pos = kv_i * kv_chunk + jnp.arange(kv_chunk)
            s = scores(kblk)                    # (B,H,qc,kc)
            if causal:
                mask = k_pos[None, :] <= q_pos[:, None]
                s = jnp.where(mask[None, None], s, NEG_INF)
            if window is not None:
                mask = (q_pos[:, None] - k_pos[None, :]) < window
                s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            o_new = o * alpha[..., None] + jnp.einsum(
                "bhqs,bshd->bhqd", p, vblk.astype(jnp.float32))
            return (m_new, l_new, o_new), None

        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        o0 = jnp.zeros((B, H, q_chunk, vD), jnp.float32)
        # checkpoint each KV step: the backward recomputes the (bq,bk) score
        # block from q/k/v instead of saving S^2 softmax residuals (flash-
        # attention backward semantics)
        (m, l, o), _ = jax.lax.scan(
            jax.checkpoint(body, prevent_cse=False), (m0, l0, o0),
            jnp.arange(n_kv))
        o = o / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(o, 1, 2)  # (B, qc, H, D)

    # causal banding: q-chunk bands stop their KV scan at the band's
    # diagonal — a static ~2x FLOP cut on the causal upper triangle
    # (the pure-XLA analogue of flash-attention block skipping).
    nq = Sq // q_chunk
    n_bands = _band_count(nq) if (causal and not use_local) else 1
    outs = []
    qr_t = jnp.moveaxis(qr, 1, 0)
    for b in range(n_bands):
        lo = b * nq // n_bands
        hi = (b + 1) * nq // n_bands
        n_kv_b = min(-(-(hi * q_chunk) // kv_chunk), Sk // kv_chunk)
        out_b = jax.lax.map(
            lambda args, n=n_kv_b: one_q_chunk(args[0], args[1], n),
            (jnp.arange(lo, hi), qr_t[lo:hi]))
        outs.append(out_b)
    out = jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, H, vD)
    if head_mask is not None:
        out = out * head_mask[None, None, :, None].astype(out.dtype)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# sharded attention dispatch: head-parallel shard_map over 'model'
# ---------------------------------------------------------------------------
def dispatch_attention(q, k, v, **kw):
    """Head-parallel attention: q heads shard over 'model'; K/V either
    shard with them (KV divisible by the axis) or stay replicated with a
    local per-head gather (GQA with few KV heads). Explicit shard_map —
    GSPMD's own partitioning of the blockwise loop replicates the whole
    attention computation otherwise. Falls back to plain chunked_attention
    without a mesh."""
    from jax.sharding import PartitionSpec as P
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    mesh = jax.sharding.get_abstract_mesh()
    names = set(mesh.axis_names)
    m = mesh.shape["model"] if "model" in names else 1
    if m <= 1 or H % m != 0 or Sq == 1:
        return chunked_attention(q, k, v, **kw)
    head_mask = kw.pop("head_mask", None)

    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    bspec = dp_axes if (dp > 1 and B % dp == 0) else None
    H_loc = H // m
    kv_sharded = KV % m == 0

    def f(ql, kl, vl):
        if not kv_sharded:
            r = jax.lax.axis_index("model")
            idx = (r * H_loc + jnp.arange(H_loc)) // G
            kl = jnp.take(kl, idx, axis=2)
            vl = jnp.take(vl, idx, axis=2)
        return chunked_attention(ql, kl, vl, **kw)

    qspec = P(bspec, None, "model", None)
    kvspec = qspec if kv_sharded else P(bspec, None, None, None)
    out = jax.shard_map(f, mesh=mesh,
                        in_specs=(qspec, kvspec, kvspec),
                        out_specs=qspec, check_vma=False)(q, k, v)
    if head_mask is not None:
        out = out * head_mask[None, None, :, None].astype(out.dtype)
    return out


# ---------------------------------------------------------------------------
# GQA full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------
def gqa_forward(p, x, positions, *, n_heads, n_kv, head_dim, rope_theta,
                causal=True, window=None, cap=None, qk_norm=False,
                norm_eps=1e-6, head_mask=None, kernel=None,
                cache_len=None, cache_dtype=None):
    """``cache_len``: when set, also return the post-rope K/V packed into a
    ring-buffer :class:`KVCache` of that many slots — the fused one-shot
    prefill path (cache state identical to stepwise ``gqa_decode``)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if qk_norm:
        q = rmsnorm(p["q_norm"], q, norm_eps)
        k = rmsnorm(p["k_norm"], k, norm_eps)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    if kernel is not None:
        # elastic flash kernel: the head prefix is skipped inside the
        # kernel (fwd + bwd), not masked after the fact
        o = kernel(q, k, v, causal=causal, window=window, cap=cap,
                   head_mask=head_mask)
    else:
        o = dispatch_attention(q, k, v, causal=causal, window=window,
                               cap=cap, head_mask=head_mask)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    if cache_len is None:
        return out
    return out, _ring_pack(k, v, cache_len, cache_dtype or k.dtype)


def _ring_pack(k, v, C: int, dtype):
    """Pack full-prefill K/V (B,S,KV,D) into the ring-buffer cache layout:
    slot j holds the *last* prompt position ≡ j (mod C) — exactly the state
    stepwise ``gqa_decode`` leaves after writing positions 0..S-1."""
    S = k.shape[1]
    slots = jnp.arange(C)
    idx = (S - 1) - ((S - 1 - slots) % C)
    valid = (idx >= 0)[None, :, None, None]
    gather = jnp.maximum(idx, 0)
    kc = jnp.where(valid, jnp.take(k, gather, axis=1), 0).astype(dtype)
    vc = jnp.where(valid, jnp.take(v, gather, axis=1), 0).astype(dtype)
    return KVCache(kc, vc)


# ---------------------------------------------------------------------------
# GQA decode (one token, ring-buffer cache for sliding windows)
# ---------------------------------------------------------------------------
class KVCache(NamedTuple):
    k: jax.Array  # (B, C, KV, D) — C = min(max_len, window)
    v: jax.Array


def gqa_cache_init(batch, max_len, n_kv, head_dim, window=None,
                   dtype=jnp.bfloat16):
    c = min(max_len, window) if window else max_len
    shape = (batch, c, n_kv, head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def gqa_decode(p, x, cache: KVCache, pos, *, n_heads, n_kv, head_dim,
               rope_theta, window=None, cap=None, qk_norm=False,
               norm_eps=1e-6, head_mask=None):
    """x: (B,1,d). pos: scalar int32 (current position). Returns (out, cache).

    head_mask: optional (H,) 0/1 query-head prefix (CFL elastic attention
    width) — masked heads' outputs are zeroed before ``wo``, so the masked
    parent decode equals the head-sliced submodel's (its ``wo`` keeps only
    the kept heads' rows)."""
    B = x.shape[0]
    C = cache.k.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if qk_norm:
        q = rmsnorm(p["q_norm"], q, norm_eps)
        k = rmsnorm(p["k_norm"], k, norm_eps)
    posv = jnp.full((B, 1), pos, jnp.int32)
    q = apply_rope(q, posv, rope_theta)
    k = apply_rope(k, posv, rope_theta)

    slot = pos % C
    ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype),
                                             slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype),
                                             slot, axis=1)

    G = n_heads // n_kv
    qr = q.reshape(B, n_kv, G, head_dim)
    s = jnp.einsum("bkgd,bskd->bkgs", qr.astype(jnp.float32),
                   ck.astype(jnp.float32)) / math.sqrt(head_dim)
    s = softcap(s, cap)
    # slot s holds position pos - ((pos - s) mod C); valid iff >= 0
    slots = jnp.arange(C)
    slot_pos = pos - ((pos - slots) % C)
    s = jnp.where(slot_pos[None, None, None, :] >= 0, s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", pattn, cv.astype(jnp.float32))
    o = o.reshape(B, 1, n_heads, head_dim).astype(x.dtype)
    if head_mask is not None:
        o = o * head_mask[None, None, :, None].astype(o.dtype)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return out, KVCache(ck, cv)


# ---------------------------------------------------------------------------
# MLA (deepseek-v2): full forward + absorbed decode on compressed cache
# ---------------------------------------------------------------------------
class MLACache(NamedTuple):
    c_kv: jax.Array   # (B, C, kv_lora)
    k_rope: jax.Array  # (B, C, qk_rope)


def mla_cache_init(batch, max_len, mla, dtype=jnp.bfloat16):
    return MLACache(jnp.zeros((batch, max_len, mla.kv_lora_rank), dtype),
                    jnp.zeros((batch, max_len, mla.qk_rope_dim), dtype))


def _mla_qkv(p, x, positions, mla, norm_eps):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q_nope, q_rope = jnp.split(q, [mla.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, 10_000.0)
    dkv = x @ p["w_dkv"].astype(x.dtype)
    c_kv, k_rope = jnp.split(dkv, [mla.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(p["kv_norm"], c_kv, norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, 10_000.0)[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def mla_forward(p, x, positions, *, n_heads, mla, causal=True, norm_eps=1e-6,
                head_mask=None, cache_len=None, cache_dtype=None):
    """``cache_len``: when set, also return the compressed-latent cache
    (positions 0..S-1 filled, the rest zeros) — the fused prefill path."""
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, positions, mla, norm_eps)
    k_nope = jnp.einsum("bsc,chk->bshk", c_kv, p["w_uk"].astype(x.dtype))
    v = jnp.einsum("bsc,chk->bshk", c_kv, p["w_uv"].astype(x.dtype))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  k_nope.shape[:3] + (mla.qk_rope_dim,))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    # v head dim may differ from qk dim (handled by the blockwise path)
    o = dispatch_attention(q, k, v, causal=causal, head_mask=head_mask,
                           scale=1.0 / math.sqrt(mla.qk_nope_dim +
                                                 mla.qk_rope_dim))
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    if cache_len is None:
        return out
    dt = cache_dtype or c_kv.dtype
    S = x.shape[1]
    ck = jnp.zeros((x.shape[0], cache_len, mla.kv_lora_rank), dt)
    cr = jnp.zeros((x.shape[0], cache_len, mla.qk_rope_dim), dt)
    ck = jax.lax.dynamic_update_slice_in_dim(ck, c_kv.astype(dt), 0, axis=1)
    cr = jax.lax.dynamic_update_slice_in_dim(cr, k_rope.astype(dt), 0,
                                             axis=1)
    return out, MLACache(ck, cr)


def mla_decode(p, x, cache: MLACache, pos, *, n_heads, mla, norm_eps=1e-6,
               head_mask=None):
    """Absorbed MLA decode: attention runs in the compressed latent space."""
    B = x.shape[0]
    posv = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, posv, mla, norm_eps)
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache.c_kv, c_kv.astype(cache.c_kv.dtype), pos, axis=1)
    cr = jax.lax.dynamic_update_slice_in_dim(
        cache.k_rope, k_rope.astype(cache.k_rope.dtype), pos, axis=1)
    # absorb W_uk into q:  (B,1,H,nope) @ (lora,H,nope) -> (B,H,lora)
    q_abs = jnp.einsum("bhk,chk->bhc", q_nope[:, 0],
                       p["w_uk"].astype(x.dtype))
    s = jnp.einsum("bhc,bsc->bhs", q_abs.astype(jnp.float32),
                   ck.astype(jnp.float32))
    s += jnp.einsum("bhk,bsk->bhs", q_rope[:, 0].astype(jnp.float32),
                    cr.astype(jnp.float32))
    s /= math.sqrt(mla.qk_nope_dim + mla.qk_rope_dim)
    valid = jnp.arange(ck.shape[1])[None, None, :] <= pos
    s = jnp.where(valid, s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o_c = jnp.einsum("bhs,bsc->bhc", pr, ck.astype(jnp.float32))
    o = jnp.einsum("bhc,chk->bhk", o_c.astype(x.dtype),
                   p["w_uv"].astype(x.dtype))
    if head_mask is not None:
        o = o * head_mask[None, :, None].astype(o.dtype)
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"].astype(x.dtype))[:, None, :]
    return out, MLACache(ck, cr)
