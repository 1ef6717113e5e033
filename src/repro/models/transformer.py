"""Segment-structured model assembly for every assigned architecture.

A model is a sequence of *segments* (homogeneous `lax.scan`-able layer
runs) — see configs.base.Segment. Supports:
  * dense / GQA / MLA attention blocks, sliding windows, softcaps, qk-norm
  * MoE blocks (sort-dispatch, shared experts)
  * Mamba2 (SSD) blocks, hybrid shared-attention interleave (zamba2)
  * encoder-only (hubert) and modality frontends (VLM / audio stubs)
  * full-sequence forward (train / prefill) and cached single-token decode
  * CFL elastic masks (d_ff / heads / experts) for gated submodels
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, Segment
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (embed, embed_init, layernorm, layernorm_init,
                                 mlp, mlp_init, rmsnorm, rmsnorm_init,
                                 softcap, _he)

Params = Dict[str, Any]


def _norm_init(cfg: ModelConfig, d):
    return layernorm_init(d) if cfg.norm_type == "layernorm" else rmsnorm_init(d)


def _norm(cfg: ModelConfig, p, x):
    if cfg.norm_type == "layernorm":
        return layernorm(p, x, cfg.norm_eps)
    return rmsnorm(p, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _attn_block_init(key, cfg: ModelConfig, use_moe: bool,
                     d_ff: Optional[int] = None):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p = {"ln1": _norm_init(cfg, d), "ln2": _norm_init(cfg, d)}
    if cfg.attn_type == "mla":
        p["attn"] = attn_lib.mla_init(ks[0], d, cfg.n_heads, cfg.mla)
    else:
        p["attn"] = attn_lib.gqa_init(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                      cfg.head_dim, cfg.qk_norm)
    if use_moe:
        p["moe"] = moe_lib.moe_init(ks[1], d, cfg.moe, cfg.mlp_gated)
    else:
        p["mlp"] = mlp_init(ks[1], d, d_ff or cfg.d_ff, cfg.mlp_gated)
    if cfg.post_norms:
        p["post_ln1"] = _norm_init(cfg, d)
        p["post_ln2"] = _norm_init(cfg, d)
    return p


def _stacked(init_fn, key, n):
    """vmap an init over layer index -> stacked params (n leading)."""
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_params(key: jax.Array, cfg: ModelConfig,
                dtype=jnp.float32) -> Params:
    keys = jax.random.split(key, len(cfg.segments) + 4)
    p: Params = {}
    p["embed"] = embed_init(keys[0], cfg.padded_vocab, cfg.d_model)
    segs = []
    for i, seg in enumerate(cfg.segments):
        k = keys[i + 1]
        if seg.kind == "attn":
            segs.append({"blocks": _stacked(
                lambda kk, s=seg: _attn_block_init(kk, cfg, s.use_moe),
                k, seg.n_layers)})
        elif seg.kind == "attn_pair":
            k1, k2 = jax.random.split(k)
            segs.append({
                "local": _stacked(
                    lambda kk, s=seg: _attn_block_init(kk, cfg, s.use_moe),
                    k1, seg.n_layers),
                "global": _stacked(
                    lambda kk, s=seg: _attn_block_init(kk, cfg, s.use_moe),
                    k2, seg.n_layers)})
        elif seg.kind == "ssm":
            segs.append({"blocks": _stacked(
                lambda kk: {"ln": _norm_init(cfg, cfg.d_model),
                            "mamba": ssm_lib.mamba_init(kk, cfg.d_model,
                                                        cfg.ssm)},
                k, seg.n_layers)})
        else:
            raise ValueError(seg.kind)
    p["segments"] = segs
    if cfg.shared_attn_d_ff:
        p["shared_attn"] = _attn_block_init(
            keys[-3], cfg, use_moe=False, d_ff=cfg.shared_attn_d_ff)
    p["final_norm"] = _norm_init(cfg, cfg.d_model)
    if not cfg.tie_embeddings:
        p["lm_head"] = {"w": _he(keys[-2], (cfg.d_model, cfg.padded_vocab),
                                 cfg.d_model)}
    if dtype != jnp.float32:
        p = jax.tree.map(lambda a: a.astype(dtype)
                         if a.dtype == jnp.float32 else a, p)
    return p


# ---------------------------------------------------------------------------
# full-sequence block application
# ---------------------------------------------------------------------------
def _ckpt(fn):
    """Inner remat: recompute attention/MLP/SSD internals in the backward
    pass instead of saving them (flash-attention-style; keeps the per-group
    activation transient at O(B·S·d) instead of O(B·S·S·H) / O(B·S·f))."""
    return jax.checkpoint(fn, prevent_cse=False)


def _apply_attn_block(bp, x, positions, cfg: ModelConfig, window, use_moe,
                      masks, kernels, gate=None, cache_len=None,
                      cache_dtype=None):
    """``gate`` (scalar 0/1) multiplies the block's residual contributions —
    the CFL depth-elastic dimension in parent coordinates: with gate=0 the
    block is exactly the identity (pure additive residual), matching an
    extracted submodel that dropped this layer.

    ``cache_len``: fused-prefill mode — the attention call also returns its
    decode cache (KV ring buffer / MLA latents) and the block returns
    ``(x, aux, cache)``; remat is skipped (prefill is inference-only)."""
    h = _norm(cfg, bp["ln1"], x)
    head_mask = None if masks is None else masks.get("heads")
    cache = None
    if cfg.attn_type == "mla":
        def attn_fn(p_, h_):
            return attn_lib.mla_forward(
                p_, h_, positions, n_heads=cfg.n_heads, mla=cfg.mla,
                causal=cfg.causal, norm_eps=cfg.norm_eps,
                head_mask=head_mask, cache_len=cache_len,
                cache_dtype=cache_dtype)
    else:
        kern = None if kernels is None else kernels.get("attention")
        kv_len = None if cache_len is None else (
            min(cache_len, window) if window else cache_len)

        def attn_fn(p_, h_):
            return attn_lib.gqa_forward(
                p_, h_, positions, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                causal=cfg.causal, window=window, cap=cfg.attn_softcap,
                qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps,
                head_mask=head_mask, kernel=kern, cache_len=kv_len,
                cache_dtype=cache_dtype)
    if cache_len is None:
        a = _ckpt(attn_fn)(bp["attn"], h)
    else:
        a, cache = attn_fn(bp["attn"], h)
    if cfg.post_norms:
        a = _norm(cfg, bp["post_ln1"], a)
    if gate is not None:
        a = a * gate.astype(a.dtype)
    x = x + a
    h = _norm(cfg, bp["ln2"], x)
    aux = jnp.zeros((), jnp.float32)
    if use_moe:
        expert_mask = None if masks is None else masks.get("experts")
        moe_kern = None if kernels is None else kernels.get("moe")
        m, moe_aux = _ckpt(lambda p_, h_: moe_lib.moe_forward(
            p_, h_, cfg.moe, act=cfg.act, expert_mask=expert_mask,
            kernel=moe_kern))(bp["moe"], h)
        aux = moe_aux["aux_loss"] + moe_aux["z_loss"]
    else:
        width_mask = None if masks is None else masks.get("ff")
        mlp_kern = None if kernels is None else kernels.get("mlp")
        m = _ckpt(lambda p_, h_: mlp(p_, h_, cfg.act,
                                     width_mask=width_mask,
                                     kernel=mlp_kern))(bp["mlp"], h)
    if cfg.post_norms:
        m = _norm(cfg, bp["post_ln2"], m)
    if gate is not None:
        m = m * gate.astype(m.dtype)
        aux = aux * gate.astype(aux.dtype)
    if cache_len is None:
        return x + m, aux
    return x + m, aux, cache


def _apply_ssm_block(bp, x, cfg: ModelConfig, masks, kernels, gate=None,
                     cache_len=None, cache_dtype=None):
    h = _norm(cfg, bp["ln"], x)
    head_mask = None if masks is None else masks.get("ssm_heads")
    kern = None if kernels is None else kernels.get("ssd")
    if cache_len is not None:
        y, cache = ssm_lib.mamba_forward(
            bp["mamba"], h, cfg.ssm, norm_eps=cfg.norm_eps,
            head_mask=head_mask, kernel=kern, return_cache=True,
            cache_dtype=cache_dtype)
        if gate is not None:
            y = y * gate.astype(y.dtype)
        return x + y, jnp.zeros((), jnp.float32), cache
    y = _ckpt(lambda p_, h_: ssm_lib.mamba_forward(
        p_, h_, cfg.ssm, norm_eps=cfg.norm_eps, head_mask=head_mask,
        kernel=kern))(bp["mamba"], h)
    if gate is not None:
        y = y * gate.astype(y.dtype)
    return x + y, jnp.zeros((), jnp.float32)


def _segment_forward(seg_p, seg: Segment, x, positions, cfg: ModelConfig,
                     masks, kernels, remat: bool, depth_mask=None):
    """Scan a segment over its stacked layer params.

    depth_mask: optional (n_layers,) 0/1 per-layer gates (CFL depth
    elasticity) — scanned alongside the layer params; when None the
    original ungated program is emitted (production train path unchanged).
    """
    gated = depth_mask is not None

    def split(inp):
        return inp if gated else (inp, None)

    def attn_body(carry, inp):
        x, aux = carry
        layer_p, g = split(inp)
        window = seg.sliding_window or cfg.sliding_window
        x, a = _apply_attn_block(layer_p, x, positions, cfg, window,
                                 seg.use_moe, masks, kernels, gate=g)
        return (x, aux + a), None

    def pair_body(carry, inp):
        x, aux = carry
        layer_p, g = split(inp)
        lp, gp = layer_p["local"], layer_p["global"]
        x, a1 = _apply_attn_block(lp, x, positions, cfg,
                                  seg.pair_local_window, seg.use_moe, masks,
                                  kernels, gate=g)
        x, a2 = _apply_attn_block(gp, x, positions, cfg, None, seg.use_moe,
                                  masks, kernels, gate=g)
        return (x, aux + a1 + a2), None

    def ssm_body(carry, inp):
        x, aux = carry
        layer_p, g = split(inp)
        x, a = _apply_ssm_block(layer_p, x, cfg, masks, kernels, gate=g)
        return (x, aux + a), None

    if seg.kind == "attn":
        body, xs = attn_body, seg_p["blocks"]
    elif seg.kind == "attn_pair":
        body, xs = pair_body, {"local": seg_p["local"],
                               "global": seg_p["global"]}
    else:
        body, xs = ssm_body, seg_p["blocks"]
    if gated:
        xs = (xs, depth_mask)
    carry0 = (x, jnp.zeros((), jnp.float32))
    n = seg.n_layers
    if remat:
        # two-level remat scan: outer scan over layer *groups* with a
        # checkpoint boundary, inner scan over the g layers of a group.
        # Saved group carries are sequence-sharded over 'model' (cheap), so
        # the group size is chosen small — the backward-recompute transient
        # (g layers of block internals alive at once) dominates, and pair
        # segments already hold two blocks per step.
        g = _remat_group(n)
        if seg.kind == "attn_pair":
            g = max(1, g // 2)
        if g >= 1:
            xs_g = jax.tree.map(
                lambda a: a.reshape((n // g, g) + a.shape[1:]), xs)

            def group_body(carry, gxs):
                (xc, auxc), _ = jax.lax.scan(body, carry, gxs)
                # sequence-parallel saved carry: the checkpointed residual
                # stream is sharded over 'model' on the sequence dim, so
                # saved activations cost B*S*d/(dp*tp) per group (Megatron-SP
                # style; XLA inserts the AG/RS pair at the boundary)
                xc = _constrain(xc, ("pod", "data"), "model", None)
                return (xc, auxc), None

            (x, aux), _ = jax.lax.scan(
                jax.checkpoint(group_body, prevent_cse=False), carry0, xs_g)
            return x, aux
    (x, aux), _ = jax.lax.scan(body, carry0, xs)
    return x, aux


def _remat_group(n: int) -> int:
    """Largest divisor of n not exceeding ~sqrt(n)."""
    import math
    target = int(math.isqrt(n)) + 1
    best = 1
    for g in range(1, target + 1):
        if n % g == 0:
            best = g
    return best


def embed_inputs(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
                 dtype=None):
    """Returns x (B,S,d). Handles modality frontends (stub embeddings)."""
    if cfg.frontend == "audio":
        x = batch["frames"]                       # (B,S,d) precomputed
    elif cfg.frontend == "vision":
        tok = embed(params["embed"], batch["tokens"], scale=cfg.embed_scale)
        img = batch["image_embeds"].astype(tok.dtype)     # (B,F,d)
        F = img.shape[1]
        x = jnp.concatenate([img, tok[:, F:, :]], axis=1)
    else:
        x = embed(params["embed"], batch["tokens"], scale=cfg.embed_scale)
    if dtype is not None:
        x = x.astype(dtype)
    return x


def forward(params: Params, cfg: ModelConfig, batch: Dict[str, Any], *,
            masks=None, kernels=None, remat: bool = False,
            activation_dtype=None, last_only: bool = False,
            return_hidden: bool = False):
    """Full-sequence forward -> (logits (B,S,V), aux_loss scalar).

    Logits stay in the activation dtype — CE handles precision internally
    (upcasting the whole (B,S,V) tensor to fp32 would double the largest
    buffer in the model for no accuracy benefit in the loss reductions).
    """
    x = embed_inputs(params, cfg, batch, activation_dtype)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    aux = jnp.zeros((), jnp.float32)
    depth_masks = None if masks is None else masks.get("depth")
    # the shared (hybrid) block is kept whole by every submodel: its d_ff
    # differs from cfg.d_ff and its params are shared, so width/depth/head
    # masks must not leak into it
    shared_masks = None if masks is None else (
        {k: v for k, v in masks.items()
         if k not in ("ff", "depth", "heads")} or None)
    for si, (seg_p, seg) in enumerate(zip(params["segments"], cfg.segments)):
        dm = None if depth_masks is None else depth_masks[si]
        x, a = _segment_forward(seg_p, seg, x, positions, cfg, masks,
                                kernels, remat, depth_mask=dm)
        aux = aux + a
        if seg.shared_attn_after:
            x, a2 = _apply_attn_block(params["shared_attn"], x, positions,
                                      cfg, cfg.sliding_window, False,
                                      shared_masks, kernels)
            aux = aux + a2
    x = _norm(cfg, params["final_norm"], x)
    if return_hidden:
        return x, aux
    if last_only:
        x = x[:, -1:, :]
    logits = x @ _unembed_w(params, cfg)
    logits = _constrain(logits, ("pod", "data"), None, "model")
    return softcap(logits, cfg.final_softcap), aux


def _unembed_w(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"]["table"].T
    return params["lm_head"]["w"]


def _constrain(x, *spec):
    """Sharding constraint over the ambient abstract mesh: only names
    present in the mesh are kept (no-op on unmeshed single-device runs).
    Under a mesh a constraint that cannot be applied raises."""
    names = set(jax.sharding.get_abstract_mesh().axis_names)
    if not names:
        return x

    def fix(s):
        if isinstance(s, tuple):
            t = tuple(a for a in s if a in names)
            return t if t else None
        return s if (s is None or s in names) else None
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*[fix(s) for s in spec]))


@jax.custom_vjp
def _grad_dtype_barrier(x):
    """Identity whose backward casts the cotangent to the primal dtype —
    stops fp32 loss-side cotangents from materialising fp32 copies of
    bf16 activations through scan transposes."""
    return x


def _gdb_fwd(x):
    return x, jnp.zeros((0,), x.dtype)


def _gdb_bwd(tok, g):
    return (g.astype(tok.dtype),)


_grad_dtype_barrier.defvjp(_gdb_fwd, _gdb_bwd)


def chunked_softmax_xent(x, w, targets, mask, *, cap=None, chunk=256):
    """Fused unembed + CE, scanned over sequence chunks: the full (B,S,V)
    logits tensor is never materialised (the backward recomputes each
    chunk's logits from x and w — checkpointed scan body).

    x: (B,S,d) hidden states; w: (d,V); targets/mask: (B,S).
    Returns mean CE over mask.
    """
    B, S, d = x.shape
    cs = S
    for c in range(min(chunk, S), 0, -1):
        if S % c == 0:
            cs = c
            break
    nc = S // cs
    x = _grad_dtype_barrier(x)
    xr = jnp.moveaxis(x.reshape(B, nc, cs, d), 1, 0)
    tr = jnp.moveaxis(targets.reshape(B, nc, cs), 1, 0)
    mr = jnp.moveaxis(mask.reshape(B, nc, cs), 1, 0)

    def body(carry, inp):
        ce_sum, m_sum = carry
        xc, tc, mc = inp
        xc = _grad_dtype_barrier(xc)
        logits = xc @ w.astype(xc.dtype)
        logits = _constrain(logits, ("pod", "data"), None, "model")
        logits = softcap(logits, cap)
        lf = logits.astype(jnp.float32)
        mx = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
        shifted = lf - mx
        lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
        vio = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
        tgt = jnp.sum(jnp.where(vio == tc[..., None], shifted, 0.0), axis=-1)
        ce_sum = ce_sum + jnp.sum((lse - tgt) * mc)
        m_sum = m_sum + jnp.sum(mc)
        return (ce_sum, m_sum), None

    (ce_sum, m_sum), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False),
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xr, tr, mr))
    return ce_sum / jnp.maximum(m_sum, 1.0)


def cross_entropy(logits, targets, mask):
    """Vocab-sharding-friendly CE: no gather along the (possibly sharded)
    vocab dim — the target logit is extracted with an iota==target mask
    (partitions to a local select + psum), and reductions upcast
    per-element (fusable) instead of materialising fp32 logits."""
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    shifted = lf - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    tgt = jnp.sum(jnp.where(vocab_iota == targets[..., None], shifted, 0.0),
                  axis=-1)
    ce = (lse - tgt) * mask
    return jnp.sum(ce) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------
def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, Any], *,
            masks=None, kernels=None, remat: bool = False,
            activation_dtype=None):
    hidden, aux = forward(params, cfg, batch, masks=masks, kernels=kernels,
                          remat=remat, activation_dtype=activation_dtype,
                          return_hidden=True)
    w = _unembed_w(params, cfg)
    if cfg.encoder_only:
        labels = batch["labels"]                 # (B,S)
        mask = batch.get("loss_mask",
                         jnp.ones(labels.shape, jnp.float32))
        ce = chunked_softmax_xent(hidden, w, labels, mask,
                                  cap=cfg.final_softcap)
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        # shift via roll + masked last position (keeps S chunkable)
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1)
        pos = jnp.arange(S)[None, :]
        mask = (pos < S - 1).astype(jnp.float32)
        if cfg.frontend == "vision":
            F = batch["image_embeds"].shape[1]
            mask = mask * (pos >= F).astype(jnp.float32)
        mask = jnp.broadcast_to(mask, (B, S))
        ce = chunked_softmax_xent(hidden, w, targets, mask,
                                  cap=cfg.final_softcap)
    return ce + aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# decode (single token, cached)
# ---------------------------------------------------------------------------
class DecodeCaches(NamedTuple):
    segments: Tuple[Any, ...]     # per-segment stacked caches
    shared: Any                   # per-site caches for the shared attn block


def _stack_cache(single, n):
    return jax.tree.map(lambda a: jnp.zeros((n,) + a.shape, a.dtype), single)


def init_decode_caches(cfg: ModelConfig, batch: int, max_len: int,
                       dtype=jnp.bfloat16) -> DecodeCaches:
    segs = []
    n_shared_sites = sum(1 for s in cfg.segments if s.shared_attn_after)
    for seg in cfg.segments:
        if seg.kind == "attn":
            window = seg.sliding_window or cfg.sliding_window
            if cfg.attn_type == "mla":
                single = attn_lib.mla_cache_init(batch, max_len, cfg.mla,
                                                 dtype)
            else:
                single = attn_lib.gqa_cache_init(
                    batch, max_len, cfg.n_kv_heads, cfg.head_dim, window,
                    dtype)
            segs.append(_stack_cache(single, seg.n_layers))
        elif seg.kind == "attn_pair":
            loc = _stack_cache(attn_lib.gqa_cache_init(
                batch, max_len, cfg.n_kv_heads, cfg.head_dim,
                seg.pair_local_window, dtype), seg.n_layers)
            glob = _stack_cache(attn_lib.gqa_cache_init(
                batch, max_len, cfg.n_kv_heads, cfg.head_dim, None, dtype),
                seg.n_layers)
            segs.append({"local": loc, "global": glob})
        else:
            segs.append(_stack_cache(ssm_lib.ssm_cache_init(
                batch, cfg.d_model, cfg.ssm, dtype), seg.n_layers))
    shared = None
    if n_shared_sites:
        shared = _stack_cache(attn_lib.gqa_cache_init(
            batch, max_len, cfg.n_kv_heads, cfg.head_dim,
            cfg.sliding_window, dtype), n_shared_sites)
    return DecodeCaches(tuple(segs), shared)


def _decode_attn_block(bp, x, cache, pos, cfg: ModelConfig, window,
                       masks=None, kernels=None, gate=None):
    h = _norm(cfg, bp["ln1"], x)
    head_mask = None if masks is None else masks.get("heads")
    if cfg.attn_type == "mla":
        a, cache = attn_lib.mla_decode(bp["attn"], h, cache, pos,
                                       n_heads=cfg.n_heads, mla=cfg.mla,
                                       norm_eps=cfg.norm_eps,
                                       head_mask=head_mask)
    else:
        a, cache = attn_lib.gqa_decode(
            bp["attn"], h, cache, pos, n_heads=cfg.n_heads,
            n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta, window=window, cap=cfg.attn_softcap,
            qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps, head_mask=head_mask)
    if cfg.post_norms:
        a = _norm(cfg, bp["post_ln1"], a)
    if gate is not None:
        a = a * gate.astype(a.dtype)
    x = x + a
    h = _norm(cfg, bp["ln2"], x)
    if "moe" in bp:
        expert_mask = None if masks is None else masks.get("experts")
        moe_kern = None if kernels is None else kernels.get("moe")
        m, _ = moe_lib.moe_forward(bp["moe"], h, cfg.moe, act=cfg.act,
                                   expert_mask=expert_mask, kernel=moe_kern)
    else:
        width_mask = None if masks is None else masks.get("ff")
        mlp_kern = None if kernels is None else kernels.get("mlp")
        m = mlp(bp["mlp"], h, cfg.act, width_mask=width_mask,
                kernel=mlp_kern)
    if cfg.post_norms:
        m = _norm(cfg, bp["post_ln2"], m)
    if gate is not None:
        m = m * gate.astype(m.dtype)
    return x + m, cache


def decode_step(params: Params, cfg: ModelConfig, caches: DecodeCaches,
                token, pos, activation_dtype=None, masks=None, kernels=None):
    """token: (B,1) int32; pos: scalar int32. -> (logits (B,V), caches).

    ``masks``/``kernels`` mirror :func:`forward`'s elastic surface on the
    decode path: per-dimension 0/1 fwd masks gate heads / experts / d_ff /
    ssm-heads / depth in parent coordinates so a masked decode matches the
    extracted submodel's decode exactly (the serving subsystem relies on
    this to batch tenants with different specs in one program)."""
    x = embed(params["embed"], token, scale=cfg.embed_scale)
    if activation_dtype is not None:
        x = x.astype(activation_dtype)
    depth_masks = None if masks is None else masks.get("depth")
    # the shared (hybrid) block is kept whole by every submodel — see forward
    shared_masks = None if masks is None else (
        {k: v for k, v in masks.items()
         if k not in ("ff", "depth", "heads")} or None)
    new_segs = []
    shared_idx = 0
    new_shared = caches.shared
    for si, (seg_p, seg, seg_c) in enumerate(zip(
            params["segments"], cfg.segments, caches.segments)):
        dm = None if depth_masks is None else depth_masks[si]
        gated = dm is not None

        def split(inp):
            return inp if gated else (inp[0], inp[1], None)

        if seg.kind == "ssm":
            head_mask = None if masks is None else masks.get("ssm_heads")

            def body(x, inp):
                lp, lc, g = split(inp)
                h = _norm(cfg, lp["ln"], x)
                y, lc = ssm_lib.mamba_decode(lp["mamba"], h, lc, cfg.ssm,
                                             norm_eps=cfg.norm_eps,
                                             head_mask=head_mask)
                if g is not None:
                    y = y * g.astype(y.dtype)
                return x + y, lc
            xs = (seg_p["blocks"], seg_c, dm) if gated \
                else (seg_p["blocks"], seg_c)
            x, nc = jax.lax.scan(body, x, xs)
            new_segs.append(nc)
        elif seg.kind == "attn":
            window = seg.sliding_window or cfg.sliding_window

            def body(x, inp, window=window):
                lp, lc, g = split(inp)
                return _decode_attn_block(lp, x, lc, pos, cfg, window,
                                          masks, kernels, gate=g)
            xs = (seg_p["blocks"], seg_c, dm) if gated \
                else (seg_p["blocks"], seg_c)
            x, nc = jax.lax.scan(body, x, xs)
            new_segs.append(nc)
        else:  # attn_pair
            def body(x, inp):
                lp, lc, g = split(inp)
                x, c_loc = _decode_attn_block(lp["local"], x, lc["local"],
                                              pos, cfg,
                                              seg.pair_local_window,
                                              masks, kernels, gate=g)
                x, c_glob = _decode_attn_block(lp["global"], x, lc["global"],
                                               pos, cfg, None,
                                               masks, kernels, gate=g)
                return x, {"local": c_loc, "global": c_glob}
            lp_all = {"local": seg_p["local"], "global": seg_p["global"]}
            xs = (lp_all, seg_c, dm) if gated else (lp_all, seg_c)
            x, nc = jax.lax.scan(body, x, xs)
            new_segs.append(nc)
        if seg.shared_attn_after:
            site_cache = jax.tree.map(lambda a: a[shared_idx], new_shared)
            x, site_cache = _decode_attn_block(params["shared_attn"], x,
                                               site_cache, pos, cfg,
                                               cfg.sliding_window,
                                               shared_masks, kernels)
            new_shared = jax.tree.map(
                lambda full, upd: full.at[shared_idx].set(upd),
                new_shared, site_cache)
            shared_idx += 1
    x = _norm(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["table"].T.astype(x.dtype)
    else:
        logits = x @ params["lm_head"]["w"].astype(x.dtype)
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits[:, 0], DecodeCaches(tuple(new_segs), new_shared)


# ---------------------------------------------------------------------------
# fused prefill (full forward that also fills DecodeCaches in one program)
# ---------------------------------------------------------------------------
def _segment_prefill(seg_p, seg: Segment, x, positions, cfg: ModelConfig,
                     masks, kernels, depth_mask, max_len, cache_dtype):
    """Scan the segment's layers, emitting each layer's decode cache as a
    stacked ys output — the (n_layers, B, ...) layout `_stack_cache` uses."""
    gated = depth_mask is not None

    def split(inp):
        return inp if gated else (inp, None)

    def attn_body(x, inp):
        layer_p, g = split(inp)
        window = seg.sliding_window or cfg.sliding_window
        x, _, c = _apply_attn_block(layer_p, x, positions, cfg, window,
                                    seg.use_moe, masks, kernels, gate=g,
                                    cache_len=max_len,
                                    cache_dtype=cache_dtype)
        return x, c

    def pair_body(x, inp):
        layer_p, g = split(inp)
        x, _, cl = _apply_attn_block(layer_p["local"], x, positions, cfg,
                                     seg.pair_local_window, seg.use_moe,
                                     masks, kernels, gate=g,
                                     cache_len=max_len,
                                     cache_dtype=cache_dtype)
        x, _, cg = _apply_attn_block(layer_p["global"], x, positions, cfg,
                                     None, seg.use_moe, masks, kernels,
                                     gate=g, cache_len=max_len,
                                     cache_dtype=cache_dtype)
        return x, {"local": cl, "global": cg}

    def ssm_body(x, inp):
        layer_p, g = split(inp)
        x, _, c = _apply_ssm_block(layer_p, x, cfg, masks, kernels, gate=g,
                                   cache_len=max_len,
                                   cache_dtype=cache_dtype)
        return x, c

    if seg.kind == "attn":
        body, xs = attn_body, seg_p["blocks"]
    elif seg.kind == "attn_pair":
        body, xs = pair_body, {"local": seg_p["local"],
                               "global": seg_p["global"]}
    else:
        body, xs = ssm_body, seg_p["blocks"]
    if gated:
        xs = (xs, depth_mask)
    return jax.lax.scan(body, x, xs)


def prefill(params: Params, cfg: ModelConfig, tokens, max_len: int, *,
            masks=None, kernels=None, cache_dtype=jnp.float32,
            activation_dtype=None):
    """One-shot prefill: full forward over ``tokens`` (B,S) that fills
    `DecodeCaches` for positions 0..S-1 in a single compiled program.

    Returns ``(last_logits (B,V) fp32 softcapped, caches)`` — the caches
    (and logits) match running :func:`decode_step` over the prompt token by
    token, so generation continues at ``pos = S``."""
    x = embed(params["embed"], tokens, scale=cfg.embed_scale)
    if activation_dtype is not None:
        x = x.astype(activation_dtype)
    B, S = tokens.shape[0], tokens.shape[1]
    if S > max_len:
        raise ValueError(f"prompt length {S} exceeds max_len {max_len}")
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    depth_masks = None if masks is None else masks.get("depth")
    shared_masks = None if masks is None else (
        {k: v for k, v in masks.items()
         if k not in ("ff", "depth", "heads")} or None)
    new_segs = []
    site_caches = []
    for si, (seg_p, seg) in enumerate(zip(params["segments"], cfg.segments)):
        dm = None if depth_masks is None else depth_masks[si]
        x, seg_c = _segment_prefill(seg_p, seg, x, positions, cfg, masks,
                                    kernels, dm, max_len, cache_dtype)
        new_segs.append(seg_c)
        if seg.shared_attn_after:
            x, _, c = _apply_attn_block(params["shared_attn"], x, positions,
                                        cfg, cfg.sliding_window, False,
                                        shared_masks, kernels,
                                        cache_len=max_len,
                                        cache_dtype=cache_dtype)
            site_caches.append(c)
    shared = None
    if site_caches:
        shared = jax.tree.map(lambda *xs: jnp.stack(xs), *site_caches)
    x = _norm(cfg, params["final_norm"], x)
    x = x[:, -1:, :]
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["table"].T.astype(x.dtype)
    else:
        logits = x @ params["lm_head"]["w"].astype(x.dtype)
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits[:, 0], DecodeCaches(tuple(new_segs), shared)
