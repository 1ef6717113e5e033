"""Shared neural-net layers (pure functions over param pytrees).

Everything is a plain function ``f(params, x, ...)`` with params as nested
dicts of jnp arrays — no framework dependency, shard_map/pjit friendly.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def _he(key, shape, fan_in, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * (1.0 / math.sqrt(fan_in))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rmsnorm_init(d):
    return {"scale": jnp.zeros((d,), jnp.float32)}  # gemma-style (1+scale)


import functools


@functools.lru_cache(maxsize=None)
def _make_rmsnorm(eps: float):
    """RMSNorm with a hand-written backward: all wide tensors stay in the
    compute dtype; fp32 appears only in (…,1)-shaped reduction results.
    (The autodiff backward of the naive formulation materialises fp32
    copies of x — several GB per layer at production shapes.)"""

    def fwd_math(scale, x):
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True,
                       dtype=jnp.float32)
        inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
        g = (1.0 + scale).astype(x.dtype)
        return g * x * inv, inv

    @jax.custom_vjp
    def f(scale, x):
        return fwd_math(scale, x)[0]

    def fwd(scale, x):
        y, inv = fwd_math(scale, x)
        return y, (scale, x, inv)

    def bwd(res, dy):
        scale, x, inv = res
        g = (1.0 + scale).astype(x.dtype)
        xn = x * inv
        d_scale = jnp.sum((dy * xn).astype(jnp.float32),
                          axis=tuple(range(dy.ndim - 1)))
        # d_x = g*inv*dy - x*inv^3/n * sum(g*dy*x)
        n = x.shape[-1]
        s = jnp.sum(dy * g * x, axis=-1, keepdims=True,
                    dtype=jnp.float32).astype(x.dtype)
        d_x = g * inv * dy - xn * inv * inv * (s / n)
        return (d_scale.astype(scale.dtype), d_x)

    f.defvjp(fwd, bwd)
    return f


def rmsnorm(params, x, eps=1e-6):
    return _make_rmsnorm(float(eps))(params["scale"], x)


def layernorm_init(d):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(params, x, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True, dtype=jnp.float32)
    var = jnp.mean(jnp.square(x - mu.astype(x.dtype)), axis=-1,
                   keepdims=True, dtype=jnp.float32)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    y = (x - mu.astype(x.dtype)) * inv
    return params["scale"].astype(x.dtype) * y + \
        params["bias"].astype(x.dtype)


def groupnorm(x, groups, eps=1e-5):
    """Channel-last group norm for the CNN parent model (no learned affine
    here; affine lives in the conv that follows)."""
    b, h, w, c = x.shape
    xg = x.reshape(b, h, w, groups, c // groups).astype(jnp.float32)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    return xg.reshape(b, h, w, c).astype(x.dtype)


# ---------------------------------------------------------------------------
# activations / caps
# ---------------------------------------------------------------------------
# single source of truth for activation semantics — the tile-skipping
# kernels (repro.kernels) fuse these at the tile write and their oracles
# (kernels.ref) must match bit-for-bit, so all three import this table
ACTIVATIONS = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
}


def act_fn(name: str):
    try:
        return ACTIVATIONS[name]
    except KeyError:
        raise ValueError(name) from None


def softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(dim: int, theta: float):
    return theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (optionally gated / GLU)
# ---------------------------------------------------------------------------
def mlp_init(key, d_model, d_ff, gated=True):
    ks = jax.random.split(key, 3)
    p = {"wi": _he(ks[0], (d_model, d_ff), d_model),
         "wo": _he(ks[1], (d_ff, d_model), d_ff)}
    if gated:
        p["wg"] = _he(ks[2], (d_model, d_ff), d_model)
    return p


def mlp(params, x, act="silu", *, width_mask=None, kernel=None):
    """width_mask: optional (d_ff,) 0/1 mask — CFL elastic width.

    kernel: optional elastic-matmul op (repro.kernels.dispatch 'mlp'
    contract) — masked width tiles are then *skipped* (up/gate skip
    output tiles, the down projection skips contraction tiles) instead of
    multiplied by zero.
    """
    if kernel is not None:
        return kernel(params, x, act, width_mask)
    a = act_fn(act)
    h = x @ params["wi"].astype(x.dtype)
    if "wg" in params:
        h = a(x @ params["wg"].astype(x.dtype)) * h
    else:
        h = a(h)
    if width_mask is not None:
        h = h * width_mask.astype(h.dtype)
    return h @ params["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# embedding
# ---------------------------------------------------------------------------
def embed_init(key, vocab, d_model):
    return {"table": jax.random.normal(key, (vocab, d_model)) * 0.02}


def embed(params, ids, *, scale=False):
    t = params["table"]
    out = _embed_lookup(t, ids)
    if scale:
        out = out * math.sqrt(t.shape[-1])
    return out


def _embed_lookup(table, ids):
    """Vocab-sharded embedding lookup.

    Plain `take` from a vocab-sharded table makes GSPMD all-gather the full
    table (and produce a replicated fp32 scatter in the backward). Under a
    mesh with a 'model' axis we instead shard_map: each model rank gathers
    its local rows (masked), then a psum over 'model' reconstructs — the
    backward is a purely local scatter-add into the local shard.
    """
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.get_abstract_mesh()
    names = set(mesh.axis_names)
    V = table.shape[0]
    msize = mesh.shape["model"] if "model" in names else 1
    if "model" not in names or V % msize != 0 or ids.ndim != 2 \
            or ids.shape[1] == 1:
        return jnp.take(table, ids, axis=0)

    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    bspec = dp_axes if (dp > 1 and ids.shape[0] % dp == 0) else None

    def f(tbl, ids_l):
        r = jax.lax.axis_index("model")
        vloc = tbl.shape[0]
        local = ids_l - r * vloc
        ok = (local >= 0) & (local < vloc)
        out = jnp.take(tbl, jnp.clip(local, 0, vloc - 1), axis=0)
        out = jnp.where(ok[..., None], out, jnp.zeros((), out.dtype))
        return jax.lax.psum(out, "model")

    other = tuple(a for a in names if a not in ("model",) + (dp_axes or ()))
    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(P("model", None), P(bspec, None)),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(table, ids)


def unembed(params, x, *, cap=None):
    logits = x @ params["table"].T.astype(x.dtype)
    return softcap(logits, cap)
