"""Unit tests for the model substrate: norms, CE, attention path, MoE,
transformer submodel extraction."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import ARCHS, reduced
from repro.configs.base import MoEConfig
from repro.core import (TransformerSubSpec, extract_transformer,
                        full_transformer_spec, pad_transformer)
from repro.models import moe as moe_lib
from repro.models import transformer as T
from repro.models.attention import chunked_attention
from repro.models.layers import rmsnorm
from repro.kernels.ref import flash_attention_ref


# ---------------------------------------------------------------------------
def test_rmsnorm_custom_vjp_matches_autodiff():
    p = {"scale": jax.random.normal(jax.random.PRNGKey(0), (32,)) * 0.1}
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))

    def naive(p, x, eps=1e-6):
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        return ((1.0 + p["scale"]) * x.astype(jnp.float32) *
                jax.lax.rsqrt(var + eps))

    g1 = jax.grad(lambda p, x: jnp.sum(jnp.sin(rmsnorm(p, x))),
                  argnums=(0, 1))(p, x)
    g2 = jax.grad(lambda p, x: jnp.sum(jnp.sin(naive(p, x))),
                  argnums=(0, 1))(p, x)
    np.testing.assert_allclose(np.asarray(g1[0]["scale"]),
                               np.asarray(g2[0]["scale"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(g1[1]), np.asarray(g2[1]),
                               atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(
    s=st.sampled_from([64, 128]),
    v=st.sampled_from([96, 256]),
    chunk=st.sampled_from([16, 64, 1024]),
)
def test_chunked_softmax_xent_matches_naive(s, v, chunk):
    key = jax.random.PRNGKey(s + v)
    B, d = 2, 16
    x = jax.random.normal(key, (B, s, d))
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, v))
    t = jax.random.randint(jax.random.fold_in(key, 2), (B, s), 0, v)
    mask = (jax.random.uniform(jax.random.fold_in(key, 3), (B, s)) > 0.2
            ).astype(jnp.float32)
    ce = T.chunked_softmax_xent(x, w, t, mask, chunk=chunk)
    logits = x @ w
    lp = jax.nn.log_softmax(logits, -1)
    ll = jnp.take_along_axis(lp, t[..., None], -1)[..., 0]
    ce_ref = -jnp.sum(ll * mask) / jnp.sum(mask)
    np.testing.assert_allclose(float(ce), float(ce_ref), rtol=1e-5)


@settings(max_examples=8, deadline=None)
@given(
    causal=st.booleans(),
    window=st.sampled_from([None, 32]),
    cap=st.sampled_from([None, 25.0]),
    g=st.sampled_from([1, 4]),
)
def test_chunked_attention_matches_naive(causal, window, cap, g):
    key = jax.random.PRNGKey(17)
    B, S, H, D = 2, 128, 4, 32
    kv = H // g
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, kv, D))
    v = jax.random.normal(ks[2], (B, S, kv, D))
    y = chunked_attention(q, k, v, causal=causal, window=window, cap=cap,
                          q_chunk=32, kv_chunk=32)
    yr = flash_attention_ref(q, k, v, causal=causal, window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-5)


def test_moe_matches_dense_reference():
    mc = MoEConfig(n_experts=4, top_k=2, d_ff_expert=16, n_shared=1,
                   capacity_factor=8.0)
    key = jax.random.PRNGKey(3)
    d = 8
    mp = moe_lib.moe_init(key, d, mc)
    x = jax.random.normal(key, (2, 16, d))
    y, aux = moe_lib.moe_forward(mp, x, mc)
    xt = x.reshape(-1, d)
    logits = (xt @ mp["router"]).astype(jnp.float32)
    gv, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), mc.top_k)
    gv = gv / gv.sum(-1, keepdims=True)
    ref = jnp.zeros_like(xt)
    for e in range(mc.n_experts):
        h = jax.nn.silu(xt @ mp["wg"][e]) * (xt @ mp["wi"][e])
        ref += (h @ mp["wo"][e]) * ((idx == e) * gv).sum(-1)[:, None]
    ref += (jax.nn.silu(xt @ mp["shared"]["wg"]) *
            (xt @ mp["shared"]["wi"])) @ mp["shared"]["wo"]
    np.testing.assert_allclose(np.asarray(y.reshape(-1, d)),
                               np.asarray(ref), atol=1e-5)
    assert float(aux["aux_loss"]) > 0


def test_moe_expert_mask_prefix_disables():
    mc = MoEConfig(n_experts=4, top_k=2, d_ff_expert=16, capacity_factor=8.0)
    key = jax.random.PRNGKey(4)
    mp = moe_lib.moe_init(key, 8, mc)
    x = jax.random.normal(key, (1, 8, 8))
    mask = jnp.array([1.0, 1.0, 0.0, 0.0])
    y, _ = moe_lib.moe_forward(mp, x, mc, expert_mask=mask)
    # equivalent to a 2-expert model
    mp2 = dict(mp)
    mp2["router"] = mp["router"][:, :2]
    mp2["wi"], mp2["wg"], mp2["wo"] = (mp["wi"][:2], mp["wg"][:2],
                                       mp["wo"][:2])
    mc2 = dataclasses.replace(mc, n_experts=2)
    y2, _ = moe_lib.moe_forward(mp2, x, mc2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), atol=1e-5)


# ---------------------------------------------------------------------------
# transformer-level CFL elasticity
# ---------------------------------------------------------------------------
def test_extract_transformer_depth_and_width():
    cfg = reduced(ARCHS["granite-3-8b"], n_layers=4)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    spec = TransformerSubSpec(layers=((0, 2),), ff_frac=0.5)
    sub, sub_cfg = extract_transformer(params, cfg, spec)
    assert sub_cfg.n_layers == 2
    assert sub_cfg.d_ff == (cfg.d_ff // 2) // 8 * 8
    batch = {"tokens": jnp.ones((2, 32), jnp.int32)}
    logits, _ = T.forward(sub, sub_cfg, batch)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_pad_transformer_roundtrip():
    cfg = reduced(ARCHS["granite-3-8b"], n_layers=4)
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    spec = TransformerSubSpec(layers=((1, 3),), ff_frac=0.5)
    sub, _ = extract_transformer(params, cfg, spec)
    padded = pad_transformer(sub, params, cfg, spec)
    # kept layers' attention weights survive in place
    wq_full = params["segments"][0]["blocks"]["attn"]["wq"]
    wq_pad = padded["segments"][0]["blocks"]["attn"]["wq"]
    np.testing.assert_allclose(np.asarray(wq_pad[1]), np.asarray(wq_full[1]))
    np.testing.assert_allclose(np.asarray(wq_pad[0]),
                               np.zeros_like(wq_full[0]))
    # width-sliced mlp is zero-padded beyond the kept prefix
    ff = sub["segments"][0]["blocks"]["mlp"]["wi"].shape[-1]
    wi_pad = padded["segments"][0]["blocks"]["mlp"]["wi"]
    assert bool(jnp.all(wi_pad[1, :, ff:] == 0))


def test_extract_transformer_moe_experts():
    cfg = reduced(ARCHS["granite-moe-1b-a400m"], n_layers=2)
    params = T.init_params(jax.random.PRNGKey(2), cfg)
    spec = TransformerSubSpec(layers=((0, 1),), expert_frac=0.5)
    sub, sub_cfg = extract_transformer(params, cfg, spec)
    assert sub_cfg.moe.n_experts == 2
    batch = {"tokens": jnp.ones((2, 32), jnp.int32)}
    logits, _ = T.forward(sub, sub_cfg, batch)
    assert bool(jnp.all(jnp.isfinite(logits)))
