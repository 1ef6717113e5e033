"""Plain reference for multi-tenant serving of a granite-style decoder.

Written from the configuration file in plain ``jax.numpy``; it imports
nothing of the system under test. The model: token embedding (tied with
the output projection), per layer RMSNorm with a ``(1 + scale)`` gain,
grouped-query attention with rotary embeddings on the two halves of each
head, causal softmax, and a SiLU-gated MLP; a final RMSNorm. A tenant's
submodel keeps a prefix of the MLP's hidden units, a prefix of the query
heads (whole groups) and a subset of the layers: a dropped layer adds
nothing to the residual stream.

``logits`` runs the whole sequence at once (no cache), so agreement with
a served decode checks prefill, the cache and the decode step together.
The weights are made here from the seed (``init_params``); the benchmark
hands the same arrays to the system under test.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def padded_vocab(c: Dict) -> int:
    """Embedding rows: the vocabulary rounded up to 256."""
    return -(-c["vocab_size"] // 256) * 256


def init_params(key, c: Dict, dtype=jnp.float32) -> Dict:
    """Parent weights in the parent's tree layout (layers stacked)."""
    d, h, kv, hd, f = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"],
                       c["intermediate_size"])
    n = c["num_hidden_layers"]
    ks = iter(jax.random.split(key, 12))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    blocks = {
        "ln1": {"scale": normal((n, d), 0.1)},
        "ln2": {"scale": normal((n, d), 0.1)},
        "attn": {"wq": normal((n, d, h, hd), 1 / math.sqrt(d)),
                 "wk": normal((n, d, kv, hd), 1 / math.sqrt(d)),
                 "wv": normal((n, d, kv, hd), 1 / math.sqrt(d)),
                 "wo": normal((n, h, hd, d), 1 / math.sqrt(h * hd))},
        "mlp": {"wi": normal((n, d, f), 1 / math.sqrt(d)),
                "wo": normal((n, f, d), 1 / math.sqrt(f)),
                "wg": normal((n, d, f), 1 / math.sqrt(d))},
    }
    return {"embed": {"table": normal((padded_vocab(c), d), 0.02)},
            "segments": [{"blocks": blocks}],
            "final_norm": {"scale": normal((d,), 0.1)}}


# ---------------------------------------------------------------------------
# a tenant's submodel
# ---------------------------------------------------------------------------
def ff_active(c: Dict, frac: float) -> int:
    return max(8, (int(c["intermediate_size"] * frac) // 8) * 8)


def heads_active(c: Dict, frac: float) -> int:
    g = c["num_attention_heads"] // c["num_key_value_heads"]
    return max(g, (int(round(c["num_attention_heads"] * frac)) // g) * g)


def tenant_masks(c: Dict, layers: Sequence[int], ff_frac: float,
                 head_frac: float):
    """(ff mask (F,), head mask (H,), layer gates (L,)) as float32."""
    ff = np.zeros(c["intermediate_size"], np.float32)
    ff[:ff_active(c, ff_frac)] = 1.0
    hm = np.zeros(c["num_attention_heads"], np.float32)
    hm[:heads_active(c, head_frac)] = 1.0
    gate = np.zeros(c["num_hidden_layers"], np.float32)
    gate[list(layers)] = 1.0
    return ff, hm, gate


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return ((1.0 + scale.astype(jnp.float32)) * x32 * inv).astype(x.dtype)


def _rope(x, theta):
    """x: (S, H, D); rotate the two halves of each head by position."""
    s, _, dh = x.shape
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def make_logits(c: Dict):
    """A jitted ``logits(params, tokens, ff, heads, gates, first)`` giving
    the next-token logits (float32) at positions ``first`` .. S-1 of one
    sequence ``tokens`` (S,), for one tenant's masks. ``first`` is static."""
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    group = h // kv

    def layer(x, lp, ff, heads, gate):
        s = x.shape[0]
        dt = x.dtype
        hn = _rms(x, lp["ln1"]["scale"], eps)
        q = _rope(jnp.einsum("sd,dhk->shk", hn, lp["attn"]["wq"].astype(dt)),
                  theta)
        k = _rope(jnp.einsum("sd,dhk->shk", hn, lp["attn"]["wk"].astype(dt)),
                  theta)
        v = jnp.einsum("sd,dhk->shk", hn, lp["attn"]["wv"].astype(dt))
        k = jnp.repeat(k, group, axis=1)          # query head i -> kv i//g
        v = jnp.repeat(v, group, axis=1)
        sc = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(causal[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hqs,shk->qhk", pr, v)
        o = o * heads[None, :, None].astype(dt)
        a = jnp.einsum("shk,hkd->sd", o, lp["attn"]["wo"].astype(dt))
        x = x + gate.astype(dt) * a
        hn = _rms(x, lp["ln2"]["scale"], eps)
        u = jax.nn.silu(hn @ lp["mlp"]["wg"].astype(dt)) * \
            (hn @ lp["mlp"]["wi"].astype(dt))
        u = u * ff.astype(dt)
        return x + gate.astype(dt) * (u @ lp["mlp"]["wo"].astype(dt))

    def logits(params, tokens, ff, heads, gates, first):
        table = params["embed"]["table"]
        x = table[tokens]
        blocks = params["segments"][0]["blocks"]
        for i in range(c["num_hidden_layers"]):
            lp = jax.tree.map(lambda a, i=i: a[i], blocks)
            x = layer(x, lp, ff, heads, gates[i])
        x = _rms(x[first:], params["final_norm"]["scale"], eps)
        return (x @ table.T.astype(x.dtype)).astype(jnp.float32)

    return jax.jit(logits, static_argnums=(5,))


def served_gaps(logits_fn, params, prompt: np.ndarray, served: np.ndarray,
                masks, length: int, *, control=None):
    """For one served request: the gap by which each served token's logit
    lies below the reference's best at its position; with ``control``
    (logits_fn, params, matmul precision) also the gap of the token that
    the control puts first there.

    prompt: the prompt as served (padded to the server's window); served:
    the generated tokens. Token j is predicted at position len(prompt)-1+j
    of prompt + served[:-1]. The sequence is padded at its end to
    ``length`` so that one compiled program serves every request; causal
    attention keeps the padding out of the positions that are read. The
    reference runs at ``highest`` matmul precision."""
    n = len(served)
    seq = np.zeros(length, np.int32)
    seq[:len(prompt) + n - 1] = np.concatenate([prompt, served[:-1]])
    first = len(prompt) - 1
    ff, heads, gates = (jnp.asarray(m) for m in masks)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(logits_fn(params, jnp.asarray(seq), ff, heads,
                                   gates, first))[:n]
    best = ref.max(-1)
    gaps = best - ref[np.arange(n), served]
    if control is None:
        return gaps, None
    fn, cparams, prec = control
    with jax.default_matmul_precision(prec):
        ctl = np.asarray(fn(cparams, jnp.asarray(seq), ff, heads, gates,
                            first))[:n]
    return gaps, best - ref[np.arange(n), ctl.argmax(-1)]
