"""The model half of a serving cell whose configuration is a decoder of
grouped-query attention and a gated SiLU MLP with tied embeddings
(granite-style), as ``reference/granite_ref.py`` writes it.

The serving driver (``drivers/serve.py``), the per-layer readers and the
controls reach the model only through these functions, found by the
configuration's ``"arch"`` key (``archs/<arch>.py``):

* ``program_config(cell)`` — the system's own model config, checked
  against the configuration file's widths; ``family(cell)`` — the
  system's elastic family over it;
* ``init_params(cell, seed)`` — the parent weights from the seed, made
  by the reference in one jitted call;
* ``tenant_specs(cell, seed)`` — the tenants' distinct submodels drawn
  from the seed, and ``program_spec(spec)`` — one as the system's
  submodel spec;
* ``vocab_size(cell)`` — the ids the traffic draws from;
* ``reference_gaps(cell, seed, served, length, control=)`` — the plain
  reference over served requests;
* ``token_flops(cell, ...)``, ``prompt_flops(cell, ...)``,
  ``decode_step_bytes(cell, ...)`` — the work a submodel requires, from
  its shapes.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import numpy as np

from chipbench.harness import seeds
from chipbench.reference import granite_ref

WIDTH_KEYS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
              "intermediate_size": "d_ff", "vocab_size": "vocab_size",
              "num_hidden_layers": "n_layers", "rope_theta": "rope_theta",
              "rms_norm_eps": "norm_eps"}


def program_config(cell):
    """The system's own model config, cut to the file's depth; every width
    must equal the configuration file's."""
    from repro.configs.archs import ARCHS
    from repro.configs.base import depth_cut
    c = cell.config["model"]
    pcfg = depth_cut(ARCHS[cell.config["system_arch"]],
                     c["num_hidden_layers"])
    for ours, theirs in WIDTH_KEYS.items():
        if getattr(pcfg, theirs) != c[ours]:
            raise ValueError(f"{cell.config['name']}: {ours} is {c[ours]} "
                             f"in the configuration, "
                             f"{getattr(pcfg, theirs)} in the system")
    if not pcfg.tie_embeddings or pcfg.act != "silu" or not pcfg.mlp_gated:
        raise ValueError("the system's model is not the configured one")
    return pcfg


def family(cell):
    from repro.core.elastic import TransformerElasticFamily
    return TransformerElasticFamily(program_config(cell))


def init_params(cell, seed: int):
    c = cell.config["model"]
    return jax.jit(lambda k: granite_ref.init_params(k, c))(
        seeds.jax_key(seed, 4))


def vocab_size(cell) -> int:
    return cell.config["model"]["vocab_size"]


def tenant_specs(cell, seed: int) -> List[Dict]:
    """The distinct submodels the tenants use: the mix's ``specs``
    submodels (kept layers, MLP and head fractions) drawn from the seed,
    tenant t using submodel t mod specs."""
    n_layers = cell.config["model"]["num_hidden_layers"]
    widths = cell.config["elastic_widths"]
    rng = seeds.stream(seed, 13)
    out = []
    for _ in range(int(cell.traffic["specs"])):
        k = int(rng.integers(1, n_layers + 1))
        layers = tuple(sorted(rng.choice(n_layers, k, replace=False)
                              .tolist()))
        out.append({"layers": layers,
                    "ff_frac": float(rng.choice(widths)),
                    "head_frac": float(rng.choice(widths))})
    return out


def program_spec(spec: Dict):
    from repro.core.submodel import TransformerSubSpec
    return TransformerSubSpec(layers=(spec["layers"],),
                              ff_frac=spec["ff_frac"],
                              attn_head_frac=spec["head_frac"])


def reference_gaps(cell, seed: int,
                   served: Sequence[Tuple[np.ndarray, np.ndarray, Dict]],
                   length: int, *, control=None):
    """Widest gap of the served tokens over ``served`` (prompt as served,
    generated tokens, submodel) requests, each run as one sequence padded
    to ``length``; with ``control`` (dtype, matmul precision) also the
    widest gap of the tokens that the reference computed that way puts
    first at the same positions. Returns (widest, widest control, tokens
    compared)."""
    c = cell.config["model"]
    params = init_params(cell, seed)
    ref_fn = granite_ref.make_logits(c)
    ctl = None
    if control is not None:
        dtype, prec = control
        ctl = (ref_fn, jax.tree.map(lambda a: a.astype(dtype), params), prec)
    widest, widest_ctl, n = 0.0, 0.0, 0
    for prompt, tokens, s in served:
        masks = granite_ref.tenant_masks(c, s["layers"], s["ff_frac"],
                                         s["head_frac"])
        g, gc_ = granite_ref.served_gaps(
            ref_fn, params, prompt, np.asarray(tokens, np.int32), masks,
            length, control=ctl)
        widest = max(widest, float(g.max()))
        if gc_ is not None:
            widest_ctl = max(widest_ctl, float(gc_.max()))
        n += len(g)
    return widest, widest_ctl, n


# ---------------------------------------------------------------------------
# required work: what the submodel needs, not what a masked parent-width
# program executes, so a path that skips masked tiles cannot read above
# 100% of a peak. Multiply-adds count 2; norms, activations and softmax
# are left out (each well under 1% of a layer's matrix work at these
# widths).
# ---------------------------------------------------------------------------
def token_flops(cell, spec: Dict, context: int) -> float:
    """FLOPs of one token through the tenant's submodel, attending to
    ``context`` positions: q/k/v/o and gated-MLP matmuls of each kept
    layer, attention scores and values, and the output projection over
    the real vocabulary."""
    c = cell.config["model"]
    d, hd, kv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    h = granite_ref.heads_active(c, spec["head_frac"])
    f = granite_ref.ff_active(c, spec["ff_frac"])
    per_layer = (2 * d * h * hd + 2 * 2 * d * kv * hd + 2 * h * hd * d
                 + 3 * 2 * d * f + 2 * 2 * h * hd * context)
    return float(len(spec["layers"]) * per_layer
                 + 2 * d * c["vocab_size"])


def prompt_flops(cell, spec: Dict, n: int) -> float:
    """A prompt of ``n`` real tokens, each attending to those before it."""
    base = token_flops(cell, spec, 0)
    d_attn = token_flops(cell, spec, 1) - base
    return float(n * base + d_attn * n * (n + 1) / 2)


def decode_step_bytes(cell, specs: Sequence[Dict],
                      positions: Sequence[int], bytes_per: int = 4) -> float:
    """Bytes one batched decode step must read: the weights of every unit
    that at least one active slot's submodel keeps, once, and each active
    slot's keys and values over its context."""
    if not specs:
        return 0.0
    c = cell.config["model"]
    d, hd, kv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    total = c["vocab_size"] * d + d                   # table + final norm
    for layer in range(c["num_hidden_layers"]):
        users = [s for s in specs if layer in s["layers"]]
        if not users:
            continue
        h = max(granite_ref.heads_active(c, s["head_frac"]) for s in users)
        f = max(granite_ref.ff_active(c, s["ff_frac"]) for s in users)
        total += 2 * d + d * h * hd + 2 * d * kv * hd + h * hd * d
        total += 3 * d * f
    weights = total * bytes_per
    kv_bytes = sum(len(s["layers"]) * 2 * kv * hd * p * bytes_per
                   for s, p in zip(specs, positions))
    return float(weights + kv_bytes)
