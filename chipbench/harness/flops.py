"""The operations and bytes each piece of work requires, from its shapes.

Counts what the submodel needs, not what a masked parent-width program
executes, so a path that skips masked tiles cannot read above 100% of a
peak. Multiply-adds count 2; norms, activations and softmax are left out
(each well under 1% of a layer's matrix work at these widths).
"""
from __future__ import annotations

from typing import Dict, Sequence


from chipbench.reference.cnn_ref import channels as cnn_channels
from chipbench.reference.granite_ref import ff_active, heads_active


def cnn_forward_flops(model: Dict, genes: Sequence[int]) -> float:
    """Forward FLOPs of one sample through the submodel ``genes`` (kept
    blocks per stage, then width in percent per stage): 3x3 convs at the
    submodel's own channel counts, and the classifier."""
    n_st = len(model["stages"])
    g = model["groupnorm_groups"]
    hw = model["image_size"] ** 2
    total = 2 * 9 * model["in_channels"] * model["stem_channels"] * hw
    cin = model["stem_channels"]
    for s, (cmax, _) in enumerate(model["stages"]):
        hw //= 4
        c = cnn_channels(cmax, genes[n_st + s] / 100.0, g)
        total += 2 * 9 * cin * c * hw
        total += int(genes[s]) * 2 * (2 * 9 * c * c * hw)
        cin = c
    total += 2 * cin * model["n_classes"]
    return float(total)


def cnn_round_flops(model: Dict, genes: Sequence[Sequence[int]],
                    trained: Sequence[int], evaluated: Sequence[int]
                    ) -> float:
    """A round's required FLOPs: per client, forward and backward (3x the
    forward) for each trained sample, and a forward per eval sample."""
    return float(sum(cnn_forward_flops(model, g) * (3 * nt + ne)
                     for g, nt, ne in zip(genes, trained, evaluated)))


def token_flops(c: Dict, spec: Dict, context: int) -> float:
    """FLOPs of one token through the tenant's submodel, attending to
    ``context`` positions: q/k/v/o and gated-MLP matmuls of each kept
    layer, attention scores and values, and the output projection over
    the real vocabulary."""
    d, hd, kv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    h = heads_active(c, spec["head_frac"])
    f = ff_active(c, spec["ff_frac"])
    per_layer = (2 * d * h * hd + 2 * 2 * d * kv * hd + 2 * h * hd * d
                 + 3 * 2 * d * f + 2 * 2 * h * hd * context)
    return float(len(spec["layers"]) * per_layer
                 + 2 * d * c["vocab_size"])


def prompt_flops(c: Dict, spec: Dict, n: int) -> float:
    """A prompt of ``n`` real tokens, each attending to those before it."""
    base = token_flops(c, spec, 0)
    d_attn = token_flops(c, spec, 1) - base
    return float(n * base + d_attn * n * (n + 1) / 2)


def decode_step_bytes(c: Dict, specs: Sequence[Dict],
                      positions: Sequence[int], bytes_per: int = 4) -> float:
    """Bytes one batched decode step must read: the weights of every unit
    that at least one active slot's submodel keeps, once, and each active
    slot's keys and values over its context."""
    if not specs:
        return 0.0
    d, hd, kv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    total = c["vocab_size"] * d + d                   # table + final norm
    for layer in range(c["num_hidden_layers"]):
        users = [s for s in specs if layer in s["layers"]]
        if not users:
            continue
        h = max(heads_active(c, s["head_frac"]) for s in users)
        f = max(ff_active(c, s["ff_frac"]) for s in users)
        total += 2 * d + d * h * hd + 2 * d * kv * hd + h * hd * d
        total += 3 * d * f
    weights = total * bytes_per
    kv_bytes = sum(len(s["layers"]) * 2 * kv * hd * p * bytes_per
                   for s, p in zip(specs, positions))
    return float(weights + kv_bytes)
