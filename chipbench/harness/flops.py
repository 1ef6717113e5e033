"""The operations each CFL round requires, from the CNN's shapes.

Counts what the submodel needs, not what a masked parent-width program
executes, so a path that skips masked tiles cannot read above 100% of a
peak. Multiply-adds count 2; norms, activations and softmax are left out
(each well under 1% of a layer's matrix work at these widths). A serving
model's counts live in its model file (``archs/<arch>.py``).
"""
from __future__ import annotations

from typing import Dict, Sequence

from chipbench.reference.cnn_ref import channels as cnn_channels


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
