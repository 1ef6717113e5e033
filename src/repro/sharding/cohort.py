"""Cohort-axis sharding for the batched FL round engine.

The engine's native layout stacks every per-client tensor on a leading
client axis (K, ...) — params broadcast, masks, data, batch indices,
deltas. Clients are embarrassingly parallel until the aggregation
reduction, so sharding that axis over a 1-D ``cohort`` mesh scales a round
across devices with exactly one collective per round (the weighted
reduce inside the fused aggregate+apply program, which GSPMD lowers to a
reduce-scatter/all-gather pair over ``cohort``).

Inputs are committed via ``shard_cohort`` (device_put with a
``PartitionSpec('cohort')`` leaf sharding); jit then propagates the layout
through the vmapped train/eval programs, so outputs (deltas, trained
params, accuracies) come back cohort-sharded without per-program
annotations.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh


def cohort_mesh(n_shards: Optional[int] = None, *,
                devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """1-D mesh over the first ``n_shards`` devices, axis name 'cohort'."""
    devs = list(devices) if devices is not None else list(jax.devices())
    n = n_shards or len(devs)
    if n > len(devs):
        raise ValueError(f"cohort_mesh: {n} shards > {len(devs)} devices")
    return make_mesh((n,), ("cohort",), devices=devs[:n])


def cohort_axis_sharding(mesh: jax.sharding.Mesh) -> NamedSharding:
    """Leading (client) axis over 'cohort'; all trailing dims replicated."""
    return NamedSharding(mesh, P("cohort"))


def effective_cohort_shards(n_clients: int, requested: int,
                            n_devices: Optional[int] = None) -> int:
    """Largest shard count ≤ requested (and ≤ device count) that divides
    the cohort — keeps every client shard rectangular so the stacked
    layout needs no padding clients."""
    if n_devices is None:
        n_devices = len(jax.devices())
    cap = max(1, min(int(requested), n_devices, n_clients))
    for s in range(cap, 0, -1):
        if n_clients % s == 0:
            return s
    return 1


def shard_cohort(tree, sharding: Optional[NamedSharding]):
    """Commit every leaf of a stacked (K, ...) pytree to the cohort
    sharding (no-op when sharding is None). Already-committed leaves with
    the same sharding are not copied."""
    if sharding is None:
        return tree
    return jax.device_put(tree, sharding)
