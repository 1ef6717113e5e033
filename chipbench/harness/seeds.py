"""Seeds the run derives from ``--seed`` (any whole number, also far
above 32 bits)."""
from __future__ import annotations

import numpy as np


def stream(seed: int, *tags: int) -> np.random.Generator:
    """An independent generator per (seed, tags)."""
    return np.random.default_rng(np.random.SeedSequence(
        entropy=int(seed), spawn_key=tuple(int(t) for t in tags)))


def small(seed: int, tag: int, bits: int = 28) -> int:
    """A non-negative int under 2**bits, for APIs that take 32-bit seeds
    and do arithmetic on them."""
    return int(stream(seed, tag).integers(0, 2 ** bits))


def jax_key(seed: int, tag: int):
    import jax
    return jax.random.PRNGKey(small(seed, tag, 31))
