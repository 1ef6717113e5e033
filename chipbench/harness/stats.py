"""Percentiles and the norm comparison used by the correctness checks."""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile over all values (numpy's default)."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))


def leaf_norms(tree_leaves: Sequence[np.ndarray]) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in tree_leaves])


def leaf_gaps(prog: Sequence[np.ndarray], ref: Sequence[np.ndarray],
              keep: np.ndarray) -> np.ndarray:
    """Per leaf: the gap between the program's and the reference's norm
    of that leaf, over the reference's norm of the leaf or of the median
    kept leaf, whichever is larger; 0 for leaves left out."""
    pn, rn = leaf_norms(prog), leaf_norms(ref)
    med = float(np.median(rn[keep])) if keep.any() else 0.0
    gaps = np.abs(pn - rn) / np.maximum(np.maximum(rn, med), 1e-30)
    return np.where(keep, gaps, 0.0)


def global_gap(prog: Sequence[np.ndarray], ref: Sequence[np.ndarray],
               keep: np.ndarray) -> float:
    """Gap between the program's and the reference's norm over all kept
    leaves together, over the reference's."""
    pn, rn = leaf_norms(prog)[keep], leaf_norms(ref)[keep]
    r = float(np.sqrt(np.sum(rn ** 2)))
    return abs(float(np.sqrt(np.sum(pn ** 2))) - r) / max(r, 1e-30)


def keep_leaves(ref_first_update: Sequence[np.ndarray],
                floor: float = 1e-3) -> np.ndarray:
    """Leaves that the reference moves: a leaf whose first update is
    under ``floor`` times the median leaf's moves by round-off alone and
    is left out (a rule on the reference, never on names)."""
    rn = leaf_norms(ref_first_update)
    med = float(np.median(rn[rn > 0])) if (rn > 0).any() else 0.0
    return rn >= floor * med
