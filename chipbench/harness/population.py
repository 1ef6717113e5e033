"""The federated population of the CFL cells, made from the seed.

Synthetic CIFAR-style images (a smooth template per class, a smooth
per-sample deformation and pixel noise), split into clients that each hold
the same number of samples: a share ``dominant`` of a client's samples
comes from its own dominant class, the rest from the others. Each client
has a data-quality level (0 clean, 1-3 Gaussian blur, 4 sharpened). The
images are made on the device in one jitted call; labels, partition and
quality levels are drawn on the host. Every seed gives the same sizes.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import seeds

BLUR_SIGMAS = (0.0, 0.6, 1.0, 1.5)


def _smooth(key, shape, cutoff):
    n, h, w, c = shape
    coarse = jax.random.normal(key, (n, cutoff, cutoff, c))
    img = jax.image.resize(coarse, (n, h, w, c), "bicubic")
    lo = img.min(axis=(1, 2, 3), keepdims=True)
    hi = img.max(axis=(1, 2, 3), keepdims=True)
    return (img - lo) / (hi - lo + 1e-8)


def _blur(x, sigma):
    r = 3
    t = jnp.arange(-r, r + 1, dtype=jnp.float32)
    k = jnp.exp(-0.5 * (t / sigma) ** 2)
    k = k / k.sum()
    c = x.shape[-1]
    kh = jnp.tile(k[:, None, None, None], (1, 1, 1, c))
    kw = jnp.tile(k[None, :, None, None], (1, 1, 1, c))
    dn = ("NHWC", "HWIO", "NHWC")
    y = jax.lax.conv_general_dilated(x, kh, (1, 1), "SAME",
                                     dimension_numbers=dn,
                                     feature_group_count=c)
    return jax.lax.conv_general_dilated(y, kw, (1, 1), "SAME",
                                        dimension_numbers=dn,
                                        feature_group_count=c)


@functools.partial(jax.jit, static_argnames=("h", "w", "c", "n_classes"))
def _images(key, y, quality, *, h, w, c, n_classes):
    kt, kd, kn = jax.random.split(key, 3)
    n = y.shape[0]
    templates = _smooth(kt, (n_classes, h, w, c), 4)
    x = 0.75 * templates[y] + 0.25 * _smooth(kd, (n, h, w, c), 3) + \
        0.08 * jax.random.normal(kn, (n, h, w, c))
    x = jnp.clip(x, 0.0, 1.0)
    variants = [x] + [_blur(x, s) for s in BLUR_SIGMAS[1:]]
    variants.append(jnp.clip(x + 0.5 * (x - _blur(x, 1.0)), 0.0, 1.0))
    stack = jnp.stack(variants)                        # (5, N, H, W, C)
    return jnp.take_along_axis(
        stack, quality[None, :, None, None, None], axis=0)[0]


def make(seed: int, p: Dict) -> Dict:
    """Per-client train/test dicts of host arrays, quality levels, and
    the stacked arrays (``x``, ``y``, ``ex``, ``ey``) the reference uses.

    p: the traffic file's ``population`` block: clients,
    samples_per_client, test_per_client, image_size, channels, n_classes,
    dominant (share of the dominant class), quality_levels."""
    k = int(p["clients"])
    n_tr, n_te = int(p["samples_per_client"]), int(p["test_per_client"])
    n_cls = int(p["n_classes"])
    rng = seeds.stream(seed, 1)
    per = n_tr + n_te
    n_dom = int(round(per * float(p["dominant"])))
    labels = np.empty((k, per), np.int32)
    for i in range(k):
        dom = i % n_cls
        others = rng.choice([c for c in range(n_cls) if c != dom],
                            per - n_dom)
        labels[i] = rng.permutation(np.concatenate(
            [np.full(n_dom, dom, np.int32), others.astype(np.int32)]))
    quality = rng.integers(0, int(p["quality_levels"]), k)
    qs = np.repeat(quality, per).astype(np.int32)
    x = _images(seeds.jax_key(seed, 2), jnp.asarray(labels.reshape(-1)),
                jnp.asarray(qs), h=int(p["image_size"]),
                w=int(p["image_size"]), c=int(p["channels"]),
                n_classes=n_cls)
    x = np.asarray(x).reshape((k, per) + x.shape[1:])
    train = [{"x": x[i, :n_tr], "y": labels[i, :n_tr]} for i in range(k)]
    test = [{"x": x[i, n_tr:], "y": labels[i, n_tr:]} for i in range(k)]
    return {"train": train, "test": test, "quality": quality.tolist(),
            "x": x[:, :n_tr], "y": labels[:, :n_tr],
            "ex": x[:, n_tr:], "ey": labels[:, n_tr:]}
