"""Plain reference for CFL rounds of the paper's elastic CNN.

Written from the paper's Alg. 3-4 and the configuration file, in plain
``jax.numpy``; it imports nothing of the system under test. Each client
trains its submodel (a prefix of every stage's channels, a prefix of
every stage's blocks) with momentum SGD and global-norm clipping on its
own batch stream, evaluates it on its test split, and the server applies
the sample-weighted mean of the zero-padded updates.

Submodels are computed at parent width with inactive channels held at 0,
and GroupNorm over the active prefix split into ``groups`` equal groups,
which equals the extracted submodel's forward. The weights are made here
from the seed (``init_params``); the benchmark hands the same arrays to
the system under test.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def channels(c: int, frac: float, groups: int) -> int:
    """Active channels of a stage at width ``frac``: a whole number of
    GroupNorm groups, at least one."""
    return max(groups, int(round(c * frac / groups)) * groups)


def init_params(key, m: Dict) -> Dict:
    """Parent weights from ``key`` in the parent's tree layout."""
    stages = [tuple(s) for s in m["stages"]]
    n = 2 + sum(b for _, b in stages) * 4 + len(stages)
    ks = iter(jax.random.split(key, 2 * n))

    def conv(cin, cout):
        return {"w": jax.random.normal(next(ks), (3, 3, cin, cout))
                / math.sqrt(9 * cin),
                "b": 0.01 * jax.random.normal(next(ks), (cout,))}

    def dense(cin, cout):
        return {"w": jax.random.normal(next(ks), (cin, cout))
                / math.sqrt(cin),
                "b": 0.01 * jax.random.normal(next(ks), (cout,))}

    p = {"stem": conv(m["in_channels"], m["stem_channels"]), "stages": []}
    cin = m["stem_channels"]
    for cout, nb in stages:
        st = {"down": conv(cin, cout), "blocks": []}
        for _ in range(nb):
            st["blocks"].append({
                "conv1": conv(cout, cout), "conv2": conv(cout, cout),
                "gate": {"fc1": dense(cout, m["gate_hidden"]),
                         "fc2": dense(m["gate_hidden"], 1)}})
        p["stages"].append(st)
        cin = cout
    p["head"] = dense(cin, m["n_classes"])
    return p


# ---------------------------------------------------------------------------
# submodel description: per stage active channels and kept blocks
# ---------------------------------------------------------------------------
def spec_arrays(m: Dict, genes: Sequence[Sequence[int]]):
    """(K, S) active channels and (K, S) kept blocks from each client's
    genes (kept blocks per stage, then width in percent per stage)."""
    n_st = len(m["stages"])
    g = m["groupnorm_groups"]
    act, depth = [], []
    for gene in genes:
        depth.append([int(d) for d in gene[:n_st]])
        act.append([channels(c, w / 100.0, g)
                    for (c, _), w in zip(m["stages"], gene[n_st:])])
    return np.asarray(act, np.int32), np.asarray(depth, np.int32)


def _conv(p, x, stride=1):
    y = jax.lax.conv_general_dilated(
        x, p["w"].astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"].astype(x.dtype)


def _groupnorm(x, n_active, groups, eps=1e-5):
    """GroupNorm over the first ``n_active`` channels, split into
    ``groups`` equal groups; the other channels come out 0."""
    c = x.shape[-1]
    ch = jnp.arange(c)
    size = jnp.maximum(n_active // groups, 1)
    onehot = ((ch[:, None] // size) == jnp.arange(groups)[None, :]) & \
        (ch[:, None] < n_active)
    a = onehot.astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    count = x.shape[1] * x.shape[2] * jnp.maximum(a.sum(0), 1.0)
    mean_g = jnp.einsum("bhwc,cg->bg", x32, a) / count
    dev = x32 - jnp.einsum("cg,bg->bc", a, mean_g)[:, None, None, :]
    var_g = jnp.einsum("bhwc,cg->bg", dev * dev, a) / count
    inv = jnp.einsum("cg,bg->bc", a, jax.lax.rsqrt(var_g + eps))
    return (dev * inv[:, None, None, :]).astype(x.dtype)


def forward(p, m: Dict, x, act, depth):
    """Logits of the submodel (``act`` channels and ``depth`` blocks per
    stage, each an int array of length n_stages)."""
    g = m["groupnorm_groups"]
    x = jax.nn.relu(_groupnorm(_conv(p["stem"], x), m["stem_channels"], g))
    for s, st in enumerate(p["stages"]):
        mask = (jnp.arange(st["down"]["w"].shape[-1]) < act[s]).astype(
            x.dtype)
        x = jax.nn.relu(_groupnorm(_conv(st["down"], x, 2) * mask,
                                   act[s], g))
        for b, bp in enumerate(st["blocks"]):
            keep = (b < depth[s]).astype(x.dtype)
            h = jax.nn.relu(_groupnorm(_conv(bp["conv1"], x) * mask,
                                       act[s], g))
            h = _groupnorm(_conv(bp["conv2"], h) * mask, act[s], g)
            x = jax.nn.relu(x + keep * h)
    feat = jnp.mean(x, axis=(1, 2))
    return feat @ p["head"]["w"].astype(x.dtype) + \
        p["head"]["b"].astype(x.dtype)


def param_mask(p, m: Dict, act, depth):
    """1 on the entries the submodel owns, 0 elsewhere."""
    def prefix(n, total):
        return (jnp.arange(total) < n).astype(jnp.float32)

    out = {"stem": jax.tree.map(jnp.ones_like, p["stem"]), "stages": []}
    prev = prefix(p["stem"]["w"].shape[-1], p["stem"]["w"].shape[-1])
    for s, st in enumerate(p["stages"]):
        cmax = st["down"]["w"].shape[-1]
        mk = prefix(act[s], cmax)
        cc = mk[:, None] * mk[None, :]
        o = {"down": {"w": jnp.broadcast_to(prev[:, None] * mk[None, :],
                                            st["down"]["w"].shape),
                      "b": mk}, "blocks": []}
        for b, bp in enumerate(st["blocks"]):
            keep = (b < depth[s]).astype(jnp.float32)
            o["blocks"].append({
                "conv1": {"w": keep * jnp.broadcast_to(cc, bp["conv1"]["w"]
                                                       .shape),
                          "b": keep * mk},
                "conv2": {"w": keep * jnp.broadcast_to(cc, bp["conv2"]["w"]
                                                       .shape),
                          "b": keep * mk},
                "gate": jax.tree.map(lambda a: keep * jnp.ones_like(a),
                                     bp["gate"])})
        out["stages"].append(o)
        prev = mk
    out["head"] = {"w": jnp.broadcast_to(prev[:, None],
                                         p["head"]["w"].shape),
                   "b": jnp.ones_like(p["head"]["b"])}
    return out


# ---------------------------------------------------------------------------
# the batch order: an epoch-shuffled stream per client and round
# ---------------------------------------------------------------------------
def client_seed(fl_seed: int, round_idx: int, k: int) -> int:
    return fl_seed * 7 + round_idx * 131 + k


def batch_stream(n: int, batch: int, seed: int, epochs: int) -> np.ndarray:
    """(steps, batch) sample indices: each epoch a fresh permutation from
    one ``RandomState(seed)``, cut into whole batches."""
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(epochs):
        perm = rng.permutation(n)
        end = n - (n % batch) if n >= batch else n
        out.extend(perm[i:i + batch] for i in range(0, end, batch))
    return np.stack(out)


# ---------------------------------------------------------------------------
# one CFL round
# ---------------------------------------------------------------------------
def make_round(m: Dict, *, lr: float, momentum: float, clip: float,
               dtype=jnp.float32):
    """A jitted ``round(params, x, y, idx, ex, ey, act, depth, weights)``
    returning (new params, per-client eval accuracy).

    x: (K, N, H, W, C) client data; idx: (K, S, B) batch indices;
    ex/ey: client test splits; act/depth: (K, n_stages); weights: (K,).
    ``dtype`` is the precision the clients compute and keep their
    weights in (float32 for the reference, bfloat16 for the control).
    """
    def loss_fn(p, x, y, act, depth):
        logits = forward(p, m, x, act, depth).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, y[:, None], -1))

    def client(p0, x, y, idx, ex, ey, act, depth):
        p0 = jax.tree.map(lambda a: a.astype(dtype), p0)
        mask = param_mask(p0, m, act, depth)
        mu0 = jax.tree.map(jnp.zeros_like, p0)

        def step(carry, ix):
            p, mu = carry
            g = jax.grad(loss_fn)(p, x[ix].astype(dtype), y[ix], act, depth)
            g = jax.tree.map(lambda a, k: a * k.astype(a.dtype), g, mask)
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
                                for a in jax.tree.leaves(g)))
            scale = jnp.minimum(1.0, clip / (norm + 1e-9))
            g = jax.tree.map(lambda a: (a * scale).astype(a.dtype), g)
            mu = jax.tree.map(lambda a, b: (momentum * a + b).astype(a.dtype),
                              mu, g)
            p = jax.tree.map(lambda a, b: (a - lr * b).astype(a.dtype), p, mu)
            return (p, mu), None

        (pe, _), _ = jax.lax.scan(step, (p0, mu0), idx)
        delta = jax.tree.map(lambda a, b, k: (a.astype(jnp.float32)
                                              - b.astype(jnp.float32)) * k,
                             p0, pe, mask)
        logits = forward(pe, m, ex.astype(dtype), act, depth)
        acc = jnp.mean((jnp.argmax(logits, -1) == ey).astype(jnp.float32))
        return delta, acc

    def rnd(params, x, y, idx, ex, ey, act, depth, weights):
        deltas, accs = jax.vmap(client, in_axes=(None, 0, 0, 0, 0, 0, 0, 0))(
            params, x, y, idx, ex, ey, act, depth)
        w = weights.astype(jnp.float32)
        new = jax.tree.map(
            lambda p, d: p - jnp.tensordot(w, d, 1) / jnp.sum(w),
            params, deltas)
        return new, accs

    return jax.jit(rnd)


def run_rounds(params, m: Dict, data: Dict, genes_per_round: List,
               *, fl_seed: int, lr: float, momentum: float, clip: float,
               batch: int, epochs: int, dtype=jnp.float32,
               precision: str = "highest") -> List:
    """Follow the rounds whose client genes are given; returns the params
    after each round (host numpy leaves) and each round's accuracies.

    data: {"x": (K,N,...), "y": (K,N), "ex", "ey"} host arrays, every
    client holding N training samples."""
    k, n = data["y"].shape
    rnd = make_round(m, lr=lr, momentum=momentum, clip=clip, dtype=dtype)
    dev = {key: jnp.asarray(v) for key, v in data.items()}
    weights = jnp.full((k,), float(n), jnp.float32)
    out = []
    with jax.default_matmul_precision(precision):
        for r, genes in enumerate(genes_per_round):
            act, depth = spec_arrays(m, genes)
            idx = np.stack([batch_stream(n, batch,
                                         client_seed(fl_seed, r, c), epochs)
                            for c in range(k)])
            params, accs = rnd(params, dev["x"], dev["y"], jnp.asarray(idx),
                               dev["ex"], dev["ey"], jnp.asarray(act),
                               jnp.asarray(depth), weights)
            out.append((jax.tree.map(np.asarray, params), np.asarray(accs)))
    return out
