"""Alg. 2 — the online-trained accuracy predictor.

A four-layer MLP (exactly as the paper states) mapping
(submodel structure, data quality) -> predicted test accuracy, trained
online on the (x_k=(q_k, ω_k^t), y_k=acc_k^t) profiles the clients upload
each round; training stops once the predictor converges (paper: "one or
two CFL rounds of samples suffice").

Family-agnostic: submodel structure features come from the
``ElasticFamily`` spec-space surface (``featurize`` / ``feature_dim``), so
one predictor class serves the paper CNN's (depth, width) genes and the
transformer/SSM zoo's (d_ff, experts, SSD heads, depth-gate) genes alike;
the predictor itself only appends the data-quality one-hot.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.elastic import ElasticFamily, family_for
from repro.optim import adamw, apply_updates

N_QUALITY_LEVELS = 5


def featurize(cfg, spec, quality: int) -> np.ndarray:
    """Structure + quality features; bounded [0,1]-ish. ``cfg`` may be any
    family config or an ElasticFamily instance."""
    fam = family_for(cfg)
    q = np.zeros(N_QUALITY_LEVELS, np.float32)
    q[int(quality)] = 1.0
    return np.concatenate([fam.featurize(spec), q]).astype(np.float32)


def feature_dim(cfg) -> int:
    return family_for(cfg).feature_dim + N_QUALITY_LEVELS


class AccuracyPredictor:
    """4-layer MLP, sigmoid head (accuracy in [0,1])."""

    def __init__(self, cfg, hidden: int = 64, lr: float = 3e-3,
                 seed: int = 0, converge_mae: float = 0.03):
        self.family: ElasticFamily = family_for(cfg)
        self.cfg = self.family.cfg
        d = feature_dim(self.family)
        self._structure: Dict[Tuple, np.ndarray] = {}
        key = jax.random.PRNGKey(seed)
        ks = jax.random.split(key, 4)
        dims = [d, hidden, hidden, hidden, 1]
        self.params = [
            {"w": jax.random.normal(ks[i], (dims[i], dims[i + 1])) /
             np.sqrt(dims[i]), "b": jnp.zeros((dims[i + 1],))}
            for i in range(4)]
        self.opt = adamw(lr)
        self.opt_state = self.opt.init(self.params)
        self.buffer_x: List[np.ndarray] = []
        self.buffer_y: List[float] = []
        self.converged = False
        self.converge_mae = converge_mae
        self.last_mae = float("inf")

        def net(params, x):
            h = x
            for i, layer in enumerate(params):
                h = h @ layer["w"] + layer["b"]
                if i < 3:
                    h = jax.nn.relu(h)
            return jax.nn.sigmoid(h[..., 0])

        def loss(params, x, y):
            pred = net(params, x)
            return jnp.mean(jnp.square(pred - y))

        self._net = jax.jit(net)

        @jax.jit
        def train_step(params, opt_state, x, y):
            l, g = jax.value_and_grad(loss)(params, x, y)
            upd, opt_state = self.opt.update(g, opt_state, params)
            return apply_updates(params, upd), opt_state, l
        self._train_step = train_step

    def _features(self, specs: Sequence, qualities: Sequence[int],
                  n_rows: int) -> np.ndarray:
        """``featurize`` rows of (spec, quality), zero rows below them up
        to ``n_rows``; a spec's structure features are memoised by its
        genes."""
        nf = self.family.feature_dim
        x = np.zeros((n_rows, nf + N_QUALITY_LEVELS), np.float32)
        for i, (spec, q) in enumerate(zip(specs, qualities)):
            key = self.family.genes(spec)
            f = self._structure.get(key)
            if f is None:
                f = self._structure[key] = np.asarray(
                    self.family.featurize(spec), np.float32)
            x[i, :nf] = f
            x[i, nf + int(q)] = 1.0
        return x

    # -- Alg. 2 ------------------------------------------------------------
    def add_profiles(self, samples: Sequence[Tuple]):
        """samples: (spec, quality_level, observed_accuracy)."""
        for spec, q, acc in samples:
            self.buffer_x.append(self._features([spec], [q], 1)[0])
            self.buffer_y.append(float(acc))

    def train_round(self, epochs: int = 1):
        """One epoch over all collected profiles per FL round (Alg. 2);
        freezes itself once MAE converges (paper §III-B1)."""
        if self.converged or not self.buffer_x:
            return self.last_mae
        x = jnp.asarray(np.stack(self.buffer_x))
        y = jnp.asarray(np.asarray(self.buffer_y, np.float32))
        for _ in range(epochs):
            self.params, self.opt_state, _ = self._train_step(
                self.params, self.opt_state, x, y)
        pred = self._net(self.params, x)
        self.last_mae = float(jnp.mean(jnp.abs(pred - y)))
        if self.last_mae < self.converge_mae and len(self.buffer_y) >= 16:
            self.converged = True
        return self.last_mae

    # -- Alg. 1's `f_t` ------------------------------------------------------
    def predict(self, spec, quality: int) -> float:
        x = jnp.asarray(featurize(self.family, spec, quality))[None]
        return float(self._net(self.params, x)[0])

    def predict_rows(self, specs: Sequence, qualities: Sequence[int], *,
                     pad_to: Optional[int] = None) -> np.ndarray:
        """Scores of the rows (specs[i], qualities[i]) in one device call.
        Rows are padded to ``pad_to`` (a caller's fixed batch, so varying
        row counts share one compiled shape) and the padding is dropped."""
        n = len(specs)
        x = self._features(specs, qualities, max(n, pad_to or 0))
        with obs.span("search.predict"):
            obs.count("search.predict_calls")
            obs.count("search.predict_rows", n)
            return np.asarray(self._net(self.params, jnp.asarray(x)))[:n]

    def predict_batch(self, specs: Sequence, quality: int) -> np.ndarray:
        return self.predict_rows(specs, [quality] * len(specs))
