"""LM training driver (CPU-scale end-to-end; production shapes go through
dryrun.py).

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \
      --steps 200 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import ARCHS, get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_train_step
from repro.models import transformer as T


def synthetic_lm_batches(cfg, batch: int, seq: int, seed: int = 0
                         ) -> Iterator[Dict]:
    """Deterministic synthetic language: a noisy order-2 Markov chain over
    the vocab — has real structure for the model to learn (loss should
    drop well below uniform log V)."""
    rng = np.random.RandomState(seed)
    V = cfg.vocab_size
    # random sparse transition table: each (a, b) context has 4 likely nexts
    ctx_next = rng.randint(0, V, size=(257, 4))
    while True:
        toks = np.zeros((batch, seq), np.int32)
        toks[:, :2] = rng.randint(0, V, size=(batch, 2))
        for t in range(2, seq):
            ctx = (toks[:, t - 1] * 31 + toks[:, t - 2]) % 257
            choice = rng.randint(0, 4, size=batch)
            nxt = ctx_next[ctx, choice]
            noise = rng.randint(0, V, size=batch)
            use_noise = rng.rand(batch) < 0.1
            toks[:, t] = np.where(use_noise, noise, nxt)
        batch_dict = {"tokens": jnp.asarray(toks)}
        if ARCHS.get(cfg.name.replace("-smoke", ""), cfg).frontend == \
                "vision" or cfg.frontend == "vision":
            batch_dict["image_embeds"] = jnp.zeros(
                (batch, cfg.frontend_tokens, cfg.d_model), jnp.float32)
        if cfg.frontend == "audio":
            batch_dict = {
                "frames": jnp.asarray(
                    rng.randn(batch, seq, cfg.d_model).astype(np.float32)),
                "labels": jnp.asarray(toks % cfg.vocab_size)}
        yield batch_dict


def train(arch: str, *, steps: int = 100, batch: int = 8, seq: int = 256,
          lr: float = 3e-4, use_reduced: bool = True, n_layers: int = 4,
          d_model: int = 256, seed: int = 0, log_every: int = 10,
          checkpoint_path: str = None):
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg, n_layers=n_layers, d_model=d_model)
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params / 1e6:.1f}M "
          f"vocab={cfg.vocab_size} seq={seq} batch={batch}")

    step_fn, opt = make_train_step(cfg, lr=lr, remat=False)
    step_fn = jax.jit(step_fn)
    opt_state = opt.init(params)
    data = synthetic_lm_batches(cfg, batch, seq, seed)

    history = []
    t0 = time.time()
    for i in range(steps):
        b = next(data)
        params, opt_state, metrics = step_fn(params, opt_state, b)
        if i % log_every == 0 or i == steps - 1:
            loss = float(metrics["loss"])
            history.append({"step": i, "loss": loss})
            print(f"step {i:5d}  loss {loss:8.4f}  "
                  f"({(time.time() - t0) / (i + 1):.2f}s/step)")
    if checkpoint_path:
        save_checkpoint(checkpoint_path, params,
                        metadata={"arch": cfg.name, "steps": steps})
        print("checkpoint ->", checkpoint_path)
    return params, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b", choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--full", action="store_true",
                    help="full config (needs a pod; default is reduced)")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    train(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
          lr=args.lr, use_reduced=not args.full, n_layers=args.layers,
          d_model=args.d_model, seed=args.seed,
          checkpoint_path=args.checkpoint)


if __name__ == "__main__":
    main()
