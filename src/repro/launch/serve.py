"""Serving CLI — a thin driver over ``repro.serving``.

Batches requests through the multi-tenant :class:`serving.EdgeServer`
(fused one-shot prefill + masked parent-space decode). ``--elastic``
gives each request a random submodel spec, demonstrating distinct-spec
tenants decoded in one compiled program; ``--check-prefill`` asserts
the fused prefill matches the token-by-token decode path at ≤1e-5.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-2.7b \
      --batch 4 --prompt-len 64 --gen 32

``--full`` keeps the config's published widths; with ``--layers N`` it
also cuts the depth to N layers (``configs.depth_cut``), which is how a
published-width parent fits one chip.
"""
from __future__ import annotations

import argparse
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, depth_cut, get_config, reduced
from repro.core.elastic import family_for
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serving.batcher import Request
from repro.serving.server import EdgeServer


def check_prefill_parity(params, cfg, tokens, max_len: int,
                         tol: float = 1e-5) -> float:
    """Assert the fused one-shot prefill leaves the same cache state (and
    last-position logits) as stepping the prompt token by token.

    Both legs run at ``highest`` matmul precision: the check compares two
    float32 computations, and the TPU's default precision rounds f32
    matmul inputs to bf16, which alone exceeds ``tol``.

    Each cache array and the logits are held to ``tol`` times the larger
    of 1 and their largest magnitude: float32 rounding error grows with
    the values, and at published widths (granite-3-8b, d_model 4096) the
    logits and K/V entries reach about 5, where an absolute 1e-5 is some
    20 ulps. Arrays within [-1, 1] keep the absolute bound. Returns the
    largest absolute difference."""
    with jax.default_matmul_precision("highest"):
        logits_f, caches_f = jax.jit(
            lambda p, t: T.prefill(p, cfg, t, max_len))(params, tokens)
        caches_s = T.init_decode_caches(cfg, tokens.shape[0], max_len,
                                        jnp.float32)
        step = jax.jit(lambda p, c, t, i: T.decode_step(p, cfg, c, t, i))
        logits_s = None
        for i in range(tokens.shape[1]):
            logits_s, caches_s = step(params, caches_s, tokens[:, i:i + 1],
                                      jnp.int32(i))
    pairs = list(zip(jax.tree.leaves(caches_f), jax.tree.leaves(caches_s)))
    pairs.append((logits_f, logits_s))
    worst = 0.0
    for a, b in pairs:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(a - b)))
        bound = tol * max(1.0, float(jnp.max(jnp.abs(b))))
        if err > bound:
            raise AssertionError(f"fused prefill diverges from stepwise "
                                 f"decode: {err:.2e} > {bound:.2e}")
        worst = max(worst, err)
    return worst


def serve(arch: str, *, batch: int = 4, prompt_len: int = 64, gen: int = 32,
          use_reduced: bool = True, n_layers: int = None,
          d_model: int = 256, seed: int = 0, temperature: float = 0.0,
          elastic: bool = False, check_prefill: bool = False,
          backend: str = None):
    """``use_reduced`` shrinks widths and depth (``reduced``, depth 4 by
    default); otherwise the published widths are kept and ``n_layers``,
    when given, cuts only the depth (``depth_cut``)."""
    cfg = get_config(arch)
    if cfg.encoder_only:
        raise SystemExit(f"{arch} is encoder-only; no decode path")
    if use_reduced:
        cfg = reduced(cfg, n_layers=n_layers or 4, d_model=d_model)
    elif n_layers:
        full_depth = cfg.n_layers
        cfg = depth_cut(cfg, n_layers)
        print(f"depth cut: {arch} {full_depth} -> {cfg.n_layers} layers at "
              f"published widths (d_model={cfg.d_model}, heads="
              f"{cfg.n_heads}/{cfg.n_kv_heads}x{cfg.head_dim}, d_ff="
              f"{cfg.d_ff}, vocab={cfg.vocab_size})")
    # independent streams: params / prompts / sampling never share a key
    key = jax.random.PRNGKey(seed)
    params_key, prompt_key, sample_key = jax.random.split(key, 3)
    family = family_for(cfg)
    params = family.init_params(params_key)

    prompts = np.asarray(jax.random.randint(
        prompt_key, (batch, prompt_len), 0, cfg.vocab_size))
    if check_prefill:
        worst = check_prefill_parity(params, cfg, jnp.asarray(prompts),
                                     prompt_len + gen)
        print(f"fused-prefill parity: max|Δ| = {worst:.2e} "
              f"(≤ 1e-5 × max(1, max|value|) per array)")

    rng = random.Random(seed)
    specs = [family.random_spec(rng) if elastic else None
             for _ in range(batch)]
    server = EdgeServer(family, params, slots=min(batch, 8),
                        prompt_len=prompt_len, max_new_tokens=gen,
                        temperature=temperature,
                        seed=int(np.asarray(sample_key)[-1]),
                        backend=backend)
    reqs = [Request(uid=b, spec=specs[b], prompt=prompts[b],
                    max_new_tokens=gen) for b in range(batch)]
    t0 = time.time()
    completions = server.run(reqs)
    t_total = time.time() - t0

    tps = batch * gen / max(t_total, 1e-9)
    mode = "elastic multi-tenant" if elastic else "full-parent"
    print(f"arch={cfg.name} batch={batch} prompt={prompt_len} gen={gen} "
          f"[{mode}]")
    print(f"serve: {t_total:.2f}s ({tps:.1f} tok/s aggregate), "
          f"programs={server.compiled_programs()}")
    print("sample generations (token ids):")
    for c in completions[:2]:
        print(f"  req{c.uid}: {c.tokens[:16]} ...")
    return completions, {"serve_s": t_total, "tokens_per_s": tps,
                         "programs": server.compiled_programs()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-2.7b", choices=sorted(ARCHS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--full", action="store_true",
                    help="published widths (default: reduced widths)")
    ap.add_argument("--layers", type=int, default=None,
                    help="depth (reduced: default 4; --full: cut to N)")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--elastic", action="store_true",
                    help="serve a random submodel spec per request")
    ap.add_argument("--check-prefill", action="store_true",
                    help="assert fused prefill == stepwise decode (≤1e-5)")
    ap.add_argument("--backend", default=None,
                    help="kernels.dispatch backend for decode tile-skipping")
    args = ap.parse_args()
    enable_compile_cache()
    serve(args.arch, batch=args.batch, prompt_len=args.prompt_len,
          gen=args.gen, use_reduced=not args.full, n_layers=args.layers,
          d_model=args.d_model, temperature=args.temperature,
          elastic=args.elastic, check_prefill=args.check_prefill,
          backend=args.backend)


if __name__ == "__main__":
    main()
