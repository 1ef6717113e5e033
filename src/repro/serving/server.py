"""Multi-tenant masked decode: many submodels, one compiled program.

The server batches tenants with *different* submodel specs by running
the parent-space masked decode (``models.transformer.decode_step`` with
per-tenant forward masks) vmapped over a fixed slot axis. The training
engine's exactness contract carries over: a tenant's masked decode
equals its extracted dense submodel's decode, so one program serves
every spec.

Compiled-program budget (asserted in tests/test_serving.py): exactly
three jitted programs regardless of tenant churn —

* ``prefill``  — one-shot prompt prefill of a single slot (fused
  ``models.transformer.prefill``; fills the slot's ``DecodeCaches`` in
  one program);
* ``write``    — scatter a prefilled slot cache into the stacked tenant
  cache at a *traced* slot index;
* ``step``     — one masked decode step for all slots at once (vmap over
  the slot axis: per-tenant cache, token, position, and mask values).

Tenant admit/evict changes only array *values* (mask pytrees, slot
indices, positions), never shapes — so churn never recompiles.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import transformer as T
from repro.serving.batcher import Completion, ContinuousBatcher, Request


class EdgeServer:
    """Multi-tenant batched decode server over a trained parent.

    params: parent-space params (e.g. ``CFLSession.params``).
    slots: fixed tenant axis (padded; admit/evict churns values only).
    prompt_len: fixed prompt window — shorter prompts are front-padded
        with ``pad_token`` (the padded prompt is the served prompt),
        longer ones keep their last ``prompt_len`` tokens.
    backend: ``kernels.dispatch`` backend for tile-skipping decode ops
        (None = dense masked XLA path).
    """

    def __init__(self, family, params, *, slots: int = 4,
                 prompt_len: int = 32, max_new_tokens: int = 32,
                 backend: Optional[str] = None, cache_dtype=jnp.float32,
                 temperature: float = 0.0, seed: int = 0,
                 pad_token: int = 0, trace_logits: bool = False):
        if not getattr(family, "supports_decode", False):
            raise ValueError(
                f"family {family.name!r} has no cached decode path")
        self.family = family
        self.cfg = family.cfg
        self.params = params
        self.slots = slots
        self.prompt_len = prompt_len
        self.max_new_tokens = max_new_tokens
        self.max_len = prompt_len + max_new_tokens
        self.temperature = temperature
        self.pad_token = pad_token
        self.trace_logits = trace_logits
        self._key = jax.random.PRNGKey(seed)
        self._kernels = None
        if backend is not None:
            from repro.kernels.dispatch import kernel_dispatch
            self._kernels = kernel_dispatch(backend).table(family.name)

        self.batcher = ContinuousBatcher(slots)
        # stacked tenant caches: (slots, 1, ...) — each slot a batch-1 decode
        single = T.init_decode_caches(self.cfg, 1, self.max_len, cache_dtype)
        self._caches = jax.tree.map(
            lambda a: jnp.zeros((slots,) + a.shape, a.dtype), single)
        # host-side per-slot state; empty slots hold the full-parent mask
        # placeholder so the stacked mask pytree always has the same shapes
        full_fwd = self._host_masks(family.full_spec())
        self._slot_masks: List[Any] = [full_fwd] * slots
        self._slot_pos = np.zeros((slots,), np.int32)
        self._slot_tok = np.zeros((slots,), np.int32)
        # submit times by uid, kept while spans record (``repro.obs``)
        self._submitted: Dict[Any, int] = {}

        cfg, kern, cdt = self.cfg, self._kernels, cache_dtype

        def _prefill(params, tokens, fwd):
            return T.prefill(params, cfg, tokens, self.max_len, masks=fwd,
                             kernels=kern, cache_dtype=cdt)

        def _write(caches, new, idx):
            return jax.tree.map(lambda full, u: full.at[idx].set(u),
                                caches, new)

        def _step(params, caches, toks, pos, fwd):
            def one(c, t, p, f):
                logits, c = T.decode_step(params, cfg, c, t[None, None], p,
                                          masks=f, kernels=kern)
                return logits[0], c
            return jax.vmap(one, in_axes=(0, 0, 0, 0))(caches, toks, pos,
                                                       fwd)

        self._prefill_fn = jax.jit(_prefill)
        self._write_fn = jax.jit(_write, donate_argnums=(0,))
        self._step_fn = jax.jit(_step, donate_argnums=(1,))

    # -- internals ---------------------------------------------------------
    def _host_masks(self, spec):
        fwd = self.family.decode_masks(spec)
        return jax.tree.map(np.asarray, fwd)

    def _fit_prompt(self, prompt: np.ndarray) -> np.ndarray:
        p = np.asarray(prompt, np.int32).reshape(-1)
        if len(p) >= self.prompt_len:
            return p[-self.prompt_len:]
        pad = np.full((self.prompt_len - len(p),), self.pad_token, np.int32)
        return np.concatenate([pad, p])

    def _stacked_masks(self):
        return jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)),
                            *self._slot_masks)

    def _sample(self, logits: np.ndarray) -> int:
        if self.temperature <= 0.0:
            return int(np.argmax(logits))
        self._key, sub = jax.random.split(self._key)
        return int(jax.random.categorical(
            sub, jnp.asarray(logits) / self.temperature))

    def _admit_one(self, slot: int, req: Request) -> Optional[Completion]:
        submitted = self._submitted.pop(req.uid, None)
        if submitted is not None:
            obs.record("serve.queue", submitted, time.perf_counter_ns(),
                       uid=req.uid)
        with obs.span("serve.admit", uid=req.uid):
            toks = self._fit_prompt(req.prompt)
            spec = req.spec if req.spec is not None \
                else self.family.full_spec()
            with obs.span("serve.masks"):
                host_fwd = self._host_masks(spec)
                fwd = jax.tree.map(jnp.asarray, host_fwd)
            with obs.span("serve.prefill"):
                logits, slot_caches = self._prefill_fn(self.params,
                                                       toks[None], fwd)
            with obs.span("serve.write"):
                self._caches = self._write_fn(self._caches, slot_caches,
                                              jnp.int32(slot))
            self._slot_masks[slot] = host_fwd
            self._slot_pos[slot] = self.prompt_len
            with obs.span("serve.first_token_wait"):
                logits0 = np.asarray(logits[0])
                tok = self._sample(logits0)
            self._slot_tok[slot] = tok
            return self.batcher.record(
                slot, tok, logits0 if self.trace_logits else None)

    # -- public API --------------------------------------------------------
    def submit(self, request: Request) -> None:
        if request.max_new_tokens > self.max_new_tokens:
            # the cache budget is max_len = prompt_len + max_new_tokens;
            # longer generations would decode past the allocated positions
            request = dataclasses.replace(
                request, max_new_tokens=self.max_new_tokens)
        if obs.active():
            self._submitted[request.uid] = time.perf_counter_ns()
        self.batcher.submit(request)

    def step(self) -> List[Completion]:
        """One scheduler tick: admit queued requests into free slots
        (prefill + first token), then run one batched decode step for all
        occupied slots. Returns completions finished this tick."""
        admitted = self.batcher.admit()
        with obs.span("serve.step", admitted=len(admitted),
                      active=len(self.batcher.occupied())):
            return self._step(admitted)

    def _step(self, admitted: List[int]) -> List[Completion]:
        done: List[Completion] = []
        for slot in admitted:
            c = self._admit_one(slot, self.batcher.request_at(slot))
            if c is not None:
                done.append(c)
        active = self.batcher.occupied()
        if not active:
            return done
        with obs.span("serve.stack_masks"):
            fwd = self._stacked_masks()
        with obs.span("serve.decode_dispatch"):
            logits_all, self._caches = self._step_fn(
                self.params, self._caches, jnp.asarray(self._slot_tok),
                jnp.asarray(self._slot_pos), fwd)
        with obs.span("serve.logits_wait"):
            logits_np = np.asarray(logits_all)
        with obs.span("serve.sample"):
            for slot in active:
                self._slot_pos[slot] += 1
                tok = self._sample(logits_np[slot])
                self._slot_tok[slot] = tok
                c = self.batcher.record(
                    slot, tok,
                    logits_np[slot] if self.trace_logits else None)
                if c is not None:
                    done.append(c)
        return done

    def run(self, requests: Sequence[Request]) -> List[Completion]:
        """Serve ``requests`` to completion (continuous batching: slots
        are re-admitted as tenants finish)."""
        for r in requests:
            self.submit(r)
        done: List[Completion] = []
        while self.batcher.busy:
            done.extend(self.step())
        order = {r.uid: i for i, r in enumerate(requests)}
        return sorted(done, key=lambda c: order.get(c.uid, len(order)))

    def compiled_programs(self) -> Dict[str, Optional[int]]:
        """Per-function compiled-program counts (None if the runtime does
        not expose a cache-size probe)."""
        out = {}
        for name, fn in (("prefill", self._prefill_fn),
                         ("write", self._write_fn),
                         ("step", self._step_fn)):
            get = getattr(fn, "_cache_size", None)
            out[name] = get() if callable(get) else None
        return out
