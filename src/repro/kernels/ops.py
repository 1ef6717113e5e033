"""Back-compat kernel wrappers with model-facing signatures.

Superseded by ``repro.kernels.dispatch`` (backend-aware op tables); kept
as thin aliases so PR-1/2 call sites keep working. ``model_kernels``
now registers the elastic MLP/MoE ops alongside attention + ssd — the
width kernel was previously exported but unreachable from
``models.transformer.forward``.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.backend import default_interpret
from repro.kernels.dispatch import kernel_dispatch
from repro.kernels.elastic_matmul import elastic_dense, elastic_matmul
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


def attention_op(q, k, v, *, causal=True, window=None, cap=None,
                 head_mask=None, interpret=None, bq=128, bk=256):
    """(B,Sq,H,D)x(B,Sk,KV,D) -> (B,Sq,H,D); contract matches
    models.attention.chunked_attention. Differentiable and elastic over
    ``head_mask`` (runtime head prefix) — thin alias over the dispatch
    table's ``attention`` op."""
    return flash_attention(q, k, v, head_mask, causal=causal, window=window,
                           cap=cap, bq=bq, bk=bk, interpret=interpret)


def ssd_op(xh, dt, A, Bm, Cm, chunk, *, head_mask=None, interpret=None):
    """Contract matches models.ssm.ssd_chunked (returns (y, None) — the
    final state is only used by decode, which has its own path). Forward-
    only alias; the differentiable head-prefix op lives in dispatch."""
    ha = None if head_mask is None else \
        jnp.sum(head_mask > 0).astype(jnp.int32)
    y = ssd_scan(xh, dt.astype(jnp.float32), A, Bm, Cm, chunk=chunk,
                 h_active=ha, interpret=interpret)
    return y, None


def elastic_mlp_matmul(x, w, k_active, *, interpret=None):
    """(…, K) @ (K, N) with active output prefix k_active (CFL width).
    Back-compat alias over the differentiable ``elastic_dense``."""
    return elastic_dense(x, w, n_active=k_active, interpret=interpret)


def model_kernels(interpret=None):
    """Back-compat model-facing dict: the dispatch table (mlp / moe / ssd /
    attention elastic ops — attention included since the flash kernel grew
    its head prefix + backward). ``interpret=None`` follows the host."""
    backend = "interpret" if default_interpret(interpret) else "tpu"
    return kernel_dispatch(backend).table(
        "transformer")
