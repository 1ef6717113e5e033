"""Compile every Pallas kernel family for a described TPU v5e at real
widths — no chip needed: the TPU compiler refuses here what the chip
would refuse (unaligned tiles, too much fast memory), which interpret-mode
parity tests cannot see.

Shapes: granite-3-8b MLP (4096 -> 12800) and head geometry (32 heads over
8 KV heads, head_dim 128), the paper CNN's conv stem and stages,
mamba2-2.7b SSD (80 heads x 64, d_state 128, chunk 256), and
granite-moe-1b-a400m expert routing (32 experts, top-8, d 1024, d_ff 512).
Each test asserts the kernel reached the program as a ``tpu_custom_call``.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import (elastic_conv2d, elastic_dense, flash_attention,
                           grouped_elastic_matmul, ssd_scan)
from repro.kernels.moe_dispatch import moe_combine, moe_dispatch
from repro.kernels.ssd_scan import ssd_scan_bwd


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip, with the
    persistent compilation cache off (such entries cannot be read back
    without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    yield make
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _loss_grad(fn, argnums):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                    argnums=argnums)


# granite-3-8b MLP up-projection: f32 train rows, bf16 decode rows
@pytest.mark.parametrize("m,dtype", [(256, jnp.float32), (4, jnp.bfloat16)])
def test_elastic_dense_fwd_granite_mlp(sds, m, dtype):
    def f(x, w):
        return elastic_dense(x, w, n_active=6400, act="silu",
                             interpret=False)
    _assert_kernel(f, sds((m, 4096), dtype), sds((4096, 12800), dtype))


def test_elastic_dense_grad_granite_mlp(sds):
    def f(x, w):
        return elastic_dense(x, w, k_active=6400, interpret=False)
    _assert_kernel(_loss_grad(f, (0, 1)), sds((256, 12800)),
                   sds((12800, 4096)))


# paper CNN (batch 32, 32x32x3): stem, each stage's stride-2 down conv,
# and a stage-3 block conv
@pytest.mark.parametrize("hw,cin,cout,stride", [
    (32, 3, 32, 1), (32, 32, 32, 2), (16, 32, 64, 2), (8, 64, 128, 2),
    (4, 128, 128, 1)])
def test_elastic_conv2d_paper_cnn(sds, hw, cin, cout, stride):
    def f(x, w, b):
        return elastic_conv2d(x, w, b, stride=stride,
                              cin_active=max(1, cin // 2),
                              cout_active=cout // 2, interpret=False)
    _assert_kernel(f, sds((32, hw, hw, cin)), sds((3, 3, cin, cout)),
                   sds((cout,)))


def test_elastic_conv2d_grad_paper_cnn(sds):
    def f(x, w, b):
        return elastic_conv2d(x, w, b, stride=2, cin_active=32,
                              cout_active=32, interpret=False)
    _assert_kernel(_loss_grad(f, (0, 1, 2)), sds((32, 16, 16, 32)),
                   sds((3, 3, 32, 64)), sds((64,)))


def _granite_attn(sds):
    return (sds((1, 1024, 32, 128)), sds((1, 1024, 8, 128)),
            sds((1, 1024, 8, 128)), sds((32,)))


def test_flash_attention_fwd_granite(sds):
    def f(q, k, v, head_mask):
        return flash_attention(q, k, v, head_mask, causal=True,
                               interpret=False)
    _assert_kernel(f, *_granite_attn(sds))


def test_flash_attention_grad_granite(sds):
    def f(q, k, v, head_mask):
        return flash_attention(q, k, v, head_mask, causal=True,
                               interpret=False)
    _assert_kernel(_loss_grad(f, (0, 1, 2)), *_granite_attn(sds))


def _mamba_ssd(sds):
    B, S, H, P, N = 1, 1024, 80, 64, 128
    return (sds((B, S, H, P)), sds((B, S, H)), sds((H,)), sds((B, S, 1, N)),
            sds((B, S, 1, N)))


def test_ssd_scan_fwd_mamba2(sds):
    def f(xh, dt, A, Bm, Cm):
        return ssd_scan(xh, dt, A, Bm, Cm, 256, h_active=40,
                        interpret=False, return_states=True)
    _assert_kernel(f, *_mamba_ssd(sds))


def test_ssd_scan_bwd_mamba2(sds):
    def f(xh, dt, A, Bm, Cm, states, dy):
        return ssd_scan_bwd(xh, dt, A, Bm, Cm, states, dy, 256,
                            h_active=40, interpret=False)
    _assert_kernel(f, *_mamba_ssd(sds), sds((1, 4, 80, 64, 128)),
                   sds((1, 1024, 80, 64)))


# granite-moe-1b-a400m: 1024 tokens routed top-8 over 32 experts,
# capacity 1.25 x T·k / E = 320 rows per expert
E, CAP, T, K, D, FF = 32, 320, 1024, 8, 1024, 512


def test_grouped_elastic_matmul_granite_moe(sds):
    def f(xs, ws):
        return grouped_elastic_matmul(xs, ws, 16, interpret=False)
    _assert_kernel(f, sds((E, CAP, D)), sds((E, D, FF)))
    _assert_kernel(_loss_grad(f, (0, 1)), sds((E, CAP, D)), sds((E, D, FF)))


def _route(sds):
    i32 = jnp.int32
    return (sds((T, D)), sds((E * CAP,), i32), sds((E * CAP,), i32),
            sds((T * K,), i32), sds((T * K,), i32))


def test_moe_dispatch_granite_moe(sds):
    def f(xt, slot_src, slot_valid, dest_tj, kept_tj):
        return moe_dispatch(xt, slot_src, slot_valid, dest_tj, kept_tj,
                            n_experts=E, cap=CAP, interpret=False)
    _assert_kernel(f, *_route(sds))
    _assert_kernel(_loss_grad(f, 0), *_route(sds))


def test_moe_combine_granite_moe(sds):
    def f(y_flat, gate_eff, dest_tj, slot_src, slot_valid, slot_gate):
        return moe_combine(y_flat, gate_eff, dest_tj, slot_src, slot_valid,
                           slot_gate, interpret=False)
    i32 = jnp.int32
    args = (sds((E * CAP, D)), sds((T, K)), sds((T * K,), i32),
            sds((E * CAP,), i32), sds((E * CAP,), i32), sds((E * CAP,)))
    _assert_kernel(f, *args)
    _assert_kernel(_loss_grad(f, (0, 1)), *args)
