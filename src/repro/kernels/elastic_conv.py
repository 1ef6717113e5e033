"""Channel-prefix elastic conv2d — im2col lowering onto the elastic matmul.

The CFL CNN parent masks a *prefix* of channels per stage; the dense
masked forward (``core.elastic.masked_forward``) still pays full-channel
conv FLOPs and multiplies by 0/1. This lowers each SAME conv to a matmul
whose contraction dimension is ordered **channel-major** — K index
``c * (kh*kw) + tap`` — so an input-channel prefix ``cin_active`` becomes
a *contraction prefix* ``cin_active * kh * kw`` and an output-channel
prefix ``cout_active`` an output-column prefix; both are skipped (not
zeroed) by ``elastic_dense``'s tile-skipping kernel, bias fused at the
write.

The im2col patches are materialised (kh*kw× the activation — the known
cost of this lowering; acceptable at the paper-CNN scales, and the patch
tensor itself is what lets masked tiles be skipped). The lowering is
built from differentiable slicing, so the backward runs through
``elastic_dense``'s tile-skipping VJP and a pad/slice-transpose col2im —
no custom VJP needed here.

Semantics (matching the dense masked path, where inactive input channels
are already zero): ``y = (conv(x ⊙ cin_mask, w) + b) ⊙ cout_mask``.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.elastic_matmul import elastic_dense


def _im2col(x, kh: int, kw: int, stride: int):
    """SAME-padded patch extraction, channel-major contraction layout.

    x: (B, H, W, C) -> (B*oh*ow, C*kh*kw) with K index c*(kh*kw) + tap,
    plus the (B, oh, ow) output geometry.
    """
    B, H, W, C = x.shape
    oh = -(-H // stride)
    ow = -(-W // stride)
    pad_h = max((oh - 1) * stride + kh - H, 0)
    pad_w = max((ow - 1) * stride + kw - W, 0)
    xp = jnp.pad(x, ((0, 0), (pad_h // 2, pad_h - pad_h // 2),
                     (pad_w // 2, pad_w - pad_w // 2), (0, 0)))
    taps = []
    for i in range(kh):
        for j in range(kw):
            taps.append(xp[:, i:i + (oh - 1) * stride + 1:stride,
                           j:j + (ow - 1) * stride + 1:stride, :])
    pat = jnp.stack(taps, axis=-1)                 # (B, oh, ow, C, kh*kw)
    return pat.reshape(B * oh * ow, C * kh * kw), (B, oh, ow)


def elastic_conv2d(x, w, b=None, *, stride: int = 1, cin_active=None,
                   cout_active=None, interpret: bool | None = None,
                   bm: int = 128, bn: int = 128, bk: int = 128):
    """Tile-skipping SAME conv. x: (B,H,W,Cin); w: (kh,kw,Cin,Cout);
    b: (Cout,) fused bias; cin_active / cout_active: runtime int32 channel
    prefixes (None = full). NHWC/HWIO, matching models.cnn._conv.
    """
    kh, kw, Cin, Cout = w.shape
    pat, (B, oh, ow) = _im2col(x, kh, kw, stride)
    # (kh,kw,Cin,Cout) -> channel-major (Cin*kh*kw, Cout)
    wmat = jnp.transpose(w, (2, 0, 1, 3)).reshape(Cin * kh * kw, Cout)
    ka = None if cin_active is None else cin_active * (kh * kw)
    y = elastic_dense(pat, wmat, b, k_active=ka, n_active=cout_active,
                      bm=bm, bn=bn, bk=bk, interpret=interpret)
    return y.reshape(B, oh, ow, Cout)
