"""Fused residual-bottleneck block forward (eval/inference) in Pallas.

The reference's fused-conv-epilogue kernel class
(paddle/fluid/operators/fused/conv_fusion_op.cc:62 conv+bias+activation
(+residual) via cudnnConvolutionBiasActivationForward, placed by the
inference fusion passes together with conv_bn_fuse_pass) — rebuilt as
cross-layer persistent activation blocking, which is what the v5e
roofline actually rewards (pre-round ResNet device trace: conv fusions run at 92%
of HBM peak, so the only lever is moving FEWER bytes):

one kernel instance computes an ENTIRE image's bottleneck block
    out = relu(conv3(relu(conv2(relu(conv1(x))))) + x)
with every intermediate living in VMEM — at ResNet-50 shapes a full
[H*W, C] activation plane is at most 1.6 MB, so the chain needs ONE
HBM read of x and ONE write of out, where the per-conv XLA schedule
round-trips every intermediate (~4 big passes per block).

The 3x3 conv runs as 9 shifted matmuls over the flattened [H*W, M]
plane: tap (dy, dx) contributes shift_rows(y1, dy*W+dx) @ W2[tap],
with column-edge taps masked (a row shift in flat index wraps across
image rows exactly where x+dx leaves [0, W)). All matmuls accumulate
in f32 on the MXU.

Scope: stride-1 identity bottleneck blocks (13 of ResNet-50's 16),
NHWC, eval mode — BatchNorm folds into conv scale/bias ahead of the
call (inference/fusion.py). TRAIN-mode chaining is mathematically
blocked by exact batch-norm: stats over (N, H, W) must complete before
the normalized output feeds the next conv, so each BN boundary forces
either an HBM round trip or a full re-read of x per BN (measured and
derived in the r5 ceiling note of a pre-round record).

MEASURED RESULT (v5e b128 eval forward, scan-16 floor-subtracted,
tools/fused_eval_bench.py): the kernel LOSES to XLA's per-conv
schedule — 10.2-12.7 ms fused vs 8.6-9.6 ms eager across variants
(9 shifted matmuls; im2col single-matmul; image packing; stage-1/2
gating). The HBM bytes it saves are real, but XLA's convolutions use
the hardware conv path with years of layout tuning while this kernel
pays VPU shuffles for the im2col and 50%-lane matmuls at M=64 — at
~9 ms the eval forward is close enough to its bandwidth floor that
the VPU overhead dominates the saved traffic. The kernel therefore
ships OFF by default (enable_fused_conv_eval() / PT_FUSED_CONV_EVAL=1
to opt in) as the reference-parity fused-conv-epilogue capability +
a pinned-down negative result, not as the default path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from .naming import named_pallas_call


def _shift_rows(v, s, hw):
    """rows i of the result read v[i + s]; out-of-range rows are 0."""
    if s == 0:
        return v
    z = jnp.zeros((abs(s), v.shape[1]), v.dtype)
    if s > 0:
        return jnp.concatenate([v[s:], z], axis=0)
    return jnp.concatenate([z, v[:s]], axis=0)


def _block_kernel(x_ref, w1_ref, w2_ref, w3_ref, b1_ref, b2_ref, b3_ref,
                  o_ref, *, h, w, m, c, g):
    """One instance processes ``g`` whole images, stacked on the row
    axis ([g*H*W, C]) so the matmuls stay MXU-sized even at the late
    stages' tiny spatial planes (stage 4: 49 rows/image — per-image
    matmuls measured 0.85x XLA; packed rows win)."""
    hw = h * w
    rows = g * hw
    x = x_ref[0]  # [g*HW, C]
    f32 = jnp.float32
    # conv1 (1x1) + bias + relu
    y1 = jax.lax.dot_general(x, w1_ref[...], (((1,), (0,)), ((), ())),
                             preferred_element_type=f32)
    y1 = jnp.maximum(y1 + b1_ref[...], 0.0).astype(x.dtype)
    # conv2 (3x3, pad 1): in-VMEM im2col (9 shifted copies stacked on
    # lanes) + ONE deep matmul — contraction 9*M keeps the MXU fed
    # where 9 separate M-deep taps ran it at a fraction of peak.
    # Validity of tap (dy, dx) at in-image position p (row index % HW):
    # p + dy*W + dx in [0, HW) exactly captures the y bound (the x
    # bound catches the dx spill across row ends), so the same mask
    # also stops shifts from reading the NEIGHBOURING image in the
    # row-packed layout.
    pos = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % hw
    col = pos % w
    pieces = []
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            s = dy * w + dx
            sh = _shift_rows(y1, s, rows)
            valid = (pos + s >= 0) & (pos + s < hw)
            if dx == -1:
                valid = valid & (col != 0)
            elif dx == 1:
                valid = valid & (col != w - 1)
            pieces.append(jnp.where(valid, sh, 0))
    im2col = jnp.concatenate(pieces, axis=1)  # [g*HW, 9*M]
    acc = jax.lax.dot_general(im2col, w2_ref[...],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=f32)
    y2 = jnp.maximum(acc + b2_ref[...], 0.0).astype(x.dtype)
    # conv3 (1x1) + bias + residual + relu
    y3 = jax.lax.dot_general(y2, w3_ref[...], (((1,), (0,)), ((), ())),
                             preferred_element_type=f32)
    y3 = y3 + b3_ref[...] + x.astype(f32)
    o_ref[0] = jnp.maximum(y3, 0.0).astype(o_ref.dtype)


def _images_per_instance(n, hw):
    """Measured on v5e (b128 eval sweep): packing multiple images per
    instance to widen the late stages' matmuls LOST outright (12.7 vs
    10.2 ms full-model — the im2col masks and lane shuffles grow with
    the packed plane and the VPU, not the MXU, is the binding unit
    here), so instances stay one image."""
    return 1


def fused_bottleneck_eval(x, w1, b1, w2, b2, w3, b3):
    """x [N, H, W, C] NHWC; w1 [C, M], w2 [9*M, M] (taps stacked
    ky-major), w3 [M, C]; biases [1, ·] f32 (BN pre-folded). Returns
    relu(conv3(relu(conv2(relu(conv1(x))))) + x)."""
    n, h, w, c = x.shape
    m = w1.shape[1]
    hw = h * w
    g = _images_per_instance(n, hw)
    xf = x.reshape(n // g, g * hw, c)

    def pinned(shape):
        nd = len(shape)
        return pl.BlockSpec((*shape,), lambda i: (0,) * nd,
                            memory_space=pltpu.VMEM)

    plane = pl.BlockSpec((1, g * hw, c), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM)
    out = named_pallas_call(
        "fused_conv_block",
        functools.partial(_block_kernel, h=h, w=w, m=m, c=c, g=g),
        grid=(n // g,),
        in_specs=[plane, pinned(w1.shape), pinned(w2.shape),
                  pinned(w3.shape), pinned(b1.shape), pinned(b2.shape),
                  pinned(b3.shape)],
        out_specs=plane,
        out_shape=jax.ShapeDtypeStruct((n // g, g * hw, c), x.dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * n * hw * (c * m * 2 + 9 * m * m),
            bytes_accessed=2 * x.size * x.dtype.itemsize,
            transcendentals=0),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # stage-1 planes (two [3136, 256] bf16 in/out, double
            # buffered, plus the [3136, 64] chain intermediates) need
            # ~19 MB — above the 16 MB default scoped budget, well
            # under the chip's physical VMEM
            vmem_limit_bytes=64 * 1024 * 1024),
    )(xf, w1, w2, w3, b1, b2, b3)
    return out.reshape(n, h, w, c)


def fold_bn(conv_w, gamma, beta, mean, var, eps):
    """BN -> conv scale/bias fold (the conv_bn_fuse_pass algebra, at
    call time on eval stats): returns (scaled [out_c, in_c, kh, kw]
    weights, bias [out_c] f32)."""
    scale = (gamma / jnp.sqrt(var + eps)).astype(jnp.float32)
    wf = (conv_w.astype(jnp.float32) *
          scale[:, None, None, None]).astype(conv_w.dtype)
    bias = (beta - mean * scale).astype(jnp.float32)
    return wf, bias


def pack_bottleneck(block):
    """Fold the three BNs of a BottleneckBlock and pack its conv
    weights into the kernel's matmul layouts. Returns the 7-tuple of
    fused_bottleneck_eval parameters (w1, b1, w2, b2, w3, b3 minus x).
    Weight layout in this repo is [out_c, in_c, kh, kw] regardless of
    data_format (inference/fusion.py)."""
    def fold(conv, bn):
        return fold_bn(conv.weight.value, bn.weight.value,
                       bn.bias.value, bn._mean.value,
                       bn._variance.value, bn._epsilon)

    w1, b1 = fold(block.conv1, block.bn1)
    w2, b2 = fold(block.conv2, block.bn2)
    w3, b3 = fold(block.conv3, block.bn3)
    m = w1.shape[0]
    w1m = w1[:, :, 0, 0].T  # [C, M]
    # [M_out, M_in, 3, 3] -> taps ky-major [9*M_in, M_out]
    w2m = w2.transpose(2, 3, 1, 0).reshape(9 * m, m)
    w3m = w3[:, :, 0, 0].T  # [M, C]
    return (w1m, b1[None, :], w2m, b2[None, :], w3m, b3[None, :])


import os as _os

_FUSED_EVAL_ENABLED = bool(int(_os.environ.get("PT_FUSED_CONV_EVAL",
                                               "0")))


def enable_fused_conv_eval(enabled: bool = True) -> None:
    """Opt in to routing eval bottleneck blocks through the fused
    kernel (measured slower than XLA on v5e — see module docstring;
    kept for parity with conv_fusion_op and for backends/shapes where
    the trade flips)."""
    global _FUSED_EVAL_ENABLED
    _FUSED_EVAL_ENABLED = bool(enabled)


def fused_bottleneck_supported(block, x_shape, data_format,
                               backend: Optional[str] = None) -> bool:
    """Gate: opted in, stride-1 dilation-1 ungrouped identity
    bottleneck with plain BatchNorm2D norms, NHWC, TPU-family backend,
    plane fits comfortably in VMEM."""
    from ...nn.norm import BatchNorm2D
    from .flash_attention import _FORCE_DEPTH
    if not _FUSED_EVAL_ENABLED:
        return False
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu" and _FORCE_DEPTH == 0:
        return False
    if data_format != "NHWC" or block.downsample is not None:
        return False
    if block.conv2._stride not in (1, (1, 1)):
        return False
    if block.conv2._dilation not in (1, (1, 1)):
        return False
    if getattr(block.conv2, "_groups", 1) != 1:
        return False
    # pack_bottleneck folds _mean/_variance/_epsilon — plain BN only
    if not all(type(bn) is BatchNorm2D
               for bn in (block.bn1, block.bn2, block.bn3)):
        return False
    n, h, w, c = x_shape
    if h * w < 784:
        # stage-3/4 planes (196/49 positions): per-image matmuls are
        # too small for the MXU and packing lost (see
        # _images_per_instance) — XLA keeps those blocks
        return False
    m = block.conv1.weight.shape[0]
    # x + out + y1/y2/acc + weights, double-buffered planes
    vmem = (2 * h * w * c * 2 + h * w * m * (2 * 2 + 4) +
            (c * m * 2 + 9 * m * m) * 2) * 2
    return vmem < 100 * 2 ** 20 and c == 4 * m
