"""Quantization primitives and QAT/PTQ drivers."""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import dispatch
from ..nn.common import Linear
from ..nn.conv import Conv2D
from ..nn.layer import Layer
from ..tensor import Tensor

F = dispatch.wrapped_ops


@dataclasses.dataclass
class QuantConfig:
    weight_bits: int = 8
    activation_bits: int = 8
    weight_quantize_type: str = "channel_wise_abs_max"
    activation_quantize_type: str = "moving_average_abs_max"
    moving_rate: float = 0.9
    quantizable_layer_type: tuple = ("Linear", "Conv2D")


def fake_quant(x, scale, bits: int = 8):
    """Symmetric fake-quant with straight-through estimator
    (reference: fake_quantize_op kernels). Dispatched through the op layer
    so the eager tape records the STE gradient."""
    qmax = float(2 ** (bits - 1) - 1)

    def _fq(v, s):
        s = jnp.maximum(s, 1e-8)
        q = jnp.clip(jnp.round(v / s * qmax), -qmax, qmax)
        fq = q * s / qmax
        return v + jax.lax.stop_gradient(fq - v)

    return dispatch.call_fn(_fq, "fake_quant", True, (x, scale), {})


def quant_dequant(x, bits: int = 8, axis: Optional[int] = None):
    """Quantize to int8 + dequant scales (the PTQ conversion step)."""
    raw = np.asarray(x.value if isinstance(x, Tensor) else x)
    qmax = float(2 ** (bits - 1) - 1)
    if axis is None:
        scale = np.abs(raw).max()
        q = np.clip(np.round(raw / max(scale, 1e-8) * qmax), -qmax,
                    qmax).astype(np.int8)
        return q, np.float32(scale)
    mv = np.moveaxis(raw, axis, 0)
    scale = np.abs(mv.reshape(mv.shape[0], -1)).max(axis=1)
    q = np.clip(np.round(mv / np.maximum(scale, 1e-8)[
        (slice(None),) + (None,) * (mv.ndim - 1)] * qmax), -qmax,
        qmax).astype(np.int8)
    return np.moveaxis(q, 0, axis), scale.astype(np.float32)


class FakeQuantLayer(Layer):
    """Observes activation abs-max (moving average) and fake-quants."""

    def __init__(self, bits: int = 8, moving_rate: float = 0.9):
        super().__init__()
        self.bits = bits
        self.moving_rate = moving_rate
        self.register_buffer("scale", Tensor(jnp.ones(())))
        self._initialized = False

    def forward(self, x):
        if self.training:
            cur = F["max"](F["abs"](x.detach() if isinstance(x, Tensor)
                                    else x))
            cur_v = cur.value if isinstance(cur, Tensor) else cur
            if not self._initialized:
                self.scale.set_value(cur_v)
                self._initialized = True
            else:
                self.scale.set_value(self.moving_rate * self.scale.value +
                                     (1 - self.moving_rate) * cur_v)
        return fake_quant(x, self.scale, self.bits)


class QuantizedLinear(Layer):
    """Linear with fake-quant on weight (per-channel) + activation."""

    def __init__(self, inner: Linear, config: QuantConfig):
        super().__init__()
        self.inner = inner
        self.act_quant = FakeQuantLayer(config.activation_bits,
                                        config.moving_rate)
        self.w_bits = config.weight_bits
        self.per_channel = "channel" in config.weight_quantize_type

    def _w_scale(self):
        w = self.inner.weight
        if self.per_channel:
            s = F["max"](F["abs"](w.detach()), axis=0, keepdim=True)
        else:
            s = F["max"](F["abs"](w.detach()))
        return s

    def forward(self, x):
        x = self.act_quant(x)
        wq = fake_quant(self.inner.weight, self._w_scale(), self.w_bits)
        return F["linear"](x, wq, self.inner.bias)


class QuantizedConv2D(Layer):
    def __init__(self, inner: Conv2D, config: QuantConfig):
        super().__init__()
        self.inner = inner
        self.act_quant = FakeQuantLayer(config.activation_bits,
                                        config.moving_rate)
        self.w_bits = config.weight_bits

    def forward(self, x):
        x = self.act_quant(x)
        w = self.inner.weight
        s = F["max"](F["abs"](w.detach()))
        wq = fake_quant(w, s, self.w_bits)
        return F["conv2d"](x, wq, self.inner.bias, self.inner._stride,
                           self.inner._padding, self.inner._dilation,
                           self.inner._groups, self.inner._data_format)


class ImperativeQuantAware:
    """QAT driver (reference: slim ImperativeQuantAware.quantize — swaps
    quantizable layers for quant-aware versions in place)."""

    def __init__(self, config: Optional[QuantConfig] = None, **kw):
        self.config = config or QuantConfig(**kw)

    def quantize(self, model: Layer) -> Layer:
        self._convert(model)
        return model

    def _convert(self, layer: Layer) -> None:
        for name, sub in list(layer._sub_layers.items()):
            if isinstance(sub, Linear):
                layer._sub_layers[name] = QuantizedLinear(sub, self.config)
            elif isinstance(sub, Conv2D) and type(sub) is Conv2D:
                layer._sub_layers[name] = QuantizedConv2D(sub, self.config)
            else:
                self._convert(sub)

    def save_quantized_model(self, model: Layer, path: str,
                             input_spec=None) -> None:
        from ..static.program import build_program
        model.eval()
        prog = build_program(model, input_spec)
        prog.save(path)


class PTQ:
    """Post-training quantization: run calibration batches through
    observers, then export int8 weights + scales
    (reference: slim PostTrainingQuantization)."""

    def __init__(self, bits: int = 8):
        self.bits = bits
        self.act_ranges: Dict[str, float] = {}
        self._hooks = []

    def _observer(self, name):
        def hook(layer, inputs, outputs):
            x = inputs[0]
            v = float(np.abs(np.asarray(
                x.value if isinstance(x, Tensor) else x)).max())
            self.act_ranges[name] = max(self.act_ranges.get(name, 0.0), v)
        return hook

    def calibrate(self, model: Layer, data_iter, num_batches: int = 8
                  ) -> None:
        model.eval()
        for name, sub in model.named_sublayers():
            if isinstance(sub, (Linear, Conv2D)):
                self._hooks.append(
                    sub.register_forward_post_hook(self._observer(name)))
        for i, batch in enumerate(data_iter):
            if i >= num_batches:
                break
            xs = batch[0] if isinstance(batch, (list, tuple)) else batch
            model(xs if isinstance(xs, Tensor) else Tensor(
                jnp.asarray(np.asarray(xs))))
        for h in self._hooks:
            h.remove()
        self._hooks.clear()

    def quantize_weights(self, model: Layer) -> Dict[str, dict]:
        """Return {layer_name: {weight_int8, weight_scale, act_scale}}."""
        out = {}
        for name, sub in model.named_sublayers():
            if isinstance(sub, Linear):
                q, s = quant_dequant(sub.weight, self.bits, axis=1)
                out[name] = {"weight_int8": q, "weight_scale": s,
                             "act_scale": self.act_ranges.get(name)}
            elif isinstance(sub, Conv2D):
                q, s = quant_dequant(sub.weight, self.bits, axis=0)
                out[name] = {"weight_int8": q, "weight_scale": s,
                             "act_scale": self.act_ranges.get(name)}
        return out


# --------------------------------------------------------------------------
# int8 EXECUTION path (reference: slim quantization_pass.py rewrites the
# program for quantized inference; trt_int8_calibrator.cc feeds TensorRT
# int8 engines). TPU-native: weights stored as int8 arrays, activations
# quantized on the fly, and the matmul/conv runs as an int8 x int8 ->
# int32 XLA dot/conv (the MXU's native int8 path) with one scale-multiply
# to come back to float.
# --------------------------------------------------------------------------

def quantize_int8(x, scale):
    """Symmetric rounding quantization to int8 (execution-path analog of
    the reference's quantize_op): q = clip(round(x / scale * 127))."""
    raw = x.value if isinstance(x, Tensor) else jnp.asarray(x)
    s = jnp.maximum(jnp.asarray(scale), 1e-8)
    return jnp.clip(jnp.round(raw / s * 127.0), -127, 127).astype(jnp.int8)


def dequantize_int8(q, scale):
    """reference dequantize_op: float = q * scale / 127."""
    raw = q.value if isinstance(q, Tensor) else jnp.asarray(q)
    return raw.astype(jnp.float32) * (jnp.asarray(scale) / 127.0)


class Int8Linear(Layer):
    """Linear executing as int8 x int8 -> int32 on the MXU.

    Weight is held as an int8 buffer with a per-output-channel scale;
    the activation quantizes against the calibrated abs-max. One float
    multiply recovers the result scale — XLA fuses it into the dot's
    epilogue."""

    def __init__(self, inner: Linear, act_scale: float, bits: int = 8):
        super().__init__()
        assert bits == 8, "int8 execution supports 8-bit only"
        q, w_scale = quant_dequant(inner.weight, bits, axis=1)
        self.register_buffer("weight_int8", Tensor(jnp.asarray(q)))
        self.register_buffer("weight_scale",
                             Tensor(jnp.asarray(w_scale)))  # [out]
        self.register_buffer("act_scale",
                             Tensor(jnp.asarray(np.float32(act_scale))))
        self.bias = inner.bias

    def forward(self, x):
        def kernel(xv, wq, ws, asc, *maybe_bias):
            qx = quantize_int8(xv, asc)
            acc = jax.lax.dot_general(
                qx, wq, (((qx.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            out = acc.astype(jnp.float32) * (
                (asc / 127.0) * (ws / 127.0))
            if maybe_bias:
                out = out + maybe_bias[0]
            return out

        args = [x, self.weight_int8, self.weight_scale, self.act_scale]
        if self.bias is not None:
            args.append(self.bias)
        return dispatch.call_fn(kernel, "int8_linear", False,
                                tuple(args), {})


class Int8Conv2D(Layer):
    """Conv2D executing as int8 x int8 -> int32 (per-tensor weight
    scale; NCHW)."""

    def __init__(self, inner: Conv2D, act_scale: float, bits: int = 8):
        super().__init__()
        assert bits == 8
        q, w_scale = quant_dequant(inner.weight, bits, axis=None)
        self.register_buffer("weight_int8", Tensor(jnp.asarray(q)))
        self.register_buffer("weight_scale",
                             Tensor(jnp.asarray(np.float32(w_scale))))
        self.register_buffer("act_scale",
                             Tensor(jnp.asarray(np.float32(act_scale))))
        self.bias = inner.bias
        self._stride = inner._stride
        self._padding = inner._padding
        self._dilation = inner._dilation
        self._groups = inner._groups
        self._data_format = inner._data_format

    def forward(self, x):
        # same stride/padding/dilation normalization as the fp32 conv2d
        # kernel (ops/nn_functional.py) so both paths accept identical
        # configs
        from ..ops.nn_functional import _conv_padding, _norm_tuple
        stride, padding = self._stride, self._padding
        dilation, groups = self._dilation, self._groups

        def kernel(xv, wq, ws, asc, *maybe_bias):
            qx = quantize_int8(xv, asc)
            acc = jax.lax.conv_general_dilated(
                qx, wq, window_strides=_norm_tuple(stride, 2),
                padding=_conv_padding(padding, 2, stride, dilation,
                                      wq.shape[2:]),
                rhs_dilation=_norm_tuple(dilation, 2),
                feature_group_count=groups,
                preferred_element_type=jnp.int32)
            out = acc.astype(jnp.float32) * (
                (asc / 127.0) * (ws / 127.0))
            if maybe_bias:
                out = out + maybe_bias[0].reshape(1, -1, 1, 1)
            return out

        args = [x, self.weight_int8, self.weight_scale, self.act_scale]
        if self.bias is not None:
            args.append(self.bias)
        return dispatch.call_fn(kernel, "int8_conv2d", False,
                                tuple(args), {})


# symmetric-quantization ranges shared by the KV compute path (the
# in-VMEM kernel dequant, ops/pallas/paged_attention.py), the paged
# pool append (models/gpt.py paged_kv_append) and the r23 spill/wire
# blob codecs (serving/prefix_cache.py pack_page_blob): ONE definition
# so "deq = q * s / qmax" means the same thing in every tier a page
# visits — device, host blob, disk blob, wire
KV_QMAX_INT8 = 127.0
KV_QMAX_INT4 = 7.0


def quantize_kv(x, eps: float = 1e-8):
    """Symmetric int8 quantization for KV-cache tokens: per-(token,
    head) abs-max over the head_dim axis — the finest granularity that
    stays outside the attention contractions, so one scale multiply per
    page row recovers the values (deq = q * s / 127, the same
    convention as quantize_int8/dequantize_int8 above). Returns
    ``(int8 values [..., H, D], float32 scales [..., H])``. Used by the
    paged KV cache (models/gpt.py PagedKVCache int8 mode), where
    halving KV bytes directly halves the dominant decode-step HBM
    category (pre-round decode trace: 5.5 GB/step of KV at b128)."""
    raw = x.value if isinstance(x, Tensor) else jnp.asarray(x)
    s = jnp.maximum(jnp.max(jnp.abs(raw.astype(jnp.float32)), axis=-1),
                    eps)
    q = jnp.clip(jnp.round(raw.astype(jnp.float32) / s[..., None]
                           * KV_QMAX_INT8),
                 -KV_QMAX_INT8, KV_QMAX_INT8).astype(jnp.int8)
    return q, s.astype(jnp.float32)


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Inverse of quantize_kv: deq = q * scale / 127."""
    raw = q.value if isinstance(q, Tensor) else jnp.asarray(q)
    s = scale.value if isinstance(scale, Tensor) else jnp.asarray(scale)
    return (raw.astype(jnp.float32) *
            (s.astype(jnp.float32) / KV_QMAX_INT8)[..., None]
            ).astype(dtype)


# --------------------------------------------------------------------------
# Host-lane KV blob codecs (r23, serving/prefix_cache.py pack_page_blob).
# Pure numpy: these run on the engine's HOST thread against page blocks
# already copied off-device (spill, fetch_pages, drain handoff), so
# they must not touch jax. The math is PINNED to the device-side
# convention above — quantize_kv_np(x) is bit-equal to quantize_kv(x)
# on float32 input (tests/test_kv_substrate.py), and decode is exactly
# deq = q * s / qmax, the same formula the Ragged Paged Attention
# kernel applies in-VMEM. int4 packs two values per byte along
# head_dim (low nibble first, ceil(D/2) bytes per row).
# --------------------------------------------------------------------------

def quantize_kv_np(x: np.ndarray, eps: float = 1e-8):
    """Numpy twin of :func:`quantize_kv`: per-(token, head) abs-max
    over the last axis, ``q = clip(round(x / s * 127))`` int8, scales
    float32. Returns ``(q [..., H, D], s [..., H])``."""
    raw = np.asarray(x, np.float32)
    s = np.maximum(np.max(np.abs(raw), axis=-1), eps).astype(np.float32)
    q = np.clip(np.round(raw / s[..., None] * KV_QMAX_INT8),
                -KV_QMAX_INT8, KV_QMAX_INT8).astype(np.int8)
    return q, s


def dequantize_kv_np(q: np.ndarray, scale: np.ndarray,
                     dtype=np.float32) -> np.ndarray:
    """Numpy twin of :func:`dequantize_kv`: deq = q * s / 127."""
    return (np.asarray(q, np.float32) *
            (np.asarray(scale, np.float32) / KV_QMAX_INT8)[..., None]
            ).astype(dtype)


def quantize_kv_int4_np(x: np.ndarray, eps: float = 1e-8):
    """Symmetric int4 KV quantization (host lane): per-(token, head)
    abs-max scales like int8, ``q = clip(round(x / s * 7), -7, 7)``,
    two nibbles packed per byte along head_dim (low nibble = even
    index; odd head_dim zero-pads the final high nibble). Returns
    ``(packed uint8 [..., H, ceil(D/2)], s float32 [..., H])``."""
    raw = np.asarray(x, np.float32)
    s = np.maximum(np.max(np.abs(raw), axis=-1), eps).astype(np.float32)
    q = np.clip(np.round(raw / s[..., None] * KV_QMAX_INT4),
                -KV_QMAX_INT4, KV_QMAX_INT4).astype(np.int8)
    d = q.shape[-1]
    if d % 2:
        q = np.concatenate(
            [q, np.zeros(q.shape[:-1] + (1,), np.int8)], axis=-1)
    # two's-complement nibbles: q & 0xF maps [-7, 7] into [0, 15]
    lo = (q[..., 0::2].astype(np.uint8)) & 0x0F
    hi = (q[..., 1::2].astype(np.uint8)) & 0x0F
    return (lo | (hi << 4)).astype(np.uint8), s


def dequantize_kv_int4_np(packed: np.ndarray, scale: np.ndarray,
                          head_dim: int, dtype=np.float32) -> np.ndarray:
    """Inverse of :func:`quantize_kv_int4_np`: unpack nibbles
    (sign-extended), deq = q * s / 7, truncated back to ``head_dim``."""
    p = np.asarray(packed, np.uint8)
    lo = (p & 0x0F).astype(np.int8)
    hi = ((p >> 4) & 0x0F).astype(np.int8)
    # sign-extend 4-bit two's complement
    lo = np.where(lo > 7, lo - 16, lo)
    hi = np.where(hi > 7, hi - 16, hi)
    q = np.empty(p.shape[:-1] + (p.shape[-1] * 2,), np.int8)
    q[..., 0::2] = lo
    q[..., 1::2] = hi
    q = q[..., :head_dim]
    return (q.astype(np.float32) *
            (np.asarray(scale, np.float32) / KV_QMAX_INT4)[..., None]
            ).astype(dtype)


class WeightOnlyInt8Linear(Layer):
    """Weight-ONLY int8 linear for decode/serving, where weight
    STREAMING is the bottleneck (pre-round decode roofline: at small
    per-step batch the matmuls are bandwidth-bound on the weights, so
    halving weight bytes approaches 2x tokens/s; activations carry
    negligible traffic and stay bf16/f32 — the reference analog is
    TensorRT's weight-only int8 engines, trt_int8_calibrator.cc
    capability). No calibration needed: only weights quantize
    (per-out-channel abs-max), the dot runs in the activation dtype and
    the per-column scale applies to the OUTPUT (x @ deq(W) ==
    (x @ W_q) * s — one [*, out] multiply XLA fuses into the dot
    epilogue, keeping the int8->bf16 convert inside the dot's operand
    read instead of materializing a dequantized copy)."""

    def __init__(self, inner):
        super().__init__()
        q, s = quant_dequant(inner.weight, 8, axis=1)
        self.register_buffer("weight_int8", Tensor(jnp.asarray(q)))
        self.register_buffer("weight_scale",
                             Tensor(jnp.asarray(s, dtype=jnp.float32)))
        self.bias = getattr(inner, "bias", None)
        self.in_features = inner.weight.shape[0]
        self.out_features = inner.weight.shape[1]

    def forward(self, x):
        def kernel(xv, wq, ws, *maybe_bias):
            qmax = 127.0
            acc = jax.lax.dot_general(
                xv, wq.astype(xv.dtype),
                (((xv.ndim - 1,), (0,)), ((), ())))
            out = acc * (ws.astype(xv.dtype) / qmax)
            if maybe_bias:
                out = out + maybe_bias[0].astype(out.dtype)
            return out

        args = [x, self.weight_int8, self.weight_scale]
        if self.bias is not None:
            args.append(self.bias)
        return dispatch.call_fn(kernel, "weight_only_int8_linear", False,
                                tuple(args), {})


def convert_to_weight_only_int8(model: Layer, extra_types=()) -> int:
    """Swap every [in, out]-weighted linear-like layer for a
    WeightOnlyInt8Linear IN PLACE; returns the number converted. By
    default covers nn.Linear plus the tensor-parallel linears (their
    single-chip forward is the same x @ W (+ b)); embeddings and norms
    stay float. For decode this halves the streamed weight bytes —
    the dominant cost per generated token.

    Tensor-parallel layers keep their sharding: the original weight
    pspec is propagated onto the int8 buffer (quantization is
    per-out-channel, so the layout is unchanged) and the per-column
    scale gets the weight's axis-1 spec. Under mp_degree > 1 a warning
    is still emitted — the converted layer no longer applies the
    original layer's activation constraints (gather_output /
    input_is_parallel plumbing), so verify the partitioner's choices."""
    import warnings

    from jax.sharding import PartitionSpec as P

    from ..distributed.mp_layers import (ColumnParallelLinear,
                                         RowParallelLinear)
    from ..distributed.topology import get_hybrid_communicate_group
    types = (Linear, ColumnParallelLinear, RowParallelLinear,
             *extra_types)
    hcg = get_hybrid_communicate_group()
    mp_degree = hcg.get_model_parallel_world_size() if hcg else 1
    count = 0

    def convert(layer: Layer) -> None:
        nonlocal count
        for name, sub in list(layer._sub_layers.items()):
            if type(sub) in types:
                pspec = getattr(sub.weight, "pspec", None)
                if mp_degree > 1 and pspec is not None:
                    warnings.warn(
                        f"convert_to_weight_only_int8: converting "
                        f"{type(sub).__name__} {name!r} under "
                        f"mp_degree={mp_degree}; the weight pspec "
                        f"{pspec} is propagated to the int8 buffer but "
                        "the original layer's activation constraints "
                        "are dropped — check the resulting sharding",
                        UserWarning, stacklevel=3)
                new = WeightOnlyInt8Linear(sub)
                if pspec is not None:
                    # quantized per-out-channel: same [in, out] layout,
                    # so the weight spec carries over; the [out] scale
                    # follows the weight's out axis
                    new.weight_int8.pspec = pspec
                    new.weight_int8.is_distributed = True
                    out_axis = pspec[1] if len(pspec) > 1 else None
                    new.weight_scale.pspec = P(out_axis)
                    new.weight_scale.is_distributed = True
                layer._sub_layers[name] = new
                count += 1
            else:
                convert(sub)

    convert(model)
    return count


def convert_to_int8(model: Layer, ptq: "PTQ") -> Layer:
    """Swap calibrated Linear/Conv2D layers for int8-executing versions
    (reference: quantization_pass.py program rewrite). The model must
    have been run through ptq.calibrate() first."""
    from ..core.enforce import InvalidArgumentError

    def convert(layer: Layer, prefix: str = "") -> None:
        for name, sub in list(layer._sub_layers.items()):
            full = f"{prefix}.{name}" if prefix else name
            act = ptq.act_ranges.get(full)
            if type(sub) is Linear or type(sub) is Conv2D:
                if act is None:
                    raise InvalidArgumentError(
                        f"no calibration range for layer {full!r}; run "
                        "PTQ.calibrate() over representative data first")
                if type(sub) is Conv2D:
                    if sub._data_format not in ("NCHW", None):
                        raise InvalidArgumentError(
                            f"int8 conversion of layer {full!r}: only "
                            "NCHW Conv2D is supported (got "
                            f"{sub._data_format!r})")
                    layer._sub_layers[name] = Int8Conv2D(sub, act)
                else:
                    layer._sub_layers[name] = Int8Linear(sub, act)
            else:
                convert(sub, full)

    convert(model)
    return model
