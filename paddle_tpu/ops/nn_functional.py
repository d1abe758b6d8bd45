"""Neural-net functional ops (pure functional, jax-native).

Reference parity: python/paddle/nn/functional/ (activation.py, common.py,
conv.py, norm.py, pooling.py, loss.py, input.py) backed by the operator
kernels under paddle/fluid/operators/. Convs/matmuls route to
lax.conv_general_dilated / jnp.matmul so XLA tiles them onto the MXU;
data layout follows the reference's NCHW default with a data_format arg.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.rng import next_key

# --------------------------------------------------------------------------
# activations (reference: python/paddle/nn/functional/activation.py)
# --------------------------------------------------------------------------

def relu(x):
    return jax.nn.relu(x)


def relu6(x):
    return jnp.clip(x, 0.0, 6.0)


def leaky_relu(x, negative_slope=0.01):
    return jax.nn.leaky_relu(x, negative_slope)


def prelu(x, weight):
    weight = jnp.asarray(weight)
    if weight.size > 1:  # per-channel on axis 1 (NCHW convention)
        shape = [1] * x.ndim
        shape[1] = weight.size
        weight = weight.reshape(shape)
    return jnp.where(x >= 0, x, weight * x)


def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=True, key=None):
    if training:
        k = key if key is not None else next_key()
        slope = jax.random.uniform(k, x.shape, dtype=x.dtype,
                                   minval=lower, maxval=upper)
    else:
        slope = (lower + upper) / 2.0
    return jnp.where(x >= 0, x, slope * x)


def elu(x, alpha=1.0):
    return jax.nn.elu(x, alpha)


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772):
    return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))


def celu(x, alpha=1.0):
    return jax.nn.celu(x, alpha)


def gelu(x, approximate=False):
    return jax.nn.gelu(x, approximate=approximate)


def silu(x):
    return jax.nn.silu(x)


swish = silu


def mish(x):
    return jax.nn.mish(x)


def sigmoid(x):
    return jax.nn.sigmoid(x)


def log_sigmoid(x):
    return jax.nn.log_sigmoid(x)


def hardsigmoid(x, slope=1.0 / 6.0, offset=0.5):
    return jnp.clip(slope * x + offset, 0.0, 1.0)


def hardswish(x):
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


def hardtanh(x, min=-1.0, max=1.0):  # noqa: A002
    return jnp.clip(x, min, max)


def hardshrink(x, threshold=0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


def softshrink(x, threshold=0.5):
    return jnp.where(x > threshold, x - threshold,
                     jnp.where(x < -threshold, x + threshold, 0.0))


def tanhshrink(x):
    return x - jnp.tanh(x)


def softplus(x, beta=1.0, threshold=20.0):
    return jnp.where(beta * x > threshold, x,
                     jnp.log1p(jnp.exp(beta * x)) / beta)


def softsign(x):
    return jax.nn.soft_sign(x)


def tanh(x):
    return jnp.tanh(x)


def softmax(x, axis=-1, dtype=None):
    if dtype is not None:
        x = x.astype(dtype)
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis=-1, dtype=None):
    if dtype is not None:
        x = x.astype(dtype)
    return jax.nn.log_softmax(x, axis=axis)


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, key=None):
    k = key if key is not None else next_key()
    g = jax.random.gumbel(k, x.shape, dtype=x.dtype)
    y = jax.nn.softmax((x + g) / temperature, axis=axis)
    if hard:
        idx = jnp.argmax(y, axis=axis, keepdims=True)
        y_hard = jnp.zeros_like(y)
        y_hard = jnp.put_along_axis(y_hard, idx, 1.0, axis=axis,
                                    inplace=False)
        y = jax.lax.stop_gradient(y_hard - y) + y  # straight-through
    return y


def maxout(x, groups, axis=1):
    c = x.shape[axis]
    new_shape = list(x.shape)
    new_shape[axis] = c // groups
    new_shape.insert(axis + 1, groups)
    return jnp.max(x.reshape(new_shape), axis=axis + 1)


def glu(x, axis=-1):
    a, b = jnp.split(x, 2, axis=axis)
    return a * jax.nn.sigmoid(b)


# --------------------------------------------------------------------------
# linear / embedding (reference: nn/functional/common.py, input.py)
# --------------------------------------------------------------------------

def linear(x, weight, bias=None):
    """x @ weight + bias; weight is [in, out] (reference convention)."""
    out = jnp.matmul(x, weight)
    if bias is not None:
        out = out + bias
    return out


def embedding(x, weight, padding_idx=None, sparse=False):
    out = jnp.take(weight, x, axis=0)
    if padding_idx is not None:
        mask = (x != padding_idx)[..., None].astype(out.dtype)
        out = out * mask
    return out


def one_hot(x, num_classes):
    return jax.nn.one_hot(x, num_classes)


def bilinear(x1, x2, weight, bias=None):
    # weight: [out, in1, in2]
    out = jnp.einsum("bi,oij,bj->bo", x1, weight, x2)
    if bias is not None:
        out = out + bias
    return out


def dropout(x, p=0.5, training=True, mode="upscale_in_train", axis=None,
            key=None):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return x * (1.0 - p)
        return x
    k = key if key is not None else next_key()
    if axis is not None:
        axes = (axis,) if isinstance(axis, int) else tuple(axis)
        mask_shape = tuple(s if i in axes else 1 for i, s in
                           enumerate(x.shape))
    else:
        mask_shape = x.shape
    keep = jax.random.bernoulli(k, 1.0 - p, mask_shape)
    if mode == "upscale_in_train":
        return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    return jnp.where(keep, x, 0.0).astype(x.dtype)


def dropout2d(x, p=0.5, training=True, data_format="NCHW", key=None):
    axis = (0, 1) if data_format == "NCHW" else (0, 3)
    return dropout(x, p, training, axis=axis, key=key)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", key=None):
    axis = (0, 1) if data_format == "NCDHW" else (0, 4)
    return dropout(x, p, training, axis=axis, key=key)


def alpha_dropout(x, p=0.5, training=True, key=None):
    if not training or p == 0.0:
        return x
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    k = key if key is not None else next_key()
    keep = jax.random.bernoulli(k, 1.0 - p, x.shape)
    a = (1.0 / np.sqrt((1.0 - p) * (1.0 + p * alpha_p ** 2))) if p < 1 else 0.0
    b = -a * alpha_p * p
    return (a * jnp.where(keep, x, alpha_p) + b).astype(x.dtype)


def label_smooth(label, prior_dist=None, epsilon=0.1):
    n = label.shape[-1]
    if prior_dist is not None:
        return (1.0 - epsilon) * label + epsilon * prior_dist
    return (1.0 - epsilon) * label + epsilon / n


# --------------------------------------------------------------------------
# convolution (reference: nn/functional/conv.py, operators/conv_op.cc)
# --------------------------------------------------------------------------

def _conv_dimension_numbers(ndim, channel_last):
    # data_format only changes the input/output layout; the weight stays
    # [out_c, in_c, *k] in the reference (conv_op.cc filter layout), so
    # the rhs spec is OI* either way.
    if ndim == 3:
        return ("NWC", "OIW", "NWC") if channel_last else \
            ("NCW", "OIW", "NCW")
    if ndim == 4:
        return ("NHWC", "OIHW", "NHWC") if channel_last else \
            ("NCHW", "OIHW", "NCHW")
    return ("NDHWC", "OIDHW", "NDHWC") if channel_last else \
        ("NCDHW", "OIDHW", "NCDHW")


def _norm_tuple(v, n):
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


def _conv_padding(padding, nsp, stride, dilation, ksize):
    """Translate reference padding spec (int, list, 'SAME', 'VALID')."""
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, int):
        return [(padding, padding)] * nsp
    padding = list(padding)
    if len(padding) == nsp and all(isinstance(p, int) for p in padding):
        return [(p, p) for p in padding]
    if len(padding) == 2 * nsp:
        return [(padding[2 * i], padding[2 * i + 1]) for i in range(nsp)]
    return [tuple(p) for p in padding]


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW"):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    nsp = 2
    dn = jax.lax.conv_dimension_numbers(
        x.shape, weight.shape, _conv_dimension_numbers(4, channel_last))
    out = jax.lax.conv_general_dilated(
        x, weight, window_strides=_norm_tuple(stride, nsp),
        padding=_conv_padding(padding, nsp, stride, dilation,
                              weight.shape[2:]),
        rhs_dilation=_norm_tuple(dilation, nsp),
        dimension_numbers=dn, feature_group_count=groups)
    if bias is not None:
        shape = [1, -1, 1, 1] if not channel_last else [1, 1, 1, -1]
        out = out + bias.reshape(shape)
    return out


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL"):
    channel_last = data_format == "NLC"
    dn = jax.lax.conv_dimension_numbers(
        x.shape, weight.shape, _conv_dimension_numbers(3, channel_last))
    out = jax.lax.conv_general_dilated(
        x, weight, window_strides=_norm_tuple(stride, 1),
        padding=_conv_padding(padding, 1, stride, dilation, weight.shape[2:]),
        rhs_dilation=_norm_tuple(dilation, 1),
        dimension_numbers=dn, feature_group_count=groups)
    if bias is not None:
        shape = [1, -1, 1] if not channel_last else [1, 1, -1]
        out = out + bias.reshape(shape)
    return out


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW"):
    channel_last = data_format == "NDHWC"
    dn = jax.lax.conv_dimension_numbers(
        x.shape, weight.shape, _conv_dimension_numbers(5, channel_last))
    out = jax.lax.conv_general_dilated(
        x, weight, window_strides=_norm_tuple(stride, 3),
        padding=_conv_padding(padding, 3, stride, dilation, weight.shape[2:]),
        rhs_dilation=_norm_tuple(dilation, 3),
        dimension_numbers=dn, feature_group_count=groups)
    if bias is not None:
        shape = [1, -1, 1, 1, 1] if not channel_last else [1, 1, 1, 1, -1]
        out = out + bias.reshape(shape)
    return out


def _out_padding_from_size(in_sp, output_size, stride, padding,
                           dilation, ksp, nsp):
    """Derive output_padding from a requested output_size (reference
    conv_transpose output_size arg). Valid range per dim: [0, stride)."""
    st = _norm_tuple(stride, nsp)
    dl = _norm_tuple(dilation, nsp)
    osz = _norm_tuple(output_size, nsp)
    op = []
    for i in range(nsp):
        if isinstance(padding, str):
            # SAME: base out = in*stride; VALID: zero padding
            if padding.upper() == "SAME":
                base = in_sp[i] * st[i]
            else:
                base = (in_sp[i] - 1) * st[i] + dl[i] * (ksp[i] - 1) + 1
        else:
            pd = _norm_tuple(padding, nsp)
            base = (in_sp[i] - 1) * st[i] - 2 * pd[i] + \
                dl[i] * (ksp[i] - 1) + 1
        op.append(int(osz[i]) - base)
    if any(o < 0 or o >= st[i] for i, o in enumerate(op)):
        raise ValueError(
            f"output_size {tuple(int(o) for o in osz)} unreachable from "
            f"input {tuple(in_sp)}: derived output_padding {op} must be "
            f"in [0, stride) per dim (stride {st})")
    return tuple(op)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     output_size=None, data_format="NCHW"):
    """Transposed conv via gradient-of-conv (reference conv2d_transpose_op).
    weight layout matches the reference: [in, out//groups, kh, kw]."""
    if output_size is not None:
        sp = x.shape[1:3] if data_format == "NHWC" else x.shape[2:4]
        output_padding = _out_padding_from_size(
            sp, output_size, stride, padding, dilation, weight.shape[2:4],
            2)
    channel_last = data_format == "NHWC"
    nsp = 2
    strides = _norm_tuple(stride, nsp)
    dilations = _norm_tuple(dilation, nsp)
    pads = _conv_padding(padding, nsp, stride, dilation, weight.shape[2:])
    if isinstance(pads, str):
        pads = [(0, 0)] * nsp if pads == "VALID" else None
    out_pad = _norm_tuple(output_padding, nsp)
    kh = [(weight.shape[2 + i] - 1) * dilations[i] + 1 for i in range(nsp)]
    trans_pads = [(kh[i] - 1 - pads[i][0],
                   kh[i] - 1 - pads[i][1] + out_pad[i]) for i in range(nsp)]
    # flip spatial dims & swap io: [in, out//g, kh, kw] -> [out//g? ...]
    w = jnp.flip(weight, axis=tuple(range(2, weight.ndim)))
    if groups > 1:
        ci, co_g = weight.shape[0], weight.shape[1]
        w = w.reshape(groups, ci // groups, co_g, *weight.shape[2:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape(groups * co_g, ci // groups, *weight.shape[2:])
    else:
        w = jnp.swapaxes(w, 0, 1)
    dn = jax.lax.conv_dimension_numbers(
        x.shape, w.shape, _conv_dimension_numbers(4, channel_last))
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=trans_pads,
        lhs_dilation=strides, rhs_dilation=dilations,
        dimension_numbers=dn, feature_group_count=groups)
    if bias is not None:
        shape = [1, -1, 1, 1] if not channel_last else [1, 1, 1, -1]
        out = out + bias.reshape(shape)
    return out


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     output_size=None, data_format="NCL"):
    if output_size is not None:
        sp = (x.shape[1],) if data_format == "NLC" else (x.shape[2],)
        output_padding = _out_padding_from_size(
            sp, output_size, stride, padding, dilation,
            (weight.shape[2],), 1)[0]
    x4 = jnp.expand_dims(x, -1 if data_format == "NCL" else 2)
    w4 = jnp.expand_dims(weight, -1)
    out = conv2d_transpose(
        x4, w4, bias, stride=(_norm_tuple(stride, 1)[0], 1),
        padding=(_norm_tuple(padding, 1)[0], 0) if isinstance(
            padding, (int, list, tuple)) else padding,
        output_padding=(_norm_tuple(output_padding, 1)[0], 0),
        dilation=(_norm_tuple(dilation, 1)[0], 1), groups=groups,
        data_format="NCHW" if data_format == "NCL" else "NHWC")
    return jnp.squeeze(out, -1 if data_format == "NCL" else 2)


# --------------------------------------------------------------------------
# pooling (reference: nn/functional/pooling.py, operators/pool_op.cc)
# --------------------------------------------------------------------------

def _pool(x, init, reduce_fn, ksize, stride, padding, nsp, channel_last,
          ceil_mode=False):
    ksize = _norm_tuple(ksize, nsp)
    stride = _norm_tuple(stride if stride is not None else ksize, nsp)
    if isinstance(padding, str):
        pads = padding.upper()
    else:
        p = _conv_padding(padding, nsp, stride, 1, ksize)
        pads = p
    if channel_last:
        window = (1,) + ksize + (1,)
        strides = (1,) + stride + (1,)
        if not isinstance(pads, str):
            pads = [(0, 0)] + pads + [(0, 0)]
    else:
        window = (1, 1) + ksize
        strides = (1, 1) + stride
        if not isinstance(pads, str):
            pads = [(0, 0), (0, 0)] + pads
    return jax.lax.reduce_window(x, init, reduce_fn, window, strides, pads), \
        (window, strides, pads)


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCHW"):
    channel_last = data_format == "NHWC"
    out, _ = _pool(x, -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
                   else jnp.iinfo(x.dtype).min, jax.lax.max, kernel_size,
                   stride, padding, 2, channel_last, ceil_mode)
    out = out.astype(x.dtype)
    if return_mask:
        mask = _max_pool_indices(x, kernel_size, stride, padding,
                                 channel_last)
        return out, mask
    return out


def _max_pool_indices(x, kernel_size, stride, padding, channel_last):
    nsp = x.ndim - 2
    ksize = _norm_tuple(kernel_size, nsp)
    stride_t = _norm_tuple(stride if stride is not None else kernel_size, nsp)
    # Build linear spatial indices then reduce-window an argmax via a packed
    # (value, index) trick: encode index in low bits impossible generically —
    # use patch extraction instead (fine for the index path, which is rare).
    if channel_last:
        x_ncs = jnp.moveaxis(x, -1, 1)
    else:
        x_ncs = x
    n, c = x_ncs.shape[:2]
    spatial = x_ncs.shape[2:]
    lin = jnp.arange(int(np.prod(spatial))).reshape(spatial)
    pads = _conv_padding(padding, nsp, stride_t, 1, ksize)
    if isinstance(pads, str):
        pads = [(0, 0)] * nsp
    xp = jnp.pad(x_ncs, [(0, 0), (0, 0)] + list(pads),
                 constant_values=-jnp.inf)
    lp = jnp.pad(lin, list(pads), constant_values=-1)
    out_sp = tuple((xp.shape[2 + i] - ksize[i]) // stride_t[i] + 1
                   for i in range(nsp))
    patches = []
    lins = []
    for offs in np.ndindex(*ksize):
        sl = tuple(_np_slice(offs[i], out_sp[i], stride_t[i])
                   for i in range(nsp))
        patches.append(xp[(slice(None), slice(None)) + sl])
        lins.append(lp[sl])
    stacked = jnp.stack(patches, axis=-1)
    lin_stacked = jnp.stack(lins, axis=-1)
    arg = jnp.argmax(stacked, axis=-1)
    idx = jnp.take_along_axis(
        jnp.broadcast_to(lin_stacked, stacked.shape), arg[..., None],
        axis=-1)[..., 0]
    if channel_last:
        idx = jnp.moveaxis(idx, 1, -1)
    return idx.astype(jnp.int32)


def _np_slice(start, num, step):
    return slice(start, start + num * step, step)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW"):
    channel_last = data_format == "NHWC"
    summed, (window, strides, pads) = _pool(
        x, 0.0, jax.lax.add, kernel_size, stride, padding, 2, channel_last,
        ceil_mode)
    if divisor_override:
        return (summed / divisor_override).astype(x.dtype)
    if exclusive and not isinstance(pads, str):
        ones = jnp.ones_like(x)
        counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                       strides, pads)
        return (summed / counts).astype(x.dtype)
    denom = np.prod(_norm_tuple(kernel_size, 2))
    return (summed / denom).astype(x.dtype)


def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False):
    x4 = jnp.expand_dims(x, -1)
    out = max_pool2d(x4, (_norm_tuple(kernel_size, 1)[0], 1),
                     (_norm_tuple(stride, 1)[0], 1) if stride else None,
                     (_norm_tuple(padding, 1)[0], 0) if isinstance(
                         padding, int) else padding,
                     ceil_mode, return_mask)
    if return_mask:
        return jnp.squeeze(out[0], -1), jnp.squeeze(out[1], -1)
    return jnp.squeeze(out, -1)


def avg_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True):
    x4 = jnp.expand_dims(x, -1)
    out = avg_pool2d(x4, (_norm_tuple(kernel_size, 1)[0], 1),
                     (_norm_tuple(stride, 1)[0], 1) if stride else None,
                     (_norm_tuple(padding, 1)[0], 0) if isinstance(
                         padding, int) else padding,
                     ceil_mode, exclusive)
    return jnp.squeeze(out, -1)


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCDHW"):
    channel_last = data_format == "NDHWC"
    out, _ = _pool(x, -jnp.inf, jax.lax.max, kernel_size, stride, padding, 3,
                   channel_last, ceil_mode)
    return out.astype(x.dtype)


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCDHW"):
    channel_last = data_format == "NDHWC"
    summed, (window, strides, pads) = _pool(
        x, 0.0, jax.lax.add, kernel_size, stride, padding, 3, channel_last,
        ceil_mode)
    if divisor_override:
        return (summed / divisor_override).astype(x.dtype)
    if exclusive and not isinstance(pads, str):
        counts = jax.lax.reduce_window(jnp.ones_like(x), 0.0, jax.lax.add,
                                       window, strides, pads)
        return (summed / counts).astype(x.dtype)
    return (summed / np.prod(_norm_tuple(kernel_size, 3))).astype(x.dtype)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW"):
    channel_last = data_format == "NHWC"
    out_size = _norm_tuple(output_size, 2)
    sp_axes = (1, 2) if channel_last else (2, 3)
    in_size = tuple(x.shape[a] for a in sp_axes)
    if all(i % o == 0 for i, o in zip(in_size, out_size)):
        k = tuple(i // o for i, o in zip(in_size, out_size))
        return avg_pool2d(x, k, k, 0, data_format=data_format)
    # General case: mean over variable windows via cumulative sums.
    return _adaptive_pool_general(x, out_size, sp_axes, "avg")


def adaptive_max_pool2d(x, output_size, return_mask=False,
                        data_format="NCHW"):
    channel_last = data_format == "NHWC"
    out_size = _norm_tuple(output_size, 2)
    sp_axes = (1, 2) if channel_last else (2, 3)
    in_size = tuple(x.shape[a] for a in sp_axes)
    if all(i % o == 0 for i, o in zip(in_size, out_size)):
        k = tuple(i // o for i, o in zip(in_size, out_size))
        return max_pool2d(x, k, k, 0, return_mask=return_mask,
                          data_format=data_format)
    return _adaptive_pool_general(x, out_size, sp_axes, "max")


def _adaptive_pool_general(x, out_size, sp_axes, mode):
    out = x
    for ax, osz in zip(sp_axes, out_size):
        isz = out.shape[ax]
        starts = (np.arange(osz) * isz) // osz
        ends = ((np.arange(osz) + 1) * isz + osz - 1) // osz
        slices = []
        for s, e in zip(starts, ends):
            seg = jnp.take(out, jnp.arange(s, e), axis=ax)
            red = jnp.mean(seg, axis=ax, keepdims=True) if mode == "avg" \
                else jnp.max(seg, axis=ax, keepdims=True)
            slices.append(red)
        out = jnp.concatenate(slices, axis=ax)
    return out


def adaptive_avg_pool1d(x, output_size):
    x4 = jnp.expand_dims(x, -1)
    return jnp.squeeze(adaptive_avg_pool2d(x4, (output_size, 1)), -1)


def adaptive_max_pool1d(x, output_size, return_mask=False):
    x4 = jnp.expand_dims(x, -1)
    out = jnp.squeeze(adaptive_max_pool2d(x4, (output_size, 1)), -1)
    if return_mask:
        # divisible case: argmax within each window, offset to input index
        n, c, l = x.shape
        o = int(output_size)
        if l % o == 0:
            k = l // o
            win = x.reshape(n, c, o, k)
            idx = jnp.argmax(win, axis=-1) + jnp.arange(o)[None, None] * k
            return out, idx.astype(jnp.int64)
        raise NotImplementedError(
            "return_mask needs input length divisible by output_size")
    return out


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW"):
    out_size = _norm_tuple(output_size, 3)
    sp_axes = (1, 2, 3) if data_format == "NDHWC" else (2, 3, 4)
    return _adaptive_pool_general(x, out_size, sp_axes, "avg")


def adaptive_max_pool3d(x, output_size, return_mask=False,
                        data_format="NCDHW"):
    """Adaptive 3-D max pool (reference: nn/functional/pooling.py
    adaptive_max_pool3d, operators/pool_op.cc adaptive path)."""
    out_size = _norm_tuple(output_size, 3)
    sp_axes = (1, 2, 3) if data_format == "NDHWC" else (2, 3, 4)
    in_size = tuple(x.shape[a] for a in sp_axes)
    if all(i % o == 0 for i, o in zip(in_size, out_size)):
        k = tuple(i // o for i, o in zip(in_size, out_size))
        return max_pool3d(x, k, k, 0, data_format=data_format)
    return _adaptive_pool_general(x, out_size, sp_axes, "max")


# --------------------------------------------------------------------------
# normalization (reference: nn/functional/norm.py, operators/*norm_op.cc)
# --------------------------------------------------------------------------

def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    axes = tuple(range(x.ndim - len(normalized_shape), x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out.astype(x.dtype)


def rms_norm(x, weight=None, epsilon=1e-6, axis=-1):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axis,
                   keepdims=True)
    out = x * jax.lax.rsqrt(var + epsilon).astype(x.dtype)
    if weight is not None:
        out = out * weight
    return out


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW"):
    """Returns (out, new_mean, new_var). The stateful Layer handles updating
    running stats; reference semantics: momentum*old + (1-momentum)*new
    (operators/batch_norm_op.cc)."""
    ch_axis = 1 if data_format.startswith("NC") and x.ndim > 1 else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    if training:
        # E[x^2]-E[x]^2 in ONE traversal: jnp.var re-reads x after the
        # mean pass, and on bf16 ResNet-scale activations the extra
        # HBM passes dominated the train-mode forward (measured 6.2 ms
        # of a 14.7 ms ResNet-50 fwd step before this fusion — XLA
        # fuses these two sibling reductions over xf into one pass).
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=axes)
        var = jnp.maximum(jnp.mean(xf * xf, axis=axes) - mean * mean, 0.0)
        new_rm = momentum * running_mean + (1.0 - momentum) * mean
        new_rv = momentum * running_var + (1.0 - momentum) * var
    else:
        mean, var = running_mean, running_var
        new_rm, new_rv = running_mean, running_var
    shape = [1] * x.ndim
    shape[ch_axis] = -1
    out = (x - mean.reshape(shape)) * jax.lax.rsqrt(
        var.reshape(shape) + epsilon)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out.astype(x.dtype), new_rm, new_rv


def instance_norm(x, weight=None, bias=None, epsilon=1e-5):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        shape = [1, -1] + [1] * (x.ndim - 2)
        out = out * weight.reshape(shape)
    if bias is not None:
        shape = [1, -1] + [1] * (x.ndim - 2)
        out = out + bias.reshape(shape)
    return out.astype(x.dtype)


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW"):
    if data_format == "NHWC":
        x = jnp.moveaxis(x, -1, 1)
    n, c = x.shape[:2]
    spatial = x.shape[2:]
    g = x.reshape(n, num_groups, c // num_groups, *spatial)
    axes = tuple(range(2, g.ndim))
    mean = jnp.mean(g, axis=axes, keepdims=True)
    var = jnp.var(g, axis=axes, keepdims=True)
    out = ((g - mean) * jax.lax.rsqrt(var + epsilon)).reshape(x.shape)
    shape = [1, -1] + [1] * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    if data_format == "NHWC":
        out = jnp.moveaxis(out, 1, -1)
    return out.astype(x.dtype)


def normalize(x, p=2, axis=1, epsilon=1e-12):
    norm = jnp.linalg.norm(x, ord=p, axis=axis, keepdims=True)
    return x / jnp.maximum(norm, epsilon)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW"):
    if data_format == "NHWC":
        x = jnp.moveaxis(x, -1, 1)
    sq = jnp.square(x)
    half = size // 2
    pads = [(0, 0), (half, size - 1 - half)] + [(0, 0)] * (x.ndim - 2)
    sq_p = jnp.pad(sq, pads)
    window = jnp.stack([sq_p[:, i:i + x.shape[1]] for i in range(size)],
                       axis=0).sum(0)
    out = x / jnp.power(k + alpha * window, beta)
    if data_format == "NHWC":
        out = jnp.moveaxis(out, 1, -1)
    return out


# --------------------------------------------------------------------------
# attention — jnp reference impl; the Pallas flash kernel lives in
# ops/pallas/flash_attention.py and is picked by scaled_dot_product_attention
# when shapes/backend allow.
# --------------------------------------------------------------------------

# Flash-vs-XLA crossover, measured on v5e (r4): XLA's fused attention
# wins at S<=256, flash wins from S=512 up — confirmed across d=64 and
# d=128, causal and not, by a scanned fwd+bwd sweep and by the
# full-model step (BERT-base body: 243 -> 216.6 ms/step on flash;
# pre-round records). At S>=2048 the XLA path can stop compiling
# outright — the S^2 scores no longer fit.
_FLASH_MIN_SEQ = int(__import__("os").environ.get("PT_FLASH_MIN_SEQ",
                                                  "512"))
# The FOLDED kernel has no transposes, so its crossover sits lower
# than the streaming kernel's: measured v5e b64 h12 d64 fwd+bwd
# scanned — S=256 folded 4.55 vs XLA 5.33 ms/iter (folded wins),
# S=128 folded 3.68 vs XLA 2.95 (XLA wins; grid overhead dominates a
# [128,128] score block)
_FOLDED_MIN_SEQ = int(__import__("os").environ.get(
    "PT_FOLDED_MIN_SEQ", "256"))


def _attention_kernel_plan(q_shape, k_shape):
    """How a Pallas attention kernel may run on [B, S, H, D] operands in
    the current trace: ``(mesh, spec, local_q_shape, local_k_shape)``.

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), so inside a partitioned program the kernel runs per
    shard — attention is local in batch and heads, no collective is
    needed: inside a fleet step, batch over the data axes and heads
    over mp; inside a mesh serving engine's trace, heads over its model
    axis. ``mesh`` is None in an unpartitioned trace (the kernel sees
    the global shapes). Returns None where the mesh does not divide the
    operands or shards the sequence: XLA's attention then runs, as it
    always has under GSPMD."""
    from ..distributed.mp_layers import active_hybrid_mesh
    from .pallas.paged_attention import get_head_sharding
    serving = get_head_sharding()
    if serving is not None:
        mesh, head_axis = serving
        data = ()
    else:
        mesh, head_axis = active_hybrid_mesh(), "mp"
        if mesh is None:
            return None, None, q_shape, k_shape
        if mesh.shape["sep"] > 1:
            return None
        data = tuple(a for a in ("dp", "sharding") if mesh.shape[a] > 1)
    nd = int(np.prod([mesh.shape[a] for a in data])) if data else 1
    nh = mesh.shape[head_axis]
    if q_shape[0] % nd or q_shape[2] % nh or k_shape[2] % nh:
        return None
    spec = jax.sharding.PartitionSpec(
        data or None, None, head_axis if nh > 1 else None, None)

    def local(shape):
        return (shape[0] // nd, shape[1], shape[2] // nh, shape[3])
    return mesh, spec, local(q_shape), local(k_shape)


def _run_attention_kernel(kernel, mesh, spec, q, k, v, causal, scale):
    def run(qq, kk, vv):
        return kernel(qq, kk, vv, causal=causal, scale=scale)
    if mesh is None:
        return run(q, k, v)
    return jax.shard_map(run, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)


def scaled_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, scale=None,
                                 key=None, use_flash=None):
    """q,k,v: [batch, seq, heads, head_dim] (reference layout). Computes in
    fp32 accumulation, returns q.dtype.

    ``use_flash``: None (default) = auto — the FOLDED layout-native
    Pallas kernel from key length >= PT_FOLDED_MIN_SEQ (256) when its
    shape gate admits, else the streaming flash kernel from
    >= PT_FLASH_MIN_SEQ (512); XLA's fused attention wins below those
    measured crossovers. True = a Pallas kernel whenever supported;
    False = never. Both kernels require no mask and no active
    dropout."""
    allowed = use_flash is True or (use_flash is None and
                                    k.shape[1] >= _FLASH_MIN_SEQ)
    folded_allowed = use_flash is True or (
        use_flash is None and k.shape[1] >= _FOLDED_MIN_SEQ)
    # the flash kernel's causal mask is diagonal-aligned: with sq != sk
    # (a concatenated KV cache) it would mask from position 0 instead of
    # offsetting by the cache length — the XLA path below applies the
    # correct k=sk-sq shift, so causal cross-length stays off flash
    if ((allowed or folded_allowed) and attn_mask is None and
            (not is_causal or q.shape[1] == k.shape[1]) and
            (dropout_p == 0.0 or not training)):
        from .pallas.flash_attention import (flash_attention,
                                             flash_attention_supported)
        from .pallas.folded_attention import (folded_attention,
                                              folded_attention_supported)
        plan = _attention_kernel_plan(q.shape, k.shape)
        if plan is not None:
            mesh, spec, q_local, k_local = plan
            if folded_allowed and folded_attention_supported(
                    q_local, k_local, is_causal):
                # single-K-block shapes (BERT S=512): the layout-native
                # folded kernel reads the projection's [B,S,E] rows via
                # 128-lane column groups — no [B,H,S,D] transpose (r4
                # trace: ~27 ms/step of "data formatting" on the
                # BERT-base body came from those round-trips; an r4
                # attempt at d-wide column blocks failed because Mosaic
                # rejects 64-lane blocks — the fix is 2 heads per
                # 128-lane group, split by in-kernel lane slices)
                return _run_attention_kernel(
                    folded_attention, mesh, spec, q, k, v, is_causal,
                    scale)
            if allowed and flash_attention_supported(q_local, k_local):
                # streaming shapes (GPT S>=2048): the transposing BHSD
                # kernel (its own crossover stays at _FLASH_MIN_SEQ);
                # at d=128 the strided no-transpose block DMA measured
                # as a wash (GPT step 254.0 vs 251.7 ms), so the
                # transposes stay on this path
                return _run_attention_kernel(
                    flash_attention, mesh, spec, q, k, v, is_causal,
                    scale)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qT = jnp.swapaxes(q, 1, 2)  # [b, h, sq, d]
    kT = jnp.swapaxes(k, 1, 2)
    vT = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qT, kT,
                        preferred_element_type=jnp.float32) * scale
    if is_causal:
        causal = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(causal, logits, -jnp.inf)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, -jnp.inf)
        else:
            logits = logits + attn_mask
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and training:
        probs = dropout(probs, dropout_p, training=True, key=key)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vT)
    return jnp.swapaxes(out, 1, 2)


def paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                    k_scale=None, v_scale=None, scale=None,
                    q_offsets=None):
    """Ragged paged attention over a block-paged KV pool (the decode
    analog of scaled_dot_product_attention's kernel selection): the
    Pallas page-walk kernel on TPU when the shape gate admits
    (single-token decode, lane-tiling head groups), the dense-gather
    pure-JAX reference everywhere else — both implement identical
    semantics (ops/pallas/paged_attention.py). q: [B, Sq, H, D];
    pages: [P, page, H, D] float or int8 (+ [P, page, H] scales);
    page_table: [B, max_pages] int32; seq_lens: [B] int32."""
    from .pallas.paged_attention import paged_attention as _impl
    return _impl(q, k_pages, v_pages, page_table, seq_lens,
                 k_scale=k_scale, v_scale=v_scale, scale=scale,
                 q_offsets=q_offsets)


def paged_attention_head_sharded(q, k_pages, v_pages, page_table,
                                 seq_lens, k_scale=None, v_scale=None,
                                 scale=None, q_offsets=None, mesh=None,
                                 axis=None):
    """Tensor-parallel ragged paged attention: q and the KV pools are
    sharded over heads along ``mesh[axis]`` and each device runs the
    standard kernel-selection path on its slice (attention is
    head-local, so there are no collectives and per-head arithmetic is
    bit-identical to the single-device op). ``mesh=None`` builds a
    serving mesh over min(2, device_count) devices — the benchable
    default (tools/op_benchmark.py pending case); the mesh-sharded
    decode engine passes its own."""
    from .pallas.paged_attention import \
        paged_attention_head_sharded as _impl
    if mesh is None:
        import jax as _jax
        mesh = _default_serving_mesh(min(2, _jax.device_count()))
    return _impl(q, k_pages, v_pages, page_table, seq_lens, mesh,
                 axis=axis, k_scale=k_scale, v_scale=v_scale,
                 scale=scale, q_offsets=q_offsets)


def paged_attention_fused(q, k_pages, v_pages, page_table, seq_lens,
                          w, bias=None, k_scale=None, v_scale=None,
                          scale=None, q_offsets=None):
    """Ragged paged attention with the output-projection epilogue
    fused in (r13 decode hot path): the softmax-normalized per-head
    context is head-concatenated and pushed through ``w`` ([H*D,
    E_out], optional ``bias``) inside the SAME kernel/op, returning
    the attention block's output [B, Sq, E_out] — one launch where the
    unfused path runs paged_attention + reshape + linear + bias-add.
    Kernel selection mirrors `paged_attention` (Mosaic fused kernel on
    TPU under the shape/VMEM gate, dense-gather fused reference
    elsewhere, head-sharded under an active serving mesh); both are
    the exact unfused math, so greedy decode stays bit-identical
    (ops/pallas/paged_attention.py)."""
    from .pallas.paged_attention import paged_attention_fused as _impl
    return _impl(q, k_pages, v_pages, page_table, seq_lens, w,
                 bias=bias, k_scale=k_scale, v_scale=v_scale,
                 scale=scale, q_offsets=q_offsets)


def fused_sample(hidden, weight, bias=None, transpose_y=False,
                 top_k=None, tile=2048):
    """Streaming lm_head sampling (r13): tile the logits matmul over
    the vocab dim and keep a running argmax (``top_k=None`` -> greedy
    tokens [B] int32, first-index ties exactly like ``argmax``) or a
    running top-k reservoir (``top_k=k`` -> (values, indices) [B, k]),
    so the [B, vocab] logits tensor is never materialized in HBM.
    ``weight``: [V, D] with ``transpose_y=True`` (tied-embedding
    layout) or [D, V] otherwise (ops/pallas/fused_sample.py — Mosaic
    streaming kernel on TPU, lax.scan reference elsewhere)."""
    from .pallas.fused_sample import fused_sample as _impl
    return _impl(hidden, weight, bias=bias, transpose_y=transpose_y,
                 top_k=top_k, tile=tile)


def paged_page_splice(pool, block, page=0):
    """Prefix-cache restore splice (r15 hierarchical prefix cache):
    write one page's restored content ``block`` ([page, H, D] KV
    block, or [page, H] scale block for int8 pools) into ``pool`` at
    page index ``page`` ([P+1, page, ...]; the same pool layout
    `paged_attention` walks). ``page`` may be a traced scalar, so the
    engine's jitted restore compiles ONCE and splices any page index
    (inference/continuous_batching.py restores evicted spill-tier
    blobs through this — a device_put plus this scatter replaces the
    prefix's whole prefill)."""
    return pool.at[page].set(jnp.asarray(block).astype(pool.dtype))


@functools.lru_cache(maxsize=None)
def _default_serving_mesh(model_parallel: int):
    """Memoized benchable-default mesh for
    :func:`paged_attention_head_sharded` — the op is registered in the
    dispatch registry and callable eagerly in a loop; mesh/device-array
    construction per call would be pure overhead for an identical
    result."""
    from ..distributed.topology import make_serving_mesh
    return make_serving_mesh(model_parallel)


# --------------------------------------------------------------------------
# losses (reference: nn/functional/loss.py, operators/*entropy*, bce, etc.)
# --------------------------------------------------------------------------

def _reduce(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def _sigmoid_ce(logit, target):
    """Numerically stable elementwise sigmoid cross entropy:
    max(z,0) - z*t + log1p(exp(-|z|)). Shared by the loss families."""
    return (jnp.maximum(logit, 0.0) - logit * target
            + jnp.log1p(jnp.exp(-jnp.abs(logit))))


def cross_entropy(input, label, weight=None, ignore_index=-100,  # noqa: A002
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0):
    logits = input
    if soft_label:
        logp = jax.nn.log_softmax(logits, axis=axis) if use_softmax \
            else jnp.log(jnp.clip(logits, 1e-15, None))
        tgt = label
        if label_smoothing > 0.0:
            n = logits.shape[axis]
            tgt = (1 - label_smoothing) * tgt + label_smoothing / n
        loss = -jnp.sum(tgt * logp, axis=axis)
        return _reduce(loss, reduction)
    label = label.astype(jnp.int32)
    squeeze_label = False
    if label.ndim == logits.ndim:
        label = jnp.squeeze(label, axis=axis)
        squeeze_label = True
    logp = jax.nn.log_softmax(logits, axis=axis) if use_softmax \
        else jnp.log(jnp.clip(logits, 1e-15, None))
    if label_smoothing > 0.0:
        n = logits.shape[axis]
        nll = -jnp.take_along_axis(logp, label[..., None].astype(jnp.int32),
                                   axis=axis)[..., 0]
        smooth = -jnp.mean(logp, axis=axis)
        loss = (1 - label_smoothing) * nll + label_smoothing * smooth
    else:
        loss = -jnp.take_along_axis(
            logp, jnp.expand_dims(label, axis).astype(jnp.int32),
            axis=axis).squeeze(axis)
    valid = (label != ignore_index)
    if weight is not None:
        w = jnp.take(weight, jnp.clip(label, 0, None), axis=0)
        loss = loss * w
        if reduction == "mean":
            denom = jnp.sum(jnp.where(valid, w, 0.0))
            return jnp.sum(jnp.where(valid, loss, 0.0)) / jnp.maximum(
                denom, 1e-12)
    loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        return jnp.sum(loss) / jnp.maximum(jnp.sum(valid), 1)
    return _reduce(loss, reduction)


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, axis=-1,
                               return_softmax=False,
                               numeric_stable_mode=True):
    # numeric_stable_mode accepted for reference parity: the log-softmax
    # formulation here is always the stable path
    sm = jax.nn.softmax(logits, axis=axis)
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none",
                         axis=axis)
    loss = jnp.expand_dims(loss, axis)
    if return_softmax:
        return loss, sm
    return loss


def nll_loss(input, label, weight=None, ignore_index=-100,  # noqa: A002
             reduction="mean"):
    loss = -jnp.take_along_axis(input, label[..., None].astype(jnp.int32),
                                axis=-1 if input.ndim == 2 else 1)
    loss = loss.squeeze(-1 if input.ndim == 2 else 1)
    valid = label != ignore_index
    if weight is not None:
        w = jnp.take(weight, jnp.clip(label, 0, None).astype(jnp.int32))
        loss = loss * w
        if reduction == "mean":
            return jnp.sum(jnp.where(valid, loss, 0.0)) / jnp.sum(
                jnp.where(valid, w, 0.0))
    loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        return jnp.sum(loss) / jnp.maximum(jnp.sum(valid), 1)
    return _reduce(loss, reduction)


def mse_loss(input, label, reduction="mean"):  # noqa: A002
    return _reduce(jnp.square(input - label), reduction)


def l1_loss(input, label, reduction="mean"):  # noqa: A002
    return _reduce(jnp.abs(input - label), reduction)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0):  # noqa: A002
    diff = jnp.abs(input - label)
    loss = jnp.where(diff < delta, 0.5 * diff * diff / delta,
                     diff - 0.5 * delta)
    return _reduce(loss, reduction)


def binary_cross_entropy(input, label, weight=None,  # noqa: A002
                         reduction="mean"):
    x = jnp.clip(input, 1e-12, 1.0 - 1e-12)
    loss = -(label * jnp.log(x) + (1.0 - label) * jnp.log1p(-x))
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None):
    max_val = jnp.clip(-logit, 0, None)
    if pos_weight is not None:
        log_w = (pos_weight - 1.0) * label + 1.0
        loss = (1.0 - label) * logit + log_w * (
            jnp.log1p(jnp.exp(-jnp.abs(logit))) + max_val)
    else:
        loss = (1.0 - label) * logit + max_val + jnp.log1p(
            jnp.exp(-jnp.abs(logit)))
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


def kl_div(input, label, reduction="mean"):  # noqa: A002
    loss = label * (jnp.log(jnp.clip(label, 1e-12, None)) - input)
    if reduction == "batchmean":
        return jnp.sum(loss) / input.shape[0]
    return _reduce(loss, reduction)


def margin_ranking_loss(input, other, label, margin=0.0,  # noqa: A002
                        reduction="mean"):
    loss = jnp.clip(-label * (input - other) + margin, 0, None)
    return _reduce(loss, reduction)


def hinge_embedding_loss(input, label, margin=1.0,  # noqa: A002
                         reduction="mean"):
    loss = jnp.where(label == 1.0, input,
                     jnp.clip(margin - input, 0, None))
    return _reduce(loss, reduction)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    dot = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.linalg.norm(x1, axis=axis)
    n2 = jnp.linalg.norm(x2, axis=axis)
    return dot / jnp.maximum(n1 * n2, eps)


def cosine_embedding_loss(input1, input2, label, margin=0.0,
                          reduction="mean"):
    sim = cosine_similarity(input1, input2, axis=1)
    loss = jnp.where(label == 1, 1.0 - sim,
                     jnp.clip(sim - margin, 0, None))
    return _reduce(loss, reduction)


def triplet_margin_loss(anchor, positive, negative, margin=1.0, p=2.0,
                        eps=1e-6, swap=False, reduction="mean"):
    d_pos = jnp.linalg.norm(anchor - positive + eps, ord=p, axis=-1)
    d_neg = jnp.linalg.norm(anchor - negative + eps, ord=p, axis=-1)
    if swap:
        d_neg = jnp.minimum(d_neg, jnp.linalg.norm(
            positive - negative + eps, ord=p, axis=-1))
    loss = jnp.clip(d_pos - d_neg + margin, 0, None)
    return _reduce(loss, reduction)


def square_error_cost(input, label):  # noqa: A002
    return jnp.square(input - label)


def log_loss(input, label, epsilon=1e-4):  # noqa: A002
    return -label * jnp.log(input + epsilon) - (1 - label) * jnp.log(
        1 - input + epsilon)


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum"):
    p = jax.nn.sigmoid(logit)
    ce = binary_cross_entropy_with_logits(logit, label, reduction="none")
    p_t = p * label + (1 - p) * (1 - label)
    a_t = alpha * label + (1 - alpha) * (1 - label)
    loss = a_t * jnp.power(1 - p_t, gamma) * ce
    if normalizer is not None:
        loss = loss / normalizer
    return _reduce(loss, reduction)


# --------------------------------------------------------------------------
# vision utils (reference: nn/functional/vision.py, common.py)
# --------------------------------------------------------------------------

def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW"):
    channel_last = data_format in ("NHWC", "NWC", "NDHWC")
    nsp = x.ndim - 2
    sp_axes = tuple(range(1, 1 + nsp)) if channel_last else \
        tuple(range(2, 2 + nsp))
    in_size = [x.shape[a] for a in sp_axes]
    if size is None:
        sf = _norm_tuple(scale_factor, nsp)
        size = [int(i * s) for i, s in zip(in_size, sf)]
    else:
        size = list(_norm_tuple(size, nsp))
    method = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
              "trilinear": "linear", "bicubic": "cubic",
              "area": "linear"}[mode]
    new_shape = list(x.shape)
    for a, s in zip(sp_axes, size):
        new_shape[a] = s
    if mode == "nearest":
        # match reference nearest (floor) semantics
        idx = [jnp.floor(jnp.arange(s) * (i / s)).astype(jnp.int32)
               for s, i in zip(size, in_size)]
        out = x
        for a, ix in zip(sp_axes, idx):
            out = jnp.take(out, ix, axis=a)
        return out
    if align_mode == 1 and method == "linear" and not align_corners:
        # reference align_mode=1: asymmetric src = dst/scale (the default
        # jax.image.resize linear path is the align_mode=0 half-pixel
        # map). Manual per-axis lerp with edge-clamped gathers — the
        # reference clamps at the boundary, scale_and_translate zero-pads.
        out = x
        for a, (osz, isz) in zip(sp_axes, zip(size, in_size)):
            src = jnp.arange(osz) * (isz / osz)
            i0 = jnp.clip(jnp.floor(src).astype(jnp.int32), 0, isz - 1)
            i1 = jnp.clip(i0 + 1, 0, isz - 1)
            frac = (src - i0).astype(out.dtype)
            shape = [1] * out.ndim
            shape[a] = osz
            frac = frac.reshape(shape)
            out = (jnp.take(out, i0, axis=a) * (1 - frac) +
                   jnp.take(out, i1, axis=a) * frac)
        return out
    return jax.image.resize(x, new_shape, method=method)


def upsample(x, size=None, scale_factor=None, mode="nearest",
             align_corners=False, align_mode=0, data_format="NCHW"):
    return interpolate(x, size, scale_factor, mode, align_corners,
                       align_mode, data_format)


def pixel_shuffle(x, upscale_factor, data_format="NCHW"):
    r = upscale_factor
    if data_format == "NCHW":
        n, c, h, w = x.shape
        x = x.reshape(n, c // (r * r), r, r, h, w)
        x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
        return x.reshape(n, c // (r * r), h * r, w * r)
    n, h, w, c = x.shape
    x = x.reshape(n, h, w, r, r, c // (r * r))
    x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
    return x.reshape(n, h * r, w * r, c // (r * r))


def pixel_unshuffle(x, downscale_factor, data_format="NCHW"):
    r = downscale_factor
    if data_format == "NCHW":
        n, c, h, w = x.shape
        x = x.reshape(n, c, h // r, r, w // r, r)
        x = jnp.transpose(x, (0, 1, 3, 5, 2, 4))
        return x.reshape(n, c * r * r, h // r, w // r)
    n, h, w, c = x.shape
    x = x.reshape(n, h // r, r, w // r, r, c)
    x = jnp.transpose(x, (0, 2, 4, 1, 3, 5)).reshape(
        n, h // r, w // r, c * r * r)
    return x


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    """im2col (reference unfold_op). x: [N, C, H, W] ->
    [N, C*kh*kw, L]."""
    n, c, h, w = x.shape
    kh, kw = _norm_tuple(kernel_sizes, 2)
    sh, sw = _norm_tuple(strides, 2)
    dh, dw = _norm_tuple(dilations, 2)
    pads = _conv_padding(paddings, 2, (sh, sw), (dh, dw), (kh, kw))
    xp = jnp.pad(x, [(0, 0), (0, 0)] + list(pads))
    oh = (xp.shape[2] - (dh * (kh - 1) + 1)) // sh + 1
    ow = (xp.shape[3] - (dw * (kw - 1) + 1)) // sw + 1
    cols = []
    for i in range(kh):
        for j in range(kw):
            patch = xp[:, :, i * dh:i * dh + oh * sh:sh,
                       j * dw:j * dw + ow * sw:sw]
            cols.append(patch)
    out = jnp.stack(cols, axis=2)  # [N, C, kh*kw, oh, ow]
    return out.reshape(n, c * kh * kw, oh * ow)


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True):
    """x: [N,C,H,W], grid: [N,Hg,Wg,2] in [-1,1]."""
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1.0) * ((w - 1) / 2.0) if align_corners else \
        ((grid[..., 0] + 1.0) * w - 1.0) / 2.0
    gy = (grid[..., 1] + 1.0) * ((h - 1) / 2.0) if align_corners else \
        ((grid[..., 1] + 1.0) * h - 1.0) / 2.0

    def sample_one(img, px, py):
        # img: [C,H,W]; px,py: [Hg,Wg]
        if mode == "nearest":
            ix = jnp.clip(jnp.round(px), 0, w - 1).astype(jnp.int32)
            iy = jnp.clip(jnp.round(py), 0, h - 1).astype(jnp.int32)
            return img[:, iy, ix]
        x0 = jnp.floor(px)
        y0 = jnp.floor(py)
        x1, y1 = x0 + 1, y0 + 1
        wx1 = px - x0
        wy1 = py - y0
        vals = 0.0
        for (xi, wxf) in ((x0, 1.0 - wx1), (x1, wx1)):
            for (yi, wyf) in ((y0, 1.0 - wy1), (y1, wy1)):
                valid = (xi >= 0) & (xi <= w - 1) & (yi >= 0) & (yi <= h - 1)
                ix = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
                iy = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
                v = img[:, iy, ix]
                if padding_mode == "zeros":
                    v = jnp.where(valid[None], v, 0.0)
                vals = vals + v * (wxf * wyf)[None]
        return vals

    return jax.vmap(sample_one)(x, gx, gy)


def affine_grid(theta, out_shape, align_corners=True):
    n, c, h, w = out_shape
    if align_corners:
        ys = jnp.linspace(-1.0, 1.0, h)
        xs = jnp.linspace(-1.0, 1.0, w)
    else:
        ys = (jnp.arange(h) * 2 + 1) / h - 1
        xs = (jnp.arange(w) * 2 + 1) / w - 1
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    base = jnp.stack([gx, gy, jnp.ones_like(gx)], axis=-1)  # [h,w,3]
    return jnp.einsum("nij,hwj->nhwi", theta, base)


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW"):
    if data_format == "NHWC":
        out = temporal_shift(jnp.transpose(x, (0, 3, 1, 2)), seg_num,
                             shift_ratio)
        return jnp.transpose(out, (0, 2, 3, 1))
    n, c, h, w = x.shape
    nt = n // seg_num
    x5 = x.reshape(nt, seg_num, c, h, w)
    fold = int(c * shift_ratio)
    left = jnp.concatenate([x5[:, 1:, :fold],
                            jnp.zeros_like(x5[:, :1, :fold])], axis=1)
    right = jnp.concatenate([jnp.zeros_like(x5[:, :1, fold:2 * fold]),
                             x5[:, :-1, fold:2 * fold]], axis=1)
    mid = x5[:, :, 2 * fold:]
    return jnp.concatenate([left, right, mid], axis=2).reshape(n, c, h, w)


def channel_shuffle(x, groups, data_format="NCHW"):
    if data_format == "NCHW":
        n, c, h, w = x.shape
        x = x.reshape(n, groups, c // groups, h, w)
        x = jnp.swapaxes(x, 1, 2)
        return x.reshape(n, c, h, w)
    n, h, w, c = x.shape
    x = x.reshape(n, h, w, groups, c // groups)
    x = jnp.swapaxes(x, 3, 4)
    return x.reshape(n, h, w, c)


def sequence_mask(x, maxlen=None, dtype="int64"):
    lengths = x  # reference name: sequence_mask(x, maxlen, dtype)
    maxlen = int(maxlen) if maxlen is not None else None
    if maxlen is None:
        raise ValueError(
            "sequence_mask requires maxlen under XLA static shapes")
    row = jnp.arange(maxlen)
    return (row[None, :] < jnp.asarray(lengths)[..., None]).astype(
        jnp.dtype(dtype))


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1):
    """col2im, the inverse of unfold (reference fold_op).
    x: [N, C*kh*kw, L] -> [N, C, H, W]; overlapping patches sum."""
    n = x.shape[0]
    oh_img, ow_img = _norm_tuple(output_sizes, 2)
    kh, kw = _norm_tuple(kernel_sizes, 2)
    sh, sw = _norm_tuple(strides, 2)
    dh, dw = _norm_tuple(dilations, 2)
    pads = _conv_padding(paddings, 2, (sh, sw), (dh, dw), (kh, kw))
    (pt, pb), (pl, pr) = pads
    hp, wp = oh_img + pt + pb, ow_img + pl + pr
    oh = (hp - (dh * (kh - 1) + 1)) // sh + 1
    ow = (wp - (dw * (kw - 1) + 1)) // sw + 1
    c = x.shape[1] // (kh * kw)
    cols = x.reshape(n, c, kh * kw, oh, ow)
    out = jnp.zeros((n, c, hp, wp), x.dtype)
    for i in range(kh):
        for j in range(kw):
            out = out.at[:, :, i * dh:i * dh + oh * sh:sh,
                         j * dw:j * dw + ow * sw:sw].add(
                cols[:, :, i * kw + j])
    return out[:, :, pt:pt + oh_img, pl:pl + ow_img]


def lp_pool2d(x, norm_type, kernel_size, stride=None, padding=0,
              ceil_mode=False, data_format="NCHW"):
    """Power-average pooling: (sum |x|^p / 1)^(1/p) over each window."""
    p = float(norm_type)
    powed = jnp.abs(x) ** p
    pooled = avg_pool2d(powed, kernel_size, stride, padding,
                        ceil_mode=ceil_mode, exclusive=False,
                        data_format=data_format)
    k = _norm_tuple(kernel_size, 2)
    return (pooled * (k[0] * k[1])) ** (1.0 / p)


def thresholded_relu(x, threshold=1.0):
    return jnp.where(x > threshold, x, 0.0).astype(x.dtype)


def pad3d(x, pad, mode="constant", value=0.0,  # noqa: A002
          data_format="NCDHW"):
    """5-D pad over (D, H, W) of NCDHW/NDHWC (reference pad3d_op).
    pad = [left, right, top, bottom, front, back]."""
    from .manipulation import pad as _pad
    l, r, t, b, f, bk = pad
    if data_format == "NCDHW":
        width = [(0, 0), (0, 0), (f, bk), (t, b), (l, r)]
    else:  # NDHWC
        width = [(0, 0), (f, bk), (t, b), (l, r), (0, 0)]
    flat = [v for w in width for v in w]
    return _pad(x, flat, mode=mode, value=value)


def zeropad2d(x, padding, data_format="NCHW"):
    from .manipulation import pad as _pad
    return _pad(x, list(padding), mode="constant", value=0.0,
                data_format=data_format)


def soft_margin_loss(input, label, reduction="mean"):  # noqa: A002
    """log(1 + exp(-label * input)); label in {-1, 1}. Stable softplus
    form (overflow-free for large margins)."""
    loss = jax.nn.softplus(-label.astype(input.dtype) * input)
    return _reduce(loss, reduction)


def multi_label_soft_margin_loss(input, label, weight=None,  # noqa: A002
                                 reduction="mean"):
    y = label.astype(input.dtype)
    loss = -(y * jax.nn.log_sigmoid(input) +
             (1.0 - y) * jax.nn.log_sigmoid(-input))
    if weight is not None:
        loss = loss * weight
    loss = loss.mean(axis=-1)
    return _reduce(loss, reduction)


def poisson_nll_loss(input, label, log_input=True, full=False,  # noqa: A002
                     epsilon=1e-8, reduction="mean"):
    if log_input:
        loss = jnp.exp(input) - label * input
    else:
        loss = input - label * jnp.log(input + epsilon)
    if full:
        # evaluate log on a safe argument so the untaken where-branch
        # cannot poison gradients with nan (label==0 is common)
        safe = jnp.where(label > 1.0, label, 1.0)
        stirling = safe * jnp.log(safe) - safe + \
            0.5 * jnp.log(2.0 * jnp.pi * safe)
        loss = loss + jnp.where(label > 1.0, stirling, 0.0)
    return _reduce(loss, reduction)


def gaussian_nll_loss(input, label, variance, full=False,  # noqa: A002
                      epsilon=1e-6, reduction="mean"):
    var = jnp.clip(variance, epsilon, None)
    loss = 0.5 * (jnp.log(var) + (input - label) ** 2 / var)
    if full:
        loss = loss + 0.5 * jnp.log(jnp.asarray(2.0 * jnp.pi, input.dtype))
    return _reduce(loss, reduction)


def data_norm(x, batch_size, batch_sum, batch_square_sum, epsilon=1e-4):
    """CTR feature normalization from ACCUMULATED batch statistics
    (reference data_norm_op.cc: means = batch_sum / batch_size, scales =
    sqrt(batch_size / batch_square_sum) — batch_square_sum accumulates
    CENTERED squares, so scales is 1/std)."""
    mean = batch_sum / batch_size
    scale = jnp.sqrt(batch_size / jnp.maximum(batch_square_sum, epsilon))
    return (x - mean) * scale
