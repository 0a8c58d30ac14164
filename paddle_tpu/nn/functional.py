"""nn functional ops.

Parity: python/paddle/nn/functional/ (activation.py, common.py, conv.py,
pooling.py, norm.py, loss.py, input.py) lowered to XLA HLO — convs and
matmuls hit the MXU via lax.conv_general_dilated/dot_general; everything
else is fusable elementwise HLO.
"""

from __future__ import annotations

import builtins
import functools
import math as pymath
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dtype as dtypes
from ..core.tensor import Tensor
from ..ops.dispatch import apply_op, ensure_tensor
from ..ops.random import split_key

# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def _act(opname, jfn):
    def op(x, name=None):
        return apply_op(opname, jfn, ensure_tensor(x))

    op.__name__ = opname
    return op


# A/B'd on-chip vs an output-mask custom vjp (save relu OUTPUT for the
# backward mask instead of the input): neutral — XLA already avoids a
# second activation round trip by rematerializing the mask in the fused
# backward, so the plain rule stays.
_relu_plain = _act("relu", jax.nn.relu)


def relu(x, name=None):
    # peephole: a frozen-stats fused conv+BN output (see fused_conv_bn)
    # carries a re-dispatch closure that puts THIS relu inside the Pallas
    # epilogue; under jit the relu-less fused call is dead code, so the
    # whole Conv2D->BatchNorm->ReLU block becomes one kernel.
    rerun = getattr(x, "_fused_relu_rerun", None)
    if rerun is not None:
        return rerun()
    out = _relu_plain(x)
    pending = getattr(x, "_fused_bn_pending", None)
    if pending is not None and not pending[-1]:
        # training-mode chain fusion: record that a ReLU sits between the
        # fused BN and its consumer, so the next fused conv's prologue
        # applies it in VMEM (this materialized relu is then dead code)
        out._fused_bn_pending = pending[:-1] + (True,)
    return out


relu.__name__ = "relu"
relu6 = _act("relu6", jax.nn.relu6)
sigmoid = _act("sigmoid", jax.nn.sigmoid)
tanh = _act("tanh", jnp.tanh)
silu = _act("silu", jax.nn.silu)
swish = silu
mish = _act("mish", lambda a: a * jnp.tanh(jax.nn.softplus(a)))
hardswish = _act("hardswish", jax.nn.hard_swish)
hardsigmoid = _act("hardsigmoid", lambda a: jnp.clip(a / 6.0 + 0.5, 0.0, 1.0))
tanhshrink = _act("tanhshrink", lambda a: a - jnp.tanh(a))
softsign = _act("softsign", jax.nn.soft_sign)
selu_ = None


def gelu(x, approximate=False, name=None) -> Tensor:
    return apply_op("gelu", lambda a: jax.nn.gelu(a, approximate=approximate), ensure_tensor(x))


def leaky_relu(x, negative_slope=0.01, name=None) -> Tensor:
    return apply_op("leaky_relu", lambda a: jax.nn.leaky_relu(a, negative_slope), ensure_tensor(x))


def elu(x, alpha=1.0, name=None) -> Tensor:
    return apply_op("elu", lambda a: jax.nn.elu(a, alpha), ensure_tensor(x))


def celu(x, alpha=1.0, name=None) -> Tensor:
    return apply_op("celu", lambda a: jax.nn.celu(a, alpha), ensure_tensor(x))


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None) -> Tensor:
    return apply_op("selu", lambda a: scale * jnp.where(a > 0, a, alpha * jnp.expm1(a)), ensure_tensor(x))


def prelu(x, weight, data_format="NCHW", name=None) -> Tensor:
    x, weight = ensure_tensor(x), ensure_tensor(weight)

    def _f(a, w):
        if w.size > 1:
            shape = [1] * a.ndim
            ch_axis = 1 if data_format[1] == "C" else a.ndim - 1
            shape[ch_axis] = w.size
            w = w.reshape(shape)
        return jnp.where(a > 0, a, w * a)

    return apply_op("prelu", _f, x, weight)


def softplus(x, beta=1.0, threshold=20.0, name=None) -> Tensor:
    return apply_op(
        "softplus",
        lambda a: jnp.where(a * beta > threshold, a, jax.nn.softplus(a * beta) / beta),
        ensure_tensor(x),
    )


def softshrink(x, threshold=0.5, name=None) -> Tensor:
    return apply_op(
        "softshrink",
        lambda a: jnp.where(a > threshold, a - threshold, jnp.where(a < -threshold, a + threshold, 0.0)),
        ensure_tensor(x),
    )


def hardshrink(x, threshold=0.5, name=None) -> Tensor:
    return apply_op("hardshrink", lambda a: jnp.where(jnp.abs(a) > threshold, a, 0.0), ensure_tensor(x))


def hardtanh(x, min=-1.0, max=1.0, name=None) -> Tensor:
    return apply_op("hardtanh", lambda a: jnp.clip(a, min, max), ensure_tensor(x))


def thresholded_relu(x, threshold=1.0, value=0.0, name=None) -> Tensor:
    return apply_op("thresholded_relu", lambda a: jnp.where(a > threshold, a, value), ensure_tensor(x))


def log_sigmoid(x, name=None) -> Tensor:
    return apply_op("log_sigmoid", jax.nn.log_sigmoid, ensure_tensor(x))


def _softmax_body(a, axis, d):
    if d is not None:
        a = a.astype(d)
    return jax.nn.softmax(a, axis=axis)


def softmax(x, axis=-1, dtype=None, name=None) -> Tensor:
    from ..ops.dispatch import stable_closure

    x = ensure_tensor(x)
    d = dtypes.convert_dtype(dtype)
    d = np.dtype(d) if d is not None else None
    return apply_op("softmax", stable_closure(_softmax_body, int(axis), d), x)


def log_softmax(x, axis=-1, dtype=None, name=None) -> Tensor:
    x = ensure_tensor(x)
    d = dtypes.convert_dtype(dtype)

    def _f(a):
        if d is not None:
            a = a.astype(d)
        return jax.nn.log_softmax(a, axis=axis)

    return apply_op("log_softmax", _f, x)


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None) -> Tensor:
    x = ensure_tensor(x)
    g = jax.random.gumbel(split_key(), x._data.shape, x._data.dtype)

    def _f(a):
        y = jax.nn.softmax((a + g) / temperature, axis=axis)
        if hard:
            onehot = jax.nn.one_hot(jnp.argmax(y, axis=axis), y.shape[axis], dtype=y.dtype)
            onehot = jnp.moveaxis(onehot, -1, axis)
            y = onehot + y - jax.lax.stop_gradient(y)
        return y

    return apply_op("gumbel_softmax", _f, x)


def glu(x, axis=-1, name=None) -> Tensor:
    return apply_op("glu", lambda a: jax.nn.glu(a, axis=axis), ensure_tensor(x))


def maxout(x, groups, axis=1, name=None) -> Tensor:
    x = ensure_tensor(x)

    def _f(a):
        shp = list(a.shape)
        c = shp[axis]
        new = shp[:axis] + [c // groups, groups] + shp[axis + 1 :]
        return jnp.max(a.reshape(new), axis=axis + 1)

    return apply_op("maxout", _f, x)


# ---------------------------------------------------------------------------
# Linear / embedding / dropout
# ---------------------------------------------------------------------------


# While a train step that wants every linear's weight gradient made from
# factors written once traces its forward and backward
# (distributed/engine.py ``ShardedTrainStep``), ``routed`` is the list of
# the weights routed so far, on the tracing thread alone; None at every
# other time, and ``linear`` then builds the program it always built: the
# check is at trace time only.
_factors_once = threading.local()
_factors_once.routed = None


@jax.custom_vjp
def _matmul_factors_once(a, w):
    """``a @ w`` for a weight ``w [in, out]``, whose gradient ``a^T dy``
    reads both its factors from HBM, each written once.

    XLA runs a weight-gradient matmul with the parameter's whole
    optimizer update as its epilogue, so the gradient never reaches
    HBM; but left to itself it also folds what MADE the factors into
    the matmul's operands (d logits of the fused cross entropy,
    ``exponential`` and all; ``silu(gate) * up`` in front of
    ``down_proj``) and computes it again for every window it reads:
    16.9 ms for ``lm_head`` at Mistral-7B widths on a v5e where the same
    fusion on materialised factors takes 6.8 (PERF.md section 6, PR 41).
    The barrier is on the weight gradient's operands alone; the forward
    and the input's gradient are what autodiff makes of ``matmul``."""
    return jnp.matmul(a, w)


def _factors_once_fwd(a, w):
    return jnp.matmul(a, w), (a, w)


def _factors_once_bwd(res, dy):
    a, w = res
    da = jax.lax.dot_general(dy, w, (((dy.ndim - 1,), (1,)), ((), ())))
    rows, cots = jax.lax.optimization_barrier(
        (a.reshape(-1, w.shape[0]), dy.reshape(-1, w.shape[1])))
    return da, jax.lax.dot_general(rows, cots, (((0,), (0,)), ((), ())))


_matmul_factors_once.defvjp(_factors_once_fwd, _factors_once_bwd)


def _linear_matmul(a, w):
    routed = getattr(_factors_once, "routed", None)
    if routed is None or w.ndim != 2 or a.dtype != w.dtype:
        return jnp.matmul(a, w)
    routed.append(w)
    return _matmul_factors_once(a, w)


def linear(x, weight, bias=None, name=None) -> Tensor:
    """y = x @ W + b. Weight layout [in, out] (reference:
    python/paddle/nn/functional/common.py linear; phi matmul kernel)."""
    x, weight = ensure_tensor(x), ensure_tensor(weight)
    if bias is None:
        return apply_op("linear", lambda a, w: _linear_matmul(a, w), x, weight)
    bias = ensure_tensor(bias)
    return apply_op("linear", lambda a, w, b: _linear_matmul(a, w) + b, x, weight, bias)


def embedding(x, weight, padding_idx=None, sparse=False, name=None) -> Tensor:
    x, weight = ensure_tensor(x), ensure_tensor(weight)

    def _f(ids, w):
        out = jnp.take(w, ids, axis=0)
        if padding_idx is not None and padding_idx >= 0:
            mask = (ids == padding_idx)[..., None]
            out = jnp.where(mask, jnp.zeros((), out.dtype), out)
        return out

    return apply_op("embedding", _f, x, weight)


def one_hot(x, num_classes, name=None) -> Tensor:
    x = ensure_tensor(x)
    return Tensor(jax.nn.one_hot(x._data, num_classes, dtype=dtypes.get_default_dtype()))


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None) -> Tensor:
    x = ensure_tensor(x)
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return apply_op("dropout", lambda a: a * (1 - p), x)
        return apply_op("dropout", lambda a: a, x)
    shape = x._data.shape
    if axis is not None:
        axes = (axis,) if isinstance(axis, int) else tuple(axis)
        mask_shape = tuple(s if i in axes else 1 for i, s in enumerate(shape))
    else:
        mask_shape = shape
    keep = jax.random.bernoulli(split_key(), 1.0 - p, mask_shape)

    def _f(a):
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), jnp.zeros((), a.dtype))
        return jnp.where(keep, a, jnp.zeros((), a.dtype))

    return apply_op("dropout", _f, x)


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None) -> Tensor:
    axis = (0, 1) if data_format == "NCHW" else (0, 3)
    return dropout(x, p=p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None) -> Tensor:
    axis = (0, 1) if data_format == "NCDHW" else (0, 4)
    return dropout(x, p=p, axis=axis, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None) -> Tensor:
    x = ensure_tensor(x)
    if not training or p == 0.0:
        return apply_op("alpha_dropout", lambda a: a, x)
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    keep = jax.random.bernoulli(split_key(), 1.0 - p, x._data.shape)
    a_coef = (1.0 - p + p * alpha_p**2 * (1.0 - p)) ** -0.5
    b_coef = -a_coef * p * alpha_p

    def _f(v):
        return a_coef * jnp.where(keep, v, jnp.asarray(alpha_p, v.dtype)) + b_coef

    return apply_op("alpha_dropout", _f, x)


# ---------------------------------------------------------------------------
# Convolution / pooling
# ---------------------------------------------------------------------------


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


def _conv_padding(padding, nsp):
    if isinstance(padding, str):
        return padding.upper()
    p = _pair(padding, nsp)
    if len(p) == nsp:
        return [(x, x) for x in p]
    if len(p) == 2 * nsp:
        return [(p[2 * i], p[2 * i + 1]) for i in range(nsp)]
    return [(x, x) for x in p]


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCHW", name=None) -> Tensor:
    x, weight = ensure_tensor(x), ensure_tensor(weight)
    strides = _pair(stride)
    dil = _pair(dilation)
    pad = _conv_padding(padding, 2)
    dn = ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else ("NHWC", "OIHW", "NHWC")

    def _f(a, w, *b):
        out = jax.lax.conv_general_dilated(
            a, w, window_strides=strides, padding=pad, rhs_dilation=dil,
            dimension_numbers=dn, feature_group_count=groups,
            preferred_element_type=None,
        )
        if b:
            bb = b[0].reshape((1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1))
            out = out + bb
        return out

    if bias is None:
        return apply_op("conv2d", _f, x, weight)
    return apply_op("conv2d", _f, x, weight, ensure_tensor(bias))


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCL", name=None) -> Tensor:
    x, weight = ensure_tensor(x), ensure_tensor(weight)
    strides = _pair(stride, 1)
    dil = _pair(dilation, 1)
    pad = _conv_padding(padding, 1)
    dn = ("NCH", "OIH", "NCH") if data_format == "NCL" else ("NHC", "OIH", "NHC")

    def _f(a, w, *b):
        out = jax.lax.conv_general_dilated(
            a, w, window_strides=strides, padding=pad, rhs_dilation=dil,
            dimension_numbers=dn, feature_group_count=groups,
        )
        if b:
            bb = b[0].reshape((1, -1, 1) if data_format == "NCL" else (1, 1, -1))
            out = out + bb
        return out

    if bias is None:
        return apply_op("conv1d", _f, x, weight)
    return apply_op("conv1d", _f, x, weight, ensure_tensor(bias))


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCDHW", name=None) -> Tensor:
    x, weight = ensure_tensor(x), ensure_tensor(weight)
    strides = _pair(stride, 3)
    dil = _pair(dilation, 3)
    pad = _conv_padding(padding, 3)
    dn = ("NCDHW", "OIDHW", "NCDHW") if data_format == "NCDHW" else ("NDHWC", "OIDHW", "NDHWC")

    def _f(a, w, *b):
        out = jax.lax.conv_general_dilated(
            a, w, window_strides=strides, padding=pad, rhs_dilation=dil,
            dimension_numbers=dn, feature_group_count=groups,
        )
        if b:
            bb = b[0].reshape((1, -1, 1, 1, 1) if data_format == "NCDHW" else (1, 1, 1, 1, -1))
            out = out + bb
        return out

    if bias is None:
        return apply_op("conv3d", _f, x, weight)
    return apply_op("conv3d", _f, x, weight, ensure_tensor(bias))


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0, groups=1,
                     dilation=1, data_format="NCHW", output_size=None, name=None) -> Tensor:
    x, weight = ensure_tensor(x), ensure_tensor(weight)
    strides = _pair(stride)
    dil = _pair(dilation)
    opad = _pair(output_padding)
    p = _pair(padding)
    if output_size is not None:
        # reference: output_size overrides output_padding — back out the
        # padding that yields the requested spatial dims
        target = _pair(output_size)
        sp = 2 if data_format == "NCHW" else 1
        opad = []
        for d in range(2):
            in_d = int(x.shape[sp + d])
            k_d = (int(weight.shape[2 + d]) - 1) * dil[d] + 1
            base = (in_d - 1) * strides[d] - 2 * p[d] + k_d
            extra = int(target[d]) - base
            if not 0 <= extra < max(strides[d], dil[d]):
                raise ValueError(
                    f"conv2d_transpose output_size[{d}]={target[d]} not "
                    f"reachable from base {base} with stride {strides[d]}")
            opad.append(extra)
        opad = tuple(opad)
    dn = ("NCHW", "IOHW", "NCHW") if data_format == "NCHW" else ("NHWC", "IOHW", "NHWC")

    def _f(a, w, *b):
        kh = (w.shape[2] - 1) * dil[0] + 1
        kw = (w.shape[3] - 1) * dil[1] + 1
        pad = [
            (kh - 1 - p[0], kh - 1 - p[0] + opad[0]),
            (kw - 1 - p[1], kw - 1 - p[1] + opad[1]),
        ]
        out = jax.lax.conv_general_dilated(
            a, jnp.flip(w, (2, 3)), window_strides=(1, 1), padding=pad,
            lhs_dilation=strides, rhs_dilation=dil, dimension_numbers=dn,
            feature_group_count=groups,
        )
        if b:
            bb = b[0].reshape((1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1))
            out = out + bb
        return out

    if bias is None:
        return apply_op("conv2d_transpose", _f, x, weight)
    return apply_op("conv2d_transpose", _f, x, weight, ensure_tensor(bias))


def _pool(x, kernel, stride, padding, reducer, init, data_format, count_include_pad=True, is_avg=False, ceil_mode=False):
    ksize = _pair(kernel)
    strides = _pair(stride if stride is not None else kernel)
    nd = x.ndim

    if data_format == "NCHW":
        window = (1, 1) + ksize
        ws = (1, 1) + strides
        spatial = (2, 3)
    else:
        window = (1,) + ksize + (1,)
        ws = (1,) + strides + (1,)
        spatial = (1, 2)

    if isinstance(padding, str):
        pad_cfg = padding.upper()
        if ceil_mode:
            raise NotImplementedError("ceil_mode with SAME/VALID string "
                                      "padding is not supported")
    else:
        p = _pair(padding)
        pad_cfg = [(0, 0)] * nd
        for i, ax in enumerate(spatial):
            extra = 0
            if ceil_mode:
                extra, _ = _ceil_pool_extra(int(x.shape[ax]), ksize[i],
                                            strides[i], p[i])
            pad_cfg[ax] = (p[i], p[i] + extra)

    def _f(a):
        if is_avg:
            ones = jnp.ones_like(a)
            s = jax.lax.reduce_window(a, 0.0, jax.lax.add, window, ws, pad_cfg)
            if count_include_pad and not isinstance(pad_cfg, str):
                denom = float(np.prod(ksize))
                return s / denom
            c = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, ws, pad_cfg)
            return s / c
        return jax.lax.reduce_window(a, init, reducer, window, ws, pad_cfg)

    return _f


def _ceil_pool_extra(dim: int, k: int, s: int, p: int):
    """Right/bottom extension for ceil_mode pooling with the reference's
    window-drop rule: a window starting entirely in the padding is dropped
    ((o-1)*s must be < dim + p)."""
    o = (dim + 2 * p - k + s - 1) // s + 1
    if (o - 1) * s >= dim + p:
        o -= 1
    return max(0, (o - 1) * s + k - (dim + 2 * p)), o


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False, return_mask=False,
               data_format="NCHW", name=None):
    x = ensure_tensor(x)
    if ceil_mode and not return_mask:
        # extend right/bottom with -inf so the last partial window counts,
        # then reuse the plain VALID-pool path
        ks = _pair(kernel_size)
        st = ks if stride is None else _pair(stride)
        pd = _pair(padding) if not isinstance(padding, int) else (padding, padding)
        hw_axes = (2, 3) if data_format == "NCHW" else (1, 2)
        shape = x.shape
        eh, _ = _ceil_pool_extra(int(shape[hw_axes[0]]), ks[0], st[0], pd[0])
        ew, _ = _ceil_pool_extra(int(shape[hw_axes[1]]), ks[1], st[1], pd[1])
        if eh or ew:
            pads = [(0, 0)] * 4
            pads[hw_axes[0]] = (0, eh)
            pads[hw_axes[1]] = (0, ew)

            def _pad(a):
                return jnp.pad(a, pads, constant_values=-jnp.inf)

            x = apply_op("ceil_pad", _pad, x)
    if return_mask:
        ks = _pair(kernel_size)
        st = ks if stride is None else _pair(stride)
        pd = _pair(padding) if not isinstance(padding, int) else (padding, padding)

        def _f(a):
            if data_format != "NCHW":
                a = jnp.transpose(a, (0, 3, 1, 2))
            N, C, H, W = a.shape
            extra = [0, 0]
            if ceil_mode:  # extend right/bottom so the last partial window counts
                extra[0], _ = _ceil_pool_extra(H, ks[0], st[0], pd[0])
                extra[1], _ = _ceil_pool_extra(W, ks[1], st[1], pd[1])
            ap = jnp.pad(a, ((0, 0), (0, 0), (pd[0], pd[0] + extra[0]),
                             (pd[1], pd[1] + extra[1])),
                         constant_values=-jnp.inf)
            oh = (H + 2 * pd[0] + extra[0] - ks[0]) // st[0] + 1
            ow = (W + 2 * pd[1] + extra[1] - ks[1]) // st[1] + 1
            iy = (jnp.arange(oh)[:, None] * st[0] + jnp.arange(ks[0])[None, :])  # [oh,kh]
            ix = (jnp.arange(ow)[:, None] * st[1] + jnp.arange(ks[1])[None, :])  # [ow,kw]
            win = ap[:, :, iy[:, None, :, None], ix[None, :, None, :]]  # [N,C,oh,ow,kh,kw]
            win = win.reshape(N, C, oh, ow, ks[0] * ks[1])
            arg = jnp.argmax(win, axis=-1)
            pooled = jnp.take_along_axis(win, arg[..., None], axis=-1)[..., 0]
            # flat index into the UNPADDED input (reference mask semantics)
            dy = arg // ks[1]
            dx = arg % ks[1]
            yy = iy[:, 0][None, None, :, None] + dy - pd[0]
            xx = ix[:, 0][None, None, None, :] + dx - pd[1]
            mask = (yy * W + xx).astype(jnp.int32)
            if data_format != "NCHW":
                pooled = jnp.transpose(pooled, (0, 2, 3, 1))
                mask = jnp.transpose(mask, (0, 2, 3, 1))
            return pooled, mask

        return apply_op("max_pool2d_with_mask", _f, x)
    f = _pool(x, kernel_size, stride, padding, jax.lax.max, -jnp.inf, data_format)
    return apply_op("max_pool2d", f, x)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False, exclusive=True,
               divisor_override=None, data_format="NCHW", name=None) -> Tensor:
    x = ensure_tensor(x)
    if divisor_override is not None:
        # reference semantics: the window SUM divided by the override
        # (pads included — count_include_pad path gives the raw sum)
        kh, kw = _pair(kernel_size)
        f = _pool(x, kernel_size, stride, padding, jax.lax.add, 0.0,
                  data_format, count_include_pad=True, is_avg=True,
                  ceil_mode=ceil_mode)

        def _f(a, _inner=f):
            return _inner(a) * (kh * kw / float(divisor_override))

        return apply_op("avg_pool2d", _f, x)
    f = _pool(x, kernel_size, stride, padding, jax.lax.add, 0.0, data_format,
              count_include_pad=not exclusive, is_avg=True,
              ceil_mode=ceil_mode)
    return apply_op("avg_pool2d", f, x)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None) -> Tensor:
    x = ensure_tensor(x)
    out_hw = _pair(output_size)

    def _f(a):
        if data_format == "NCHW":
            n, c, h, w = a.shape
            a2 = a
        else:
            n, h, w, c = a.shape
            a2 = jnp.transpose(a, (0, 3, 1, 2))
        oh, ow = out_hw
        # split into oh x ow regions via mean over reshaped blocks when divisible
        if h % oh == 0 and w % ow == 0:
            out = a2.reshape(n, c, oh, h // oh, ow, w // ow).mean(axis=(3, 5))
        else:
            # general adaptive regions (reference pooling.h AdaptStartIndex/
            # AdaptEndIndex): start = floor(i*in/out), end = ceil((i+1)*in/out)
            h0 = [int(pymath.floor(i * h / oh)) for i in range(oh)]
            h1 = [int(pymath.ceil((i + 1) * h / oh)) for i in range(oh)]
            w0 = [int(pymath.floor(j * w / ow)) for j in range(ow)]
            w1 = [int(pymath.ceil((j + 1) * w / ow)) for j in range(ow)]
            rows = []
            for i in range(oh):
                cols = []
                for j in range(ow):
                    cols.append(a2[:, :, h0[i]:h1[i], w0[j]:w1[j]].mean(axis=(2, 3)))
                rows.append(jnp.stack(cols, axis=-1))
            out = jnp.stack(rows, axis=-2)
        if data_format != "NCHW":
            out = jnp.transpose(out, (0, 2, 3, 1))
        return out

    return apply_op("adaptive_avg_pool2d", _f, x)


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None) -> Tensor:
    x = ensure_tensor(x)
    out_hw = _pair(output_size)

    def _f(a):
        n, c, h, w = a.shape
        oh, ow = out_hw
        if h % oh == 0 and w % ow == 0:
            bh, bw = h // oh, w // ow
            win = a.reshape(n, c, oh, bh, ow, bw).transpose(0, 1, 2, 4, 3, 5)
            flat = win.reshape(n, c, oh, ow, bh * bw)
            out = flat.max(-1)
            if not return_mask:
                return out
            arg = flat.argmax(-1)
            dh, dw = arg // bw, arg % bw
            gh = jnp.arange(oh)[None, None, :, None] * bh + dh
            gw = jnp.arange(ow)[None, None, None, :] * bw + dw
            return out, (gh * w + gw).astype(jnp.int32)
        hi = [int(pymath.floor(i * h / oh)) for i in range(oh)] + [h]
        wi = [int(pymath.floor(i * w / ow)) for i in range(ow)] + [w]
        rows, irow = [], []
        for i in range(oh):
            cols, icol = [], []
            for j in range(ow):
                patch = a[:, :, hi[i]:hi[i + 1], wi[j]:wi[j + 1]]
                ph, pw = patch.shape[2], patch.shape[3]
                flat = patch.reshape(n, c, ph * pw)
                cols.append(flat.max(-1))
                arg = flat.argmax(-1)
                icol.append((hi[i] + arg // pw) * w + (wi[j] + arg % pw))
            rows.append(jnp.stack(cols, axis=-1))
            irow.append(jnp.stack(icol, axis=-1))
        out = jnp.stack(rows, axis=-2)
        if not return_mask:
            return out
        return out, jnp.stack(irow, axis=-2).astype(jnp.int32)

    nouts = 2 if return_mask else None
    return apply_op("adaptive_max_pool2d", _f, x, nouts=nouts)


def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False, ceil_mode=False, name=None) -> Tensor:
    x = ensure_tensor(x)
    k = kernel_size if isinstance(kernel_size, int) else kernel_size[0]
    s = stride if stride is not None else k
    s = s if isinstance(s, int) else s[0]
    p = padding if isinstance(padding, int) else padding[0]

    def _f(a):
        extra = _ceil_pool_extra(a.shape[-1], k, s, p)[0] if ceil_mode else 0
        out = jax.lax.reduce_window(a, -jnp.inf, jax.lax.max, (1, 1, k),
                                    (1, 1, s),
                                    [(0, 0), (0, 0), (p, p + extra)])
        if not return_mask:
            return out
        # windows gather: argmax position -> index into the UNPADDED axis
        n_win = out.shape[-1]
        pos = jnp.arange(n_win)[:, None] * s - p + jnp.arange(k)[None, :]
        valid = (pos >= 0) & (pos < a.shape[-1])
        g = jnp.where(valid[None, None], a[..., jnp.clip(pos, 0, a.shape[-1] - 1)],
                      -jnp.inf)
        arg = g.argmax(-1)
        idx = jnp.take_along_axis(jnp.broadcast_to(pos, arg.shape + (k,)),
                                  arg[..., None], -1)[..., 0]
        return out, idx.astype(jnp.int32)

    nouts = 2 if return_mask else None
    res = apply_op("max_pool1d", _f, x, nouts=nouts)
    return res


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True, ceil_mode=False, name=None) -> Tensor:
    x = ensure_tensor(x)
    k = kernel_size if isinstance(kernel_size, int) else kernel_size[0]
    s = stride if stride is not None else k
    s = s if isinstance(s, int) else s[0]
    p = padding if isinstance(padding, int) else padding[0]

    def _f(a):
        extra = _ceil_pool_extra(a.shape[-1], k, s, p)[0] if ceil_mode else 0
        t = jax.lax.reduce_window(a, 0.0, jax.lax.add, (1, 1, k), (1, 1, s),
                                  [(0, 0), (0, 0), (p, p + extra)])
        if not exclusive:
            return t / k
        # exclusive: divide by the VALID element count per window
        ones = jnp.ones((1, 1, a.shape[-1]), a.dtype)
        cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, (1, 1, k),
                                    (1, 1, s),
                                    [(0, 0), (0, 0), (p, p + extra)])
        return t / jnp.maximum(cnt, 1.0)

    return apply_op("avg_pool1d", _f, x)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05, name=None) -> Tensor:
    x = ensure_tensor(x)
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    naxes = tuple(range(x.ndim - len(normalized_shape), x.ndim))

    tensors = [x]
    has_w = weight is not None
    has_b = bias is not None
    if has_w:
        tensors.append(ensure_tensor(weight))
    if has_b:
        tensors.append(ensure_tensor(bias))

    def _f(a, *wb):
        mean = jnp.mean(a, axis=naxes, keepdims=True)
        var = jnp.var(a, axis=naxes, keepdims=True)
        out = (a - mean) * jax.lax.rsqrt(var + epsilon)
        i = 0
        if has_w:
            out = out * wb[i]
            i += 1
        if has_b:
            out = out + wb[i]
        return out

    return apply_op("layer_norm", _f, *tensors)


def rms_norm(x, weight=None, epsilon=1e-6, name=None) -> Tensor:
    """RMSNorm (reference: incubate fused_rms_norm,
    phi/kernels/fusion/gpu/fused_rms_norm*). XLA fuses this chain."""
    x = ensure_tensor(x)
    tensors = [x]
    if weight is not None:
        tensors.append(ensure_tensor(weight))

    def _f(a, *w):
        # (an einsum mean-square was A/B'd here like the flash delta fix
        # and measured neutral-to-slower — XLA already fuses this chain)
        ms = jnp.mean(jnp.square(a.astype(jnp.float32)), axis=-1, keepdims=True)
        out = (a.astype(jnp.float32) * jax.lax.rsqrt(ms + epsilon)).astype(a.dtype)
        if w:
            out = out * w[0]
        return out

    return apply_op("rms_norm", _f, *tensors)


def _bn_train_fwd(a, w, b, axes, epsilon):
    if a.dtype in (jnp.bfloat16, jnp.float16):
        # single-pass stats in fp32: both channel reductions share one
        # read of ``a`` (half the stat-pass HBM traffic of mean-then-var
        # on a bandwidth-bound conv step), taken about each channel's
        # first element so that E[d^2]-E[d]^2 cancels by the pivot's few
        # std, not by mean/std. Pinned at mean/std 10 and 100 by tests/
        # test_nn.py::test_batch_norm_bf16_single_pass_stats_tolerance.
        af = a.astype(jnp.float32)
        pivot = jax.lax.stop_gradient(af[tuple(
            slice(0, 1) if i in axes else slice(None) for i in range(a.ndim))])
        d = af - pivot
        md = jnp.mean(d, axis=axes, keepdims=True)
        v = jnp.maximum(jnp.mean(d * d, axis=axes, keepdims=True) - md * md, 0.0)
        m = pivot + md
    else:
        # fp32/fp64: two-pass mean/var in the input dtype — E[x^2]-E[x]^2
        # cancels catastrophically for large-mean fp32 inputs
        af = a
        m = jnp.mean(af, axis=axes, keepdims=True)
        v = jnp.var(af, axis=axes, keepdims=True)
    r = jax.lax.rsqrt(v + epsilon)
    cdt = af.dtype
    g = r if w is None else r * w.astype(cdt)
    shift = -m * g if b is None else b.astype(cdt) - m * g
    y = (af * g + shift).astype(a.dtype)
    return y, (a, m, r, w, b)


def _bn_train_bwd(axes, epsilon, res, dy):
    # Standard fused BN backward (dx in one elementwise pass + two
    # reductions that share one read of (dy, x)). Residuals are (x, m, r)
    # — x-hat is recomputed here rather than materialized in the forward,
    # which saves a full activation-tensor round trip to HBM; on a
    # bandwidth-bound ResNet step that is the difference between the
    # autodiff BN and this rule.
    a, m, r, w, b = res
    cdt = m.dtype  # fp32 for half inputs, the input dtype otherwise
    af = a.astype(cdt)
    dyf = dy.astype(cdt)
    xhat = (af - m) * r
    s1 = jnp.mean(dyf, axis=axes, keepdims=True)
    s2 = jnp.mean(dyf * xhat, axis=axes, keepdims=True)
    g = r if w is None else r * w.astype(cdt)
    dx = (g * (dyf - s1 - xhat * s2)).astype(a.dtype)
    n = 1
    for i in axes:
        n *= a.shape[i]
    dw = None if w is None else (s2 * n).astype(w.dtype)
    db = None if b is None else (s1 * n).astype(b.dtype)
    return dx, dw, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_train(a, w, b, axes, epsilon):
    return _bn_train_fwd(a, w, b, axes, epsilon)[0]


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


def batch_norm(x, running_mean, running_var, weight=None, bias=None, training=False,
               momentum=0.9, epsilon=1e-05, data_format="NCHW", use_global_stats=None, name=None) -> Tensor:
    x = ensure_tensor(x)
    rm, rv = ensure_tensor(running_mean), ensure_tensor(running_var)
    ch_axis = 1 if data_format.startswith("NC") and x.ndim > 1 else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = [1] * x.ndim
    bshape[ch_axis] = x._data.shape[ch_axis] if x.ndim > 1 else x._data.shape[0]

    use_batch_stats = training and not use_global_stats

    tensors = [x]
    has_w, has_b = weight is not None, bias is not None
    if has_w:
        tensors.append(ensure_tensor(weight))
    if has_b:
        tensors.append(ensure_tensor(bias))

    if use_batch_stats:
        # Update running stats host-side (buffer mutation, like reference).
        batch_mean = jnp.mean(x._data, axis=axes)
        batch_var = jnp.var(x._data, axis=axes)
        rm._data = momentum * rm._data + (1 - momentum) * batch_mean.astype(rm._data.dtype)
        rv._data = momentum * rv._data + (1 - momentum) * batch_var.astype(rv._data.dtype)

        import os as _os
        _custom = _os.environ.get("PADDLE_TPU_BN_CUSTOM_VJP", "0") == "1"

        def _f(a, *wb):
            i = 0
            w_v = wb[i].reshape(bshape) if has_w else None
            if has_w:
                i += 1
            b_v = wb[i].reshape(bshape) if has_b else None
            if _custom:
                return _bn_train(a, w_v, b_v, axes, float(epsilon))
            y, _ = _bn_train_fwd(a, w_v, b_v, axes, float(epsilon))
            return y

        return apply_op("batch_norm", _f, *tensors)

    mconst = rm._data.reshape(bshape)
    vconst = rv._data.reshape(bshape)

    def _f2(a, *wb):
        out = (a - mconst) * jax.lax.rsqrt(vconst + epsilon)
        i = 0
        if has_w:
            out = out * wb[i].reshape(bshape)
            i += 1
        if has_b:
            out = out + wb[i].reshape(bshape)
        return out

    return apply_op("batch_norm", _f2, *tensors)


def fused_conv_bn(x, conv_weight, running_mean, running_var, weight, bias,
                  training=False, momentum=0.9, epsilon=1e-05,
                  use_global_stats=None, relu=False, name=None) -> Tensor:
    """Conv2D+BatchNorm(+ReLU) through the Pallas fused kernels
    (pallas_kernels/fused_conv.py). NHWC only; the conv must be a dense
    stride-1 3x3(pad 1) or 1x1(pad 0) with no bias — callers (the
    BatchNorm dispatch hook in layers_conv_norm.py) qualify shapes
    first. Semantics match batch_norm applied to conv2d's output,
    including the host-side running-stat update in training mode."""
    from ..pallas_kernels import fused_conv as fc

    x, wconv = ensure_tensor(x), ensure_tensor(conv_weight)
    rm, rv = ensure_tensor(running_mean), ensure_tensor(running_var)
    g, b = ensure_tensor(weight), ensure_tensor(bias)
    if x.ndim != 4 or wconv._data.shape[2] not in (1, 3):
        raise ValueError("fused_conv_bn: NHWC 4-D input with a 3x3 or 1x1 "
                         f"OIHW weight required, got x.ndim={x.ndim} "
                         f"w={tuple(wconv._data.shape)}")

    if training and not use_global_stats:
        eps = float(epsilon)
        pending = getattr(x, "_fused_bn_pending", None)
        if pending is not None:
            # CHAIN fusion: the input is itself a fused conv+BN(+ReLU)
            # output — consume the upstream conv's RAW output and run its
            # BN normalize(+ReLU) as the kernel's VMEM prologue. The
            # normalized tensor the model passed in is then dead code
            # under jit (nothing else reads it), so it never hits HBM.
            co_p, m_p, v_p, gp, bp, eps_p, relu_in = pending

            def _f(cp, mp, vp, gpp, bpp, wc, gg, bb):
                co, bm, bv = fc.conv_stats_pre(cp, mp, vp, gpp, bpp, wc,
                                               relu_in, eps_p)
                return fc.bn_apply(co, bm, bv, gg, bb, eps), co, bm, bv

            y, co_t, bm, bv = apply_op("fused_conv_bn_train", _f, co_p, m_p,
                                       v_p, gp, bp, wconv, g, b, nouts=4)
        else:
            def _f(a, wc, gg, bb):
                co, bm, bv = fc.conv_stats(a, wc)
                return fc.bn_apply(co, bm, bv, gg, bb, eps), co, bm, bv

            y, co_t, bm, bv = apply_op("fused_conv_bn_train", _f, x, wconv,
                                       g, b, nouts=4)
        rm._data = momentum * rm._data + (1 - momentum) * bm._data.astype(rm._data.dtype)
        rv._data = momentum * rv._data + (1 - momentum) * bv._data.astype(rv._data.dtype)
        # offer THIS unit's raw output + stats to the next qualifying conv
        y._fused_bn_pending = (co_t, bm, bv, g, b, eps, False)
        return y

    mconst = rm._data.astype(jnp.float32)
    vconst = rv._data.astype(jnp.float32)

    def _f2(a, wc, gg, bb, _relu=relu):
        scale = gg.astype(jnp.float32) * jax.lax.rsqrt(vconst + epsilon)
        shift = bb.astype(jnp.float32) - mconst * scale
        return fc.fused_conv_bn_eval(a, wc, scale, shift, _relu)

    out = apply_op("fused_conv_bn_eval", _f2, x, wconv, g, b)
    if not relu:
        # let a following F.relu re-dispatch with the relu INSIDE the
        # epilogue (the relu-less call becomes dead code under jit)
        out._fused_relu_rerun = lambda: apply_op(
            "fused_conv_bn_eval",
            lambda a, wc, gg, bb: _f2(a, wc, gg, bb, True),
            x, wconv, g, b)
    return out


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-05, data_format="NCHW", name=None) -> Tensor:
    x = ensure_tensor(x)
    tensors = [x]
    has_w, has_b = weight is not None, bias is not None
    if has_w:
        tensors.append(ensure_tensor(weight))
    if has_b:
        tensors.append(ensure_tensor(bias))

    def _f(a, *wb):
        if data_format != "NCHW":
            a = jnp.moveaxis(a, -1, 1)
        n, c = a.shape[:2]
        spatial = a.shape[2:]
        g = a.reshape(n, num_groups, c // num_groups, *spatial)
        axes = tuple(range(2, g.ndim))
        m = jnp.mean(g, axis=axes, keepdims=True)
        v = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - m) * jax.lax.rsqrt(v + epsilon)).reshape(n, c, *spatial)
        bshape = (1, c) + (1,) * len(spatial)
        i = 0
        if has_w:
            out = out * wb[i].reshape(bshape)
            i += 1
        if has_b:
            out = out + wb[i].reshape(bshape)
        if data_format != "NCHW":
            out = jnp.moveaxis(out, 1, -1)
        return out

    return apply_op("group_norm", _f, *tensors)


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-05, data_format="NCHW", name=None) -> Tensor:
    x = ensure_tensor(x)
    tensors = [x]
    has_w, has_b = weight is not None, bias is not None
    if has_w:
        tensors.append(ensure_tensor(weight))
    if has_b:
        tensors.append(ensure_tensor(bias))

    if not use_input_stats and (running_mean is None or running_var is None):
        raise ValueError(
            "instance_norm(use_input_stats=False) needs running_mean and "
            "running_var")
    rm = ensure_tensor(running_mean) if (not use_input_stats
                                         and running_mean is not None) else None
    rv = ensure_tensor(running_var) if (not use_input_stats
                                        and running_var is not None) else None
    if rm is not None:
        tensors += [rm, rv]

    def _f(a, *rest):
        c = a.shape[1]
        bshape = (1, c) + (1,) * (a.ndim - 2)
        i = 0
        wb = rest[:has_w + has_b]
        i_stats = has_w + has_b
        if rm is not None:
            # reference use_input_stats=False: normalize by the provided
            # running statistics instead of per-instance moments
            m = rest[i_stats].reshape(bshape)
            v = rest[i_stats + 1].reshape(bshape)
        else:
            axes = tuple(range(2, a.ndim))
            m = jnp.mean(a, axis=axes, keepdims=True)
            v = jnp.var(a, axis=axes, keepdims=True)
        out = (a - m) * jax.lax.rsqrt(v + eps)
        if has_w:
            out = out * wb[i].reshape(bshape)
            i += 1
        if has_b:
            out = out + wb[i].reshape(bshape)
        return out

    return apply_op("instance_norm", _f, *tensors)


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None) -> Tensor:
    x = ensure_tensor(x)
    return apply_op(
        "normalize",
        lambda a: a / jnp.maximum(jnp.linalg.norm(a, ord=p, axis=axis, keepdims=True), epsilon),
        x,
    )


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None) -> Tensor:
    x = ensure_tensor(x)

    def _f(a):
        sq = jnp.square(a)
        half = size // 2
        summed = jax.lax.reduce_window(
            sq, 0.0, jax.lax.add,
            (1, size, 1, 1), (1, 1, 1, 1),
            [(0, 0), (half, size - 1 - half), (0, 0), (0, 0)],
        )
        return a / jnp.power(k + alpha * summed / size, beta)

    return apply_op("local_response_norm", _f, x)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------


def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean",
                  soft_label=False, axis=-1, use_softmax=True, label_smoothing=0.0, name=None) -> Tensor:
    input, label = ensure_tensor(input), ensure_tensor(label)
    tensors = [input, label]
    has_w = weight is not None
    if has_w:
        tensors.append(ensure_tensor(weight))

    def _f(logits, lab, *w):
        if use_softmax:
            logp = jax.nn.log_softmax(logits, axis=axis)
        else:
            logp = jnp.log(jnp.maximum(logits, 1e-30))
        n_class = logits.shape[axis]
        if soft_label:
            target = lab
            loss = -jnp.sum(target * logp, axis=axis)
        else:
            lab_i = lab.astype(jnp.int32)
            if lab_i.ndim == logp.ndim and lab_i.shape[axis] == 1:
                lab_i = jnp.squeeze(lab_i, axis)
            # one-hot contraction, NOT take_along_axis: on TPU the one-hot
            # product lowers onto the MXU and is ~4% faster end-to-end at
            # LM vocab sizes (measured on the 134M bench; gathers lower to
            # slow dynamic-slice sequences)
            onehot = jax.nn.one_hot(lab_i, n_class, dtype=logp.dtype, axis=axis)
            if label_smoothing > 0.0:
                onehot = onehot * (1 - label_smoothing) + label_smoothing / n_class
            loss = -jnp.sum(onehot * logp, axis=axis)
            mask = (lab_i != ignore_index).astype(loss.dtype)
            loss = loss * mask
            if w:
                wsel = jnp.take(w[0], jnp.clip(lab_i, 0, n_class - 1), axis=0)
                loss = loss * wsel
                if reduction == "mean":
                    denom = jnp.sum(wsel * mask)
                    return jnp.sum(loss) / jnp.maximum(denom, 1e-12)
            if reduction == "mean":
                denom = jnp.sum(mask)
                return jnp.sum(loss) / jnp.maximum(denom, 1.0)
        return _reduce_loss(loss, reduction)

    return apply_op("cross_entropy", _f, *tensors)


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100,
                               numeric_stable_mode=True, return_softmax=False, axis=-1):
    out = cross_entropy(logits, label, soft_label=soft_label, ignore_index=ignore_index,
                        reduction="none", axis=axis)
    if return_softmax:
        return out, softmax(logits, axis=axis)
    return out


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean", name=None) -> Tensor:
    input, label = ensure_tensor(input), ensure_tensor(label)
    w_t = ensure_tensor(weight) if weight is not None else None

    def _f(logp, lab, *wargs):
        lab_i = lab.astype(jnp.int32)
        loss = -jnp.take_along_axis(logp, lab_i[..., None] if logp.ndim > 1 else lab_i, axis=-1 if logp.ndim > 1 else 0)
        loss = loss.squeeze(-1) if logp.ndim > 1 else loss
        mask = (lab_i != ignore_index).astype(loss.dtype)
        if wargs:  # per-class weights (reference nll_loss weight arg)
            cls_w = wargs[0][jnp.clip(lab_i, 0, wargs[0].shape[0] - 1)]
            mask = mask * cls_w.astype(loss.dtype)
        loss = loss * mask
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(mask), 1e-12)
        return _reduce_loss(loss, reduction)

    args = (input, label) + ((w_t,) if w_t is not None else ())
    return apply_op("nll_loss", _f, *args)


def mse_loss(input, label, reduction="mean", name=None) -> Tensor:
    input, label = ensure_tensor(input), ensure_tensor(label)
    return apply_op("mse_loss", lambda a, b: _reduce_loss(jnp.square(a - b), reduction), input, label)


def l1_loss(input, label, reduction="mean", name=None) -> Tensor:
    input, label = ensure_tensor(input), ensure_tensor(label)
    return apply_op("l1_loss", lambda a, b: _reduce_loss(jnp.abs(a - b), reduction), input, label)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None) -> Tensor:
    input, label = ensure_tensor(input), ensure_tensor(label)

    def _f(a, b):
        d = jnp.abs(a - b)
        loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
        return _reduce_loss(loss, reduction)

    return apply_op("smooth_l1_loss", _f, input, label)


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None) -> Tensor:
    input, label = ensure_tensor(input), ensure_tensor(label)
    tensors = [input, label]
    if weight is not None:
        tensors.append(ensure_tensor(weight))

    def _f(p, y, *w):
        p = jnp.clip(p, 1e-12, 1.0 - 1e-12)
        loss = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
        if w:
            loss = loss * w[0]
        return _reduce_loss(loss, reduction)

    return apply_op("binary_cross_entropy", _f, *tensors)


def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean", pos_weight=None, name=None) -> Tensor:
    logit, label = ensure_tensor(logit), ensure_tensor(label)
    tensors = [logit, label]
    has_w = weight is not None
    has_pw = pos_weight is not None
    if has_w:
        tensors.append(ensure_tensor(weight))
    if has_pw:
        tensors.append(ensure_tensor(pos_weight))

    def _f(z, y, *rest):
        i = 0
        w = rest[i] if has_w else None
        if has_w:
            i += 1
        pw = rest[i] if has_pw else None
        if pw is not None:
            logw = (pw - 1) * y + 1
            loss = (1 - y) * z + logw * jnp.logaddexp(0.0, -z)
        else:
            loss = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        if w is not None:
            loss = loss * w
        return _reduce_loss(loss, reduction)

    return apply_op("bce_with_logits", _f, *tensors)


def kl_div(input, label, reduction="mean", log_target=False, name=None) -> Tensor:
    input, label = ensure_tensor(input), ensure_tensor(label)

    def _f(logq, p):
        if log_target:
            loss = jnp.exp(p) * (p - logq)
        else:
            loss = p * (jnp.log(jnp.maximum(p, 1e-30)) - logq)
        if reduction == "batchmean":
            return jnp.sum(loss) / logq.shape[0]
        return _reduce_loss(loss, reduction)

    return apply_op("kl_div", _f, input, label)


def cosine_similarity(x1, x2, axis=1, eps=1e-8) -> Tensor:
    x1, x2 = ensure_tensor(x1), ensure_tensor(x2)

    def _f(a, b):
        num = jnp.sum(a * b, axis=axis)
        den = jnp.linalg.norm(a, axis=axis) * jnp.linalg.norm(b, axis=axis)
        return num / jnp.maximum(den, eps)

    return apply_op("cosine_similarity", _f, x1, x2)


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean", name=None) -> Tensor:
    input, other, label = ensure_tensor(input), ensure_tensor(other), ensure_tensor(label)

    def _f(a, b, y):
        return _reduce_loss(jnp.maximum(0.0, -y * (a - b) + margin), reduction)

    return apply_op("margin_ranking_loss", _f, input, other, label)


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None) -> Tensor:
    input, label = ensure_tensor(input), ensure_tensor(label)

    def _f(a, y):
        loss = jnp.where(y == 1, a, jnp.maximum(0.0, margin - a))
        return _reduce_loss(loss, reduction)

    return apply_op("hinge_embedding_loss", _f, input, label)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None) -> Tensor:
    """SDPA with [batch, seq, heads, head_dim] layout (reference:
    python/paddle/nn/functional/flash_attention.py:248
    scaled_dot_product_attention; CUDA flash_attn kernel
    phi/kernels/gpu/flash_attn_kernel.cu). On TPU, XLA fuses this; the
    Pallas flash kernel (paddle_tpu.pallas_kernels.flash_attention) is used
    for long sequences via nn.functional.flash_attention."""
    q, k, v = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    tensors = [q, k, v]
    has_mask = attn_mask is not None
    if has_mask:
        tensors.append(ensure_tensor(attn_mask))

    def _f(qq, kk, vv, *m):
        scale = 1.0 / pymath.sqrt(qq.shape[-1])
        # [b, s, h, d] -> [b, h, s, d]
        qt = jnp.swapaxes(qq, 1, 2)
        kt = jnp.swapaxes(kk, 1, 2)
        vt = jnp.swapaxes(vv, 1, 2)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
        if is_causal:
            sq, sk = scores.shape[-2], scores.shape[-1]
            causal = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
            scores = jnp.where(causal, scores, jnp.asarray(-1e9, scores.dtype))
        if m:
            mask = m[0]
            if mask.dtype == jnp.bool_:
                scores = jnp.where(mask, scores, jnp.asarray(-1e9, scores.dtype))
            else:
                scores = scores + mask
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(vt.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
        return jnp.swapaxes(out, 1, 2)

    out = apply_op("sdpa", _f, *tensors)
    if dropout_p > 0.0 and training:
        out = dropout(out, p=dropout_p, training=training)
    return out


def repeat_kv(x, rep: int) -> Tensor:
    """GQA head expansion: [b, s, kv_heads, d] -> [b, s, kv_heads*rep, d]
    (reference PaddleNLP repeat_kv; each kv head serves ``rep`` query
    heads)."""
    return apply_op("repeat_kv", lambda a: jnp.repeat(a, rep, axis=2),
                    ensure_tensor(x))


def grouped_query_sdpa(query, key, value, attn_mask=None, name=None) -> Tensor:
    """SDPA where key/value carry kv_heads <= num_heads (GQA): each kv
    head is contracted against its whole query-head group via a grouped
    einsum, so the repeat_kv-expanded [b, s, num_heads, d] K/V never
    materializes in HBM (the XLA decode fallback of the flash-decode
    path; per query head the math is exactly
    ``scaled_dot_product_attention(q, repeat_kv(k), repeat_kv(v))``).

    query: [b, s, num_heads, d]; key/value: [b, t, kv_heads, d] with
    num_heads a multiple of kv_heads (query head j reads kv head
    j // (num_heads // kv_heads)); attn_mask broadcasts like SDPA's
    ([b, 1, s, t] or per-head [b, num_heads, s, t]; bool or additive).
    """
    q, k, v = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    tensors = [q, k, v]
    has_mask = attn_mask is not None
    if has_mask:
        tensors.append(ensure_tensor(attn_mask))

    def _f(qq, kk, vv, *m):
        b, s, H, d = qq.shape
        KV = kk.shape[2]
        if H % KV:
            raise ValueError(f"num_heads ({H}) not a multiple of "
                             f"kv_heads ({KV})")
        g = H // KV
        scale = 1.0 / pymath.sqrt(d)
        qt = jnp.swapaxes(qq, 1, 2).reshape(b, KV, g, s, d)
        kt = jnp.swapaxes(kk, 1, 2)  # [b, KV, t, d]
        vt = jnp.swapaxes(vv, 1, 2)
        scores = jnp.einsum("bkgqd,bktd->bkgqt", qt, kt) * scale
        if m:
            mask = m[0]
            t = kt.shape[2]
            if mask.ndim == 4 and mask.shape[1] == H:  # per-head mask
                mask = mask.reshape(b, KV, g, *mask.shape[2:])
            else:  # [b, 1, s, t] (or broadcastable) — shared over heads
                mask = mask[:, :, None]
            if mask.dtype == jnp.bool_:
                scores = jnp.where(mask, scores,
                                   jnp.asarray(-1e9, scores.dtype))
            else:
                scores = scores + mask
        probs = jax.nn.softmax(scores.astype(jnp.float32),
                               axis=-1).astype(vt.dtype)
        out = jnp.einsum("bkgqt,bktd->bkgqd", probs, vt)
        return jnp.swapaxes(out.reshape(b, H, s, d), 1, 2)

    return apply_op("gqa_sdpa", _f, *tensors)


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None) -> Tensor:
    x = ensure_tensor(x)
    k = _pair(kernel_sizes)
    s = _pair(strides)
    p = _pair(paddings)
    d = _pair(dilations)

    def _f(a):
        n, c, h, w = a.shape
        a = jnp.pad(a, [(0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])])
        oh = (a.shape[2] - (d[0] * (k[0] - 1) + 1)) // s[0] + 1
        ow = (a.shape[3] - (d[1] * (k[1] - 1) + 1)) // s[1] + 1
        patches = []
        for i in range(k[0]):
            for j in range(k[1]):
                patches.append(a[:, :, i * d[0]: i * d[0] + oh * s[0]: s[0], j * d[1]: j * d[1] + ow * s[1]: s[1]])
        out = jnp.stack(patches, axis=2)  # n, c, k0*k1, oh, ow
        return out.reshape(n, c * k[0] * k[1], oh * ow)

    return apply_op("unfold", _f, x)


def _interp_ratio(in_len: int, out_len: int, align_corners: bool) -> float:
    """Reference ratio (interpolate_kernel.cc): (in-1)/(out-1) with corner
    alignment, in/out otherwise; 0 for single-pixel outputs."""
    if out_len <= 1:
        return 0.0
    if align_corners:
        return (in_len - 1) / (out_len - 1)
    return in_len / out_len


def _nearest_idx(in_len, out_len, align_corners):
    k = jnp.arange(out_len, dtype=jnp.float32)
    r = _interp_ratio(in_len, out_len, align_corners)
    # half-UP rounding (reference lround), not round-half-to-even
    idx = jnp.floor(r * k + 0.5) if align_corners else jnp.floor(r * k)
    return jnp.clip(idx.astype(jnp.int32), 0, in_len - 1)


def _linear_lo_hi_w(in_len, out_len, align_corners, align_mode):
    k = jnp.arange(out_len, dtype=jnp.float32)
    r = _interp_ratio(in_len, out_len, align_corners)
    if align_mode == 0 and not align_corners:
        src = jnp.maximum(r * (k + 0.5) - 0.5, 0.0)  # half-pixel, clamped
    else:
        src = r * k
    lo = jnp.clip(jnp.floor(src).astype(jnp.int32), 0, in_len - 1)
    hi = jnp.minimum(lo + 1, in_len - 1)
    w = (src - lo.astype(jnp.float32)).astype(jnp.float32)
    return lo, hi, w


def interpolate(x, size=None, scale_factor=None, mode="nearest", align_corners=False,
                align_mode=0, data_format="NCHW", name=None) -> Tensor:
    """Parity: phi/kernels/cpu/interpolate_kernel.cc — EXACT index math
    (nearest floor/lround split, bilinear align_mode/align_corners source
    positions, area as adaptive block means); jax.image.resize only for
    bicubic."""
    x = ensure_tensor(x)

    def _f(a):
        if data_format == "NCHW":
            n, c, h, w = a.shape
        else:
            n, h, w, c = a.shape
            a = jnp.transpose(a, (0, 3, 1, 2))
        if size is not None:
            oh, ow = _pair(size)
        else:
            sf = scale_factor if isinstance(scale_factor, (list, tuple)) else (scale_factor, scale_factor)
            oh, ow = int(h * sf[0]), int(w * sf[1])
        if mode == "nearest":
            iy = _nearest_idx(h, oh, align_corners)
            ix = _nearest_idx(w, ow, align_corners)
            out = a[:, :, iy[:, None], ix[None, :]]
        elif mode == "bilinear":
            ylo, yhi, wy = _linear_lo_hi_w(h, oh, align_corners, align_mode)
            xlo, xhi, wx = _linear_lo_hi_w(w, ow, align_corners, align_mode)
            cal = jnp.float64 if a.dtype == jnp.float64 else jnp.float32
            af = a.astype(cal)
            wy = wy.astype(cal)
            wx = wx.astype(cal)
            top = af[:, :, ylo, :] * (1 - wy)[None, None, :, None] \
                + af[:, :, yhi, :] * wy[None, None, :, None]
            out = (top[:, :, :, xlo] * (1 - wx)[None, None, None, :]
                   + top[:, :, :, xhi] * wx[None, None, None, :]).astype(a.dtype)
        elif mode == "area":
            # reference/torch area = adaptive average pooling block means,
            # NOT an antialiased linear resize
            if h % oh == 0 and w % ow == 0:
                out = a.reshape(a.shape[0], a.shape[1], oh, h // oh,
                                ow, w // ow).mean(axis=(3, 5)).astype(a.dtype)
            else:
                h0 = [int(pymath.floor(i * h / oh)) for i in range(oh)]
                h1 = [int(pymath.ceil((i + 1) * h / oh)) for i in range(oh)]
                w0 = [int(pymath.floor(j * w / ow)) for j in range(ow)]
                w1 = [int(pymath.ceil((j + 1) * w / ow)) for j in range(ow)]
                rows = []
                for i in range(oh):
                    cols = [a[:, :, h0[i]:h1[i], w0[j]:w1[j]].mean(axis=(2, 3))
                            for j in range(ow)]
                    rows.append(jnp.stack(cols, axis=-1))
                out = jnp.stack(rows, axis=-2).astype(a.dtype)
        else:
            method = {"bicubic": "cubic"}.get(mode, mode)
            out = jax.image.resize(a, (a.shape[0], a.shape[1], oh, ow),
                                   method=method)
        if data_format != "NCHW":
            out = jnp.transpose(out, (0, 2, 3, 1))
        return out

    return apply_op("interpolate", _f, x)


upsample = interpolate


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None) -> Tensor:
    x = ensure_tensor(x)
    r = upscale_factor

    def _f(a):
        n, c, h, w = a.shape
        a = a.reshape(n, c // (r * r), r, r, h, w)
        a = jnp.transpose(a, (0, 1, 4, 2, 5, 3))
        return a.reshape(n, c // (r * r), h * r, w * r)

    return apply_op("pixel_shuffle", _f, x)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None) -> Tensor:
    label = ensure_tensor(label)
    prior = ensure_tensor(prior_dist) if prior_dist is not None else None

    def _f(y, *pd):
        if pd:  # reference: smooth toward the given prior distribution
            return (1 - epsilon) * y + epsilon * pd[0]
        k = y.shape[-1]
        return (1 - epsilon) * y + epsilon / k

    args = (label,) + ((prior,) if prior is not None else ())
    return apply_op("label_smooth", _f, *args)


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    from ..ops.manipulation import pad as _pad

    return _pad(x, pad, mode, value, data_format)


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW", name=None) -> Tensor:
    x = ensure_tensor(x)

    def _f(a):
        nt, c, h, w = a.shape
        n = nt // seg_num
        a = a.reshape(n, seg_num, c, h, w)
        fold = int(c * shift_ratio)
        out = jnp.zeros_like(a)
        out = out.at[:, :-1, :fold].set(a[:, 1:, :fold])
        out = out.at[:, 1:, fold:2 * fold].set(a[:, :-1, fold:2 * fold])
        out = out.at[:, :, 2 * fold:].set(a[:, :, 2 * fold:])
        return out.reshape(nt, c, h, w)

    return apply_op("temporal_shift", _f, x)


def linear_with_quant(*args, **kwargs):
    raise NotImplementedError("quantized linear lands with the quantization subsystem")


# extended functional surface (vision sampling, CTC, pooling variants, loss zoo)
from .functional_extra import *  # noqa: F401,F403,E402
