"""Fused conv2d + BatchNorm + ReLU as Pallas TPU kernels.

Reference analogue: the fused conv+BN+ReLU epilogue paths in
paddle/phi/kernels/fusion/ and the cuDNN-backed conv epilogues in
phi/kernels/gpu/conv_kernel.cu. Why a hand-written kernel at all:
the round-5 xprof byte audit (benchmarks/resnet_byte_audit.json,
COVERAGE.md) showed every op of the ResNet-50 train step HBM-bound at
~660-680 GB/s with the convs already at MXU peak — XLA's fusion floor
moves each conv output once for the write, once more for the BN stat
reduce, and twice for the normalize+relu. SURVEY §7.1 reserves Pallas
for exactly this case ("where XLA fusion is insufficient").

Kernel design (NHWC, stride-1 convs — ResNet's hot shapes):

- The conv is computed as tap matmuls over the FLATTENED spatial form
  ``x2 = x.reshape(N*H*W, C)``: for kernel tap (di, dj) the
  contribution to output row ``m`` is
  ``x2[m + (di-1)*W + (dj-1)] @ w[di, dj]`` — a plain [rows, C] x
  [C, K] MXU matmul per tap (1 tap for 1x1, 9 for 3x3) accumulated into
  an fp32 VMEM scratch. Rows whose tap would cross an image edge (top/
  bottom row, left/right column — which in the flat layout would read
  the previous/next row or image) are zero-masked from an iota over the
  flat index, so no padded copy of the activation ever exists.
- A grid block is a whole number of images (block = nb*H*W rows), so
  every non-masked tap read stays inside the block: halo exchange is
  unnecessary by construction.
- The epilogue runs on the fp32 accumulator BEFORE the tile leaves
  VMEM:
  * inference: ``y = relu(acc * scale + shift)`` with the BN stats
    folded into per-channel scale/shift — conv+BN+ReLU is one HBM
    write.
  * training: the kernel writes the conv output once PLUS per-block
    channel partials (sum, sum-of-squares, reduced from the fp32
    accumulator) — the BN statistics pass costs zero extra HBM reads.
    The normalize+scale+shift(+relu) stays in XLA, which fuses it to
    one read+write, and the custom VJP reuses the existing
    ``nn/functional.py`` ``_bn_train_bwd`` formulation so autograd and
    the ``batch_norm`` path compose.

Backward: conv gradients are the standard transposed convolutions —
XLA's codegen for those is already at MXU peak (byte audit), so the
custom VJPs derive them with ``jax.vjp`` over the reference
``lax.conv_general_dilated`` expression rather than re-deriving kernel
code that could only tie.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _compiler_kwargs, _dot_prec, _interpret

__all__ = ["fused_conv_bn_eval", "fused_conv_bn_train", "conv_stats",
           "conv_stats_pre", "bn_apply", "conv_qualifies"]


def conv_qualifies(kernel, stride, padding, dilation, groups) -> bool:
    """The shapes this kernel family covers: dense stride-1 NHWC 3x3
    (pad 1) and 1x1 (pad 0) convs — ResNet's FLOP bulk. Everything else
    falls back to the XLA path at the dispatch hook."""
    if groups != 1 or tuple(dilation) != (1, 1) or tuple(stride) != (1, 1):
        return False
    k, p = tuple(kernel), tuple(padding)
    return (k == (3, 3) and p == (1, 1)) or (k == (1, 1) and p == (0, 0))


def _pick_images_per_block(n: int, hw: int, c: int, k: int, itemsize: int) -> int:
    """Images per grid block: block rows = nb*H*W, so a valid tap read
    never leaves its block. Aim for ~2-4k rows (MXU-efficient M) while
    keeping the x tile + fp32 accumulator + y tile within a few MB of
    VMEM; nb must divide N (the greedy step-down always terminates at 1)."""
    bytes_per_row = c * itemsize + k * (4 + itemsize)
    target = max(1, min(4096, (6 << 20) // max(1, bytes_per_row)) // hw)
    nb = max(1, min(n, target))
    while n % nb:
        nb -= 1
    return nb


def _taps(kh: int, kw: int, w: int):
    """(di, dj, flat-row offset) per kernel tap, pad = (k-1)//2."""
    return [(di, dj, (di - (kh - 1) // 2) * w + (dj - (kw - 1) // 2))
            for di in range(kh) for dj in range(kw)]


def _conv_acc(x_ref, w_ref, acc_ref, *, taps, hw: int, h: int, w: int,
              kh: int, kw: int, pre=None, xn_ref=None):
    """Accumulate the conv of one [BM, C] block (BM a multiple of hw)
    into the fp32 scratch ``acc_ref`` and return its value.

    ``pre``: optional (scale_ref, shift_ref, relu_in) prologue — the
    PREVIOUS BatchNorm's normalize(+ReLU) applied to the x tile in VMEM
    before the tap matmuls, so the normalized activation never exists
    in HBM (chain fusion: this kernel reads the upstream conv's RAW
    output). For 3x3 the normalized block is staged once in ``xn_ref``
    so every tap slices it."""
    prec = _dot_prec(x_ref.dtype)

    def _prologue(xs):
        ps_ref, pb_ref, relu_in = pre
        xf = xs.astype(jnp.float32) * ps_ref[:] + pb_ref[:]
        if relu_in:
            xf = jnp.maximum(xf, 0.0)
        return xf.astype(xs.dtype)

    if kh == kw == 1:
        x = x_ref[:]
        if pre is not None:
            x = _prologue(x)
        acc_ref[:] = jnp.dot(x, w_ref[0],
                             preferred_element_type=jnp.float32,
                             precision=prec)
        return acc_ref[:]

    bm = x_ref.shape[0]
    if pre is not None:
        xn_ref[:] = _prologue(x_ref[:])
        x_ref = xn_ref
    acc_ref[:] = jnp.zeros_like(acc_ref)
    for t, (di, dj, off) in enumerate(taps):
        src = max(0, off)       # first x row this tap can read
        dst = max(0, -off)      # output row it contributes to
        ln = bm - abs(off)
        # validity of destination rows dst..dst+ln against the IMAGE
        # edges (periodic in the flat index, so block position is moot)
        d = dst + jax.lax.broadcasted_iota(jnp.int32, (ln, 1), 0)
        i = (d % hw) // w
        j = d % w
        valid = None
        if di == 0:
            valid = i >= 1
        elif di == kh - 1:
            valid = i <= h - 2
        if dj == 0:
            cnd = j >= 1
            valid = cnd if valid is None else (valid & cnd)
        elif dj == kw - 1:
            cnd = j <= w - 2
            valid = cnd if valid is None else (valid & cnd)
        xs = x_ref[src:src + ln]
        if valid is not None:
            xs = jnp.where(valid, xs, jnp.zeros_like(xs))
        acc_ref[dst:dst + ln] += jnp.dot(xs, w_ref[t],
                                         preferred_element_type=jnp.float32,
                                         precision=prec)
    return acc_ref[:]


def _epilogue_kernel(x_ref, w_ref, scale_ref, shift_ref, o_ref, acc_ref, *,
                     taps, hw, h, w, kh, kw, relu):
    acc = _conv_acc(x_ref, w_ref, acc_ref, taps=taps, hw=hw, h=h, w=w,
                    kh=kh, kw=kw)
    y = acc * scale_ref[:] + shift_ref[:]  # [1, K] blocks broadcast over rows
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[:] = y.astype(o_ref.dtype)


def _stats_kernel(x_ref, w_ref, o_ref, s1_ref, s2_ref, acc_ref, *,
                  taps, hw, h, w, kh, kw, pre=None, xn_ref=None):
    acc = _conv_acc(x_ref, w_ref, acc_ref, taps=taps, hw=hw, h=h, w=w,
                    kh=kh, kw=kw, pre=pre, xn_ref=xn_ref)
    o_ref[:] = acc.astype(o_ref.dtype)
    # channel partials straight off the fp32 accumulator: the BN stat
    # pass never re-reads the conv output from HBM (and is MORE accurate
    # than reducing the rounded bf16 output — cf. the single-pass-stats
    # note at _bn_train_fwd in nn/functional.py)
    s1_ref[0] = jnp.sum(acc, axis=0, keepdims=True)
    s2_ref[0] = jnp.sum(acc * acc, axis=0, keepdims=True)


def _prep(x, w):
    n, h, w_sp, c = x.shape
    k, c_w, kh, kw = w.shape
    if c_w != c:
        raise ValueError(f"fused conv: weight in_channels {c_w} != input {c}")
    hw = h * w_sp
    # OIHW -> [taps, C, K]: tap-major planes for the kernel's matmul loop
    w_t = jnp.transpose(w, (2, 3, 1, 0)).reshape(kh * kw, c, k)
    x2 = x.reshape(n * hw, c)  # contiguous: free reshape, no HBM copy
    bm = _pick_images_per_block(n, hw, c, k, x.dtype.itemsize) * hw
    return x2, w_t, (n, h, w_sp, c, k, hw, kh, kw, bm)


def _pallas_epilogue(x, w, scale, shift, relu):
    x2, w_t, (n, h, w_sp, c, k, hw, kh, kw, bm) = _prep(x, w)
    m = x2.shape[0]
    kern = functools.partial(_epilogue_kernel, taps=_taps(kh, kw, w_sp),
                             hw=hw, h=h, w=w_sp, kh=kh, kw=kw, relu=relu)
    y = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((m, k), x.dtype),
        # pt-analysis: disable=pallas-block-divide -- bm = nb * hw where
        # _pick_images_per_block steps nb down until it divides N, so bm
        # always divides m = N * hw (invariant lives in _prep)
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, c), lambda i: (i, 0)),
            pl.BlockSpec((kh * kw, c, k), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, k), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bm, k), jnp.float32)],
        interpret=_interpret(),
        **_compiler_kwargs(),
    )(x2, w_t, scale.reshape(1, k).astype(jnp.float32),
      shift.reshape(1, k).astype(jnp.float32))
    return y.reshape(n, h, w_sp, k)


def _pallas_stats(x, w, pre=None):
    """Conv + channel-partial sums; ``pre``: optional
    (scale[C], shift[C], relu_in) prologue normalize of x in VMEM."""
    x2, w_t, (n, h, w_sp, c, k, hw, kh, kw, bm) = _prep(x, w)
    m = x2.shape[0]
    # pt-analysis: disable=pallas-block-divide -- bm = nb * hw where
    # _pick_images_per_block steps nb down until it divides N, so bm
    # always divides m = N * hw (invariant lives in _prep)
    g = m // bm
    in_specs = [
        pl.BlockSpec((bm, c), lambda i: (i, 0)),
        pl.BlockSpec((kh * kw, c, k), lambda i: (0, 0, 0)),
    ]
    args = [x2, w_t]
    scratch = [pltpu.VMEM((bm, k), jnp.float32)]
    if pre is None:
        kern = functools.partial(_stats_kernel, taps=_taps(kh, kw, w_sp),
                                 hw=hw, h=h, w=w_sp, kh=kh, kw=kw)
    else:
        ps, pb, relu_in = pre
        in_specs += [pl.BlockSpec((1, c), lambda i: (0, 0)),
                     pl.BlockSpec((1, c), lambda i: (0, 0))]
        args += [ps.reshape(1, c).astype(jnp.float32),
                 pb.reshape(1, c).astype(jnp.float32)]
        if kh != 1:
            scratch.append(pltpu.VMEM((bm, c), x.dtype))

        def kern(x_ref, w_ref, ps_ref, pb_ref, o_ref, s1_ref, s2_ref,
                 acc_ref, *xn):
            _stats_kernel(x_ref, w_ref, o_ref, s1_ref, s2_ref, acc_ref,
                          taps=_taps(kh, kw, w_sp), hw=hw, h=h, w=w_sp,
                          kh=kh, kw=kw, pre=(ps_ref, pb_ref, relu_in),
                          xn_ref=xn[0] if xn else None)

    out, s1, s2 = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((m, k), x.dtype),
                   jax.ShapeDtypeStruct((g, 1, k), jnp.float32),
                   jax.ShapeDtypeStruct((g, 1, k), jnp.float32)),
        grid=(g,),
        in_specs=in_specs,
        # partials as [g, 1, K] so each block's last two dims are the
        # array's own (a [1, K] row out of [g, K] is not a TPU tile)
        out_specs=(pl.BlockSpec((bm, k), lambda i: (i, 0)),
                   pl.BlockSpec((1, 1, k), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, 1, k), lambda i: (i, 0, 0))),
        scratch_shapes=scratch,
        interpret=_interpret(),
        **_compiler_kwargs(),
    )(*args)
    return (out.reshape(n, h, w_sp, k), jnp.sum(s1, (0, 1)),
            jnp.sum(s2, (0, 1)))


def _xla_conv(x, w):
    pad = ((1, 1), (1, 1)) if w.shape[2] == 3 else ((0, 0), (0, 0))
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), pad, dimension_numbers=("NHWC", "OIHW", "NHWC"))


# ---------------------------------------------------------------------------
# inference: conv + folded BN scale/shift (+ReLU) in one kernel
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_conv_bn_eval(x, w, scale, shift, relu=False):
    """``relu(conv2d(x, w) * scale + shift)`` with the epilogue applied
    in VMEM. x: [N, H, W, C]; w: OIHW (3x3 pad-1 or 1x1 pad-0, stride
    1); scale/shift: [K] (BN running stats pre-folded by the caller).
    Conv+BN+ReLU costs one HBM read of x and one write of y."""
    return _pallas_epilogue(x, w, scale, shift, relu)


def _eval_ref(x, w, scale, shift, relu):
    y = _xla_conv(x, w) * scale + shift
    return jnp.maximum(y, 0.0).astype(x.dtype) if relu else y.astype(x.dtype)


def _eval_fwd(x, w, scale, shift, relu):
    return fused_conv_bn_eval(x, w, scale, shift, relu), (x, w, scale, shift)


def _eval_bwd(relu, res, dy):
    # rare path (grads through frozen-stats BN): the XLA composition's
    # vjp IS the fused forward's derivative
    x, w, scale, shift = res
    _, vjp = jax.vjp(lambda *a: _eval_ref(*a, relu), x, w, scale, shift)
    return vjp(dy)


fused_conv_bn_eval.defvjp(_eval_fwd, _eval_bwd)


# ---------------------------------------------------------------------------
# training: three composable custom-vjp pieces.
#
#   conv_stats(x, w)                 -> (conv_out, mean, var)
#   conv_stats_pre(co_p, m_p, v_p, gp, bp, w, relu_in, eps_p)
#                                    -> (conv_out, mean, var)
#       — same, but the input is the UPSTREAM conv's raw output and the
#       upstream BN's normalize(+ReLU) runs as a VMEM prologue, so the
#       normalized activation never touches HBM (chain fusion).
#   bn_apply(co, m, v, gamma, beta)  -> y
#       — the normalize the model actually consumes; its VJP is the
#       existing _bn_train_bwd formulation from nn/functional.py.
#
# Gradient contract: bn_apply's dco is the FULL batch-norm backward
# (it folds the stats' dependence on co), so bn_apply returns ZERO
# cotangents for m/v; the m/v outputs of conv_stats* carry gradients
# only for their OTHER consumer — the next unit's prologue — which
# conv_stats*'s vjp (jax.vjp over the XLA reference composition)
# handles exactly. No term is dropped, none is double-counted.
# ---------------------------------------------------------------------------


def _moments_ref(co):
    cof = co.astype(jnp.float32)
    m = jnp.mean(cof, axis=(0, 1, 2))
    v = jnp.maximum(jnp.mean(cof * cof, axis=(0, 1, 2)) - m * m, 0.0)
    return m, v


def _stats_from_partials(x, s1, s2):
    cnt = x.shape[0] * x.shape[1] * x.shape[2]
    m = s1 / cnt
    v = jnp.maximum(s2 / cnt - m * m, 0.0)  # single-pass stats, fp32 acc
    return m, v


@jax.custom_vjp
def conv_stats(x, w):
    """Pallas conv whose epilogue also emits the output's channel mean/
    var — the BN statistics pass costs zero extra HBM reads."""
    co, s1, s2 = _pallas_stats(x, w)
    m, v = _stats_from_partials(x, s1, s2)
    return co, m, v


def _conv_stats_ref(x, w):
    co = _xla_conv(x, w)
    return (co,) + _moments_ref(co)


def _conv_stats_fwd(x, w):
    return conv_stats(x, w), (x, w)


def _conv_stats_bwd(res, cts):
    x, w = res
    _, vjp = jax.vjp(_conv_stats_ref, x, w)
    return vjp(cts)


conv_stats.defvjp(_conv_stats_fwd, _conv_stats_bwd)


def _fold_bn(m, v, gamma, beta, eps):
    scale = gamma.astype(jnp.float32) * jax.lax.rsqrt(v.astype(jnp.float32) + eps)
    return scale, beta.astype(jnp.float32) - m.astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def conv_stats_pre(co_p, m_p, v_p, gp, bp, w, relu_in=True, eps_p=1e-5):
    """conv_stats over ``normalize(co_p; m_p, v_p, gp, bp)`` (+ReLU),
    with the normalize applied as a VMEM prologue: the upstream BN's
    output never materializes in HBM — this kernel reads the upstream
    conv's RAW output instead."""
    ps, pb = _fold_bn(m_p, v_p, gp, bp, eps_p)
    co, s1, s2 = _pallas_stats(co_p, w, pre=(ps, pb, relu_in))
    m, v = _stats_from_partials(co_p, s1, s2)
    return co, m, v


def _conv_stats_pre_ref(co_p, m_p, v_p, gp, bp, w, relu_in, eps_p):
    ps, pb = _fold_bn(m_p, v_p, gp, bp, eps_p)
    xn = co_p.astype(jnp.float32) * ps + pb
    if relu_in:
        xn = jnp.maximum(xn, 0.0)
    co = _xla_conv(xn.astype(co_p.dtype), w)
    return (co,) + _moments_ref(co)


def _conv_stats_pre_fwd(co_p, m_p, v_p, gp, bp, w, relu_in, eps_p):
    return (conv_stats_pre(co_p, m_p, v_p, gp, bp, w, relu_in, eps_p),
            (co_p, m_p, v_p, gp, bp, w))


def _conv_stats_pre_bwd(relu_in, eps_p, res, cts):
    _, vjp = jax.vjp(
        lambda *a: _conv_stats_pre_ref(*a, relu_in, eps_p), *res)
    return vjp(cts)


conv_stats_pre.defvjp(_conv_stats_pre_fwd, _conv_stats_pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def bn_apply(co, m, v, gamma, beta, epsilon=1e-5):
    """BN normalize of a conv output given its (already computed) batch
    stats. VJP = the existing _bn_train_bwd formulation (dco carries the
    full stats chain; m/v get zero cotangents — see the contract above)."""
    r = jax.lax.rsqrt(v.astype(jnp.float32) + epsilon)
    g = r * gamma.astype(jnp.float32)
    shift = beta.astype(jnp.float32) - m.astype(jnp.float32) * g
    return (co.astype(jnp.float32) * g + shift).astype(co.dtype)


def _bn_apply_fwd(co, m, v, gamma, beta, epsilon):
    y = bn_apply(co, m, v, gamma, beta, epsilon)
    r = jax.lax.rsqrt(v.astype(jnp.float32) + epsilon)
    return y, (co, m, r, gamma, beta)


def _bn_apply_bwd(epsilon, res, dy):
    co, m, r, gamma, beta = res
    from ..nn.functional import _bn_train_bwd  # lazy: avoids import cycle

    k = co.shape[-1]
    bshape = (1, 1, 1, k)
    dco, dgamma, dbeta = _bn_train_bwd(
        (0, 1, 2), epsilon,
        (co, m.astype(jnp.float32).reshape(bshape), r.reshape(bshape),
         gamma.reshape(bshape), beta.reshape(bshape)), dy)
    zeros = jnp.zeros_like(m)  # m and v share shape/dtype
    return (dco.astype(co.dtype), zeros, zeros,
            dgamma.reshape(k).astype(gamma.dtype),
            dbeta.reshape(k).astype(beta.dtype))


bn_apply.defvjp(_bn_apply_fwd, _bn_apply_bwd)


def fused_conv_bn_train(x, w, gamma, beta, epsilon=1e-5):
    """Convenience composition: (y, batch_mean, batch_var) for one
    unchained conv+BN unit (tests and the microbench use this)."""
    co, m, v = conv_stats(x, w)
    return bn_apply(co, m, v, gamma, beta, epsilon), m, v
