"""Generated dtype x grad sweep over the op schema registry.

Parity: the reference's op_test.py discipline — every YAML-registered op
gets check_output (per dtype, fp32 oracle + low-precision tolerances,
op_test.py:2139) and check_grad (finite differences, op_test.py:3129),
with white-list exceptions (test/white_list/op_accuracy_white_list.py).
Here the registry is paddle_tpu.ops.schemas.SCHEMAS and this module IS
the generated test: one output-sweep case and one grad case per schema.
"""

import zlib

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.schemas import (SCHEMAS, WHITE_LIST, FLOAT_SWEEP,
                                    registered_op_names)
from optest import check_grad, check_output_dtypes

_NAMES = registered_op_names()

# on-chip lane partitioning:
# - PADDLE_TPU_SWEEP_SHARD="i/N" keeps _NAMES[i::N] — the full sweep
#   split across N sequential pytest invocations (run_shards.py TPU
#   lane), so EVERY schema sees real-TPU numerics (round-5; reference
#   discipline: op_test.py:2925 check_output_with_place per device).
# - PADDLE_TPU_SWEEP_STRIDE=N keeps every Nth schema — the quick
#   sampled mode, kept for ad-hoc runs.
import os as _os

_SHARD = _os.environ.get("PADDLE_TPU_SWEEP_SHARD")
if _SHARD:
    _i, _n = (int(x) for x in _SHARD.split("/"))
    _NAMES = _NAMES[_i::_n]

_STRIDE = int(_os.environ.get("PADDLE_TPU_SWEEP_STRIDE", "1"))
if _STRIDE > 1:
    _NAMES = _NAMES[::_STRIDE]

# complex dtypes have NO TPU backend support (an eager complex op also
# wedges the session's subsequent dispatches) — platform skip, like the
# reference's per-place test gating (check_output_with_place). The CPU
# lane fully covers these schemas.
_COMPLEX_OPS = {
    "fft", "ifft", "rfft", "irfft", "hfft", "ihfft",
    "fft2", "ifft2", "rfft2", "irfft2",
    "fftn", "ifftn", "rfftn", "irfftn", "hfftn", "ihfftn",
    "as_complex", "as_real", "complex", "polar",
}
if _os.environ.get("PADDLE_TPU_TEST_PLATFORM") == "tpu":
    _NAMES = [n for n in _NAMES if n not in _COMPLEX_OPS]

# flash-attention kernels: fp32 operands fail Mosaic compilation on the
# real chip ("Bad lhs type" — the MXU path expects half-precision
# operands with f32 accumulation; production only ever feeds bf16). The
# CPU lane sweeps fp32 against the oracle in interpret mode; the TPU
# lane runs the bf16 case only — documented TPU-tolerance delta.
_TPU_HALF_ONLY = {"flash_attention", "flash_attn_varlen",
                  # same MXU contract as flash: bf16 operands / f32
                  # accumulate (production dtype); fp32 swept on CPU
                  "fused_conv_bn_train", "fused_conv_bn_eval",
                  "flash_decode_attention", "paged_flash_decode_attention",
                  # quantized lanes: int8/fp8 storage + bf16 compute is
                  # the production pairing; fp32 activations swept on CPU
                  "flash_decode_attention_int8",
                  "paged_flash_decode_attention_int8", "quant_matmul"}


def test_registry_is_populated():
    # the schema registry must stay substantial and feed OP_REGISTRY
    from paddle_tpu.ops.dispatch import OP_REGISTRY

    assert len(registered_op_names()) >= 150, len(registered_op_names())
    for n in _NAMES:
        assert n in OP_REGISTRY
        meta = OP_REGISTRY[n]
        assert "dtypes" in meta and "has_grad" in meta and "args" in meta


def test_white_list_is_bounded():
    # reference keeps the accuracy white list an explicit, bounded artifact
    assert len(WHITE_LIST) <= max(1, len(SCHEMAS) // 10), (
        f"white list {len(WHITE_LIST)} exceeds 10% of {len(SCHEMAS)} ops")
    for name in WHITE_LIST:
        assert name in SCHEMAS, f"white-list entry {name} has no schema"


@pytest.mark.parametrize("name", _NAMES)
def test_output_dtype_sweep(name):
    s = SCHEMAS[name]
    wl = WHITE_LIST.get(name, {})
    if "sweep" in wl:
        pytest.skip(wl["sweep"])
    rng = np.random.RandomState(zlib.crc32(name.encode()) % (2**31))
    inputs = s.sample(rng)
    op = s.resolve()
    if s.wrap is not None:
        op = s.wrap(op)

    def op_fn(*ts):
        return op(*ts, **s.kwargs)

    float_dts = [d for d in s.dtypes if d in FLOAT_SWEEP]
    if "sweep_low" in wl:
        float_dts = [d for d in float_dts if d == "float32"]
    if (name in _TPU_HALF_ONLY
            and _os.environ.get("PADDLE_TPU_TEST_PLATFORM") == "tpu"):
        float_dts = [d for d in float_dts if d != "float32"]
    if float_dts:
        check_output_dtypes(op_fn, s.np_ref, inputs, dtypes=float_dts,
                            tol_override=s.tol)
    else:
        # int/bool ops: exact value comparison in EACH declared dtype
        # (int64 runs value-checked; without jax x64 it executes as int32,
        # which is the package's documented index-dtype behavior)
        for dt in s.dtypes:
            cast = [a if a.dtype == np.bool_ else a.astype(dt)
                    for a in inputs]
            outs = op_fn(*[paddle.to_tensor(a) for a in cast])
            outs = outs if isinstance(outs, (tuple, list)) else [outs]
            exps = s.np_ref(*cast)
            exps = exps if isinstance(exps, (tuple, list)) else [exps]
            for o, e in zip(outs, exps):
                np.testing.assert_array_equal(np.asarray(o.numpy()),
                                              np.asarray(e),
                                              err_msg=f"dtype {dt}")


_GRAD_NAMES = [n for n in _NAMES
               if SCHEMAS[n].grad and "grad" not in WHITE_LIST.get(n, {})]

# Grad policy on the chip lane: the FULL-sweep shards run the OUTPUT
# dtype sweep only — a finite-difference grad check evaluates the op
# once per perturbed input element, and each evaluation pays a compile
# and a host sync, which would put the full grad sweep hours past any
# budget. FD-vs-AD differentiation algebra is
# already pinned exhaustively by the CPU lane; the TPU-specific risk
# (bf16 matmul defaults, transcendental approximations) lives in the
# forward kernels, which the full sharded output sweep now covers. A
# sampled stride entry keeps FD grads executing against real-TPU
# numerics too (run_shards.py TPU_LANE).
if _os.environ.get("PADDLE_TPU_SWEEP_GRADS") == "0" or (
        _os.environ.get("PADDLE_TPU_TEST_PLATFORM") == "tpu" and _SHARD):
    _GRAD_NAMES = []


@pytest.mark.parametrize("name", _GRAD_NAMES)
def test_grad_finite_difference(name):
    s = SCHEMAS[name]
    rng = np.random.RandomState(zlib.crc32(name.encode()) % (2**31))
    inputs = s.sample(rng)
    op = s.resolve()
    if s.wrap is not None:
        op = s.wrap(op)

    def op_fn(*ts):
        return op(*ts, **s.kwargs)

    grad_inputs = s.grad_inputs
    if grad_inputs is None:
        grad_inputs = [i for i, a in enumerate(inputs)
                       if np.issubdtype(a.dtype, np.floating)]
    tol_kw = {}
    if s.grad_tol is not None:
        tol_kw = {"atol": s.grad_tol[0], "rtol": s.grad_tol[1]}
    check_grad(op_fn, inputs, grad_inputs=grad_inputs, kwargs=None, **tol_kw)
