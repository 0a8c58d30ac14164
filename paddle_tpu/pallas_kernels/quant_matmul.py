"""Weight-only quantized matmul with dequant fused into the Pallas
prologue.

The decode step of a served model is weight-streaming-bound: every token
re-reads the full q/k/v/o + MLP + lm_head weights from HBM. Storing them
int8 (or fp8 e4m3 where the dtype exists) halves that stream — IF the
dequant never materializes a full-width weight copy. The XLA path
(nn/quant.py ``weight_only_linear``) relies on fusion + an
optimization_barrier to get that; this kernel makes it structural:

- grid (m_blocks, n_blocks, k_blocks), k innermost/sequential;
- each cell's PROLOGUE loads one [bn, bk] int8/fp8 weight block and
  widens it to the activation dtype IN VMEM (the narrow values are what
  crossed HBM), then one MXU matmul accumulates into a f32 [bm, bn]
  output block;
- the final k step applies the per-output-channel scale to the
  accumulator — mathematically identical to scaling the weights
  (the scale is per output column), one multiply per output element
  instead of one per weight element.

Scale convention matches ``nn.quant.weight_quantize``: ``scale`` is the
DEQUANT MULTIPLIER (absmax / 127 for int8, absmax / 448 for fp8), so
``w ≈ q * scale[:, None]``.

Dispatch: ``weight_only_linear`` consults ``quant_matmul_dispatch``
(env ``PADDLE_TPU_QUANT_WEIGHTS``; default on for TPU, opt-in on CPU
where Pallas interprets) and falls back to the fused XLA form with the
reason counted — ``paddle_tpu_quant_matmul_{hits,fallbacks}_total`` —
the fused-conv/flash-decode instrumentation pattern.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.metrics import _ENABLED as _obs_on
from ..observability.metrics import counter as _obs_counter
from ._blocks import pick_block
from .flash_attention import _dot_prec, _interpret

__all__ = ["quant_matmul", "quant_matmul_enabled", "quant_matmul_dispatch"]

_QUANT_WEIGHTS_ENV = "PADDLE_TPU_QUANT_WEIGHTS"

_qm_hits = _obs_counter(
    "paddle_tpu_quant_matmul_hits_total",
    "matmuls dispatched to the Pallas weight-dequant kernel",
    ("fmt",))
_qm_fallbacks = _obs_counter(
    "paddle_tpu_quant_matmul_fallbacks_total",
    "weight-only matmuls on the XLA dequant-fusion fallback path",
    ("reason",))


def quant_matmul_enabled() -> bool:
    """Env-gated: PADDLE_TPU_QUANT_WEIGHTS=1/0 forces it; default on for
    TPU backends (where the kernel is compiled) and off on CPU (where
    Pallas runs in the slow interpreter — tests opt in explicitly)."""
    v = os.environ.get(_QUANT_WEIGHTS_ENV)
    if v is not None:
        return v != "0"
    return jax.default_backend() == "tpu"


def quant_matmul_dispatch(*, dtype, fmt: str) -> bool:
    """True -> run the Pallas ``quant_matmul``; False -> the XLA
    dequant-fusion fallback, reason counted. Python-side, so under jit
    this costs nothing after the first trace."""
    reason = None
    if not quant_matmul_enabled():
        reason = "disabled"
    elif str(dtype) not in ("float32", "bfloat16"):
        reason = "dtype"
    else:
        from ..core.autograd import is_grad_enabled

        if is_grad_enabled():
            # forward-only kernel (quantized weights are a serving
            # artifact; QAT trains through the fake-quant STE path)
            reason = "grad_mode"
    if reason is None:
        if _obs_on[0]:
            _qm_hits.labels(fmt).inc()
        return True
    if _obs_on[0]:
        _qm_fallbacks.labels(reason).inc()
    return False


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, *, nk: int):
    """One (m block, n block, k step) cell.

    Refs (blocked):
      x [bm, bk]        — activation block
      w [bn, bk] int8/fp8 — weight block, NARROW over HBM
      s [1, bn] f32     — per-output-channel dequant multipliers
      o [bm, bn] f32    — accumulator, revisited across the k steps
    """
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    # dequant prologue: widen the narrow weight block to the activation
    # dtype in VMEM; the per-channel scale moves to the accumulator
    # epilogue below (identical math, n multiplies instead of n*k)
    # (through f32: Mosaic has no direct fp8 -> bf16 cast, and the hop
    # is exact for both narrow formats)
    w = w_ref[...].astype(jnp.float32).astype(x.dtype)
    o_ref[...] += jnp.dot(x, w.T, preferred_element_type=jnp.float32,
                          precision=_dot_prec(x.dtype))

    @pl.when(pl.program_id(2) == nk - 1)
    def _scale():
        o_ref[...] = o_ref[...] * s_ref[...]


def quant_matmul(x, qweight, scale, block_m: int = 128,
                 block_n: int = 256, block_k: int = 512):
    """``x [..., K] @ dequant(qweight [N, K]).T`` -> [..., N] in x's
    dtype, dequant fused into the weight-load prologue. ``scale`` [N]
    f32 is the per-output-channel dequant multiplier
    (``nn.quant.weight_quantize``'s convention)."""
    from ..core.tensor import Tensor
    from ..ops.dispatch import apply_op

    is_tensor = isinstance(x, Tensor)

    def _f(xa, qa, sa):
        lead = xa.shape[:-1]
        K = xa.shape[-1]
        N = qa.shape[0]
        if qa.shape[1] != K:
            raise ValueError(
                f"qweight must be [N, K={K}], got {qa.shape}")
        xm = xa.reshape(-1, K)
        m = xm.shape[0]
        bm = pick_block(m, block_m)
        bn = pick_block(N, block_n)
        bk = pick_block(K, block_k)
        nk = K // bk
        s2 = sa.reshape(1, N).astype(jnp.float32)

        def _idx_x(i, j, k):
            return (i, k)

        def _idx_w(i, j, k):
            return (j, k)

        def _idx_s(i, j, k):
            return (0, j)

        def _idx_o(i, j, k):
            return (i, j)

        def kern(x_ref, w_ref, s_ref, o_ref):
            _qmm_kernel(x_ref, w_ref, s_ref, o_ref, nk=nk)

        out = pl.pallas_call(
            kern,
            grid=(m // bm, N // bn, nk),
            in_specs=[
                pl.BlockSpec((bm, bk), _idx_x),
                pl.BlockSpec((bn, bk), _idx_w),
                pl.BlockSpec((1, bn), _idx_s),
            ],
            out_specs=pl.BlockSpec((bm, bn), _idx_o),
            out_shape=jax.ShapeDtypeStruct((m, N), jnp.float32),
            interpret=_interpret(),
            # m/n grid dims are embarrassingly parallel; the k dim
            # accumulates into the revisited output block and must stay
            # sequential
            compiler_params=None if _interpret() else pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
        )(xm, qa, s2)
        return out.reshape(lead + (N,)).astype(xa.dtype)

    if is_tensor:
        return apply_op("quant_matmul", _f, x, qweight, scale)
    return _f(jnp.asarray(x), jnp.asarray(qweight), jnp.asarray(scale))
