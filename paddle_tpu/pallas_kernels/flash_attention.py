"""Flash attention as Pallas TPU kernels (forward + blockwise backward).

Reference analogue: phi/kernels/gpu/flash_attn_kernel.cu and
phi/kernels/gpu/flash_attn_grad_kernel.cu (FlashAttention-2 via dynloaded
libflashattn, fwd/bwd/varlen). TPU-native design:

- forward: online-softmax over k-blocks held in VMEM, q-blocks on the
  grid; stores the per-row logsumexp (LSE) for the backward.
- backward: two tiled kernels, exactly the FlashAttention-2 recipe —
  a dK/dV kernel (grid over k-blocks, loop over q-blocks) and a dQ
  kernel (grid over q-blocks, loop over k-blocks), both recomputing
  p = exp(s - lse) blockwise so no O(s²) tensor is ever materialized.
  delta = rowsum(dO * O) is a cheap fused XLA precompute.
- causal blocks beyond the diagonal are skipped entirely (both passes).
- varlen: packed sequences expressed as segment ids (cu_seqlens ->
  segments), masked in-kernel — the TPU equivalent of the reference's
  flash_attn_varlen path.

Matmuls keep the input dtype (bf16 on the MXU fast path) with fp32
accumulation via preferred_element_type; softmax/statistics run in fp32.

Layout: [batch, seq, heads, head_dim] (Paddle convention); internally
blocked as [b*h, s, d].
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Mosaic's default scoped-VMEM cap is 16 MB: the backward kernels hold
# full-sequence q/do (dK/dV pass) and k/v (dQ pass) refs, which at
# seq >= 8192 exceed it while the chip has 128 MB of VMEM.
VMEM_LIMIT_BYTES = 100 * 1024 * 1024


def _compiler_kwargs():
    if _interpret():
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=VMEM_LIMIT_BYTES)}


NEG_INF = -1e30


def _dot_prec(dt):
    """Kernel dot precision: f32 operands inherit the global setting
    (the TPU test lane forces 'highest' for oracle comparisons), while
    half-precision operands pin DEFAULT — Mosaic rejects an fp32-precision
    contraction on bf16 vectors ("Bad lhs type"), and bf16-operand/
    f32-accumulate IS this kernel's contract."""
    return None if dt == jnp.float32 else jax.lax.Precision.DEFAULT


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, segc_ref, segr_ref, o_ref, lse_ref, *,
                block_k: int, sm_scale: float, causal: bool, q_block: int,
                seq_len: int, varlen: bool):
    qi = pl.program_id(1)
    q = q_ref[0]  # [block_q, d] — input dtype feeds the MXU
    bq = q.shape[0]

    m = jnp.full((bq,), NEG_INF, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)
    acc = jnp.zeros((bq, q_ref.shape[-1]), jnp.float32)
    if varlen:
        seg_q = segc_ref[0]  # (block_q, 1)

    num_kb = seq_len // block_k
    if causal:
        # only k-blocks up to the diagonal contribute
        last_kb = jnp.minimum(num_kb, ((qi + 1) * q_block + block_k - 1) // block_k)
    else:
        last_kb = num_kb

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
        precision=_dot_prec(q.dtype)) * sm_scale
        mask = None
        if causal:
            qpos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            mask = qpos >= kpos
        if varlen:
            seg_k = _seg_row_slice(segr_ref, kb, block_k)  # (1, bk)
            same = seg_q == seg_k
            mask = same if mask is None else (mask & same)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32,
        precision=_dot_prec(q.dtype))
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, last_kb, body, (m, l, acc))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(l_safe))[:, None]



def _seg_row_slice(segr_ref, start_block: int, block: int):
    """Slice a (1, 1, s) segment-row ref along lanes. Mosaic requires the
    lane offset to be provably a multiple of 128, hence the hint — varlen
    callers must use 128-multiple blocks (enforced in _check_varlen_blocks)."""
    off = pl.multiple_of(start_block * block, 128)
    return segr_ref[0, :, pl.ds(off, block)]  # (1, block)


def _check_varlen_blocks(s: int, block_q: int, block_k: int):
    if _interpret():
        return  # CPU interpret mode has no lane-tiling constraint
    if block_q % 128 or block_k % 128 or s % 128:
        raise ValueError(
            f"varlen flash attention on TPU requires seq ({s}) and blocks "
            f"(q={block_q}, k={block_k}) to be multiples of 128; pad the "
            "packed stream (flash_attn_varlen does this automatically)")


def _varlen_specs(seg, s: int, *, col_block=None):
    """(extra_specs, extra_args) for the two segment-id orientations:
    column [bh, s, 1] for q rows (optionally blocked per q-block) and
    row [bh, 1, s] for k columns."""
    if col_block is None:
        col = pl.BlockSpec((1, s, 1), lambda b, i: (b, 0, 0))
    else:
        col = pl.BlockSpec((1, col_block, 1), lambda b, i: (b, i, 0))
    row = pl.BlockSpec((1, 1, s), lambda b, i: (b, 0, 0))
    return [col, row], [seg[:, :, None], seg[:, None, :]]


def _flash_fwd(q, k, v, seg, *, causal: bool, sm_scale: float, block_q: int,
               block_k: int):
    bh, s, d = q.shape
    # clamp AND make the tiling exact: a block that does not divide s
    # would silently drop the tail rows of the (bh, s // block) grid.
    # The public entries already pick_block, but the invariant belongs
    # where the grid is built (pt-analysis pallas-block-divide).
    block_q = _pick_block(s, block_q)
    block_k = _pick_block(s, block_k)
    varlen = seg is not None
    grid = (bh, s // block_q)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
    ]
    args = [q, k, v]
    if varlen:
        _check_varlen_blocks(s, block_q, block_k)
        sp, ar = _varlen_specs(seg, s, col_block=block_q)
        in_specs += sp
        args += ar

    def kern(q_ref, k_ref, v_ref, *rest):
        if varlen:
            segc_ref, segr_ref, o_ref, lse_ref = rest
        else:
            (o_ref, lse_ref) = rest
            segc_ref = segr_ref = None
        _fwd_kernel(q_ref, k_ref, v_ref, segc_ref, segr_ref, o_ref, lse_ref,
                    block_k=block_k, sm_scale=sm_scale, causal=causal,
                    q_block=block_q, seq_len=s, varlen=varlen)
    out, lse = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)),
        grid=grid,
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))),
        interpret=_interpret(),
        **_compiler_kwargs(),
    )(*args)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# backward: dK/dV kernel — grid over k-blocks, loop over q-blocks
# ---------------------------------------------------------------------------


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     segc_ref, segr_ref, dk_ref, dv_ref, *, block_q: int,
                     sm_scale: float, causal: bool, k_block: int,
                     seq_len: int, varlen: bool):
    ki = pl.program_id(1)
    k = k_ref[0]  # [block_k, d]
    v = v_ref[0]
    bk = k.shape[0]
    d = k.shape[-1]

    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)
    if varlen:
        seg_k = _seg_row_slice(segr_ref, ki, k_block)  # (1, bk)

    num_qb = seq_len // block_q
    # causal: q-blocks strictly before the diagonal see no keys of this block
    first_qb = (ki * k_block) // block_q if causal else 0

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :]
        do = do_ref[0, pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), :]      # (bq, 1)
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), :]  # (bq, 1)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
        precision=_dot_prec(q.dtype)) * sm_scale
        mask = None
        if causal:
            qpos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
            kpos = ki * k_block + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
            mask = qpos >= kpos
        if varlen:
            seg_q = segc_ref[0, pl.ds(qb * block_q, block_q), :]  # (bq, 1)
            same = seg_q == seg_k
            mask = same if mask is None else (mask & same)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)  # normalized probabilities
        dv = dv + jnp.dot(p.astype(do.dtype).T, do,
                          preferred_element_type=jnp.float32,
        precision=_dot_prec(q.dtype))
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32,
        precision=_dot_prec(q.dtype))
        ds = p * (dp - delta) * sm_scale
        dk = dk + jnp.dot(ds.astype(q.dtype).T, q,
                          preferred_element_type=jnp.float32,
        precision=_dot_prec(q.dtype))
        return dk, dv

    dk, dv = jax.lax.fori_loop(first_qb, num_qb, body, (dk, dv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dQ kernel — grid over q-blocks, loop over k-blocks
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   segc_ref, segr_ref, dq_ref, *, block_k: int,
                   sm_scale: float, causal: bool, q_block: int,
                   seq_len: int, varlen: bool):
    qi = pl.program_id(1)
    q = q_ref[0]  # [block_q, d]
    do = do_ref[0]
    lse = lse_ref[0]      # (bq, 1)
    delta = delta_ref[0]  # (bq, 1)
    bq = q.shape[0]
    d = q.shape[-1]

    dq = jnp.zeros((bq, d), jnp.float32)
    if varlen:
        seg_q = segc_ref[0]  # (bq, 1)

    num_kb = seq_len // block_k
    if causal:
        last_kb = jnp.minimum(num_kb, ((qi + 1) * q_block + block_k - 1) // block_k)
    else:
        last_kb = num_kb

    def body(kb, dq):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
        precision=_dot_prec(q.dtype)) * sm_scale
        mask = None
        if causal:
            qpos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            mask = qpos >= kpos
        if varlen:
            seg_k = _seg_row_slice(segr_ref, kb, block_k)  # (1, bk)
            same = seg_q == seg_k
            mask = same if mask is None else (mask & same)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32,
        precision=_dot_prec(q.dtype))
        ds = p * (dp - delta) * sm_scale
        dq = dq + jnp.dot(ds.astype(k.dtype), k,
                          preferred_element_type=jnp.float32,
        precision=_dot_prec(q.dtype))
        return dq

    dq = jax.lax.fori_loop(0, last_kb, body, dq)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd(q, k, v, seg, out, lse, do, *, causal: bool, sm_scale: float,
               block_q: int, block_k: int, dlse=None):
    bh, s, d = q.shape
    # same exact-tiling contract as _flash_fwd (and the same inputs pick
    # the same blocks, so fwd/bwd tile identically)
    block_q = _pick_block(s, block_q)
    block_k = _pick_block(s, block_k)
    varlen = seg is not None
    # delta = rowsum(dO * O): phrased as a dot so XLA accumulates bf16
    # products in f32 WITHOUT materializing f32 copies of dO and O (the
    # astype form emitted two [bh,s,d] f32 converts + layout copies,
    # ~4 ms/step on the 12-layer bench points)
    delta = jnp.einsum("bsd,bsd->bs", do, out,
                       preferred_element_type=jnp.float32,
        precision=_dot_prec(q.dtype))[..., None]
    if dlse is not None:
        # lse cotangent (flash-with-lse path): ds = p*(dp - delta + dlse)
        delta = delta - dlse.astype(jnp.float32)[..., None]
    lse = lse[..., None]  # [bh, s, 1] — TPU-tileable stat columns

    # dK/dV pass
    in_specs = [
        pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),        # q
        pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),  # k
        pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),  # v
        pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),        # do
        pl.BlockSpec((1, s, 1), lambda b, i: (b, 0, 0)),        # lse
        pl.BlockSpec((1, s, 1), lambda b, i: (b, 0, 0)),        # delta
    ]
    args = [q, k, v, do, lse, delta]
    if varlen:
        _check_varlen_blocks(s, block_q, block_k)
        sp, ar = _varlen_specs(seg, s)
        in_specs += sp
        args += ar

    def kern_dkdv(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest):
        if varlen:
            segc_ref, segr_ref, dk_ref, dv_ref = rest
        else:
            dk_ref, dv_ref = rest
            segc_ref = segr_ref = None
        _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         segc_ref, segr_ref, dk_ref, dv_ref, block_q=block_q,
                         sm_scale=sm_scale, causal=causal, k_block=block_k,
                         seq_len=s, varlen=varlen)

    dk, dv = pl.pallas_call(
        kern_dkdv,
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v.dtype)),
        grid=(bh, s // block_k),
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0))),
        interpret=_interpret(),
        **_compiler_kwargs(),
    )(*args)

    # dQ pass
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),  # q
        pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),        # k
        pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),        # v
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),  # do
        pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),  # lse
        pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),  # delta
    ]
    args = [q, k, v, do, lse, delta]
    if varlen:
        sp, ar = _varlen_specs(seg, s, col_block=block_q)
        in_specs += sp
        args += ar

    def kern_dq(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest):
        if varlen:
            segc_ref, segr_ref, dq_ref = rest
        else:
            (dq_ref,) = rest
            segc_ref = segr_ref = None
        _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       segc_ref, segr_ref, dq_ref, block_k=block_k,
                       sm_scale=sm_scale, causal=causal, q_block=block_q,
                       seq_len=s, varlen=varlen)

    dq = pl.pallas_call(
        kern_dq,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        grid=(bh, s // block_q),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        interpret=_interpret(),
        **_compiler_kwargs(),
    )(*args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP plumbing
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, seg, causal, sm_scale, block_q, block_k):
    out, _ = _flash_fwd(q, k, v, seg, causal=causal, sm_scale=sm_scale,
                        block_q=block_q, block_k=block_k)
    return out


def _flash_vjp_fwd(q, k, v, seg, causal, sm_scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, seg, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k)
    return out, (q, k, v, seg, out, lse)


def _flash_vjp_bwd(causal, sm_scale, block_q, block_k, res, do):
    q, k, v, seg, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, seg, out, lse, do, causal=causal,
                            sm_scale=sm_scale, block_q=block_q,
                            block_k=block_k)
    return dq, dk, dv, None


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_lse(q, k, v, seg, causal, sm_scale, block_q, block_k):
    """Flash attention that also RETURNS the log-sum-exp rows.

    For block-parallel formulations (ring attention) that merge several
    kernels' normalized partials: out = sum_i out_i * exp(lse_i - lse).
    The lse output is differentiable: d lse_r / d s_rk = p_rk, so its
    cotangent folds into the standard backward as delta_r - dlse_r
    (delta = rowsum(dO*O)) — same kernels, one extra subtraction."""
    return _flash_fwd(q, k, v, seg, causal=causal, sm_scale=sm_scale,
                      block_q=block_q, block_k=block_k)


def _flash_lse_vjp_fwd(q, k, v, seg, causal, sm_scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, seg, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k)
    return (out, lse), (q, k, v, seg, out, lse)


def _flash_lse_vjp_bwd(causal, sm_scale, block_q, block_k, res, cts):
    do, dlse = cts
    q, k, v, seg, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, seg, out, lse, do, causal=causal,
                            sm_scale=sm_scale, block_q=block_q,
                            block_k=block_k, dlse=dlse)
    return dq, dk, dv, None


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


# Shared with decode_attention.py (pallas_kernels/_blocks.py) so the
# non-divisible-length fix-up can't drift between the kernels; the
# `_pick_block` name stays importable (distributed/sequence_parallel.py).
from ._blocks import pick_block as _pick_block  # noqa: E402


def flash_attention(q, k, v, causal: bool = True, sm_scale=None,
                    block_q: int = 1024, block_k: int = 1024, segment_ids=None):
    """Flash attention on [b, s, h, d] Tensors or arrays. Returns same layout.

    Default 1024x1024 blocks: round-5 chip re-sweep on v5e — vs the
    512x512 round-3 optimum, the end-to-end train step gains +2.1% at
    seq 1024, +1.6% at 4096, and +4.0% at 8192 (fewer grid launches,
    better MXU occupancy per block; VMEM still fits at head_dim <= 128).
    Blocks are clamped to the sequence length.
    Sequences to at least 16384 train on one chip (the raised Mosaic VMEM
    cap covers the backward's full-sequence refs; measured 42.2k tok/s at
    16k, batch 2, no remat — the bench's seq16384 point); beyond that,
    shard the sequence across chips with ring attention / Ulysses
    (distributed/sequence_parallel.py — ring runs THIS kernel per hop
    via _flash_lse and merges partials by log-sum-exp).

    segment_ids: optional [b, s] int32 — packed-sequence (varlen) masking;
    attention only within equal segment ids.

    Parity: paddle.nn.functional.flash_attention.flash_attention
    (python/paddle/nn/functional/flash_attention.py); backward parity:
    phi/kernels/gpu/flash_attn_grad_kernel.cu.
    """
    from ..core.tensor import Tensor
    from ..ops.dispatch import apply_op

    is_tensor = isinstance(q, Tensor)
    seg_arr = None
    if segment_ids is not None:
        seg_arr = segment_ids._data if isinstance(segment_ids, Tensor) else jnp.asarray(segment_ids)
        seg_arr = seg_arr.astype(jnp.int32)

    def _f(qa, ka, va):
        b, s, h, d = qa.shape
        scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
        qm = jnp.moveaxis(qa, 2, 1).reshape(b * h, s, d)
        km = jnp.moveaxis(ka, 2, 1).reshape(b * h, s, d)
        vm = jnp.moveaxis(va, 2, 1).reshape(b * h, s, d)
        seg = None
        if seg_arr is not None:
            seg = jnp.repeat(seg_arr[:, None, :], h, axis=1).reshape(b * h, s)
        # The 512x512 default's VMEM budget assumes head_dim <= 128; wider
        # heads scale the per-block q/k/v refs linearly, so halve the block
        # cap to stay inside the (raised) scoped-VMEM limit.
        want_q, want_k = block_q, block_k
        if d > 128:
            want_q = min(want_q, 256)
            want_k = min(want_k, 256)
        bq = _pick_block(s, want_q)
        bk = _pick_block(s, want_k)
        if seg is not None and not _interpret():
            # varlen lane slices need 128-multiple blocks on TPU
            bq = max(bq, 128)
            bk = max(bk, 128)
        out = _flash(qm, km, vm, seg, causal, scale, bq, bk)
        return jnp.moveaxis(out.reshape(b, h, s, d), 1, 2)

    if is_tensor:
        return apply_op("flash_attention", _f, q, k, v)
    return _f(q, k, v)


def flash_attn_varlen(q, k, v, cu_seqlens, causal: bool = True, sm_scale=None,
                      block_q: int = 512, block_k: int = 512):
    """Varlen flash attention over packed sequences.

    q/k/v: [total_tokens, h, d] — sequences packed back to back;
    cu_seqlens: [n_seq + 1] int32 cumulative lengths (reference:
    flash_attn_unpadded, phi/kernels/gpu/flash_attn_kernel.cu varlen path).
    """
    from ..core.tensor import Tensor
    from ..ops.dispatch import apply_op

    def _arr(x):
        return x._data if isinstance(x, Tensor) else jnp.asarray(x)

    cu = _arr(cu_seqlens).astype(jnp.int32)
    is_tensor = any(isinstance(t, Tensor) for t in (q, k, v))
    if is_tensor:  # normalize mixed Tensor/array inputs for apply_op
        q, k, v = (t if isinstance(t, Tensor) else Tensor(jnp.asarray(t))
                   for t in (q, k, v))

    def _f(qa, ka, va):
        total = qa.shape[0]
        # token i belongs to segment j iff cu[j] <= i < cu[j+1]
        pos = jnp.arange(total, dtype=jnp.int32)
        seg = jnp.searchsorted(cu[1:], pos, side="right").astype(jnp.int32)
        # pad the packed stream to a 128 multiple (TPU lane tiling); padding
        # gets segment id -1 so no real token attends to it, and its rows are
        # sliced off below (their cotangents are zero in the backward)
        pad = (-total) % 128
        if pad and not _interpret():
            zeros = lambda a: jnp.zeros((pad,) + a.shape[1:], a.dtype)
            qa = jnp.concatenate([qa, zeros(qa)])
            ka = jnp.concatenate([ka, zeros(ka)])
            va = jnp.concatenate([va, zeros(va)])
            seg = jnp.concatenate([seg, jnp.full((pad,), -1, jnp.int32)])
        # in-segment causal positions: flash's causal mask is on absolute
        # positions, which is correct for packed sequences as long as the
        # segment mask also applies (cross-segment attention is masked out).
        out = flash_attention(qa[None], ka[None], va[None], causal=causal,
                              sm_scale=sm_scale, block_q=block_q,
                              block_k=block_k, segment_ids=seg[None])
        return out[0, :total]

    if is_tensor:
        # route through dispatch so the tape sees one grad node (parity with
        # flash_attention above; the review-caught alternative silently
        # detached packed-sequence training from autograd)
        return apply_op("flash_attn_varlen", _f, q, k, v)
    return _f(_arr(q), _arr(k), _arr(v))
