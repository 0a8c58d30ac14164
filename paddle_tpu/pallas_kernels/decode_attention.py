"""Flash-decode: GQA-native split-K Pallas attention for the decode hot path.

The serving/generation decode step runs single-query attention (q_len
small, typically 1) against the static [B, max_len, kv_heads, d] KV
caches. The plain XLA path scores the ENTIRE padded cache and — for GQA
models — first materializes the repeat_kv-expanded [B, max_len, heads, d]
K/V in HBM, multiplying the dominant HBM stream by heads/kv_heads.
This kernel is the TPU-native fix (reference analogue: the decode branch
of phi/kernels/gpu/flash_attn_kernel.cu and the flash-decoding split-K
formulation):

- split-K over the cache length: grid (B, num_kv_blocks); every KV
  block computes an online-softmax PARTIAL (running max, sum,
  unnormalized accumulator) per kv head and a small XLA combine merges
  them. A cell's K/V block is [block_k, kv_heads, d] — ALL kv heads of
  the block in one DMA: the last two block dims must be whole TPU tiles
  (or the whole axis), which a single kv head out of [.., kv_heads, d]
  is not, and one DMA per cell moves kv_heads times the bytes.
- GQA-native: each kv head's [block_k, d] slab is read ONCE and serves
  the head's whole [group * q_len, d] query bundle through a single MXU
  matmul — repeat_kv never materializes, so KV bytes drop by the group
  factor (4x for Llama-70B-style heads/kv_heads ratios).
- per-row length masking: the engine's per-slot [B] position vector is
  scalar-prefetched; each row's kv-block loop is bounded by its own
  length, blocks wholly beyond ``pos + q_len`` are skipped (the K/V
  BlockSpec index map re-points them at the row's last needed block,
  which Pallas recognizes as a revisit and does not re-fetch), and the
  boundary block masks ``kpos <= qpos`` element-wise. A mostly-empty
  cache therefore costs proportional to occupancy, not max_len; dead
  slots (the serving engine pins freed slots to pos 0) touch one block.
- bf16 (or fp32) streams with fp32 statistics and accumulation
  (preferred_element_type on both matmuls, stats never leave fp32).

Layout contract matches generation.make_kv_caches: q [B, q_len, heads,
d], caches [B, max_len, kv_heads, d], query head j reads kv head
j // (heads // kv_heads) (the repeat_kv mapping).

Dispatch: llama/gpt decode paths call ``decode_dispatch`` (env
``PADDLE_TPU_FLASH_DECODE``; default on for TPU backends, opt-in on CPU
where Pallas interprets) and fall back to XLA with reason counters —
``paddle_tpu_flash_decode_{hits,fallbacks}_total`` — mirroring the
fused-conv instrumentation pattern.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.metrics import _ENABLED as _obs_on
from ..observability.metrics import counter as _obs_counter
from ._blocks import pick_block
from .flash_attention import (NEG_INF, VMEM_LIMIT_BYTES, _dot_prec,
                              _interpret)

__all__ = ["flash_decode_attention", "flash_decode_enabled",
           "decode_dispatch", "MAX_DECODE_Q_LEN",
           "paged_flash_decode_attention", "paged_decode_dispatch",
           "MAX_PAGED_Q_LEN", "MAX_SPEC_K", "spec_verify_eligibility",
           "spec_tree_width"]

_FLASH_DECODE_ENV = "PADDLE_TPU_FLASH_DECODE"

# the kernel is built for the short-query decode window; longer chunks
# (prefill) belong to flash_attention's q-blocked grid
MAX_DECODE_Q_LEN = 8

# the paged variant also serves chunked-prefill bundles (one fixed chunk
# shape replaces every per-bucket prefill executable) and speculative
# verify bundles (q_len = spec_k + 1), so its query window is the
# chunk/bundle, not the decode step
MAX_PAGED_Q_LEN = 256

# largest per-round draft count the serving engine accepts: the verify
# bundle must fit the paged kernel's query window (ServingConfig
# validates spec_k against this so an oversized k fails at construction
# with an actionable error instead of silently falling back)
MAX_SPEC_K = MAX_PAGED_Q_LEN - 1

# Dispatch outcome counters (PR-2 fused-conv pattern): the decode
# dispatch is a python-side decision with automatic XLA fallback, so a
# config regression that silently disables the kernel family would be
# invisible without them. Under jit they fire once per TRACE.
_fd_hits = _obs_counter(
    "paddle_tpu_flash_decode_hits_total",
    "decode steps dispatched to the Pallas flash-decode kernel",
    ("model",))
_fd_fallbacks = _obs_counter(
    "paddle_tpu_flash_decode_fallbacks_total",
    "decode steps on the XLA fallback path",
    ("reason",))


def flash_decode_enabled() -> bool:
    """Env-gated: PADDLE_TPU_FLASH_DECODE=1/0 forces it; default on for
    TPU backends (where the kernel is compiled) and off on CPU (where
    Pallas runs in the slow interpreter — tests opt in explicitly)."""
    v = os.environ.get(_FLASH_DECODE_ENV)
    if v is not None:
        return v != "0"
    return jax.default_backend() == "tpu"


def _tp_sharded() -> bool:
    """True while tracing inside a tensor-parallel executable
    (``distributed.partition.tp_context``). ``pallas_call`` cannot be
    partitioned by GSPMD, so a kernel hit inside a tp>1 ``shard_map``-
    free jit would force XLA to gather the full sharded KV onto every
    device; declining here keeps the kv-head-sharded gather fallback."""
    from ..distributed.partition import tp_active

    return tp_active() > 1


def decode_dispatch(model: str, *, q_len: int, has_mask: bool,
                    dtype, quantized: bool = False) -> bool:
    """The decode-path dispatch decision for one attention layer call:
    True -> run ``flash_decode_attention``; False -> XLA fallback, with
    the reason counted. Called from the static-cache branch of the
    llama/gpt attention forwards (python-side, so under jit this costs
    nothing after the first trace).

    ``quantized``: the cache is an int8/fp8 store — hits count under a
    ``<model>_quant`` label and fallbacks under ``quant_<reason>``, so a
    config regression that silently pushes the quantized lane onto the
    XLA dequant-gather fallback is visible in the metrics."""
    reason = None
    if not flash_decode_enabled():
        reason = "disabled"
    elif _tp_sharded():
        # pallas_call can't be partitioned by GSPMD; the XLA gather
        # fallback shards cleanly on the kv-heads axis instead
        reason = "tp_sharded"
    elif has_mask:
        # caller brought its own attention mask (ragged left-padded
        # prompts): the kernel's masking is position-derived only
        reason = "external_mask"
    elif q_len > MAX_DECODE_Q_LEN:
        reason = "q_len"
    elif str(dtype) not in ("float32", "bfloat16"):
        reason = "dtype"
    else:
        from ..core.autograd import is_grad_enabled

        if is_grad_enabled():
            # forward-only kernel (decode is inference); taping it would
            # fail at vjp derivation
            reason = "grad_mode"
    if reason is None:
        if _obs_on[0]:
            _fd_hits.labels(model + ("_quant" if quantized else "")).inc()
        return True
    if _obs_on[0]:
        _fd_fallbacks.labels(("quant_" if quantized else "") + reason).inc()
    return False


def paged_decode_dispatch(model: str, *, q_len: int, has_mask: bool,
                          dtype, quantized: bool = False) -> bool:
    """Dispatch decision for the PAGED decode/chunk-prefill path: True
    -> ``paged_flash_decode_attention`` (block-table gather inside the
    kernel's index map); False -> the XLA gather fallback
    (``gather_paged_kv`` + grouped SDPA — ``gather_paged_kv_dequant``
    for quantized pools), with the reason counted under a ``paged_``
    prefix (``paged_quant_`` when the pool is quantized). Same gates as
    ``decode_dispatch`` except the query window covers the prefill
    chunk (``MAX_PAGED_Q_LEN``)."""
    reason = None
    if not flash_decode_enabled():
        reason = "disabled"
    elif _tp_sharded():
        reason = "tp_sharded"
    elif has_mask:
        reason = "external_mask"
    elif q_len > MAX_PAGED_Q_LEN:
        reason = "q_len"
    elif str(dtype) not in ("float32", "bfloat16"):
        reason = "dtype"
    else:
        from ..core.autograd import is_grad_enabled

        if is_grad_enabled():
            reason = "grad_mode"
    if reason is None:
        if _obs_on[0]:
            _fd_hits.labels(
                model + "_paged" + ("_quant" if quantized else "")).inc()
        return True
    if _obs_on[0]:
        _fd_fallbacks.labels(
            ("paged_quant_" if quantized else "paged_") + reason).inc()
    return False


def spec_tree_width(spec_tree) -> int:
    """Node count of a draft token tree with per-depth branching factors
    ``spec_tree`` (root + every level): ``[4, 2, 2]`` -> 1 + 4 + 8 + 16
    = 29. This is the verify bundle's q_len — the quantity the kernel's
    query window bounds."""
    w = wl = 1
    for f in spec_tree:
        wl *= int(f)
        w += wl
    return w


def spec_verify_eligibility(spec_k: int, dtype, spec_tree=None):
    """Will a speculative verify bundle (q_len = spec_k + 1 for a chain,
    the flattened node count for a ``spec_tree``) take the paged
    flash-decode kernel, and if not, why? Called ONCE per engine at
    construction — the per-layer dispatch still decides each trace via
    ``paged_decode_dispatch``; this is the engine-level preflight that
    records the expected path (and its fallback reason, under the
    ``spec_`` / ``spec_tree_`` prefix) so a config that silently pushes
    every verify onto the XLA gather fallback is visible in the metrics
    before any traffic arrives."""
    if spec_tree is not None:
        prefix, width = "spec_tree_", spec_tree_width(spec_tree)
    else:
        prefix, width = "spec_", spec_k + 1
    reason = None
    if not flash_decode_enabled():
        reason = "disabled"
    elif width > MAX_PAGED_Q_LEN:
        reason = "q_len"
    elif str(dtype) not in ("float32", "bfloat16"):
        reason = "dtype"
    if reason is None:
        return True, None
    if _obs_on[0]:
        _fd_fallbacks.labels(prefix + reason).inc()
    return False, reason


def _visible(length, start, *, gq: int, block_k: int, q_len: int,
             group: int, mask=None):
    """[gq, block_k] bool: which cache columns of this kv block each
    query row may attend. Identical for every kv head, so a cell builds
    it once.

    ``mask`` (None or [gq, qp] f32, 1.0 = visible, rows already expanded
    to r = i*group + g and columns zero-padded to qp): the row's
    in-bundle ancestor mask for tree-speculative verify. None keeps the
    causal bundle (kpos <= qpos) bitwise — a causal ancestor mask input
    reproduces it exactly, so the chain lane never pays the extra
    operand. Past-KV masking (everything before the bundle) is untouched
    either way: all of it is ancestry by construction."""
    kpos = start + jax.lax.broadcasted_iota(jnp.int32, (gq, block_k), 1)
    if mask is None:
        # query row r sits at absolute position pos + r // group; masking
        # kpos <= qpos covers BOTH the right-pad beyond the row's length
        # and causality inside the q_len window
        qpos = (length - q_len) \
            + jax.lax.broadcasted_iota(jnp.int32, (gq, block_k), 0) // group
        return kpos <= qpos
    # bundle node j lives at cache position (length - q_len) + j; a
    # dynamic per-column gather of mask[:, j] is not expressible in the
    # cell, so build the column one-hot [qp, block_k] and read the tile
    # through one small MXU matmul. Columns outside the bundle window
    # match no one-hot row with a non-zero mask column (the pad columns
    # are zero) and fall to the past-KV term (kpos < length - q_len),
    # which also bounds the right-pad: kpos >= length stays masked.
    qp = mask.shape[-1]
    j_col = (start - (length - q_len)) \
        + jax.lax.broadcasted_iota(jnp.int32, (qp, block_k), 1)
    onehot = (jax.lax.broadcasted_iota(
        jnp.int32, (qp, block_k), 0) == j_col).astype(jnp.float32)
    anc = jnp.dot(mask, onehot, preferred_element_type=jnp.float32)
    return (kpos < length - q_len) | (anc > 0.5)


def _decode_kernel(*refs, n_prefetch: int, block_k: int, sm_scale: float,
                   q_len: int, group: int, bound, tree: bool):
    """One (batch row, kv block) cell: every kv head's online-softmax
    partial for its whole query bundle. The K/V block carries ALL kv
    heads — a block of 1 on the kv-heads axis is not a TPU tile, and one
    DMA per cell moves kv_heads times more bytes than a per-head cell.

    Refs (blocked), after the ``n_prefetch`` scalar-prefetch refs (the
    first of which is the per-row valid kv length = pos + q_len):
      q [1, KV, gq, d]            — rows r = i*group + g per kv head
      k/v [1, block_k, KV, d]     — one cache block, all kv heads
      ks/vs [1, block_k, KV] f32  — quantized caches only (``bound``
                                    set): per-token-per-head absmax
      mask [1, gq, qp] f32        — ``tree`` only: ancestor mask
      o [1, 1, KV, gq, d] f32     — unnormalized accumulator partial
      m/l [1, 1, KV, gq, 1] f32   — running max / sum partials

    Quantized cells add a DEQUANT PROLOGUE: the int8/fp8 head slab and
    its scale column are widened to the query dtype in VMEM before the
    MXU matmuls, so the HBM stream is the narrow one. ``q * s / bound``
    in that exact order matches ``quantization.intx.unpack_absmax``
    bitwise, keeping the kernel and the XLA gather fallback
    interchangeable."""
    lens_ref = refs[0]
    q_ref, k_ref, v_ref, *refs = refs[n_prefetch:]
    if bound is not None:
        ks_ref, vs_ref, *refs = refs
    if tree:
        mask_ref, *refs = refs
    o_ref, m_ref, l_ref = refs
    length = lens_ref[pl.program_id(0)]
    start = pl.program_id(1) * block_k
    _, kv, gq, d = q_ref.shape

    @pl.when(start < length)
    def _compute():
        vis = _visible(length, start, gq=gq, block_k=block_k, q_len=q_len,
                       group=group, mask=mask_ref[0] if tree else None)
        for h in range(kv):
            q = q_ref[0, h]                    # [gq, d]
            k = k_ref[0, :, h, :]              # [block_k, d]
            v = v_ref[0, :, h, :]
            if bound is not None:
                k = (k.astype(jnp.float32) * ks_ref[0, :, h:h + 1]
                     / bound).astype(q.dtype)
                v = (v.astype(jnp.float32) * vs_ref[0, :, h:h + 1]
                     / bound).astype(q.dtype)
            sc = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                         precision=_dot_prec(q.dtype)) * sm_scale
            sc = jnp.where(vis, sc, NEG_INF)
            m = sc.max(axis=-1, keepdims=True)     # [gq, 1] f32
            p = jnp.exp(sc - m)
            o_ref[0, 0, h] = jnp.dot(p.astype(v.dtype), v,
                                     preferred_element_type=jnp.float32,
                                     precision=_dot_prec(q.dtype))
            m_ref[0, 0, h] = m
            l_ref[0, 0, h] = p.sum(axis=-1, keepdims=True)

    @pl.when(start >= length)
    def _skip():
        # skipped blocks still own their partial slots; the finite
        # NEG_INF sentinel makes them exact zeros in the combine
        # (exp(NEG_INF - m_total) underflows to 0, l contributes 0)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)


def _split_k_attention(q5, kc, vc, lens, *, block_k: int, nb: int,
                       kv_index, prefetch=(), sm_scale: float,
                       k_scale=None, v_scale=None, ancestor_mask=None):
    """The shared split-K call behind both cache layouts.

    q5 [B, q_len, KV, group, d]; kc/vc [*, *, KV, d] cut into
    [1, block_k, KV, d] blocks; lens [B] int32 -> [B, KV, gq, d] f32,
    combined and normalized, rows r = i*group + g.

    ``kv_index(b, s, lens_ref, *prefetch_refs)`` maps grid cell (row b,
    logical kv block s) to the (axis-0, axis-1) block index of its K/V
    block — the ONLY thing that differs between the contiguous cache
    and the paged pool. ``prefetch``: extra scalar-prefetch operands it
    reads (the block table). Scale pools ride the same index.

    Grid (B, nb): rows are independent ("parallel"); the kv-block axis
    must keep its order for the revisit-skip on the K/V index map."""
    from ..quantization.intx import format_bound

    B, q_len, KV, group, d = q5.shape
    gq = q_len * group
    quant = k_scale is not None
    tree = ancestor_mask is not None
    # per-kv-head query bundles as whole [gq, d] tiles: merging q_len
    # into the group axis inside the cell is a sublane relayout Mosaic
    # only takes for group % 8 == 0, so it happens here in XLA (tiny)
    qk = jnp.transpose(q5, (0, 2, 1, 3, 4)).reshape(B, KV, gq, d)

    def _idx_kv(b, s, *pf):
        return kv_index(b, s, *pf) + (0, 0)

    # scale pools as [rows * blocks_per_row, block_k, KV] (a paged pool
    # already is): each cell's [block_k, KV] block is then the array's
    # own last two dims, a legal TPU block for any block_k
    blocks_per_row = kc.shape[1] // block_k

    def _idx_scale(b, s, *pf):
        i0, i1 = kv_index(b, s, *pf)
        return (i0 * blocks_per_row + i1, 0, 0)

    in_specs = [
        pl.BlockSpec((1, KV, gq, d), lambda b, s, *pf: (b, 0, 0, 0)),
        pl.BlockSpec((1, block_k, KV, d), _idx_kv),
        pl.BlockSpec((1, block_k, KV, d), _idx_kv),
    ]
    operands = (lens.astype(jnp.int32),) + tuple(prefetch) + (qk, kc, vc)
    if quant:
        in_specs += [pl.BlockSpec((1, block_k, KV), _idx_scale)] * 2
        operands += tuple(sc.astype(jnp.float32).reshape(-1, block_k, KV)
                          for sc in (k_scale, v_scale))
    if tree:
        # rows expanded to the kernel's r = i*group + g order and the
        # contraction axis zero-padded to a lane multiple, so the cell's
        # one-hot matmul is MXU-aligned for any bundle width (29, ...)
        qp = -(-q_len // 128) * 128
        am = jnp.repeat(ancestor_mask.astype(jnp.float32), group, axis=1)
        am = jnp.pad(am, ((0, 0), (0, 0), (0, qp - q_len)))
        in_specs.append(
            pl.BlockSpec((1, gq, qp), lambda b, s, *pf: (b, 0, 0)))
        operands += (am,)

    def _idx_out(b, s, *pf):
        return (b, s, 0, 0, 0)

    kern = functools.partial(
        _decode_kernel, n_prefetch=1 + len(prefetch), block_k=block_k,
        sm_scale=sm_scale, q_len=q_len, group=group, tree=tree,
        bound=format_bound("int8" if kc.dtype == jnp.int8 else "fp8")
        if quant else None)
    interpret = _interpret()
    o_p, m_p, l_p = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(prefetch),
            grid=(B, nb),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, KV, gq, d), _idx_out),
                pl.BlockSpec((1, 1, KV, gq, 1), _idx_out),
                pl.BlockSpec((1, 1, KV, gq, 1), _idx_out),
            ],
        ),
        out_shape=(jax.ShapeDtypeStruct((B, nb, KV, gq, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, nb, KV, gq, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, nb, KV, gq, 1), jnp.float32)),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # whole-heads partial blocks grow with heads * q_len (a
            # 256-token bundle at 32 heads needs ~24 MB with the
            # lane-padded stat columns): past Mosaic's 16 MB default
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(*operands)

    # split-K combine (tiny: nb * gq * d floats per row/head): classic
    # log-sum-exp merge of the blocks' partials. Skipped blocks carry
    # (m=NEG_INF, l=0, acc=0) and contribute exact zeros; a fully-masked
    # row (dead slot) ends with l_total=0 and returns zeros.
    m_tot = m_p.max(axis=1)                        # [B, KV, gq, 1]
    alpha = jnp.exp(m_p - m_tot[:, None])          # [B, nb, KV, gq, 1]
    l_tot = (l_p * alpha).sum(axis=1)
    acc = (o_p * alpha).sum(axis=1)
    return acc / jnp.maximum(l_tot, 1e-30)


def _flash_decode(q5, kc, vc, lens, *, sm_scale: float, block_k: int,
                  k_scale=None, v_scale=None):
    """Contiguous caches [B, max_len, KV, d] (scales [B, max_len, KV]
    f32, both or neither): cell (b, s) reads cache block s of row b."""
    max_len = kc.shape[1]
    bk = pick_block(max_len, block_k)

    def _kv_index(b, s, lens):
        # blocks beyond the row's last needed block re-point AT the last
        # needed one: Pallas sees a repeated index and skips the fetch,
        # so right-pad past pos (and dead slots pinned to pos 0) cost no
        # HBM traffic beyond one block
        last = jnp.maximum(pl.cdiv(lens[b], bk) - 1, 0)
        return (b, jnp.minimum(s, last))

    return _split_k_attention(q5, kc, vc, lens, block_k=bk,
                              nb=max_len // bk, kv_index=_kv_index,
                              sm_scale=sm_scale, k_scale=k_scale,
                              v_scale=v_scale)


def _unwrap(x):
    from ..core.tensor import Tensor

    if x is None:
        return None
    return x._data if isinstance(x, Tensor) else jnp.asarray(x)


def flash_decode_attention(q, k_cache, v_cache, positions, sm_scale=None,
                           block_k: int = 256, k_scale=None, v_scale=None):
    """Flash-decode attention over the static KV caches.

    q: [B, q_len, heads, d] (q_len <= MAX_DECODE_Q_LEN); k_cache/v_cache:
    [B, max_len, kv_heads, d] with this step's tokens ALREADY written at
    [pos, pos + q_len) (the update_static_kv_cache protocol);
    ``positions``: per-row [B] int32 vector or scalar — query i of row b
    sits at absolute position positions[b] + i and attends cache
    positions <= it. Returns [B, q_len, heads, d] in q's dtype.

    heads must be a multiple of kv_heads; query head j reads kv head
    j // (heads // kv_heads) (the repeat_kv mapping) without ever
    materializing the expansion.

    QUANTIZED caches: pass the per-token-per-head absmax scales
    ``k_scale``/``v_scale`` ([B, max_len, kv_heads] f32, the
    ``make_kv_caches(kv_format=...)`` companions) and int8/fp8 caches —
    each grid cell dequantizes its block in the kernel prologue, so the
    HBM stream is the narrow one and nothing else changes.
    """
    from ..core.tensor import Tensor
    from ..ops.dispatch import apply_op

    is_tensor = isinstance(q, Tensor)
    pos_arr = positions._data if isinstance(positions, Tensor) else positions
    ks_arr, vs_arr = _unwrap(k_scale), _unwrap(v_scale)
    if (ks_arr is None) != (vs_arr is None):
        raise ValueError("pass both k_scale and v_scale or neither")

    def _f(qa, ka, va):
        B, q_len, H, d = qa.shape
        KV = ka.shape[2]
        if H % KV:
            raise ValueError(f"heads ({H}) not a multiple of kv_heads ({KV})")
        group = H // KV
        scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
        pos = jnp.asarray(pos_arr, jnp.int32)
        if pos.ndim == 0:
            pos = jnp.broadcast_to(pos, (B,))
        lens = jnp.minimum(pos + q_len, ka.shape[1])
        q5 = qa.reshape(B, q_len, KV, group, d)
        o = _flash_decode(q5, ka, va, lens, sm_scale=scale, block_k=block_k,
                          k_scale=ks_arr, v_scale=vs_arr)
        # [B, KV, q_len*group, d] rows r = i*group + g -> [B, q_len, H, d]
        o = o.reshape(B, KV, q_len, group, d)
        o = jnp.transpose(o, (0, 2, 1, 3, 4)).reshape(B, q_len, H, d)
        return o.astype(qa.dtype)

    if is_tensor:
        return apply_op("flash_decode_attention", _f, q, k_cache, v_cache)
    return _f(jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache))


def _paged_flash_decode(q5, kp, vp, bt, lens, *, sm_scale: float,
                        k_scale=None, v_scale=None, ancestor_mask=None):
    """Paged pools [num_blocks, bs, KV, d] (scales [num_blocks, bs, KV]
    f32), bt [B, nb] int32: identical math to ``_flash_decode`` — the
    only change is the K/V index map, which resolves the grid's logical
    kv-block through the scalar-prefetched block table into a physical
    pool block. Out-of-range blocks re-point at the row's LAST needed
    logical block (the same Pallas revisit-skip as the contiguous
    kernel), so a short row costs its own length, not the table width.

    ``ancestor_mask`` ([B, q_len, q_len], 1.0 = visible): per-row
    in-bundle visibility for tree-speculative verify; every cell of row
    b reads the same block. None compiles the causal bundle exactly as
    before."""
    bs = kp.shape[1]

    def _kv_index(b, s, lens, bt):
        last = jnp.maximum(pl.cdiv(lens[b], bs) - 1, 0)
        return (bt[b, jnp.minimum(s, last)], 0)

    return _split_k_attention(q5, kp, vp, lens, block_k=bs,
                              nb=bt.shape[1], kv_index=_kv_index,
                              prefetch=(bt.astype(jnp.int32),),
                              sm_scale=sm_scale, k_scale=k_scale,
                              v_scale=v_scale, ancestor_mask=ancestor_mask)


def paged_flash_decode_attention(q, k_pool, v_pool, block_table, positions,
                                 sm_scale=None, k_scale=None, v_scale=None,
                                 ancestor_mask=None):
    """Flash-decode attention over PAGED KV pools.

    q: [B, q_len, heads, d] (q_len <= MAX_PAGED_Q_LEN — the serving
    decode step OR one chunked-prefill bundle); k_pool/v_pool:
    [num_blocks, block_size, kv_heads, d] shared pools with this step's
    tokens ALREADY scattered at their table-resolved positions
    (``generation.paged_kv_cache_write``); ``block_table``: [B, nb]
    int32 — row b's logical block j lives in physical pool block
    ``block_table[b, j]``; ``positions``: per-row [B] int32 vector or
    scalar, same contract as ``flash_decode_attention``. Returns
    [B, q_len, heads, d] in q's dtype.

    QUANTIZED pools: pass the [num_blocks, block_size, kv_heads] f32
    absmax scale pools as ``k_scale``/``v_scale``
    (``make_paged_kv_pools(kv_format=...)``'s ``ks``/``vs``) — dequant
    happens in the kernel prologue, per block, behind the same
    table-indirected index map.

    TREE-SPECULATIVE bundles: ``ancestor_mask`` [B, q_len, q_len] bool
    (True = bundle node i may attend bundle node j) replaces ONLY the
    in-bundle causal mask — every query still attends all of its row's
    past KV (every committed position is an ancestor of every tree
    node). A causal lower-triangular mask reproduces the default path
    bitwise.
    """
    from ..core.tensor import Tensor
    from ..ops.dispatch import apply_op

    is_tensor = isinstance(q, Tensor)
    pos_arr = positions._data if isinstance(positions, Tensor) else positions
    bt_arr = block_table._data if isinstance(block_table, Tensor) \
        else block_table
    ks_arr, vs_arr = _unwrap(k_scale), _unwrap(v_scale)
    am_arr = _unwrap(ancestor_mask)
    if (ks_arr is None) != (vs_arr is None):
        raise ValueError("pass both k_scale and v_scale or neither")

    def _f(qa, ka, va):
        B, q_len, H, d = qa.shape
        KV = ka.shape[2]
        if H % KV:
            raise ValueError(f"heads ({H}) not a multiple of kv_heads ({KV})")
        group = H // KV
        scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
        bt = jnp.asarray(bt_arr, jnp.int32)
        if bt.ndim != 2 or bt.shape[0] != B:
            raise ValueError(
                f"block_table must be [B={B}, nb], got {bt.shape}")
        pos = jnp.asarray(pos_arr, jnp.int32)
        if pos.ndim == 0:
            pos = jnp.broadcast_to(pos, (B,))
        max_len = bt.shape[1] * ka.shape[1]
        lens = jnp.minimum(pos + q_len, max_len)
        if am_arr is not None and tuple(am_arr.shape) != (B, q_len, q_len):
            raise ValueError(
                f"ancestor_mask must be [B={B}, q_len={q_len}, "
                f"q_len={q_len}], got {tuple(am_arr.shape)}")
        q5 = qa.reshape(B, q_len, KV, group, d)
        o = _paged_flash_decode(q5, ka, va, bt, lens, sm_scale=scale,
                                k_scale=ks_arr, v_scale=vs_arr,
                                ancestor_mask=am_arr)
        o = o.reshape(B, KV, q_len, group, d)
        o = jnp.transpose(o, (0, 2, 1, 3, 4)).reshape(B, q_len, H, d)
        return o.astype(qa.dtype)

    if is_tensor:
        return apply_op("paged_flash_decode_attention", _f, q, k_pool, v_pool)
    return _f(jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool))
