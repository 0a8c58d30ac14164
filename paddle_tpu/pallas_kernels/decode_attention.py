"""Flash-decode: GQA-native one-pass Pallas attention for the decode hot path.

The serving/generation decode step runs single-query attention (q_len
small, typically 1) against the KV cache: the paged pools
[num_blocks, block_size, kv_heads, d] behind a block table, or the
static [B, max_len, kv_heads, d] caches. The plain XLA path scores the
ENTIRE padded cache and — for GQA models — first materializes the
repeat_kv-expanded K/V in HBM, multiplying the dominant HBM stream by
heads/kv_heads. This kernel is the TPU-native fix (reference analogue:
the decode branch of phi/kernels/gpu/flash_attn_kernel.cu and the
paged-attention kernels that walk a block table):

- one pass, one output: grid (B,), a row a grid step. The row's cache is
  walked in cells of several pool blocks (``_blocks_per_cell``: 128 to
  512 positions, from the shapes against a VMEM budget), the online
  softmax (running max, sum, unnormalised accumulator) lives in VMEM
  scratch across the cells, and the row is normalised and written once,
  [KV, gq, d] in the query's dtype. Nothing per cell reaches HBM and
  nothing is left for XLA to merge.
- bounded by each row's own length: the cell loop runs ``cdiv(len,
  cell)`` times and a cell fetches only the pool blocks inside the
  length, so a short row, or a dead slot the serving engine pins to
  pos 0, costs its own blocks and not the table's width.
- manual, double-buffered DMA: the pools stay in HBM (``pl.ANY``); a
  cell's blocks are scattered, so each comes by its own
  ``make_async_copy`` through the scalar-prefetched block table, the
  next cell's (or the next row's first) in flight while this one is
  scored. A block is [block_size, kv_heads, d]: ALL kv heads in one
  contiguous transfer.
- GQA-native, every head in one matmul: the cell is turned heads-first
  in VMEM and ONE batched MXU matmul serves each kv head's whole
  [group * q_len, d] query bundle — repeat_kv never materializes, so KV
  bytes drop by the group factor (4x for Llama-70B-style ratios).
- bf16 (or fp32) streams with fp32 statistics and accumulation
  (preferred_element_type on both matmuls, stats never leave fp32).
- the contiguous cache is the same call: a pool whose table is the
  identity (``_flash_decode``).

Layout contract matches generation.make_kv_caches: q [B, q_len, heads,
d], caches [B, max_len, kv_heads, d], query head j reads kv head
j // (heads // kv_heads) (the repeat_kv mapping).

Dispatch: ``generation.cached_attention``, the one cached-attention
call under the models, asks ``decode_dispatch`` (env
``PADDLE_TPU_FLASH_DECODE``; default on for TPU backends, opt-in on CPU
where Pallas interprets), which falls back to XLA with reason counters —
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
           "paged_flash_decode_attention",
           "latent_paged_flash_decode_attention",
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


def decode_dispatch(model: str, *, paged: bool, q_len: int, has_mask: bool,
                    dtype, quantized: bool = False) -> bool:
    """The dispatch decision of one ``generation.cached_attention`` call
    (python-side, so under jit it costs nothing after the first trace):
    True -> the Pallas kernel (``paged_flash_decode_attention`` over a
    paged pool, else ``flash_decode_attention``); False -> the XLA
    fallback (gather, dequantize, masked SDPA), with the reason counted.

    Hits count under ``<model>[_paged][_quant]`` and fallbacks under
    ``[paged_][quant_]<reason>``, so a config regression that silently
    pushes a lane onto the gather fallback is visible in the metrics.
    The layouts differ in the query window alone: a paged bundle covers
    the prefill chunk (``MAX_PAGED_Q_LEN``)."""
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
    elif q_len > (MAX_PAGED_Q_LEN if paged else MAX_DECODE_Q_LEN):
        reason = "q_len"
    elif str(dtype) not in ("float32", "bfloat16"):
        reason = "dtype"
    else:
        from ..core.autograd import is_grad_enabled

        if is_grad_enabled():
            # forward-only kernel (decode is inference); taping it would
            # fail at vjp derivation
            reason = "grad_mode"
    if _obs_on[0] and reason is None:
        _fd_hits.labels(model + ("_paged" if paged else "")
                        + ("_quant" if quantized else "")).inc()
    elif _obs_on[0]:
        _fd_fallbacks.labels(("paged_" if paged else "")
                             + ("quant_" if quantized else "") + reason).inc()
    return reason is None


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
    ``decode_dispatch``; this is the engine-level preflight that
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


# VMEM the streamed part of a cell may take: both slots of the K and V
# buffers, their heads-first copies and the score-sized temporaries. What is
# left of the 100 MB limit holds the query bundle, the accumulators and
# the output block, which do not grow with the cell.
_CELL_VMEM_BYTES = 8 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _blocks_per_cell(block_size: int, nb: int, kv_heads: int, d: int,
                     kv_dtype, gq: int) -> int:
    """Pool blocks one kernel cell streams and scores at once: 128 to
    512 positions (a multiple of the block size, at most the table's
    width), as many as ``_CELL_VMEM_BYTES`` holds. A pure function of
    the shapes the call can see — nothing to configure."""
    item = jnp.dtype(kv_dtype).itemsize
    if not kv_heads:
        # a latent pool [N, bs, d]: one buffer of two slots, positions
        # on the sublanes, and one head's scores
        per_pos = 2 * d * item + 3 * _round_up(gq, 8) * 4
        positions = min(max(_CELL_VMEM_BYTES // per_pos // 128 * 128, 128),
                        512)
        return max(1, min(positions // block_size, nb))
    # VMEM tiles are 8 x 32-bit sublanes by 128 lanes: a narrow dtype
    # packs more rows a tile, and the kv-heads axis pads up to it
    per_pos = 2 * 2 * _round_up(kv_heads, 32 // item) * d * item
    per_pos += 2 * kv_heads * d * 2                # K and V, heads first
    per_pos += 3 * kv_heads * _round_up(gq, 8) * 4  # scores, weights, mask
    positions = min(max(_CELL_VMEM_BYTES // per_pos // 128 * 128, 128), 512)
    return max(1, min(positions // block_size, nb))


def _decode_kernel(lens_ref, bt_ref, q_ref, *refs, bpc: int, sm_scale: float,
                   q_len: int, group: int, bound, tree: bool,
                   latent: int = 0):
    """One batch row: the row's whole attention in one pass.

    The row's cache is walked in cells of ``bpc`` pool blocks, and only
    as far as the row's own length. A cell's blocks are scattered over
    the pool, so they come by manual DMA through the scalar-prefetched
    block table into one of two VMEM slots, the next cell's in flight
    while this one is scored (the last cell of a row starts the first
    of the next row). Each block is [block_size, KV, d]: all kv heads in
    one contiguous transfer. Running max, sum and the unnormalised
    accumulator stay in VMEM scratch in float32; the row is normalised
    and written once.

    Refs, after the scalar-prefetched per-row valid kv length
    (= pos + q_len) and block table:
      q [1, KV, gq, d]               rows r = i*group + g per kv head
      k/v pools [N, bs, KV, d]       in HBM (``pl.ANY``)
      ks/vs [1, cells * cell, KV]    quantized pools only (``bound``
                                     set): the row's per-token-per-head
                                     absmax scales, f32
      mask [1, gq, qp] f32           ``tree`` only: ancestor mask
      o [1, KV, gq, d]               normalised, in the query's dtype
    then scratch: the K and V buffers [2, cell, KV, d], DMA semaphores
    [2, 2], m/l [KV, gq, 1] and acc [KV, gq, d] f32, and the slot
    holding the row's first cell.

    Quantized cells add a DEQUANT PROLOGUE: the int8/fp8 head slab and
    its scale column are widened to the query dtype in VMEM before the
    MXU matmuls, so the HBM stream is the narrow one. ``q * s / bound``
    in that exact order matches ``quantization.intx.unpack_absmax``
    bitwise, keeping the kernel and the XLA gather fallback
    interchangeable.

    ``latent`` (a width; 0: none): ONE pool [N, bs, d] with no kv-heads
    axis, a latent (MLA) cache that every query head reads whole
    (``KV`` 1). A position's vector is its key and its first ``latent``
    columns are its value, so one buffer serves both and ``o``, ``acc``
    are ``latent`` wide."""
    n_pools = 1 if latent else 2
    pools, refs = refs[:n_pools], refs[n_pools:]
    scales = (None, None)
    if bound is not None:
        scales, refs = refs[:2], refs[2:]
    if tree:
        mask_ref, *refs = refs
    o_ref, *bufs, sems, m_scr, l_scr, acc_scr, slot_ref = refs
    bs = pools[0].shape[1]
    cell = bpc * bs
    _, kv, gq, d = q_ref.shape
    b, rows = pl.program_id(0), pl.num_programs(0)
    length = lens_ref[b]
    n_cells = pl.cdiv(length, cell)

    def fetch(row, j, slot, start: bool):
        """Start (or wait for) the blocks of the row's cell ``j`` that
        lie inside its length: none for a cell beyond it."""
        live = jnp.clip(pl.cdiv(lens_ref[row] - j * cell, bs), 0, bpc)

        def _block(i, _):
            page = bt_ref[row, j * bpc + i]
            for s, (pool, buf) in enumerate(zip(pools, bufs)):
                copy = pltpu.make_async_copy(
                    pool.at[page], buf.at[slot, pl.ds(i * bs, bs)],
                    sems.at[slot, s])
                copy.start() if start else copy.wait()
        jax.lax.fori_loop(0, live, _block, None)

    def fetch_next(j, slot):
        """Start what is scored after the row's cell ``j``: its next
        cell, or the first cell of the next row."""
        more = j + 1 < n_cells

        @pl.when(more | (b + 1 < rows))
        def _start():
            fetch(jnp.where(more, b, jnp.minimum(b + 1, rows - 1)),
                  jnp.where(more, j + 1, 0), slot, True)

    def heads_first(buf, scale_ref, slot, j):
        """A slot's [cell, KV, d] as [KV, cell, d] in the query's dtype:
        one batched matmul then serves every kv head (head by head the
        MXU took three times as long, my chip run, PR 26)."""
        if bound is None:
            return jnp.swapaxes(buf[slot], 0, 1)
        at = pl.ds(pl.multiple_of(j * cell, cell), cell)
        return jnp.stack([
            (buf[slot, :, h, :].astype(jnp.float32)
             * scale_ref[0, at, h:h + 1] / bound).astype(q_ref.dtype)
            for h in range(kv)])

    @pl.when(b == 0)
    def _first():
        # a partly filled cell is scored whole and masked: what the
        # buffers hold beyond the fetched blocks must be finite
        for buf in bufs:
            buf[...] = jnp.zeros(buf.shape, buf.dtype)
        slot_ref[0] = 0
        fetch(0, 0, 0, True)

    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
    first = slot_ref[0]

    def _score(j, _):
        slot = (first + j) % 2
        fetch_next(j, 1 - slot)
        fetch(b, j, slot, False)
        vis = _visible(length, j * cell, gq=gq, block_k=cell, q_len=q_len,
                       group=group, mask=mask_ref[0] if tree else None)
        q = q_ref[0]                              # [KV, gq, d]
        if latent:
            k = bufs[0][slot][None]               # [1, cell, d]
            v = k[..., :latent]
        else:
            k, v = (heads_first(buf, sc, slot, j)
                    for buf, sc in zip(bufs, scales))
        sc = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),   # [KV, gq, cell]
            preferred_element_type=jnp.float32,
            precision=_dot_prec(q.dtype)) * sm_scale
        sc = jnp.where(vis[None], sc, NEG_INF)
        m_prev = m_scr[...]                       # [KV, gq, 1] f32
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32, precision=_dot_prec(q.dtype))
        m_scr[...] = m_new

    @pl.when(n_cells == 0)
    def _hand_on():
        # a row of length 0 scores nothing, and hands the chain on
        fetch_next(-1, 1 - first)

    jax.lax.fori_loop(0, n_cells, _score, None)
    slot_ref[0] = (first + jnp.maximum(n_cells, 1)) % 2
    # a row that attended nothing keeps l = 0 and returns zeros
    o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                ).astype(o_ref.dtype)


def _paged_flash_decode(q5, kp, vp, bt, lens, *, sm_scale: float,
                        k_scale=None, v_scale=None, ancestor_mask=None,
                        latent: int = 0):
    """The one attention call behind both cache layouts.

    q5 [B, q_len, KV, group, d]; pools [N, bs, KV, d] (scales
    [N, bs, KV] f32, both or neither); bt [B, nb] int32: row b's logical
    block j is pool block ``bt[b, j]``; lens [B] int32 -> [B, KV, gq, d]
    in q5's dtype, rows r = i*group + g.

    ``ancestor_mask`` ([B, q_len, q_len], 1.0 = visible): per-row
    in-bundle visibility for tree-speculative verify. None compiles the
    causal bundle.

    ``latent`` (0: none): ``kp`` is a latent pool [N, bs, d] with no
    kv-heads axis and ``vp`` is None; a position's value is the first
    ``latent`` columns of its key, and the result is ``latent`` wide.

    Grid (B,): a row is one grid step, and the rows run in order (the
    fetch of a row's first cell is started by the row before it)."""
    B, q_len, KV, group, d = q5.shape
    bs, nb = kp.shape[1], bt.shape[1]
    gq = q_len * group
    # the quantized pools' format, or None
    fmt = None if k_scale is None else \
        "int8" if kp.dtype == jnp.int8 else "fp8"
    tree = ancestor_mask is not None
    bpc = _blocks_per_cell(bs, nb, 0 if latent else KV, d, kp.dtype, gq)
    # per-kv-head query bundles as whole [gq, d] tiles: merging q_len
    # into the group axis inside the cell is a sublane relayout Mosaic
    # only takes for group % 8 == 0, so it happens here in XLA (tiny)
    qk = jnp.transpose(q5, (0, 2, 1, 3, 4)).reshape(B, KV, gq, d)
    operands = [lens.astype(jnp.int32), bt.astype(jnp.int32), qk, kp]
    if not latent:
        operands.append(vp)
    cells = -(-nb // bpc)
    if fmt is not None:
        # Mosaic cannot slice an HBM ref whose minor dim is under a lane
        # tile (KV < 128), so the scales do not come by the cell's DMA:
        # XLA gathers each row's through the table (1/d of the pool's
        # bytes) and the row's whole column set rides a BlockSpec
        for sc in (k_scale, v_scale):
            sc = sc.astype(jnp.float32)[bt].reshape(B, nb * bs, KV)
            operands.append(jnp.pad(
                sc, ((0, 0), (0, (cells * bpc - nb) * bs), (0, 0))))
    qp = _round_up(q_len, 128)
    if tree:
        # rows expanded to the kernel's r = i*group + g order and the
        # contraction axis zero-padded to a lane multiple, so the cell's
        # one-hot matmul is MXU-aligned for any bundle width (29, ...)
        am = jnp.repeat(ancestor_mask.astype(jnp.float32), group, axis=1)
        operands.append(jnp.pad(am, ((0, 0), (0, 0), (0, qp - q_len))))
    return _decode_call(B, q_len, KV, group, d, bs, cells, bpc,
                        jnp.dtype(kp.dtype), jnp.dtype(q5.dtype),
                        float(sm_scale), fmt, tree, _interpret(),
                        latent)(*operands)


@functools.lru_cache(maxsize=64)
def _decode_call(B, q_len, KV, group, d, bs, cells, bpc, kv_dtype, q_dtype,
                 sm_scale, fmt, tree, interpret, latent=0):
    """The ``pallas_call`` behind ``_paged_flash_decode``, one object
    for each set of shapes. The object is a jitted (inlined) callable
    that traces ``_decode_kernel`` when it first meets its operands'
    shapes, so the layers of a program, which all call with the same,
    share ONE trace of the kernel where a fresh ``pallas_call`` a layer
    traced it 24 times a program (0.17 s each on the chip's host, a
    third of what ``warmup()`` costs an executable out of the compile
    cache: PERF.md section 6, PR 28). ``fmt`` is the quantized pools'
    format, or None; ``latent`` the value width of a latent pool
    [N, bs, d], or 0."""
    from ..quantization.intx import format_bound

    gq = q_len * group
    dv = latent or d

    def row(b, *_):
        return (b, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, KV, gq, d), row), hbm] \
        + ([] if latent else [hbm])
    if fmt is not None:
        in_specs += [pl.BlockSpec((1, cells * bpc * bs, KV),
                                  lambda b, *_: (b, 0, 0))] * 2
    if tree:
        in_specs.append(pl.BlockSpec((1, gq, _round_up(q_len, 128)),
                                     lambda b, *_: (b, 0, 0)))
    bufs = [pltpu.VMEM((2, bpc * bs, d), kv_dtype)] if latent \
        else [pltpu.VMEM((2, bpc * bs, KV, d), kv_dtype)] * 2
    scratch = bufs + [
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((KV, gq, 1), jnp.float32),
        pltpu.VMEM((KV, gq, 1), jnp.float32),
        pltpu.VMEM((KV, gq, dv), jnp.float32),
        pltpu.SMEM((1,), jnp.int32)]
    kern = functools.partial(
        _decode_kernel, bpc=bpc, sm_scale=sm_scale, q_len=q_len, group=group,
        tree=tree, bound=None if fmt is None else format_bound(fmt),
        latent=latent)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KV, gq, dv), row),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B, KV, gq, dv), q_dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the accumulators and the query and output blocks grow with
            # heads * q_len (a 256-token bundle at 32 heads needs ~24 MB
            # with the lane-padded stat columns): past Mosaic's 16 MB
            # default
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )


def _flash_decode(q5, kc, vc, lens, *, sm_scale: float, block_k: int,
                  k_scale=None, v_scale=None):
    """Contiguous caches [B, max_len, KV, d] (scales [B, max_len, KV]
    f32, both or neither) are a pool whose table is the identity: row
    b's block j is block ``b * nb + j`` of the cache cut into
    ``block_k``-position blocks (a free reshape)."""
    B, max_len, KV, d = kc.shape
    bk = pick_block(max_len, block_k)
    nb = max_len // bk
    bt = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    if k_scale is not None:
        k_scale, v_scale = (s.reshape(B * nb, bk, KV)
                            for s in (k_scale, v_scale))
    return _paged_flash_decode(
        q5, kc.reshape(B * nb, bk, KV, d), vc.reshape(B * nb, bk, KV, d),
        bt, lens, sm_scale=sm_scale, k_scale=k_scale, v_scale=v_scale)


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
    each cell is dequantized in the kernel prologue, so the HBM stream
    is the narrow one and nothing else changes.
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
    happens in the kernel prologue, cell by cell.

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


def latent_paged_flash_decode_attention(q, pool, block_table, positions, *,
                                        sm_scale: float, v_width: int,
                                        max_rows=None):
    """Multi-query attention over a paged LATENT pool (MLA, absorbed
    form): every query head reads the one vector a position holds.

    q: [B, q_len, heads, d] raw array (q_len <= MAX_PAGED_Q_LEN: a decode
    step or a prefill chunk), already in the latent's space; pool:
    [num_blocks, block_size, d], this step's vectors ALREADY scattered;
    ``block_table`` [B, nb] and ``positions`` [B] as
    ``paged_flash_decode_attention`` takes them. A position's value is
    the first ``v_width`` columns of its key, so the pool is streamed
    once. Returns [B, q_len, heads, v_width] in q's dtype: softmax(q . k
    * sm_scale) v under the causal rule of the positions. The same
    kernel as the per-head pools', with one buffer (``latent``)."""
    B, q_len, H, d = q.shape
    bt = jnp.asarray(block_table, jnp.int32)
    pos = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (B,))
    lens = jnp.minimum(pos + q_len, bt.shape[1] * pool.shape[1])
    # a chunk's q_len * heads query rows are more than a cell of the
    # cache is worth streaming for at once (their float32 accumulator
    # alone is rescaled every cell): the heads go in groups, each a row
    # of the grid that walks the same cache row
    groups = _latent_head_groups(q_len, H, max_rows)
    if groups > 1:
        hg = H // groups
        q = jnp.moveaxis(q.reshape(B, q_len, groups, hg, d), 2, 1)
        q = q.reshape(B * groups, q_len, hg, d)
        bt, lens = jnp.repeat(bt, groups, 0), jnp.repeat(lens, groups, 0)
    o = _paged_flash_decode(q.reshape(q.shape[0], q_len, 1, q.shape[2], d),
                            pool, None, bt, lens, sm_scale=sm_scale,
                            latent=int(v_width))
    if groups > 1:
        o = jnp.moveaxis(o.reshape(B, groups, q_len, H // groups, v_width),
                         1, 2)
    return o.reshape(B, q_len, H, v_width)


# query rows (q_len * heads of a group) one grid row of the latent kernel
# attends at once
_LATENT_ROWS = 2048


def _latent_head_groups(q_len: int, heads: int, max_rows=None) -> int:
    """Groups the heads of a latent bundle part into: the fewest (a
    power of two dividing ``heads``) that keep ``q_len * heads /
    groups`` at or under ``max_rows``."""
    groups = 1
    while q_len * heads // groups > (max_rows or _LATENT_ROWS) \
            and heads % (2 * groups) == 0:
        groups *= 2
    return groups
