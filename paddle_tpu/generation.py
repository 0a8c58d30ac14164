"""Autoregressive text generation with a static KV cache.

Parity: the reference ecosystem's generation loop (PaddleNLP
generation_utils / paddle.incubate fused generation ops — greedy, top-k,
top-p sampling over cache_kv). TPU design: the KV cache is a set of
pre-allocated fixed-shape buffers updated with
``lax.dynamic_update_slice`` so the whole decode step is ONE jitted
program (static shapes, no per-token recompilation); the prompt is
prefilled in a single batched forward, then the token loop drives the
cached step executable.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .core.autograd import no_grad
from .core.tensor import Tensor
from .observability import tracing as _tracing
from .observability.recompile import entrypoint as _entrypoint
from .utils.functional import functional_call

__all__ = ["GenerationConfig", "generate", "generate_uncached",
           "update_static_kv_cache", "cached_attention", "kv_cache_layout",
           "make_kv_caches", "make_cached_runner",
           "select_tokens", "split_keys", "split_key_levels",
           "spec_accept_length", "spec_tree_plan", "truncated_draft",
           "make_paged_kv_pools",
           "paged_kv_cache_write", "gather_paged_kv",
           "kv_cache_write_quant", "paged_kv_cache_write_quant",
           "gather_paged_kv_dequant", "dequantize_kv_buffer",
           "kv_format_of", "kv_cache_bytes_per_token",
           "latent_cache_width", "latent_cached_attention",
           "eva_virtual_position", "eva_pool_chunks", "eva_summary_write"]


def _is_per_row(position_offset) -> bool:
    """True when ``position_offset`` is a per-row [B] vector (the serving
    engine's continuous-batching decode, where every slot sits at its own
    sequence position) rather than a shared scalar."""
    return getattr(position_offset, "ndim", 0) == 1


def kv_cache_write(buf, new, position_offset):
    """Write a step's [b, s, h, d] block into a pre-allocated
    [b, max_len, h, d] cache buffer at ``position_offset`` (the
    TPU-native dynamic_update_slice form of the reference's cache_kv
    write; one of the two halves of ``update_static_kv_cache``).

    ``position_offset`` may be a shared scalar (whole-batch decode) or a
    per-row [b] vector (slot-batched serving decode) — the vector form
    vmaps the update so each row lands at its own position."""
    from .ops.dispatch import apply_op, ensure_tensor

    def upd(b, n):
        if _is_per_row(position_offset):
            return jax.vmap(
                lambda br, nr, off: jax.lax.dynamic_update_slice(
                    br, nr.astype(br.dtype), (off, 0, 0))
            )(b, n, position_offset)
        return jax.lax.dynamic_update_slice(
            b, n.astype(b.dtype), (0, position_offset, 0, 0))

    return apply_op("kv_cache_update", upd, ensure_tensor(buf),
                    ensure_tensor(new))


def _causal_cache_mask(position_offset, s: int, max_len: int) -> Tensor:
    """The additive causal mask over a static cache of ``max_len`` key
    positions for ``s`` query tokens starting at ``position_offset`` —
    shared by the contiguous and paged cache paths so both build the
    bit-identical mask (the engine's parity oracle depends on it)."""
    kpos = jnp.arange(max_len)
    if _is_per_row(position_offset):
        po = position_offset
        qpos = po[:, None] + jnp.arange(s)          # [b, s]
        m = (kpos[None, None, :] <= qpos[:, :, None]) \
            & (kpos[None, None, :] < (po[:, None, None] + s))
        return Tensor(jnp.where(m[:, None], 0.0, -1e30).astype(jnp.float32))
    qpos = position_offset + jnp.arange(s)
    m = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < position_offset + s)
    return Tensor(jnp.where(m[None, None], 0.0, -1e30).astype(jnp.float32))


def _tree_cache_mask(position_offset, s: int, max_len: int, tree_mask):
    """Tree-speculative variant of ``_causal_cache_mask``: the ``s``
    query rows are the flattened draft-tree bundle at cache slots
    ``position_offset + i``, and ``tree_mask`` [b, s, s] (bool, True =
    visible) says which bundle slots are each node's ancestors. A node
    sees every PAST position (< offset, untouched semantics) plus its
    ancestor-or-self set inside the bundle — never a sibling branch."""
    anc = tree_mask._data if isinstance(tree_mask, Tensor) \
        else jnp.asarray(tree_mask)
    if anc.ndim != 3 or anc.shape[1] != s or anc.shape[2] != s:
        raise ValueError(
            f"tree_mask must be [batch, {s}, {s}] (one bool row per "
            f"bundle node), got shape {tuple(anc.shape)}")
    B = anc.shape[0]
    kpos = jnp.arange(max_len)
    po = position_offset._data if isinstance(position_offset, Tensor) \
        else jnp.asarray(position_offset)
    if not _is_per_row(po):
        po = jnp.broadcast_to(po, (B,))
    past = kpos[None, None, :] < po[:, None, None]          # [b, 1, max]
    rel = kpos[None, None, :] - po[:, None, None]
    in_bundle = (rel >= 0) & (rel < s)
    relc = jnp.clip(rel, 0, s - 1)
    anc_g = jnp.take_along_axis(
        anc, jnp.broadcast_to(relc, (B, s, max_len)), axis=2)
    m = past | (in_bundle & anc_g)                          # [b, s, max]
    return Tensor(jnp.where(m[:, None], 0.0, -1e30).astype(jnp.float32))


def _cache_mask(kv_cache, position_offset, s: int, max_len: int):
    """The additive cache mask for this step: the tree-ancestor mask
    when the cache dict carries one (spec-tree bundles), else the
    shared causal mask."""
    tm = kv_cache.get("tree_mask") if isinstance(kv_cache, dict) else None
    if tm is not None:
        return _tree_cache_mask(position_offset, s, max_len, tm)
    return _causal_cache_mask(position_offset, s, max_len)


def kv_cache_layout(kv_cache: dict) -> tuple:
    """``(paged, quantized)`` of a static-cache dict: whether it carries
    a ``"bt"`` block table over [num_blocks, block_size, h, d] pools
    (else contiguous [b, max_len, h, d] rows), and whether its store is
    int8/fp8 with ``"ks"``/``"vs"`` absmax scale companions."""
    return "bt" in kv_cache, "ks" in kv_cache


def kv_format_of(arr) -> str:
    """Storage format of a KV buffer, derived from its dtype (the cache
    dict needs no extra tag: int8/fp8 storage IS the format)."""
    from .quantization import intx as _intx

    d = arr._data.dtype if isinstance(arr, Tensor) else \
        jnp.asarray(arr).dtype
    if d == jnp.int8:
        return "int8"
    fp8 = _intx.fp8_dtype()
    if fp8 is not None and d == jnp.dtype(fp8):
        return "fp8"
    return "bf16"


def kv_cache_planes(config) -> int:
    """K/V planes one cached position holds: one for every decoder
    layer, and in a looped stack (``total_ut_steps`` passes over one
    set of layers) one for every pass of every layer, since pass ``t``
    of a layer writes the keys of another hidden state than pass ``t'``
    does. Plane ``t * num_hidden_layers + i`` is pass ``t`` of layer
    ``i``. Everything that sizes a cache asks here."""
    return config.num_hidden_layers * getattr(config, "total_ut_steps", 1)


def latent_cache_width(config):
    """Values one cached position holds in each layer of a LATENT cache
    (multi-head latent attention: the normalised compressed key/value of
    ``kv_lora_rank`` and the ``qk_rope_head_dim`` rotated key dimensions
    that every head shares; nothing per head), or None for a model that
    caches per-head K and V. Everything that sizes or shapes a cache
    asks here."""
    rank = getattr(config, "kv_lora_rank", None)
    return None if rank is None else rank + config.qk_rope_head_dim


# a latent pool's last axis is padded to whole lane tiles: the paged
# kernel contracts over it and slices the value off it on tile bounds.
# The padding is in the pool's bytes and in no count of what a position
# costs to read (``kv_cache_bytes_per_token``, the benchmark's rooflines)
_LATENT_LANES = 128


def _latent_pool_width(config) -> int:
    return -(-latent_cache_width(config) // _LATENT_LANES) * _LATENT_LANES


def _refuse_latent_format(kv_format: str):
    if kv_format != "bf16":
        raise ValueError(
            f"kv_format={kv_format!r}: a latent (MLA) cache is stored "
            f"unquantized; its rotated key dimensions and its latent "
            f"share one vector and no scale pool is built for it")


def kv_cache_bytes_per_token(config, kv_format: str = "bf16",
                             dtype=jnp.float32) -> int:
    """HBM bytes one cached token costs across all planes (K + V values
    plus, for quantized formats, the per-token-per-head f32 absmax
    scales; for a latent cache the one vector a layer) — the host-side
    accounting the capacity benches and the
    ``paddle_tpu_kv_bytes_per_token`` gauge report."""
    from .quantization import intx as _intx

    width = latent_cache_width(config)
    if width is not None:
        _refuse_latent_format(kv_format)
        return width * jnp.dtype(dtype).itemsize * kv_cache_planes(config)
    n_kv = config.num_key_value_heads
    head_dim = config.hidden_size // config.num_attention_heads
    if kv_format == "bf16":
        per = n_kv * head_dim * jnp.dtype(dtype).itemsize
    else:
        per = n_kv * (head_dim * _intx.format_itemsize(kv_format) + 4)
    return 2 * per * kv_cache_planes(config)


def make_paged_kv_pools(config, num_blocks: int, block_size: int, dtype,
                        kv_format: str = "bf16"):
    """Device-resident paged KV pools: a list (one per decoder layer) of
    {"k", "v"} jnp arrays shaped [num_blocks, block_size,
    num_key_value_heads, head_dim]. Slots address the pool through
    per-slot int32 block tables instead of owning contiguous rows, so
    HBM is bounded by TOKENS IN FLIGHT, not slots * worst-case length.

    A looped stack (``kv_cache_planes`` over ``num_hidden_layers``
    passes) keeps a layer's planes in ONE array of ``passes *
    num_blocks`` blocks: pass ``t`` addresses block ``b`` as ``t *
    num_blocks + b``, so a block id still covers every plane and the
    write and the kernel are handed ``block_table + t * num_blocks``.

    ``kv_format="int8"``/``"fp8"`` stores the values in the narrow dtype
    and adds per-token-per-head absmax scale pools ``ks``/``vs``
    ([num_blocks, block_size, n_kv] f32) riding the same block structure
    — writes quantize in the scatter epilogue, reads dequantize in the
    paged flash-decode prologue (or the XLA gather fallback), so KV HBM
    traffic drops ~2x and everything else (block tables, COW, prefix
    sharing, preemption) is unchanged.

    A LATENT cache (``latent_cache_width``) is one array ``"c"`` a
    layer, [num_blocks, block_size, width padded to whole lane tiles]:
    no kv-heads axis, written and read by
    ``latent_cached_attention``."""
    from .quantization import intx as _intx

    layers = config.num_hidden_layers
    if latent_cache_width(config) is not None:
        _refuse_latent_format(kv_format)
        return [{"c": jnp.zeros((num_blocks, block_size,
                                 _latent_pool_width(config)), dtype)}
                for _ in range(layers)]
    n_kv = config.num_key_value_heads
    head_dim = config.hidden_size // config.num_attention_heads
    rows = num_blocks * (kv_cache_planes(config) // layers)
    if kv_format != "bf16":
        sdt = _intx.format_dtype(kv_format)  # raises actionably for fp8
        return [{"k": jnp.zeros((rows, block_size, n_kv, head_dim), sdt),
                 "v": jnp.zeros((rows, block_size, n_kv, head_dim), sdt),
                 "ks": jnp.zeros((rows, block_size, n_kv), jnp.float32),
                 "vs": jnp.zeros((rows, block_size, n_kv), jnp.float32)}
                for _ in range(layers)]
    return [{"k": jnp.zeros((rows, block_size, n_kv, head_dim), dtype),
             "v": jnp.zeros((rows, block_size, n_kv, head_dim), dtype)}
            for _ in range(layers)]


def paged_kv_cache_write(pool, new, block_table, position_offset,
                         valid_len=None):
    """Scatter a step's [b, s, h, d] K-or-V block into the shared
    [num_blocks, block_size, h, d] pool through per-row block tables
    (the paged analogue of ``kv_cache_write``): token j of row b lands
    in physical block ``block_table[b, (pos_b + j) // block_size]`` at
    offset ``(pos_b + j) % block_size``.

    ``valid_len`` (scalar or per-row [b]) caps how many of the ``s``
    tokens are real: padded tail tokens (chunked prefill pads the last
    chunk to the fixed chunk shape) are routed into the reserved dump
    block 0 so they can never dirty a live block."""
    from .ops.dispatch import apply_op, ensure_tensor

    bt = block_table._data if isinstance(block_table, Tensor) \
        else jnp.asarray(block_table)
    po = position_offset._data if isinstance(position_offset, Tensor) \
        else position_offset
    vl = None if valid_len is None else (
        valid_len._data if isinstance(valid_len, Tensor) else valid_len)

    def upd(p, n):
        num_blocks, bs = p.shape[0], p.shape[1]
        b, s = n.shape[0], n.shape[1]
        idx = _paged_flat_indices(bt, po, vl, num_blocks, bs, b, s)
        flat = p.reshape((num_blocks * bs,) + p.shape[2:])
        flat = flat.at[idx.reshape(-1)].set(
            n.astype(p.dtype).reshape((b * s,) + n.shape[2:]))
        return flat.reshape(p.shape)

    return apply_op("paged_kv_cache_update", upd, ensure_tensor(pool),
                    ensure_tensor(new))


def _paged_flat_indices(bt, po, vl, num_blocks, bs, b, s):
    """Flat [b, s] pool indices for a paged scatter (shared by the plain
    and quantized writes): token j of row b lands at
    ``block_table[b, (pos_b + j) // bs] * bs + (pos_b + j) % bs``;
    tokens past ``valid`` route to flat slot 0 (the dump block)."""
    pos = jnp.asarray(po, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    tpos = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    blk = jnp.clip(tpos // bs, 0, bt.shape[1] - 1)
    phys = jnp.take_along_axis(jnp.asarray(bt, jnp.int32), blk, axis=1)
    idx = phys * bs + tpos % bs
    if vl is not None:
        va = jnp.asarray(vl, jnp.int32)
        if va.ndim == 0:
            va = jnp.broadcast_to(va, (b,))
        idx = jnp.where(tpos < (pos + va)[:, None], idx, 0)
    return idx


def paged_kv_cache_write_quant(pool, scales, new, block_table,
                               position_offset, valid_len=None,
                               kv_format: str = "int8"):
    """The quantizing scatter epilogue: quantize this step's [b, s, h, d]
    K-or-V block PER TOKEN PER HEAD (absmax over d — a later token can
    never force already-written tokens to be requantized, which a
    block-wide scalar scale would) and scatter values + scales through
    the block table. Returns (pool', scales')."""
    from .ops.dispatch import apply_op, ensure_tensor
    from .quantization import intx as _intx

    bt = block_table._data if isinstance(block_table, Tensor) \
        else jnp.asarray(block_table)
    po = position_offset._data if isinstance(position_offset, Tensor) \
        else position_offset
    vl = None if valid_len is None else (
        valid_len._data if isinstance(valid_len, Tensor) else valid_len)

    def upd(p, sc, n):
        num_blocks, bs = p.shape[0], p.shape[1]
        b, s = n.shape[0], n.shape[1]
        idx = _paged_flat_indices(bt, po, vl, num_blocks, bs, b, s)
        amax = _intx.absmax_along(n, axis=-1)          # [b, s, h]
        q = _intx.pack_absmax(n, amax[..., None], kv_format)
        flat = p.reshape((num_blocks * bs,) + p.shape[2:])
        flat = flat.at[idx.reshape(-1)].set(
            q.reshape((b * s,) + q.shape[2:]))
        sflat = sc.reshape((num_blocks * bs,) + sc.shape[2:])
        sflat = sflat.at[idx.reshape(-1)].set(
            amax.reshape((b * s,) + amax.shape[2:]).astype(sc.dtype))
        return flat.reshape(p.shape), sflat.reshape(sc.shape)

    return apply_op("paged_kv_cache_update_quant", upd, ensure_tensor(pool),
                    ensure_tensor(scales), ensure_tensor(new))


def kv_cache_write_quant(buf, scales, new, position_offset,
                         kv_format: str = "int8"):
    """Contiguous twin of ``paged_kv_cache_write_quant``: quantize the
    step's [b, s, h, d] block per token per head and write values into
    the int8/fp8 [b, max_len, h, d] buffer + scales into the
    [b, max_len, h] f32 buffer at ``position_offset``. Returns
    (buf', scales')."""
    from .ops.dispatch import apply_op, ensure_tensor
    from .quantization import intx as _intx

    po = position_offset._data if isinstance(position_offset, Tensor) \
        else position_offset

    def upd(b, sc, n):
        amax = _intx.absmax_along(n, axis=-1)          # [bR, s, h]
        q = _intx.pack_absmax(n, amax[..., None], kv_format)
        amax = amax.astype(sc.dtype)
        if _is_per_row(po):
            nb = jax.vmap(
                lambda br, nr, off: jax.lax.dynamic_update_slice(
                    br, nr, (off, 0, 0)))(b, q, po)
            ns = jax.vmap(
                lambda br, nr, off: jax.lax.dynamic_update_slice(
                    br, nr, (off, 0)))(sc, amax, po)
            return nb, ns
        nb = jax.lax.dynamic_update_slice(b, q, (0, po, 0, 0))
        ns = jax.lax.dynamic_update_slice(sc, amax, (0, po, 0))
        return nb, ns

    return apply_op("kv_cache_update_quant", upd, ensure_tensor(buf),
                    ensure_tensor(scales), ensure_tensor(new))


def dequantize_kv_buffer(buf, scales, out_dtype=jnp.float32):
    """Dense dequantized view of a quantized contiguous cache (the XLA
    fallback read path): [b, max_len, h, d] storage + [b, max_len, h]
    absmax scales -> float [b, max_len, h, d]."""
    from .ops.dispatch import apply_op, ensure_tensor
    from .quantization import intx as _intx

    fmt = kv_format_of(buf)

    def g(p, sc):
        return _intx.unpack_absmax(p, sc[..., None], fmt, out_dtype)

    return apply_op("kv_cache_dequant", g, ensure_tensor(buf),
                    ensure_tensor(scales))


def gather_paged_kv(pool, block_table):
    """Materialize a slot-major [b, nb*block_size, h, d] view of the
    paged pool through the block tables — the XLA fallback read path
    (CPU lane / kernel-ineligible shapes). Logically identical to the
    contiguous [b, max_len, h, d] cache: positions past a row's length
    hold whatever the pool holds there, exactly like the contiguous
    cache holds zeros — both are exact no-ops under the additive
    causal mask."""
    from .ops.dispatch import apply_op, ensure_tensor

    bt = block_table._data if isinstance(block_table, Tensor) \
        else jnp.asarray(block_table)

    def g(p):
        out = jnp.take(p, jnp.asarray(bt, jnp.int32), axis=0)
        b, nb, bs = out.shape[0], out.shape[1], out.shape[2]
        return out.reshape((b, nb * bs) + p.shape[2:])

    return apply_op("paged_kv_gather", g, ensure_tensor(pool))


def gather_paged_kv_dequant(pool, scales, block_table,
                            out_dtype=jnp.float32):
    """Quantized-pool twin of ``gather_paged_kv``: materialize the
    slot-major view AND dequantize it in one fused op (the XLA gather
    fallback for quantized pools — on the kernel path the dequant
    happens in the Pallas prologue instead and this copy never
    exists)."""
    from .ops.dispatch import apply_op, ensure_tensor
    from .quantization import intx as _intx

    bt = block_table._data if isinstance(block_table, Tensor) \
        else jnp.asarray(block_table)
    fmt = kv_format_of(pool)

    def g(p, sc):
        bi = jnp.asarray(bt, jnp.int32)
        out = jnp.take(p, bi, axis=0)
        s_out = jnp.take(sc, bi, axis=0)
        b, nb, bs = out.shape[0], out.shape[1], out.shape[2]
        deq = _intx.unpack_absmax(out, s_out[..., None], fmt, out_dtype)
        return deq.reshape((b, nb * bs) + p.shape[2:])

    return apply_op("paged_kv_gather_dequant", g, ensure_tensor(pool),
                    ensure_tensor(scales))


def _update_paged_kv_cache(kv_cache: dict, k, v, position_offset,
                           build_mask: bool, gather: bool):
    """Paged half of ``update_static_kv_cache``: scatter the step's k/v
    through the block table, then either gather the slot-major view for
    the XLA attention paths (``gather=True``) or hand the raw pools back
    for the paged Pallas kernel (``gather=False``)."""
    bt = kv_cache["bt"]
    valid = kv_cache.get("valid")
    quant = "ks" in kv_cache
    new_cache = dict(kv_cache)
    if quant:
        fmt = kv_format_of(kv_cache["k"])
        ck, cks = paged_kv_cache_write_quant(
            kv_cache["k"], kv_cache["ks"], k, bt, position_offset, valid,
            fmt)
        cv, cvs = paged_kv_cache_write_quant(
            kv_cache["v"], kv_cache["vs"], v, bt, position_offset, valid,
            fmt)
        new_cache["ks"] = cks
        new_cache["vs"] = cvs
    else:
        ck = paged_kv_cache_write(kv_cache["k"], k, bt, position_offset,
                                  valid)
        cv = paged_kv_cache_write(kv_cache["v"], v, bt, position_offset,
                                  valid)
    new_cache["k"] = ck
    new_cache["v"] = cv
    bt_arr = bt._data if isinstance(bt, Tensor) else bt
    bs = int(ck._data.shape[1] if isinstance(ck, Tensor) else ck.shape[1])
    max_len = int(bt_arr.shape[1]) * bs
    mask = _cache_mask(kv_cache, position_offset, k.shape[1], max_len) \
        if build_mask else None
    if gather:
        if quant:
            cd = (k._data if isinstance(k, Tensor) else k).dtype
            return (gather_paged_kv_dequant(ck, cks, bt, cd),
                    gather_paged_kv_dequant(cv, cvs, bt, cd),
                    new_cache, mask)
        return (gather_paged_kv(ck, bt), gather_paged_kv(cv, bt),
                new_cache, mask)
    return ck, cv, new_cache, mask


def eva_virtual_position(pos, window: int, chunk: int):
    """Where absolute position ``pos`` sits in an EVA slot's combined
    table (chunked linearized attention: exact keys inside a window of
    ``window`` positions, one summary entry per ``chunk`` positions of
    every window behind it). The table is laid out, in entries,

        [summaries of windows 0..g-1 | window g's exact keys | window g's
         summaries, being filled]

    with ``window // chunk`` summaries a window, so position ``pos`` of
    window ``g = pos // window`` is entry ``g * window // chunk + pos %
    window``: every entry before it is one its query attends (the
    summaries behind and the window's earlier keys), which is exactly
    the paged cache's causal rule on these virtual positions. Works on
    python ints, traced scalars and per-row [B] vectors alike."""
    return (pos // window) * (window // chunk) + pos % window


def eva_pool_chunks(kc, vc, phi, mu, sm_scale: float):
    """EVA's chunk summaries: keys and values ``[..., chunk, h, d]``
    pooled over the chunk axis with the learned ``phi``, ``mu`` [h, d],

        a_j = softmax_j(sm_scale * k_j . phi);  kbar = sum_j a_j k_j + mu;
        vbar = sum_j a_j v_j

    in float32 -> (kbar, vbar) ``[..., h, d]``. Raw arrays."""
    kf, vf = kc.astype(jnp.float32), vc.astype(jnp.float32)
    a = jax.nn.softmax(sm_scale * jnp.einsum(
        "...chd,hd->...ch", kf, phi.astype(jnp.float32)), axis=-2)
    kbar = jnp.einsum("...ch,...chd->...hd", a, kf) + mu.astype(jnp.float32)
    return kbar, jnp.einsum("...ch,...chd->...hd", a, vf)


def eva_summary_write(kv_cache: dict, phi, mu, position_offset, s: int,
                      window: int, chunk: int, sm_scale: float):
    """The second write of an EVA cache: for every ``chunk``-token chunk
    that the ``s`` tokens just written at ``position_offset`` COMPLETE,
    pool the chunk's keys and values (``eva_pool_chunks``) and scatter
    ``(kbar, vbar)`` into the slot's next summary entry, which lies
    behind the window's keys in the table (``eva_virtual_position``'s
    layout) until the engine rolls the window. The chunk's keys are read
    back from the pool through the table, so a chunk that a prefill
    began and decode steps finish is pooled like any other. Chunks not
    completed by this write (and pad tokens beyond ``valid``) route to
    the dump block 0, as padded K/V writes do: the executable is the
    same whatever completes.

    ``kv_cache`` is the paged dict AFTER this step's K/V write (pools
    [N, bs, h, d], ``bt`` [b, nb], optional ``valid``); ``phi``/``mu``
    [h, d]; a bundle is one token or whole aligned chunks. Cache
    plumbing under no_grad, like the scatter it follows: it returns the
    cache with both pools updated and is no op of its own on the
    dispatch surface."""
    if s != 1 and s % chunk:
        raise ValueError(
            f"an EVA cache takes a decode step or a bundle of whole "
            f"{chunk}-token chunks, got {s} tokens")

    def raw(t):
        return t._data if isinstance(t, Tensor) else jnp.asarray(t)

    bt, kp, vp = raw(kv_cache["bt"]), raw(kv_cache["k"]), raw(kv_cache["v"])
    n_blocks, bs = kp.shape[0], kp.shape[1]
    b, n_c = bt.shape[0], max(1, s // chunk)
    pos = jnp.broadcast_to(raw(position_offset).astype(jnp.int32), (b,))
    valid = jnp.broadcast_to(
        raw(kv_cache.get("valid", s)).astype(jnp.int32), (b,))
    first = pos // chunk * chunk               # the first chunk touched
    flat = [p.reshape((n_blocks * bs,) + p.shape[2:]) for p in (kp, vp)]
    idx = _paged_flat_indices(bt, eva_virtual_position(first, window, chunk),
                              None, n_blocks, bs, b, n_c * chunk)
    pooled = eva_pool_chunks(
        *(f[idx].reshape((b, n_c, chunk) + kp.shape[2:]) for f in flat),
        raw(phi), raw(mu), sm_scale)
    # the summary entries of these chunks follow the window's keys, in
    # order; those this write did not complete go to the dump block
    ends = first[:, None] + chunk * (1 + jnp.arange(n_c))[None, :]
    done = (ends > pos[:, None]) & (ends <= (pos + valid)[:, None])
    at = _paged_flat_indices(
        bt, pos // window * (window // chunk) + window
        + first % window // chunk, None, n_blocks, bs, b, n_c)
    at = jnp.where(done, at, 0).reshape(-1)
    out = dict(kv_cache)
    for name, f, new in zip(("k", "v"), flat, pooled):
        out[name] = Tensor(f.at[at].set(new.astype(f.dtype).reshape(
            (b * n_c,) + f.shape[1:])).reshape(kp.shape))
    return out


def head_rows(h, kv_caches):
    """The hidden states [b, s, hidden] a causal LM's head has to see.
    A cached forward whose caller reads one position a row (a batch of
    prefill chunks wants each row's last prompt token alone) puts
    ``head_idx`` [b] int32 into its FIRST cache dict; the head then sees
    ``h[b, head_idx[b]]`` as [b, 1, hidden], and the [b, s, vocab]
    product is never made. Without the key, all of ``h``. Cache
    plumbing under no_grad, like the scatters above: no op of its own
    on the dispatch surface.

    A batch of single-token rows of which only some are read (the
    engine's fused step: the chunks' tokens a row each, then the decode
    rows) puts ``head_pick`` [n] int32 there instead: the head sees
    rows ``h[head_pick]`` as [n, 1, hidden]."""
    first = kv_caches[0] if isinstance(kv_caches[0], dict) else {}
    raw = lambda x: x._data if isinstance(x, Tensor) else jnp.asarray(x)  # noqa: E731
    if first.get("head_pick") is not None:
        return Tensor(h._data[raw(first["head_pick"]).astype(jnp.int32)])
    idx = first.get("head_idx")
    if idx is None:
        return h
    return Tensor(jnp.take_along_axis(
        h._data, raw(idx).astype(jnp.int32)[:, None, None], axis=1))


def looped_cache_passes(one_pass, h, kv_caches, passes: int, *, fold: bool,
                        scope: str):
    """Run ``one_pass(h, caches) -> (h, new_caches)`` ``passes`` times
    over the cache of a looped stack (``kv_cache_planes``), each pass
    starting from the state the one before left and reading and writing
    its own planes. Returns ``(h, caches)`` with the caches as they came.

    PAGED (``make_paged_kv_pools``: one dict a layer, the layer's passes
    side by side in one array of ``passes * num_blocks`` blocks): pass
    ``t`` is handed the dicts with ``block_table + t * num_blocks``, so
    the write and the kernel need nothing else, and with ``fold`` the
    passes are one ``lax.fori_loop`` that carries the state and the
    pools: the program holds one pass's layer bodies, not ``passes``
    times as many (unfolded: the same arithmetic in the same order).
    CONTIGUOUS (``make_kv_caches``: a buffer a plane): pass ``t`` is
    handed buffers ``t * L .. (t + 1) * L - 1``, walked unrolled.
    ``scope`` names each pass in the device trace."""
    paged, quantized = kv_cache_layout(kv_caches[0])
    if not paged:
        if len(kv_caches) % passes:
            raise ValueError(
                f"a looped stack of {passes} passes keeps a contiguous "
                f"cache buffer for every pass and layer (make_kv_caches), "
                f"got {len(kv_caches)}")
        n, new_caches = len(kv_caches) // passes, []
        for t in range(passes):
            h, nc = one_pass(h, kv_caches[t * n:(t + 1) * n])
            new_caches += nc
        return h, new_caches
    if quantized:
        raise ValueError(
            "a looped stack's paged cache is an unquantized pool: the "
            "scale pools are not carried through the passes")
    raw = lambda x: x._data if isinstance(x, Tensor) else x  # noqa: E731
    bt, rows = raw(kv_caches[0]["bt"]), kv_caches[0]["k"].shape[0]
    if rows % passes:
        raise ValueError(
            f"a pool of {rows} blocks does not hold {passes} passes side "
            f"by side (make_paged_kv_pools)")

    def one(t, hd, kv):
        """Pass ``t`` over the pools ``kv`` ([(k, v)] a layer)."""
        with jax.named_scope(scope):
            table = Tensor(bt + t * (rows // passes))
            caches = [dict(c, k=Tensor(k), v=Tensor(v), bt=table)
                      for c, (k, v) in zip(kv_caches, kv)]
            out, nc = one_pass(Tensor(hd), caches)
        return out._data, [(c["k"]._data, c["v"]._data) for c in nc]

    kv = [(raw(c["k"]), raw(c["v"])) for c in kv_caches]
    if fold:
        hd, kv = jax.lax.fori_loop(
            0, passes, lambda t, carry: one(t, *carry), (h._data, kv))
    else:
        hd = h._data
        for t in range(passes):
            hd, kv = one(t, hd, kv)
    return Tensor(hd), [dict(c, k=Tensor(k), v=Tensor(v))
                        for c, (k, v) in zip(kv_caches, kv)]


def update_static_kv_cache(kv_cache: dict, k, v, position_offset,
                           build_mask: bool = True, gather: bool = True):
    """The static-cache protocol shared by the decoder models (llama/
    gpt): write this step's k/v [b, s, h, d] into the pre-allocated
    [b, max_len, h, d] buffers at ``position_offset`` and (unless the
    caller brings its own attn_mask — ``build_mask=False``) build the
    additive causal mask exposing only positions < offset + s.
    Returns (k_full, v_full, new_cache, mask_or_None).

    A per-row [b] ``position_offset`` vector produces per-row writes and
    a per-row [b, 1, s, max_len] mask (slots at different positions in
    one batch — the serving engine's decode step).

    PAGED caches (dict carries a ``"bt"`` block table, pools shaped
    [num_blocks, block_size, h, d]) scatter the write through the table
    instead; ``gather=True`` additionally materializes the slot-major
    [b, nb*block_size, h, d] view for the XLA attention fallbacks, while
    ``gather=False`` (the paged-kernel path, which reads the pool
    directly) skips that copy and returns the raw pools as (k, v)."""
    paged, quantized = kv_cache_layout(kv_cache)
    if paged:
        return _update_paged_kv_cache(kv_cache, k, v, position_offset,
                                      build_mask, gather)
    if quantized:  # int8/fp8 contiguous cache
        fmt = kv_format_of(kv_cache["k"])
        ck, cks = kv_cache_write_quant(kv_cache["k"], kv_cache["ks"], k,
                                       position_offset, fmt)
        cv, cvs = kv_cache_write_quant(kv_cache["v"], kv_cache["vs"], v,
                                       position_offset, fmt)
        new_cache = dict(kv_cache)
        new_cache.update({"k": ck, "v": cv, "ks": cks, "vs": cvs})
        mask = None
        if build_mask:
            max_len = int(ck._data.shape[1] if isinstance(ck, Tensor)
                          else ck.shape[1])
            mask = _cache_mask(kv_cache, position_offset, k.shape[1],
                               max_len)
        if gather:
            cd = (k._data if isinstance(k, Tensor) else k).dtype
            return (dequantize_kv_buffer(ck, cks, cd),
                    dequantize_kv_buffer(cv, cvs, cd), new_cache, mask)
        return ck, cv, new_cache, mask
    ck = kv_cache_write(kv_cache["k"], k, position_offset)
    cv = kv_cache_write(kv_cache["v"], v, position_offset)
    mask = None
    if build_mask:
        s = k.shape[1]
        max_len = int(ck._data.shape[1] if isinstance(ck, Tensor) else ck.shape[1])
        mask = _cache_mask(kv_cache, position_offset, s, max_len)
    new_cache = dict(kv_cache)
    new_cache.update({"k": ck, "v": cv})
    return ck, cv, new_cache, mask


def cached_attention(q, k, v, kv_cache: dict, position_offset, *, family: str,
                     attn_mask=None, flash_prefill: bool = False,
                     after_write=None):
    """Attention of one cached forward, the one place that knows which
    kernel reads which cache: write this call's rotated ``k``/``v``
    [b, s, kv_heads, d] into ``kv_cache`` at ``position_offset`` (python
    int, traced scalar or per-row [b]; ``update_static_kv_cache``), then
    attend ``q`` [b, s, heads, d] over what the cache holds up to each
    query's own position. Returns ``(out [b, s, heads, d], new_cache)``.

    ``decode_dispatch`` decides the reader and counts it under
    ``family``: the Pallas flash-decode kernel over the raw buffers or
    pools (GQA-native, int8/fp8 dequantized in its prologue, a spec-tree
    bundle's ``"tree_mask"`` as the paged kernel's ancestor mask), or the
    XLA fallback over the dense view under the additive mask (grouped
    where ``k`` has fewer heads than ``q``). An external ``attn_mask``
    (ragged left-padded prompts) replaces the built mask and declines the
    kernel, as a tree bundle over a contiguous cache does: that kernel
    has no mask input.

    What a model's own layout needs, it passes in. ``flash_prefill``: a
    prompt at offset 0 of a contiguous cache runs the flash-attention
    kernel over the step's ``k``/``v`` alone (equal to the masked
    attention over the padded cache; a paged chunk must read earlier
    blocks through its table and never takes it). ``after_write(cache)
    -> cache`` runs between the write and the read (a second write of
    the same step: EVA's chunk summaries).

    A paged cache that also carries ``"chunk_bt"`` [P, nb] (and
    ``"chunk_valid"`` [P]) holds the engine's fused step: a batch of
    single-token rows, the first ``P * C`` of them P prefill chunks of C
    tokens through ``chunk_bt``, the rest the decode rows through
    ``"bt"`` (``_chunk_and_step_rows_attention``)."""
    if "chunk_bt" in kv_cache:
        return _chunk_and_step_rows_attention(
            q, k, v, kv_cache, position_offset, family=family,
            attn_mask=attn_mask, after_write=after_write)
    paged, _ = kv_cache_layout(kv_cache)
    s = q.shape[1]
    if flash_prefill and not paged and attn_mask is None and s > 1 \
            and isinstance(position_offset, int) and position_offset == 0:
        _, _, new_cache, _ = update_static_kv_cache(
            kv_cache, k, v, 0, build_mask=False, gather=False)
        return _flash_causal_attention(q, k, v), new_cache
    written = _write_for_attention(q, k, v, kv_cache, position_offset,
                                   family, attn_mask, after_write)
    return _attend_written(q, written, position_offset, attn_mask), written[3]


def _write_for_attention(q, k, v, kv_cache: dict, position_offset, family,
                         attn_mask, after_write):
    """The first half of ``cached_attention``: ask ``decode_dispatch``
    who reads, write ``k``/``v`` into the cache for that reader, run
    ``after_write``. Returns ``(kernel, k_view, v_view, new_cache,
    mask)``, what ``_attend_written`` takes."""
    from .pallas_kernels.decode_attention import decode_dispatch

    paged, quantized = kv_cache_layout(kv_cache)
    tree_mask = kv_cache.get("tree_mask")
    kernel = decode_dispatch(
        family, paged=paged, q_len=q.shape[1], dtype=q.dtype,
        quantized=quantized,
        has_mask=attn_mask is not None
        or (tree_mask is not None and not paged))
    kf, vf, new_cache, mask = update_static_kv_cache(
        kv_cache, k, v, position_offset,
        build_mask=attn_mask is None and not kernel, gather=not kernel)
    if after_write is not None:
        new_cache = after_write(new_cache)
    return kernel, kf, vf, new_cache, mask


def _attend_written(q, written, position_offset, attn_mask):
    """The second half: ``q`` over what ``_write_for_attention`` left,
    by the Pallas kernel over the raw buffers or pools, or the XLA
    fallback over the dense view under the mask."""
    from .nn import functional as F
    from .pallas_kernels.decode_attention import (
        flash_decode_attention, paged_flash_decode_attention)

    kernel, kf, vf, new_cache, mask = written
    if not kernel:
        sdpa = F.grouped_query_sdpa if kf.shape[2] != q.shape[2] \
            else F.scaled_dot_product_attention
        return sdpa(q, kf, vf, attn_mask=mask if attn_mask is None
                    else attn_mask)
    scales = {"k_scale": new_cache.get("ks"), "v_scale": new_cache.get("vs")}
    if kv_cache_layout(new_cache)[0]:
        return paged_flash_decode_attention(
            q, new_cache["k"], new_cache["v"], new_cache["bt"],
            position_offset, ancestor_mask=new_cache.get("tree_mask"),
            **scales)
    return flash_decode_attention(q, kf, vf, position_offset, **scales)


def _chunk_and_step_rows_attention(q, k, v, kv_cache: dict, position_offset,
                                   **how):
    """``cached_attention`` of the engine's fused step. ``q``/``k``/``v``
    [P * C + B, 1, heads, d] are one batch of single-token rows, each at
    its own ``position_offset`` [P * C + B], so that the linear layers
    around this call see all of an iteration's rows in one pass over
    the weights; here they part again. Rows ``[:P * C]`` are P prefill
    chunks of C tokens, written through ``kv_cache["chunk_bt"]`` [P, nb]
    up to ``kv_cache["chunk_valid"]`` [P] and attended as [P, C] (a
    chunk's start is its first row's position); rows ``[P * C:]`` are
    the B decode rows through ``kv_cache["bt"]`` [B, nb], attended as
    [B, 1]: the two calls that the prefill program and the decode step
    make on their own, with the same arithmetic a row. A slot is in one
    part or in neither, so neither part reads a block the other writes
    (both write the dump block, which nobody reads), and BOTH parts are
    written before either is read: a kernel that still read the pools
    while the second write updated them in place would have XLA copy
    every pool, 6.4 GB a program at the served sizes (PERF.md section 6,
    PR 38). P, C and B follow from the shapes.

    The device trace tells the two kernel calls apart by the scope
    they are in (XLA names a Pallas call by its innermost scope):
    the decode rows' under ``_step_rows`` and the prefill rows' under
    ``_chunk_rows``, so that what reads the decode kernel's time of a
    program called ``_step`` by ``^_step.*custom-call$`` finds the
    decode rows' call and no other."""
    raw = lambda x: x._data if isinstance(x, Tensor) else x  # noqa: E731
    cbt, valid, bt = (kv_cache[key]
                      for key in ("chunk_bt", "chunk_valid", "bt"))
    P, B = raw(cbt).shape[0], raw(bt).shape[0]
    n = q.shape[0] - B
    pos = raw(position_offset)
    pos_c, pos_s = pos[:n:n // P], pos[n:]

    def parts(t):
        t = raw(t)
        return (Tensor(t[:n].reshape((P, n // P) + t.shape[2:])),
                Tensor(t[n:]))

    (qc, qs), (kc, ks), (vc, vs) = parts(q), parts(k), parts(v)
    cache = {key: val for key, val in kv_cache.items()
             if key not in ("chunk_bt", "chunk_valid")}
    chunk = _write_for_attention(
        qc, kc, vc, dict(cache, bt=cbt, valid=valid), pos_c, **how)
    pools = {key: val for key, val in chunk[3].items() if key != "valid"}
    step = _write_for_attention(qs, ks, vs, dict(pools, bt=bt), pos_s, **how)
    # the chunks' kernel reads the pools as the decode rows' write left
    # them (its XLA fallback: the dense view it gathered after its own)
    chunk = chunk[:3] + (dict(step[3], bt=cbt),) + chunk[4:]
    with jax.named_scope("_chunk_rows"):
        out_c = raw(_attend_written(qc, chunk, pos_c, how["attn_mask"]))
    with jax.named_scope("_step_rows"):
        out_s = raw(_attend_written(qs, step, pos_s, how["attn_mask"]))
    out = jnp.concatenate([out_c.reshape((n, 1) + out_c.shape[2:]), out_s])
    return Tensor(out), dict(step[3], chunk_bt=cbt, chunk_valid=valid)


# -- the latent (MLA) cache ---------------------------------------------------

def latent_absorb_below(rank: int, d_nope: int, d_v: int) -> int:
    """Query rows that must share the positions they attend before
    DECOMPRESSING those positions costs less than attending them in the
    latent's space. Per query row, position and head the absorbed form
    multiplies ``2 * rank`` values and the decompressed form ``d_nope +
    d_v`` (and both the rotated key dimensions); decompressing a position costs ``rank *
    (d_nope + d_v)`` a head, once for all the rows that share it. Below
    the ratio (171 at DeepSeek-V2's widths) a call attends absorbed. A
    pure function of the shapes: nothing to configure."""
    return -(-rank * (d_nope + d_v) // max(1, 2 * rank - d_nope - d_v))


def _latent_write(pool, latent, bt, pos, valid):
    """Scatter ``latent`` [b, s, width] (zero-padded to the pool's) into
    ``pool`` [N, bs, padded width] through the block tables; rows past
    ``valid`` go to the dump block, as ``paged_kv_cache_write``'s do."""
    n_blocks, bs, pw = pool.shape
    b, s, w = latent.shape
    idx = _paged_flat_indices(bt, pos, valid, n_blocks, bs, b, s)
    new = jnp.pad(latent.astype(pool.dtype), ((0, 0), (0, 0), (0, pw - w)))
    flat = pool.reshape(n_blocks * bs, pw).at[idx.reshape(-1)].set(
        new.reshape(b * s, pw))
    return flat.reshape(pool.shape)


def _latent_absorbed(q_nope, q_pe, pool, bt, pos, w_kvb, sm_scale, kernel):
    """Attention in the latent's space: ``q_abs_h = q_nope_h W_uk_h^T``
    beside ``q_pe_h`` against the cached vector, the weighted sum of
    latents through ``W_uv_h``. By the paged kernel over the pool, or in
    XLA over the dense view under the causal mask."""
    from .pallas_kernels.decode_attention import \
        latent_paged_flash_decode_attention

    b, s, heads, dn = q_nope.shape
    rank, pw = w_kvb.shape[0], pool.shape[-1]
    q_abs = jnp.einsum("bshd,rhd->bshr", q_nope,
                       w_kvb[..., :dn].astype(q_nope.dtype))
    q = jnp.concatenate(
        [q_abs, q_pe, jnp.zeros((b, s, heads, pw - rank - q_pe.shape[-1]),
                                q_abs.dtype)], -1)
    with jax.named_scope(f"mla_absorbed_q{s}"):
        if kernel:
            o_lat = latent_paged_flash_decode_attention(
                q, pool, bt, pos, sm_scale=sm_scale, v_width=rank)
        else:
            lat = pool[bt].reshape(b, -1, pw)       # [b, L, pw]
            sc = jnp.einsum("bshw,bkw->bhsk", q, lat.astype(q.dtype),
                            preferred_element_type=jnp.float32) * sm_scale
            mask = _causal_cache_mask(pos, s, lat.shape[1])._data
            p = jax.nn.softmax(sc + mask, axis=-1)
            o_lat = jnp.einsum("bhsk,bkr->bshr", p.astype(q.dtype),
                               lat[..., :rank].astype(q.dtype))
    return jnp.einsum("bshr,rhd->bshd", o_lat,
                      w_kvb[..., dn:].astype(o_lat.dtype))


# positions a step of the decompressed form decompresses at once
_DECOMPRESS_POSITIONS = 512


def _latent_decompressed(q_nope, q_pe, pool, bt, pos, w_kvb, sm_scale):
    """Attention on decompressed keys and values: the cached positions
    come through the table a stretch at a time, ``[k_nope | v] = c_kv
    W_kvb`` for every head, and an online softmax (float32 statistics)
    carries the rows over the stretches, as far as the longest row
    reaches. Position 0 is in the first stretch and every query sees
    it, so the running maximum is a real score from there on."""
    b, s, heads, dn = q_nope.shape
    rank, dr = w_kvb.shape[0], q_pe.shape[-1]
    dv = w_kvb.shape[-1] - dn
    bs, nb = pool.shape[1], bt.shape[1]
    kb = max(1, min(_DECOMPRESS_POSITIONS // bs, nb))
    span = kb * bs
    bt = jnp.pad(bt, ((0, 0), (0, -nb % kb)))
    qpos = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    w = w_kvb.astype(q_nope.dtype)

    def stretch(j, carry):
        m, l, acc = carry
        lat = pool[jax.lax.dynamic_slice_in_dim(bt, j * kb, kb, 1)]
        lat = lat.reshape(b, span, -1).astype(q_nope.dtype)
        kv = jnp.einsum("bkr,rhd->bkhd", lat[..., :rank], w)
        sc = (jnp.einsum("bshd,bkhd->bhsk", q_nope, kv[..., :dn],
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshd,bkd->bhsk", q_pe, lat[..., rank:rank + dr],
                           preferred_element_type=jnp.float32)) * sm_scale
        kpos = j * span + jnp.arange(span, dtype=jnp.int32)
        seen = kpos[None, None, :] <= qpos[:, :, None]          # [b, s, k]
        sc = jnp.where(seen[:, None], sc, -1e30)
        m_new = jnp.maximum(m, sc.max(-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        acc = alpha * acc + jnp.einsum(
            "bhsk,bkhd->bhsd", p.astype(kv.dtype), kv[..., dn:],
            preferred_element_type=jnp.float32)
        return m_new, alpha * l + p.sum(-1, keepdims=True), acc

    with jax.named_scope(f"mla_decompressed_q{s}"):
        reach = jnp.max(pos) + s
        m, l, acc = jax.lax.fori_loop(
            0, (reach + span - 1) // span, stretch,
            (jnp.full((b, heads, s, 1), -1e30, jnp.float32),
             jnp.zeros((b, heads, s, 1), jnp.float32),
             jnp.zeros((b, heads, s, dv), jnp.float32)))
        out = acc / jnp.maximum(l, 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q_nope.dtype)


def _latent_attend(q_nope, q_pe, pool, bt, pos, w_kvb, sm_scale, family):
    """``q`` over what the pool holds. Where the paged kernel takes the
    call (a decode step or a chunk inside its query window, on the
    chip) it attends absorbed whatever the rows: in head groups the
    kernel runs at 73% of the MXU's peak, 2.06 ns a query row and
    position, where the decompressed form's fewer operations are bound
    by the softmax's own vector work at 2.0-2.7 ns (PERF.md section 6,
    PR 43). In XLA the shapes decide (``latent_absorb_below``: the
    absorbed form keeps a whole row of scores, the decompressed one a
    stretch of positions)."""
    from .pallas_kernels.decode_attention import decode_dispatch

    s, dn = q_nope.shape[1], q_nope.shape[-1]
    rank, dv = w_kvb.shape[0], w_kvb.shape[-1] - dn
    kernel = decode_dispatch(family, paged=True, q_len=s, has_mask=False,
                             dtype=q_nope.dtype)
    if kernel or s < latent_absorb_below(rank, dn, dv):
        return _latent_absorbed(q_nope, q_pe, pool, bt, pos, w_kvb, sm_scale,
                                kernel)
    return _latent_decompressed(q_nope, q_pe, pool, bt, pos, w_kvb, sm_scale)


def latent_cached_attention(q_nope, q_pe, latent, kv_cache: dict,
                            position_offset, *, w_kvb, sm_scale: float,
                            family: str):
    """Attention of one cached forward over a LATENT cache (multi-head
    latent attention), ``cached_attention``'s twin: write this call's
    ``latent`` [b, s, rank + d_rope] (the normalised compressed
    key/value and the rotated key dimensions all heads share) into
    ``kv_cache["c"]`` at ``position_offset``, then attend ``q_nope``
    [b, s, heads, d_nope] and the rotated ``q_pe`` [b, s, heads, d_rope]
    over what the cache holds up to each query's own position. ``w_kvb``
    [rank, heads, d_nope + d_v] is the layer's decompression, used on
    the keys and values (decompressed form) or folded into the query and
    the output (absorbed form): the same mathematics, and
    ``_latent_attend`` takes the one that is cheaper for this call. Raw arrays in, ``(out [b, s, heads, d_v],
    new_cache)`` out; cache plumbing under no_grad, no op of its own on
    the dispatch surface.

    A paged cache carries ``"bt"`` (and ``"valid"``); a contiguous one
    ([b, max_len, width]) is a pool whose table is the identity. With
    ``"chunk_bt"`` the call is the engine's fused step (a batch of
    single-token rows, the first ``P * C`` of them P prefill chunks; see
    ``_chunk_and_step_rows_attention``): both parts are written, then
    each is attended as the prefill program and the decode step attend
    alone."""
    raw = lambda x: x._data if isinstance(x, Tensor) else x  # noqa: E731
    how = dict(w_kvb=w_kvb, sm_scale=sm_scale, family=family)
    pool = raw(kv_cache["c"])
    pos = jnp.asarray(raw(position_offset), jnp.int32)
    if "bt" not in kv_cache:
        b, max_len, pw = pool.shape
        bs = next(x for x in (16, 8, 4, 2, 1) if max_len % x == 0)
        bt = jnp.arange(b * (max_len // bs), dtype=jnp.int32).reshape(b, -1)
        out, new = latent_cached_attention(
            q_nope, q_pe, latent,
            dict(kv_cache, c=pool.reshape(-1, bs, pw), bt=bt),
            jnp.broadcast_to(pos, (b,)), **how)
        new = {k: v for k, v in new.items() if k != "bt"}
        return out, dict(new, c=Tensor(raw(new["c"]).reshape(pool.shape)))
    bt = jnp.asarray(raw(kv_cache["bt"]), jnp.int32)
    if "chunk_bt" in kv_cache:
        cbt = jnp.asarray(raw(kv_cache["chunk_bt"]), jnp.int32)
        valid = raw(kv_cache["chunk_valid"])
        P, B = cbt.shape[0], bt.shape[0]
        n = q_nope.shape[0] - B
        pos_c, pos_s = pos[:n:n // P], pos[n:]

        def parts(t):
            return t[:n].reshape((P, n // P) + t.shape[2:]), t[n:]

        (qn_c, qn_s), (qp_c, qp_s), (lat_c, lat_s) = (
            parts(q_nope), parts(q_pe), parts(latent))
        # both written before either is read (PERF.md section 6, PR 38)
        pool = _latent_write(pool, lat_c, cbt, pos_c, valid)
        pool = _latent_write(pool, lat_s, bt, pos_s, None)
        with jax.named_scope("_chunk_rows"):
            out_c = _latent_attend(qn_c, qp_c, pool, cbt, pos_c, **how)
        with jax.named_scope("_step_rows"):
            out_s = _latent_attend(qn_s, qp_s, pool, bt, pos_s, **how)
        out = jnp.concatenate(
            [out_c.reshape((n, 1) + out_c.shape[2:]), out_s])
        return out, dict(kv_cache, c=Tensor(pool))
    valid = kv_cache.get("valid")
    pos = jnp.broadcast_to(pos, (bt.shape[0],))
    pool = _latent_write(pool, latent, bt, pos,
                         None if valid is None else raw(valid))
    out = _latent_attend(q_nope, q_pe, pool, bt, pos, **how)
    return out, dict(kv_cache, c=Tensor(pool))


def _flash_causal_attention(q, k, v):
    """Causal flash attention of a whole prompt [b, s, h, d] against its
    own ``k``/``v`` [b, s, kv_heads, d]: the heads expanded (the Pallas
    prefill kernel wants them so) and the prompt padded to the kernel's
    128 grid. Padded queries are sliced off, and causal masking means no
    real query (row < s) ever attends a padded key (row >= s)."""
    from .nn.functional import repeat_kv
    from .pallas_kernels.flash_attention import flash_attention

    s, rep = q.shape[1], q.shape[2] // k.shape[2]
    if rep > 1:
        k, v = repeat_kv(k, rep), repeat_kv(v, rep)
    if s % 128 == 0:
        return flash_attention(q, k, v, causal=True)
    pad = ((0, 0), (0, 128 - s % 128), (0, 0), (0, 0))
    q, k, v = (Tensor(jnp.pad(t._data, pad)) for t in (q, k, v))
    return flash_attention(q, k, v, causal=True)[:, :s]


def _mask_after_eos(gen, eos_id):
    """Replace everything after the first EOS with EOS (post-hoc, static)."""
    is_eos = gen == eos_id
    seen = jnp.cumsum(is_eos.astype(jnp.int32), axis=1) - is_eos.astype(jnp.int32)
    return jnp.where(seen > 0, eos_id, gen)


@dataclass
class GenerationConfig:
    max_new_tokens: int = 32
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token_id: Optional[int] = None
    seed: int = 0


def _select_token(logits, cfg: GenerationConfig, key):
    """logits [B, V] -> next token [B]."""
    if not cfg.do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(cfg.temperature, 1e-6)
    if cfg.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -cfg.top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if cfg.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest logit value still inside the nucleus
        inside = cum - probs < cfg.top_p
        cutoff = jnp.min(jnp.where(inside, sorted_logits, jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def split_keys(keys):
    """Per-row PRNG advance: [B, 2] keys -> (new_keys [B, 2], subkeys
    [B, 2]), each row exactly ``jax.random.split(key)`` for that row —
    so a slot's key chain inside a batched decode step reproduces the
    ``key, sub = jax.random.split(key)`` chain ``generate`` drives for a
    single request."""
    pairs = jax.vmap(jax.random.split)(keys)  # [B, 2, 2]
    return pairs[:, 0], pairs[:, 1]


def split_key_levels(keys, n: int):
    """Walk the per-row chain ``n`` levels ahead WITHOUT committing:
    [B, 2] keys -> (levels [B, n+1, 2], subs [B, n, 2]) where
    ``levels[:, j]`` is each row's chain key after ``j`` splits
    (``levels[:, 0]`` is the input) and ``subs[:, j]`` is the subkey the
    j+1-th split yields — exactly the subkey ``split_keys`` would hand
    the sampler for the j+1-th emitted token.

    Speculative decoding needs the chain pre-walked: the verify step
    selects up to ``n`` candidate tokens with their per-token subkeys in
    one program, then commits the chain at ``levels[:, n_emit]`` — one
    split per EMITTED token, so the slot's key state stays the exact
    function of (seed, tokens emitted) the preemption-resume replay
    depends on."""
    levels, subs = [keys], []
    for _ in range(n):
        keys, sub = split_keys(keys)
        levels.append(keys)
        subs.append(sub)
    return jnp.stack(levels, axis=1), jnp.stack(subs, axis=1)


def spec_accept_length(drafts, candidates, spec_len):
    """Accepted-prefix emit count for one speculative verify round.

    ``drafts`` [B, k] are the proposed tokens, ``candidates`` [B, k+1]
    the target-model selections for every bundle position (candidate j
    is the token the target emits AFTER bundle position j, valid as
    long as every earlier draft matched), ``spec_len`` [B] the per-row
    live bundle width (0 = row idle). Returns ``n_emit`` [B] int32: the
    emitted tokens are ``candidates[b, :n_emit[b]]``.

    This is the Leviathan/Chen acceptance rule under the common-noise
    coupling this repo uses (draft and target select with the SAME
    per-position subkey): accept-with-prob-min(1, p/q) collapses to an
    exact token match, every emitted token is literally the one the
    non-speculative sampler would have drawn, and the target
    distribution is preserved because the output SEQUENCE is
    bit-identical to non-speculative decode — greedy and sampled both."""
    k = drafts.shape[1]
    match = (drafts == candidates[:, :k]).astype(jnp.int32)
    n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
    return jnp.minimum(n_acc + 1, jnp.asarray(spec_len, jnp.int32))


def spec_tree_plan(spec_tree):
    """Static host-side descriptor of a draft token tree with per-level
    branching factors ``spec_tree`` (e.g. ``[4, 2, 2]``): level 0 is the
    single root (the slot's current last token), level t+1 holds
    ``factors[t]`` children per level-t node, and nodes are flattened in
    BFS order — so every ancestor has a LOWER index than its
    descendants, which is what lets a per-row BFS-prefix width act as a
    truncated (shallower) tree.

    Returns a dict of numpy arrays (all static, shared by the offline
    oracle, the serving engine, and the tests):

    - ``factors`` tuple, ``depth`` D, ``nodes`` w, ``offsets`` [D+2]
      (``offsets[t]`` = first BFS index of level t, ``offsets[D+1]`` = w)
    - ``parent`` [w] int32 (``parent[0] == 0``)
    - ``depth_vec`` [w] int32 (level of each node)
    - ``anc_idx`` [w, D+1] int32: ``anc_idx[i, t]`` = node i's ancestor
      at depth t (padded with i itself past node i's depth — padded
      entries are never committed, the emit gate stops at the depth)
    - ``anc`` [w, w] bool: ancestor-or-self adjacency, the tree
      attention mask"""
    factors = tuple(int(f) for f in spec_tree)
    if not factors or any(f < 1 for f in factors):
        raise ValueError(
            f"spec_tree must be a non-empty sequence of branching "
            f"factors >= 1 per draft level, got {spec_tree!r}")
    depth = len(factors)
    offsets = [0, 1]
    wl = 1
    for f in factors:
        wl *= f
        offsets.append(offsets[-1] + wl)
    w = offsets[-1]
    parent = np.zeros(w, np.int32)
    depth_vec = np.zeros(w, np.int32)
    for t in range(depth):
        f = factors[t]
        for r in range(offsets[t + 2] - offsets[t + 1]):
            i = offsets[t + 1] + r
            parent[i] = offsets[t] + r // f
            depth_vec[i] = t + 1
    anc = np.eye(w, dtype=bool)
    for i in range(1, w):
        anc[i] |= anc[parent[i]]
    anc_idx = np.zeros((w, depth + 1), np.int32)
    for i in range(w):
        chain = [i]
        while chain[-1] != 0:
            chain.append(int(parent[chain[-1]]))
        chain.reverse()
        for t in range(depth + 1):
            anc_idx[i, t] = chain[t] if t < len(chain) else i
    return {"factors": factors, "depth": depth, "nodes": w,
            "offsets": np.asarray(offsets, np.int32), "parent": parent,
            "depth_vec": depth_vec, "anc_idx": anc_idx, "anc": anc}


# Bounded-nucleus fast path for select_tokens: a full-vocab XLA sort is
# by far the most expensive op in a decode step (CPU: ~8x a
# lax.top_k(256) on a [4, 4096] batch), so rows whose top-k filter fits
# this bound take a top_k-only path. The fallback keeps it EXACT — see
# select_tokens.
_NUCLEUS_BOUND = 256


def select_tokens(logits, keys, do_sample, temperature, top_k, top_p):
    """Per-row token selection with TRACED sampling params: [B, V]
    logits -> [B] tokens, where each row carries its own ``do_sample`` /
    ``temperature`` / ``top_k`` / ``top_p`` / PRNG key. Mixed greedy and
    sampled requests therefore share ONE compiled step program (the
    serving engine's requirement); row-wise the math is exactly
    ``_select_token`` on that row alone, so a slot's tokens match a
    standalone ``generate`` call with the same config and key chain.

    ``top_k <= 0`` and ``top_p >= 1.0`` disable their filters per row
    (same semantics as the static config path).

    Bit-exactness of the fast path: when every sampled row has
    ``0 < top_k <= _NUCLEUS_BOUND`` (and no tie straddles the bound),
    the kept set lives entirely in the top-K values, so padding those
    back to width V with -1e30 reproduces the EXACT masked-sorted array
    the full-sort path builds — every downstream softmax/cumsum/cutoff
    runs on an identical array and is bit-identical, whatever the
    backend's reduction groupings. Any row outside that envelope
    (top-p-only sampling, huge top_k, boundary ties) flips a runtime
    ``lax.cond`` to the full sort."""
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits / jnp.maximum(temperature, 1e-6)[:, None]
    K = min(_NUCLEUS_BOUND, V)

    def _filter(sorted_desc):
        """Width-V filter math given the descending-sorted logits:
        top-k threshold at the k-th largest, then the top-p nucleus
        over the k-filtered distribution (the single-sort form: the
        'sorted filtered' array is the sorted array with the < kth
        suffix dropped to -1e30, since filtering keeps a prefix)."""
        kth_idx = jnp.clip(jnp.minimum(top_k, V) - 1, 0, V - 1).astype(jnp.int32)
        kth = jnp.take_along_axis(sorted_desc, kth_idx[:, None], axis=-1)
        kfilt = (top_k > 0)[:, None]
        out = jnp.where(kfilt & (lg < kth), -1e30, lg)
        sd = jnp.where(kfilt & (sorted_desc < kth), -1e30, sorted_desc)
        probs = jax.nn.softmax(sd, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        inside = cum - probs < top_p[:, None]
        cutoff = jnp.min(jnp.where(inside, sd, jnp.inf), axis=-1,
                         keepdims=True)
        return jnp.where((top_p < 1.0)[:, None] & (out < cutoff), -1e30, out)

    tops = jax.lax.top_k(lg, K)[0]  # [B, K], descending
    padded = jnp.concatenate(
        [tops, jnp.full((B, V - K), -1e30, lg.dtype)], axis=-1)
    # Everything downstream reads ``padded`` through an optimization
    # barrier, NEVER ``tops``: lax.top_k lowers to sort+slice, which
    # XLA:CPU pattern-matches into a fast partial-sort TopK custom call
    # — but slicing the result again gets algebraically pushed back
    # into slice-of-sort, breaking the match and silently falling back
    # to a full-vocab sort (~7x this op's cost). The barrier pins the
    # concat as a materialization point so consumers can't sink
    # through it.
    padded = jax.lax.optimization_barrier(padded)
    kth_idx = jnp.clip(jnp.minimum(top_k, K) - 1, 0, K - 1).astype(jnp.int32)
    kth = jnp.take_along_axis(padded, kth_idx[:, None], axis=-1)
    # strict: values beyond the bound are all < kth, so the kept set
    # (lg >= kth) is fully inside the top-K — no tie straddles the edge
    row_fast = (~do_sample) | ((top_k > 0) & (top_k <= K)
                               & (padded[:, K - 1] < kth[:, 0]))
    lg = jax.lax.cond(
        jnp.all(row_fast),
        lambda: _filter(padded),
        lambda: _filter(jnp.sort(lg, axis=-1)[:, ::-1]))
    # per-row categorical with that row's key: the flat random-bit draw
    # for a [V] row equals the [1, V] draw generate makes at B=1
    sampled = jax.vmap(lambda k, l: jax.random.categorical(k, l))(
        keys, lg).astype(jnp.int32)
    return jnp.where(do_sample, sampled, greedy)


def truncated_draft(model, num_layers: int):
    """Self-speculative draft: a fresh model of the same family whose
    config keeps only the first ``num_layers`` decoder layers, with the
    embeddings, those layers, the final norm, and the lm head COPIED
    from ``model`` (LayerSkip-style early-exit draft — no second
    checkpoint to ship, and the vocab matches by construction).

    Weight transfer rides ``set_state_dict``'s name matching: the
    truncated model's parameter names are a strict subset of the full
    model's (``layers.0..n-1`` / ``h.0..n-1``), so the full state dict
    restores every draft tensor and the surplus layers land in the
    ``unexpected`` list."""
    import dataclasses

    cfg = model.config
    n = int(num_layers)
    if not 1 <= n <= cfg.num_hidden_layers:
        raise ValueError(
            f"truncated_draft needs 1 <= num_layers <= "
            f"{cfg.num_hidden_layers}, got {num_layers}")
    draft = type(model)(dataclasses.replace(cfg, num_hidden_layers=n))
    missing, _ = draft.set_state_dict(model.state_dict())
    if missing:  # a family whose names don't nest — refuse loudly
        raise ValueError(
            f"truncated_draft could not map {len(missing)} draft "
            f"parameters from the source model (first: {missing[0]})")
    return draft


def make_kv_caches(config, batch_size: int, max_len: int, dtype,
                   kv_format: str = "bf16"):
    """Pre-allocated static KV buffers: a list (one per plane,
    ``kv_cache_planes``: a decoder layer, or a pass of one in a looped
    stack) of {"k", "v"} jnp arrays shaped
    [batch_size, max_len, num_key_value_heads, head_dim].
    ``kv_format="int8"``/``"fp8"`` stores narrow values plus
    per-token-per-head absmax scales ``ks``/``vs`` ([b, max_len, n_kv]
    f32) — the contiguous twin of the quantized paged pools. A latent
    cache is ``"c"`` [batch_size, max_len, padded width] a layer."""
    from .quantization import intx as _intx

    if latent_cache_width(config) is not None:
        _refuse_latent_format(kv_format)
        return [{"c": jnp.zeros((batch_size, max_len,
                                 _latent_pool_width(config)), dtype)}
                for _ in range(config.num_hidden_layers)]
    n_kv = config.num_key_value_heads
    head_dim = config.hidden_size // config.num_attention_heads
    if kv_format != "bf16":
        sdt = _intx.format_dtype(kv_format)
        return [{"k": jnp.zeros((batch_size, max_len, n_kv, head_dim), sdt),
                 "v": jnp.zeros((batch_size, max_len, n_kv, head_dim), sdt),
                 "ks": jnp.zeros((batch_size, max_len, n_kv), jnp.float32),
                 "vs": jnp.zeros((batch_size, max_len, n_kv), jnp.float32)}
                for _ in range(kv_cache_planes(config))]
    return [{"k": jnp.zeros((batch_size, max_len, n_kv, head_dim), dtype),
             "v": jnp.zeros((batch_size, max_len, n_kv, head_dim), dtype)}
            for _ in range(kv_cache_planes(config))]


def make_cached_runner(model):
    """The jit-friendly functional cached forward shared by ``generate``
    and the serving engine: ``run(pb, token_ids, caches, pos,
    attn_mask=None)`` calls the model with parameters/buffers supplied
    as the ``pb`` pytree and raw-jnp caches, returning
    (logits_jnp, new_caches_jnp). ``pos`` may be a python int, a traced
    scalar, or a per-row [B] vector (serving decode)."""

    def run(pb, token_ids, caches, pos, attn_mask=None):
        with no_grad():
            # wrap every array entry (k/v buffers, and for paged caches
            # the bt/valid companions) so the cache dict round-trips the
            # model as plain Tensors
            caches_t = [{kk: vv if isinstance(vv, Tensor) else Tensor(vv)
                         for kk, vv in c.items()} for c in caches]
            am = None
            if attn_mask is not None:
                am = attn_mask if isinstance(attn_mask, Tensor) else Tensor(attn_mask)
            logits, new_caches = functional_call(
                model, pb, Tensor(token_ids), attn_mask=am,
                kv_caches=caches_t, position_offset=pos)
        return (logits._data,
                [{kk: vv._data if isinstance(vv, Tensor) else vv
                  for kk, vv in c.items()} for c in new_caches])

    return run


def generate_uncached(model, input_ids, max_new_tokens: int = 32, do_sample: bool = False,
                      temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                      eos_token_id: Optional[int] = None, seed: int = 0) -> Tensor:
    """Fallback decode for models without KV-cache plumbing: re-runs the
    full forward per token. Correct but O(n^2) — the cached path in
    ``generate`` is the serving path (llama and gpt both plumb it)."""
    cfg = GenerationConfig(max_new_tokens, do_sample, temperature, top_k, top_p,
                           eos_token_id, seed)
    ids = input_ids._data if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
    ids = ids.astype(jnp.int32)
    S = ids.shape[1]
    max_pos = getattr(model.config, "max_position_embeddings", None)
    if max_pos is not None and S + cfg.max_new_tokens > max_pos:
        raise ValueError(
            f"prompt ({S}) + max_new_tokens ({cfg.max_new_tokens}) exceeds "
            f"max_position_embeddings ({max_pos})")
    if cfg.max_new_tokens <= 0:
        return Tensor(ids)
    key = jax.random.PRNGKey(cfg.seed)
    with no_grad():
        for _ in range(cfg.max_new_tokens):
            logits = model(Tensor(ids))
            key, sub = jax.random.split(key)
            nxt = _select_token(logits._data[:, -1], cfg, sub)
            ids = jnp.concatenate([ids, nxt[:, None]], axis=1)
    if cfg.eos_token_id is not None:
        gen = _mask_after_eos(ids[:, S:], cfg.eos_token_id)
        ids = jnp.concatenate([ids[:, :S], gen], axis=1)
    return Tensor(ids)


def _normalize_prompts(input_ids, pad_token_id):
    """Normalize ``input_ids`` into (ids [B, S] int32, pad_lens or None).

    Accepts a [B, S] Tensor/array (classic equal-length prompts) or a
    ragged list/tuple of per-row token sequences. Ragged rows are
    LEFT-padded with ``pad_token_id`` to the longest prompt, and
    ``pad_lens`` [B] counts each row's leading pads so prefill/decode
    can mask them out of attention. A rectangular input combined with an
    explicit ``pad_token_id`` also enters ragged mode: leading
    ``pad_token_id`` tokens per row are treated as padding."""
    if isinstance(input_ids, (list, tuple)) and input_ids and \
            isinstance(input_ids[0], (list, tuple, np.ndarray)):
        rows = [np.asarray(r, dtype=np.int32).reshape(-1) for r in input_ids]
        lens = [r.shape[0] for r in rows]
        if any(l == 0 for l in lens):
            raise ValueError("empty prompt in ragged batch")
        S = max(lens)
        if len(set(lens)) > 1 and pad_token_id is None:
            raise ValueError(
                "ragged prompts (lengths %s) require pad_token_id for "
                "left-padding" % sorted(set(lens)))
        ids = np.full((len(rows), S), pad_token_id if pad_token_id is not None
                      else 0, np.int32)
        for b, r in enumerate(rows):
            ids[b, S - r.shape[0]:] = r
        if pad_token_id is None:
            return jnp.asarray(ids), None
        pad_lens = np.asarray([S - l for l in lens], np.int32)
        return jnp.asarray(ids), pad_lens
    ids = input_ids._data if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
    ids = ids.astype(jnp.int32)
    if pad_token_id is None:
        return ids, None
    arr = np.asarray(ids)
    # leading-run-of-pads per row (a pad id INSIDE the prompt is content)
    is_pad = arr == pad_token_id
    pad_lens = (np.cumprod(is_pad, axis=1)).sum(axis=1).astype(np.int32)
    pad_lens = np.minimum(pad_lens, arr.shape[1] - 1)  # never mask a whole row
    return ids, pad_lens


def _spec_row_keys(seed: int, B: int):
    """Per-row PRNG chain roots for the speculative path. B=1 uses
    ``PRNGKey(seed)`` directly — the exact chain ``generate`` walks, so
    single-row speculative output is bit-identical to plain generate for
    sampled requests too (the serving engine's per-request contract).
    B>1 rows get independent ``fold_in`` chains (plain generate draws
    all rows from one shared key per position, which rows advancing at
    different speculative rates cannot share; greedy output is
    key-independent and stays bit-identical at any B)."""
    root = jax.random.PRNGKey(seed)
    if B == 1:
        return root[None]
    return jax.vmap(lambda r: jax.random.fold_in(root, r))(
        jnp.arange(B, dtype=jnp.uint32))


def _generate_speculative(model, draft_model, ids, cfg: GenerationConfig,
                          spec_k: int):
    """Offline speculative decode (the serving lane's oracle): draft
    ``spec_k`` tokens with the small model, score every bundle position
    with the target in ONE cached forward (q_len = spec_k + 1), accept
    the longest draft prefix that matches the target's own selections.

    Under the common-noise coupling (draft and target select with the
    same per-position subkey — see ``spec_accept_length``) the emitted
    sequence is bit-identical to non-speculative ``generate``; the
    draft model only decides how many tokens each round advances.
    Rejected draft KV is rolled back BY POSITION: the next round's
    writes land on top of it before any query can attend it, so neither
    model's cache is ever copied or cleared."""
    B, S = ids.shape
    N = cfg.max_new_tokens
    k = int(spec_k)
    mcfg = model.config
    dcfg = draft_model.config
    if dcfg.vocab_size != mcfg.vocab_size:
        raise ValueError(
            f"draft/target vocab mismatch: draft vocab_size "
            f"({dcfg.vocab_size}) != target vocab_size "
            f"({mcfg.vocab_size}) — speculative decoding verifies draft "
            f"token ids against target logits, so both models must share "
            f"one tokenizer/vocab (e.g. build the draft with "
            f"generation.truncated_draft)")
    if S + N > dcfg.max_position_embeddings:
        raise ValueError(
            f"prompt ({S}) + max_new_tokens ({N}) exceeds the DRAFT "
            f"model's max_position_embeddings "
            f"({dcfg.max_position_embeddings}); the draft decodes the "
            f"same positions the target does")
    dtype = next(iter(model.parameters()))._data.dtype
    ddtype = next(iter(draft_model.parameters()))._data.dtype
    # verify bundles write [pos, pos+k]; the +k tail keeps every per-row
    # dynamic_update_slice window in bounds (a clamped start would SHIFT
    # the write over live entries)
    cache_len = S + N + k
    run = make_cached_runner(model)
    drun = make_cached_runner(draft_model)
    pb = {**{kk: v._data for kk, v in model.named_parameters_dict().items()},
          **{kk: v._data for kk, v in model.named_buffers_dict().items()}}
    dpb = {**{kk: v._data
              for kk, v in draft_model.named_parameters_dict().items()},
           **{kk: v._data
              for kk, v in draft_model.named_buffers_dict().items()}}
    # row-wise traced params: select_tokens row-wise == the config-static
    # _select_token, so these selections ARE plain generate's
    ds = jnp.full((B,), cfg.do_sample)
    temp = jnp.full((B,), cfg.temperature, jnp.float32)
    tkv = jnp.full((B,), cfg.top_k, jnp.int32)
    tpv = jnp.full((B,), cfg.top_p, jnp.float32)

    from .pallas_kernels.decode_attention import flash_decode_enabled
    from .pallas_kernels.quant_matmul import quant_matmul_enabled

    darch = (type(draft_model).__name__, dcfg.num_hidden_layers,
             dcfg.hidden_size, dcfg.num_attention_heads,
             dcfg.num_key_value_heads, dcfg.intermediate_size)
    gen_key = ("spec", B, S, N, k, cfg.do_sample, cfg.temperature,
               cfg.top_k, cfg.top_p, darch, flash_decode_enabled(),
               quant_matmul_enabled())
    cache_store = model.__dict__.setdefault("_generate_jit_cache", {})
    if gen_key not in cache_store:

        @jax.jit
        def sprefill(pb, dpb, ids, keys):
            caches = make_kv_caches(mcfg, B, cache_len, dtype)
            dcaches = make_kv_caches(dcfg, B, cache_len, ddtype)
            logits, caches = run(pb, ids, caches, 0)
            _, dcaches = drun(dpb, ids, dcaches, 0)
            levels, subs = split_key_levels(keys, 1)
            token = select_tokens(logits[:, -1], subs[:, 0], ds, temp,
                                  tkv, tpv)
            return token, levels[:, 1], caches, dcaches

        @functools.partial(jax.jit, donate_argnums=(1,))
        def sdraft(dpb, dcaches, tokens, pos, keys):
            # the draft proposes with the SAME subkeys the verify step
            # will select with (common-noise coupling): the proposal IS
            # the draft's guess of the target's next selection
            _, subs = split_key_levels(keys, k)
            tok = tokens
            drafts = []
            for j in range(k):
                logits, dcaches = drun(dpb, tok[:, None], dcaches, pos + j)
                tok = select_tokens(logits[:, 0], subs[:, j], ds, temp,
                                    tkv, tpv)
                drafts.append(tok)
            # write-only forward for the last draft token's KV: a full
            # accept advances past pos+k, and without this the next
            # round's draft attends a hole there (accept rate drops;
            # outputs unaffected — verify is target-authoritative)
            _, dcaches = drun(dpb, tok[:, None], dcaches, pos + k)
            return jnp.stack(drafts, axis=1), dcaches

        @functools.partial(jax.jit, donate_argnums=(1,))
        def sverify(pb, caches, tokens, drafts, pos, keys, spec_len):
            bundle = jnp.concatenate([tokens[:, None], drafts], axis=1)
            logits, caches = run(pb, bundle, caches, pos)  # [B, k+1, V]
            levels, subs = split_key_levels(keys, k + 1)
            V = logits.shape[-1]

            def _rep(x):
                return jnp.broadcast_to(
                    x[:, None], (B, k + 1)).reshape(B * (k + 1))

            cand = select_tokens(
                logits.reshape(B * (k + 1), V),
                subs.reshape(B * (k + 1), 2),
                _rep(ds), _rep(temp), _rep(tkv), _rep(tpv)).reshape(B, k + 1)
            n_emit = spec_accept_length(drafts, cand, spec_len)
            new_keys = jnp.take_along_axis(
                levels, n_emit[:, None, None], axis=1)[:, 0]
            last = jnp.take_along_axis(
                cand, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
            new_tok = jnp.where(n_emit > 0, last, tokens)
            return cand, n_emit, new_keys, new_tok, caches

        cache_store[gen_key] = (sprefill, sdraft, sverify)
    sprefill, sdraft, sverify = cache_store[gen_key]

    with _entrypoint("generation.generate"), \
            _tracing.span("generation.spec_decode", cat="generation",
                          args={"B": B, "S": S, "N": N, "k": k}):
        keys = _spec_row_keys(cfg.seed, B)
        token, keys, caches, dcaches = sprefill(pb, dpb, jnp.asarray(ids),
                                                keys)
        tok_np = np.asarray(token)
        out = [[int(tok_np[b])] for b in range(B)]
        emitted = np.ones(B, np.int64)
        pos = np.full(B, S, np.int64)
        while int(emitted.min()) < N:
            spec_len = np.minimum(k + 1, N - emitted).astype(np.int32)
            drafts, dcaches = sdraft(dpb, dcaches, token,
                                     jnp.asarray(pos, jnp.int32), keys)
            cand, n_emit, keys, token, caches = sverify(
                pb, caches, token, drafts, jnp.asarray(pos, jnp.int32),
                keys, jnp.asarray(spec_len))
            n_np = np.asarray(n_emit)
            cand_np = np.asarray(cand)
            for b in range(B):
                out[b].extend(int(t) for t in cand_np[b, :n_np[b]])
            pos += n_np
            emitted += n_np
    gen = jnp.asarray(np.stack([np.asarray(r[:N], np.int32) for r in out]))
    if cfg.eos_token_id is not None:
        gen = _mask_after_eos(gen, cfg.eos_token_id)
    return Tensor(jnp.concatenate([ids, gen], axis=1))


def _generate_speculative_tree(model, draft_model, ids,
                               cfg: GenerationConfig, spec_tree):
    """Offline TREE-speculative decode (the serving tree lane's oracle):
    the draft proposes a branching token tree (``spec_tree`` branching
    factors per level), the target scores the whole flattened tree of w
    nodes in ONE cached forward under the tree-ancestor mask, and
    acceptance walks the deepest root-to-leaf path whose every node
    matches the target's own selection for its parent.

    PRNG coupling per branch: all nodes at depth t share the chain
    subkey ``subs[:, t]`` at VERIFY (any node whose ancestor chain fully
    matched carries the true chain prefix, so its selection IS the
    non-speculative sampler's draw); at DRAFT time branch 0 of each node
    proposes with that same subkey (the exact chain guess) and branches
    r>0 diversify via ``fold_in`` on the child's global tree index.
    Emitted sequences stay bit-identical to non-speculative ``generate``
    — greedy and sampled — the tree only changes how many tokens each
    round advances.

    Accepted-path KV is committed BY POSITION in both models' caches
    (gather the path nodes' slots, scatter them onto the contiguous
    positions; non-committed entries route back onto their own slot, a
    same-value no-op), and the next round's writes land on top of every
    rejected slot before any query can attend it."""
    plan = spec_tree_plan(spec_tree)
    D, w = plan["depth"], plan["nodes"]
    off = [int(o) for o in plan["offsets"]]
    factors = plan["factors"]
    parent = jnp.asarray(plan["parent"])
    depth_vec = jnp.asarray(plan["depth_vec"])
    anc_idx = jnp.asarray(plan["anc_idx"])
    anc = jnp.asarray(plan["anc"])
    B, S = ids.shape
    N = cfg.max_new_tokens
    mcfg = model.config
    dcfg = draft_model.config
    if dcfg.vocab_size != mcfg.vocab_size:
        raise ValueError(
            f"draft/target vocab mismatch: draft vocab_size "
            f"({dcfg.vocab_size}) != target vocab_size "
            f"({mcfg.vocab_size}) — speculative decoding verifies draft "
            f"token ids against target logits, so both models must share "
            f"one tokenizer/vocab (e.g. build the draft with "
            f"generation.truncated_draft)")
    if S + N + D > min(dcfg.max_position_embeddings,
                       mcfg.max_position_embeddings):
        raise ValueError(
            f"prompt ({S}) + max_new_tokens ({N}) + tree depth ({D}) "
            f"exceeds max_position_embeddings "
            f"({min(dcfg.max_position_embeddings, mcfg.max_position_embeddings)}) "
            f"— tree nodes take RoPE/positional indices up to pos + depth")
    dtype = next(iter(model.parameters()))._data.dtype
    ddtype = next(iter(draft_model.parameters()))._data.dtype
    # verify bundles write [pos, pos+w-1]; the +w tail keeps every
    # per-row write window in bounds (the draft always drafts the FULL
    # tree — the accept gate, not the draft, enforces per-row budgets)
    cache_len = S + N + w
    run = make_cached_runner(model)
    drun = make_cached_runner(draft_model)
    pb = {**{kk: v._data for kk, v in model.named_parameters_dict().items()},
          **{kk: v._data for kk, v in model.named_buffers_dict().items()}}
    dpb = {**{kk: v._data
              for kk, v in draft_model.named_parameters_dict().items()},
           **{kk: v._data
              for kk, v in draft_model.named_buffers_dict().items()}}
    ds = jnp.full((B,), cfg.do_sample)
    temp = jnp.full((B,), cfg.temperature, jnp.float32)
    tkv = jnp.full((B,), cfg.top_k, jnp.int32)
    tpv = jnp.full((B,), cfg.top_p, jnp.float32)

    from .pallas_kernels.decode_attention import flash_decode_enabled
    from .pallas_kernels.quant_matmul import quant_matmul_enabled

    def _rep(x, m):
        return jnp.broadcast_to(x[:, None], (B, m)).reshape(B * m)

    def _with_tree(caches, n):
        tm = jnp.broadcast_to(anc[:n, :n][None], (B, n, n))
        return [dict(c, tree_mask=tm, tree_depth=depth_vec[:n])
                for c in caches]

    def _strip(caches):
        return [{kk: c[kk] for kk in ("k", "v")} for c in caches]

    def _kv_path_move(caches, src, dst):
        # gather the [B, D+1] source slots, scatter onto the dest slots
        # (functional: every gather reads the pre-move buffer; routed
        # no-op writes collide only with identical values)
        def mv(buf):
            return jax.vmap(lambda bu, s_, d_: bu.at[d_].set(bu[s_]))(
                buf, src, dst)
        return [{kk: mv(vv) for kk, vv in c.items()} for c in caches]

    darch = (type(draft_model).__name__, dcfg.num_hidden_layers,
             dcfg.hidden_size, dcfg.num_attention_heads,
             dcfg.num_key_value_heads, dcfg.intermediate_size)
    gen_key = ("spec_tree", B, S, N, factors, cfg.do_sample,
               cfg.temperature, cfg.top_k, cfg.top_p, darch,
               flash_decode_enabled(), quant_matmul_enabled())
    cache_store = model.__dict__.setdefault("_generate_jit_cache", {})
    if gen_key not in cache_store:

        @jax.jit
        def tprefill(pb, dpb, ids, keys):
            caches = make_kv_caches(mcfg, B, cache_len, dtype)
            dcaches = make_kv_caches(dcfg, B, cache_len, ddtype)
            logits, caches = run(pb, ids, caches, 0)
            _, dcaches = drun(dpb, ids, dcaches, 0)
            levels, subs = split_key_levels(keys, 1)
            token = select_tokens(logits[:, -1], subs[:, 0], ds, temp,
                                  tkv, tpv)
            return token, levels[:, 1], caches, dcaches

        @functools.partial(jax.jit, donate_argnums=(1,))
        def tdraft(dpb, dcaches, tokens, pos, keys):
            # level-t forward re-feeds the WHOLE tree-so-far (square
            # ancestor mask — past-KV masking stays untouched, so a
            # rectangular "new nodes only" query is not expressible);
            # earlier nodes' KV is rewritten bit-identically
            _, subs = split_key_levels(keys, D + 1)
            tok_tree = jnp.zeros((B, w), jnp.int32).at[:, 0].set(tokens)
            for t in range(D):
                n = off[t + 1]
                logits, dc = drun(dpb, tok_tree[:, :n],
                                  _with_tree(dcaches, n), pos)
                dcaches = _strip(dc)
                lvl = logits[:, off[t]:n]             # [B, w_t, V]
                f = factors[t]
                w_next = off[t + 2] - off[t + 1]
                # greedy: branch 0 = argmax EXPLICITLY (bit-parity with
                # the verify selection under any top_k tie-break),
                # branches r>0 = the r-th ranked token
                tk = jax.lax.top_k(lvl, f)[1].astype(jnp.int32)
                tk = tk.at[:, :, 0].set(
                    jnp.argmax(lvl, axis=-1).astype(jnp.int32))
                children = tk.reshape(B, w_next)
                if cfg.do_sample:
                    V = lvl.shape[-1]
                    base = subs[:, t]                 # the chain subkey
                    gidx = off[t + 1] + jnp.arange(w_next,
                                                   dtype=jnp.uint32)
                    folded = jax.vmap(lambda kk: jax.vmap(
                        lambda g: jax.random.fold_in(kk, g))(gidx))(base)
                    use_base = (jnp.arange(w_next) % f) == 0
                    keys_lvl = jnp.where(
                        use_base[None, :, None],
                        jnp.broadcast_to(base[:, None], (B, w_next, 2)),
                        folded)
                    sampled = select_tokens(
                        jnp.repeat(lvl, f, axis=1).reshape(B * w_next, V),
                        keys_lvl.reshape(B * w_next, 2),
                        _rep(ds, w_next), _rep(temp, w_next),
                        _rep(tkv, w_next),
                        _rep(tpv, w_next)).reshape(B, w_next)
                    children = jnp.where(ds[:, None], sampled, children)
                tok_tree = tok_tree.at[:, off[t + 1]:off[t + 2]].set(
                    children)
            # write-only forward at full width: leaf KV, so a deep
            # accept never leaves the draft attending a hole next round
            _, dc = drun(dpb, tok_tree, _with_tree(dcaches, w), pos)
            return tok_tree[:, 1:], _strip(dc)

        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def tverify(pb, caches, dcaches, tokens, drafts, pos, keys,
                    spec_len):
            bundle = jnp.concatenate([tokens[:, None], drafts], axis=1)
            logits, cl = run(pb, bundle, _with_tree(caches, w), pos)
            caches = _strip(cl)
            levels, subs = split_key_levels(keys, D + 1)
            node_keys = jnp.take(subs, depth_vec, axis=1)  # [B, w, 2]
            V = logits.shape[-1]
            cand = select_tokens(
                logits.reshape(B * w, V), node_keys.reshape(B * w, 2),
                _rep(ds, w), _rep(temp, w), _rep(tkv, w),
                _rep(tpv, w)).reshape(B, w)
            # deepest fully-matching root-to-leaf path: a node survives
            # iff its own token matches the target's selection for its
            # parent AND every ancestor survives (D parent-AND sweeps)
            match = jnp.concatenate(
                [jnp.ones((B, 1), bool),
                 bundle[:, 1:] == jnp.take(cand, parent[1:], axis=1)],
                axis=1)
            acc = match & (jnp.arange(w)[None, :]
                           < jnp.asarray(spec_len, jnp.int32)[:, None])
            for _ in range(D):
                acc = acc & jnp.take(acc, parent, axis=1)
            score = jnp.where(acc, depth_vec[None, :] + 1, 0)
            best = jnp.argmax(score, axis=1)
            n_emit = jnp.take_along_axis(score, best[:, None],
                                         axis=1)[:, 0]
            path = jnp.take(anc_idx, best, axis=0)         # [B, D+1]
            emitted = jnp.take_along_axis(cand, path, axis=1)
            new_keys = jnp.take_along_axis(
                levels, n_emit[:, None, None], axis=1)[:, 0]
            last = jnp.take_along_axis(cand, best[:, None], axis=1)[:, 0]
            new_tok = jnp.where(n_emit > 0, last, tokens)
            # commit the accepted path by position in BOTH caches:
            # slot pos+t <- slot pos+path[t] for 1 <= t < n_emit, every
            # other entry routes back onto its own source slot (no-op)
            tt = jnp.arange(D + 1)[None, :]
            src = pos[:, None] + path
            dst = pos[:, None] + tt
            commit = (tt < n_emit[:, None]) & (tt >= 1)
            dst = jnp.where(commit, dst, src)
            caches = _kv_path_move(caches, src, dst)
            dcaches = _kv_path_move(dcaches, src, dst)
            return (emitted, n_emit, new_keys, new_tok, caches, dcaches)

        cache_store[gen_key] = (tprefill, tdraft, tverify)
    tprefill, tdraft, tverify = cache_store[gen_key]

    with _entrypoint("generation.generate"), \
            _tracing.span("generation.spec_tree_decode", cat="generation",
                          args={"B": B, "S": S, "N": N,
                                "factors": list(factors), "nodes": w}):
        keys = _spec_row_keys(cfg.seed, B)
        token, keys, caches, dcaches = tprefill(pb, dpb, jnp.asarray(ids),
                                                keys)
        tok_np = np.asarray(token)
        out = [[int(tok_np[b])] for b in range(B)]
        emitted_n = np.ones(B, np.int64)
        pos = np.full(B, S, np.int64)
        while int(emitted_n.min()) < N:
            # per-row BFS-prefix width: clamp the tree DEPTH to the
            # remaining budget (0 remaining -> width 0 -> row idles)
            rem = N - emitted_n
            spec_len = np.asarray(
                [off[min(D, int(r) - 1) + 1] if r > 0 else 0
                 for r in rem], np.int32)
            drafts, dcaches = tdraft(dpb, dcaches, token,
                                     jnp.asarray(pos, jnp.int32), keys)
            em, n_emit, keys, token, caches, dcaches = tverify(
                pb, caches, dcaches, token, drafts,
                jnp.asarray(pos, jnp.int32), keys, jnp.asarray(spec_len))
            n_np = np.asarray(n_emit)
            em_np = np.asarray(em)
            for b in range(B):
                out[b].extend(int(t) for t in em_np[b, :n_np[b]])
            pos += n_np
            emitted_n += n_np
    gen = jnp.asarray(np.stack([np.asarray(r[:N], np.int32) for r in out]))
    if cfg.eos_token_id is not None:
        gen = _mask_after_eos(gen, cfg.eos_token_id)
    return Tensor(jnp.concatenate([ids, gen], axis=1))


def generate(model, input_ids, max_new_tokens: int = 32, do_sample: bool = False,
             temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
             eos_token_id: Optional[int] = None, seed: int = 0,
             loop_mode: str = "scan", pad_token_id: Optional[int] = None,
             stream: bool = False, draft_model=None, spec_k: int = 4,
             spec_tree=None, kv_format: str = "bf16", tp: int = 1):
    """Generate continuations for ``input_ids`` [B, S]; returns [B, S+N].

    Greedy by default; sampling with temperature/top-k/top-p when
    ``do_sample``. Stops early only via post-hoc masking (static shapes).

    ``loop_mode="scan"`` (default) compiles the WHOLE decode loop into one
    program (``lax.scan`` over the token index) — one dispatch and one
    result transfer for N tokens instead of N host round trips;
    ``"python"`` drives one jitted step per token (useful for streaming
    consumers that want tokens as they land). In python mode with an
    ``eos_token_id`` the token loop exits as soon as every row has
    emitted EOS (the result is padded back to [B, S+N] with EOS, so the
    output contract is unchanged).

    Ragged prompts: pass a list of per-row token sequences (or a
    pre-padded [B, S] batch) together with ``pad_token_id`` — rows are
    LEFT-padded and an attention mask hides the pads through prefill AND
    every decode step. Pad positions keep their absolute cache/RoPE
    indices: RoPE scores depend only on relative distance, so a
    left-padded row decodes exactly like its unpadded twin (for learned
    position embeddings the shift is absolute, like other left-padding
    implementations).

    ``stream=True`` (forces python mode) returns a generator that yields
    one np.int32 [B] token vector per generated position as it lands
    (EOS-masked rows keep yielding EOS) and stops early once every row
    is done.

    ``draft_model=`` enables SPECULATIVE decoding (offline oracle for
    the serving engine's spec lane): the draft proposes ``spec_k``
    tokens per round and the target scores the whole bundle in one
    cached forward. Outputs are bit-identical to the non-speculative
    path — greedy at any batch size, sampled at B=1 (B>1 sampled rows
    use independent per-row key chains; see ``_spec_row_keys``) — the
    draft only changes how fast rows advance. Unsupported together with
    ``stream`` and with ragged/left-padded prompts (``pad_token_id``).

    ``spec_tree=[4, 2, 2]`` (requires ``draft_model``, replaces the
    single ``spec_k`` chain) drafts a branching token TREE instead: the
    draft samples ``factors[t]`` children per level-t node, the target
    scores the whole flattened tree in one forward under the
    tree-ancestor mask, and the deepest fully-matching root-to-leaf
    path is emitted. Same bit-parity contract as the chain lane; see
    ``spec_tree_plan`` for the flattening.

    ``kv_format="int8"``/``"fp8"`` stores the KV cache quantized
    (per-token-per-head absmax scales; fp8 = e4m3 where the jnp dtype
    exists, int8 the portable floor): cache writes quantize, the
    flash-decode kernel dequantizes in its prologue (the XLA fallback
    dequantizes at the gather), halving decode KV bytes. Greedy outputs
    at the tiny-model test points match bf16 token-for-token (pinned in
    tests/test_quantization_serving.py); logits move by the absmax
    rounding step. Not supported with ``draft_model`` here — the
    serving engine's spec lane runs on quantized pools instead.

    ``tp=N`` runs the whole generate tensor-parallel over the first N
    devices (the offline oracle for the serving engine's tp lane): the
    params are rule-sharded Megatron-style via
    ``distributed.partition.partition_rules_for(model)``, the KV caches
    shard on the kv-heads axis, and the executables compile with
    explicit shardings. Token outputs are bit-identical to tp=1 at the
    test points (logits agree to psum reduction order). The params are
    re-placed on the mesh each call — an oracle path, not a serving
    path. Not supported with ``draft_model`` (the engine's spec lane is
    the sharded one)."""
    cfg = GenerationConfig(max_new_tokens, do_sample, temperature, top_k, top_p,
                           eos_token_id, seed)
    from .quantization.intx import KV_FORMATS

    if kv_format not in KV_FORMATS:
        raise ValueError(
            f"kv_format must be one of {KV_FORMATS}, got {kv_format!r}")
    if kv_format != "bf16":
        from .quantization.intx import format_dtype

        format_dtype(kv_format)  # actionable error when fp8 is absent
        if draft_model is not None:
            raise ValueError(
                "kv_format is not supported with draft_model in offline "
                "generate — run speculative decoding on the serving "
                "engine (ServingConfig.kv_format), whose draft/verify "
                "lane operates on quantized pools")
    tp = int(tp)
    if tp > 1 and draft_model is not None:
        raise ValueError(
            "tp > 1 is not supported with draft_model in offline "
            "generate — run speculative decoding on the serving engine "
            "(ServingConfig(tp=N, ...) with draft_model), whose "
            "draft/verify executables compile over the TP mesh")
    ids, pad_lens = _normalize_prompts(input_ids, pad_token_id)
    ragged = pad_lens is not None
    B, S = ids.shape
    max_len = S + cfg.max_new_tokens
    config = model.config
    if max_len > config.max_position_embeddings:
        raise ValueError(
            f"prompt ({S}) + max_new_tokens ({cfg.max_new_tokens}) exceeds "
            f"max_position_embeddings ({config.max_position_embeddings}); the "
            "position table (RoPE / learned embeddings) has no entries past "
            "that position")
    dtype = next(iter(model.parameters()))._data.dtype

    params = {k: v._data for k, v in model.named_parameters_dict().items()}
    buffers = {k: v._data for k, v in model.named_buffers_dict().items()}

    def make_caches():
        return make_kv_caches(config, B, max_len, dtype, kv_format)

    base_run = make_cached_runner(model)

    def run(pb, token_ids, caches, pos, pads=None):
        if pads is None:
            return base_run(pb, token_ids, caches, pos)
        # ragged: causal mask that ALSO hides each row's left pads, for
        # prefill and for every decode step (pads live at cache positions
        # 0..pad_len-1 forever, so the default causal mask would attend
        # them)
        s = token_ids.shape[1]
        kpos = jnp.arange(max_len)
        qpos = pos + jnp.arange(s)
        m = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < pos + s)
        m = m[None] & (kpos[None, None, :] >= pads[:, None, None])
        mask = jnp.where(m[:, None], 0.0, -1e30).astype(jnp.float32)
        return base_run(pb, token_ids, caches, pos, attn_mask=mask)

    if stream:
        loop_mode = "python"
    if loop_mode not in ("scan", "python"):
        raise ValueError(f"loop_mode must be 'scan' or 'python', got {loop_mode!r}")
    if cfg.max_new_tokens <= 0:
        if stream:
            return iter(())
        return Tensor(ids)
    if spec_tree is not None and draft_model is None:
        raise ValueError(
            "spec_tree requires draft_model: the tree nodes are drafted "
            "by the small model — pass draft_model= (e.g. "
            "generation.truncated_draft) or drop spec_tree")
    if draft_model is not None and (spec_tree is not None or spec_k >= 1):
        if stream:
            raise ValueError(
                "stream=True is not supported with draft_model: the "
                "speculative loop emits a variable number of tokens per "
                "round — drop draft_model to stream, or poll the serving "
                "engine's Request.stream()")
        if ragged:
            raise ValueError(
                "draft_model is not supported with ragged/left-padded "
                "prompts (pad_token_id): the speculative verify derives "
                "its masking from positions only — pass equal-length "
                "prompts or drop draft_model")
        if spec_tree is not None:
            return _generate_speculative_tree(model, draft_model, ids,
                                              cfg, spec_tree)
        return _generate_speculative(model, draft_model, ids, cfg, spec_k)

    # jitted executables are cached on the model so repeat generate() calls
    # with the same shapes/config reuse the compiled programs; the KV cache
    # pytree is donated so decode updates buffers in place
    # eos only shapes the scan-mode whole-generate program; python-mode
    # executables are eos-independent (masking happens outside jit) and
    # must not recompile per eos id
    # the flash-decode env gate is a python-side dispatch baked into the
    # trace: flipping it must not reuse executables traced the other way
    from .pallas_kernels.decode_attention import flash_decode_enabled
    from .pallas_kernels.quant_matmul import quant_matmul_enabled

    gen_key = (B, S, cfg.max_new_tokens, cfg.do_sample, cfg.temperature,
               cfg.top_k, cfg.top_p,
               cfg.eos_token_id if loop_mode == "scan" else None, loop_mode,
               ragged, flash_decode_enabled(), kv_format,
               quant_matmul_enabled(), tp)

    # tensor-parallel oracle path: rule-shard the params over a tp-mesh
    # and compile the executables with explicit shardings (the same
    # fixpoint discipline as the serving engine's tp executables — see
    # distributed/partition.py)
    tp_mesh_obj = None
    if tp > 1:
        from .distributed import partition as _partition

        _partition.validate_tp(config, tp)
        tp_mesh_obj = _partition.tp_mesh(tp)
        _tp_rules = _partition.partition_rules_for(model)
        _rep = _partition.replicated(tp_mesh_obj)
        from jax.sharding import NamedSharding as _NS

        _pb_sh = {
            name: _NS(tp_mesh_obj, spec)
            for name, spec in _partition.match_partition_rules(
                _tp_rules, {**params, **buffers}).items()}
        _ckeys = {"k": 4, "v": 4}
        if kv_format != "bf16":
            _ckeys.update({"ks": 3, "vs": 3})
        _cache_sh = [
            {kk: _NS(tp_mesh_obj, _partition.kv_cache_spec(nd))
             for kk, nd in _ckeys.items()}
            for _ in range(kv_cache_planes(config))]

    cache_store = model.__dict__.setdefault("_generate_jit_cache", {})
    if gen_key not in cache_store:

        def prefill(pb, ids, caches, pads):
            logits, caches = run(pb, ids, caches, 0, pads)
            return logits[:, -1], caches

        def step(pb, token, caches, pos, key, pads):
            logits, caches = run(pb, token[:, None], caches, pos, pads)
            nxt = _select_token(logits[:, 0], cfg, key)
            return nxt, caches

        def generate_program(pb, ids, key, pads):
            """The WHOLE generate as ONE program: cache init + prefill +
            first-token select + (N-1)-step ``lax.scan`` decode + EOS
            masking + prompt concat. A single dispatch and a single
            result transfer: a per-token python loop pays a host
            dispatch and a token fetch per step, which a small model's
            decode step does not hide."""
            caches = make_caches()
            logits, caches = run(pb, ids, caches, 0, pads)
            key, sub = jax.random.split(key)
            token = _select_token(logits[:, -1], cfg, sub)

            def body(carry, i):
                token, caches, key = carry
                key, sub = jax.random.split(key)
                logits, caches = run(pb, token[:, None], caches, S + i, pads)
                nxt = _select_token(logits[:, 0], cfg, sub)
                return (nxt, caches, key), nxt

            (_, caches, _), toks = jax.lax.scan(
                body, (token, caches, key),
                jnp.arange(cfg.max_new_tokens - 1, dtype=jnp.int32))
            gen = jnp.concatenate([token[:, None], jnp.swapaxes(toks, 0, 1)],
                                  axis=1)  # [B, N]
            if cfg.eos_token_id is not None:
                gen = _mask_after_eos(gen, cfg.eos_token_id)
            return jnp.concatenate([ids, gen], axis=1)

        if tp > 1:
            # explicit in/out shardings on every executable keep the
            # KV-cache layouts a fixpoint across calls (one compile per
            # gen_key, same as tp=1)
            prefill = _partition.tp_jit(
                prefill, tp=tp, mesh=tp_mesh_obj,
                in_shardings=(_pb_sh, _rep, _cache_sh, _rep),
                out_shardings=(_rep, _cache_sh))
            step = _partition.tp_jit(
                step, tp=tp, mesh=tp_mesh_obj,
                in_shardings=(_pb_sh, _rep, _cache_sh, _rep, _rep, _rep),
                out_shardings=(_rep, _cache_sh),
                donate_argnums=(2,))
            generate_program = _partition.tp_jit(
                generate_program, tp=tp, mesh=tp_mesh_obj,
                in_shardings=(_pb_sh, _rep, _rep, _rep),
                out_shardings=_rep)
        else:
            prefill = jax.jit(prefill)
            step = jax.jit(step, donate_argnums=(2,))
            generate_program = jax.jit(generate_program)

        cache_store[gen_key] = (prefill, step, generate_program)
    prefill, step, generate_program = cache_store[gen_key]

    pb = {**params, **buffers}
    if tp > 1:
        pb = {name: jax.device_put(v, _pb_sh[name])
              for name, v in pb.items()}
        from .observability import perf as _perf_mesh
        _perf_mesh.note_entry_mesh("generation.generate", {"tp": tp})
    key = jax.random.PRNGKey(cfg.seed)
    pads = jnp.asarray(pad_lens) if ragged else None

    def python_token_iter():
        """One jitted step per token; yields the np.int32 [B] token
        vector per position, EOS-masked, exiting early once every row
        has emitted EOS."""
        with _entrypoint("generation.generate"):
            with _tracing.span("generation.prefill", cat="generation",
                               args={"B": B, "S": S}):
                caches = make_caches()
                last_logits, caches = prefill(pb, ids, caches, pads)
            k = key
            k, sub = jax.random.split(k)
            token = _select_token(last_logits, cfg, sub)
            done = np.zeros(B, bool)
            decode_sp = _tracing.begin_span(
                "generation.decode", cat="generation",
                args={"B": B, "N": cfg.max_new_tokens})
            try:
                for i in range(cfg.max_new_tokens):
                    if i > 0:
                        k, sub = jax.random.split(k)
                        # pos as a traced scalar: one compiled step
                        # executable for all tokens
                        token, caches = step(pb, token, caches,
                                             jnp.asarray(S + i - 1, jnp.int32),
                                             sub, pads)
                    tok_np = np.asarray(token).astype(np.int32)
                    if cfg.eos_token_id is not None:
                        tok_np = np.where(done, cfg.eos_token_id, tok_np)
                        done |= tok_np == cfg.eos_token_id
                    yield tok_np
                    if cfg.eos_token_id is not None and done.all():
                        return
            finally:
                _tracing.end_span(decode_sp)

    # recompile-monitor attribution: prefill/step/whole-program compiles
    # charge to this entry; a compile after the first completed generate
    # (new B/S/N or config) is surfaced as a retrace
    if stream:
        return python_token_iter()

    # perf-ledger item accounting: generated tokens per entry call, so
    # the ledger can report bytes/token and tokens/s for this entry
    from .observability import perf as _perf

    with _entrypoint("generation.generate"):
        if loop_mode == "scan" and cfg.max_new_tokens > 1:
            # one span for the whole fused program: prefill + decode are
            # a single dispatch in scan mode, host-side phases don't exist
            with _tracing.span("generation.generate", cat="generation",
                               args={"B": B, "S": S,
                                     "N": cfg.max_new_tokens,
                                     "mode": "scan"}):
                out = Tensor(generate_program(pb, ids, key, pads))
            _perf.note_entry_items("generation.generate",
                                   B * cfg.max_new_tokens)
            return out

        if cfg.eos_token_id is not None:
            # early-exit python loop: host-syncs each token (the
            # streaming path already pays that), stops once every row is
            # done, pads the tail back to N with EOS
            toks = list(python_token_iter())
            _perf.note_entry_items("generation.generate", B * len(toks))
            gen = np.stack(toks, axis=1)
            if gen.shape[1] < cfg.max_new_tokens:
                pad = np.full((B, cfg.max_new_tokens - gen.shape[1]),
                              cfg.eos_token_id, np.int32)
                gen = np.concatenate([gen, pad], axis=1)
            return Tensor(jnp.concatenate(
                [ids, jnp.asarray(gen)], axis=1))

        with _tracing.span("generation.prefill", cat="generation",
                           args={"B": B, "S": S}):
            caches = make_caches()
            last_logits, caches = prefill(pb, ids, caches, pads)
        key, sub = jax.random.split(key)
        token = _select_token(last_logits, cfg, sub)

        with _tracing.span("generation.decode", cat="generation",
                           args={"B": B, "N": cfg.max_new_tokens}):
            out = [token]
            for i in range(1, cfg.max_new_tokens):
                key, sub = jax.random.split(key)
                # pos as a traced scalar: one compiled step executable for all tokens
                token, caches = step(pb, token, caches, jnp.asarray(S + i - 1, jnp.int32), sub, pads)
                out.append(token)
            gen = jnp.stack(out, axis=1)  # [B, N]
        _perf.note_entry_items("generation.generate", B * cfg.max_new_tokens)
        return Tensor(jnp.concatenate([ids, gen], axis=1))


# retrace warnings for the generate entry cite this definition
from .observability.recompile import \
    register_entry_location as _register_entry  # noqa: E402

_register_entry("generation.generate", generate)
