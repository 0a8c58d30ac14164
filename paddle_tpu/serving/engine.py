"""Continuous-batching serving engine over a paged KV cache.

The TPU-native translation of iteration-level scheduling (Orca) +
PagedAttention-class KV management (vLLM) + RadixAttention-style prefix
reuse, built on this repo's static-shape decode substrate. Device HBM
holds ONE fixed pool of KV blocks (per layer, [num_blocks, block_size,
kv_heads, d]); each slot's cache is an int32 block table into the pool.
Capacity is bounded by TOKENS IN FLIGHT instead of slots * worst-case
length — a short request strands at most ``block_size - 1`` token
slots, not ``max_len - L``. On top of the pool:

* **prefix sharing**: a prompt whose prefix was already prefilled (same
  tokens, same positions — e.g. a shared system prompt) adopts those
  blocks by reference from the host-side prefix cache instead of
  recomputing them; ref-counted copy-on-write forks a shared block on
  the first divergent write, so sharing is invisible to outputs.
* **chunked prefill**: prompts are admitted in fixed-size chunks
  interleaved with decode steps, so a long prompt never
  head-of-line-blocks running requests for its whole length. The chunks
  of the slots that prefill in one iteration are the rows of ONE
  ``[P, C]`` program: every prefilling slot advances one chunk an
  iteration, the rows the iteration's last program has left carry the
  earliest-admitted slot's further chunks, and the weights are read
  once for all of them (an iteration's lone chunk rides the ``[1, C]``
  form of the same body: two executables, ``serving.prefill_chunk`` and
  ``serving.prefill_chunk[P]``). Where slots are decoding, the
  iteration's last set of rows rides the decode step's own program
  (``serving.step+chunk[P]``: one pass over the weights for the step
  and the rows), on every engine whose step is one pass through one
  table layout with its tokens on the device: no windowed layout, no
  looped stack, no draft model, ``tp`` 1; such an engine keeps the
  ``[P, C]`` width alone (a lone chunk rides it too). A prompt's
  first token is read from the device once the iteration's decode step
  is dispatched.
* **preemption by recompute**: under pool pressure the latest-admitted
  request is preempted — its blocks freed, the request requeued at the
  queue front with its generated tokens folded into the prefill and its
  PRNG chain replayed, so the resumed decode is bit-identical and
  nothing is ever re-delivered.
* ``draft_model=``: SPECULATIVE DECODING. Decode is KV-bandwidth-bound,
  so idle FLOPs verify ``spec_k`` draft tokens per slot per round: ONE
  jitted draft program (k cached draft-model forwards over draft KV
  pools that share the target's block tables), then ONE jitted verify
  scoring the whole [B, k+1] bundle with the target through
  ``paged_flash_decode_attention``'s q_len > 1 path. Acceptance is the
  Leviathan/Chen rule under a common-noise coupling: draft and target
  select with the SAME per-position PRNG subkey, so
  accept-with-prob-min(1, p/q) collapses to exact token match and the
  emitted sequence is BIT-IDENTICAL to non-speculative decode — greedy
  and sampled — while the chain still advances one split per emitted
  token (preemption replay untouched). Rejected draft KV rolls back BY
  POSITION (the next bundle overwrites it before any in-length query
  can attend it); variable per-slot accept length is a per-row position
  bump through the block tables. Requests opt out (or shrink k) via
  ``SamplingParams.spec_k``; opted-out rows ride the verify bundle at
  width 1 as plain decode steps, so mixed pools share the same two
  executables — each compiles exactly once.

ONE jitted pool-wide decode step runs per iteration: per-slot positions
/ sampling params / PRNG keys / active mask and the block tables are
traced arrays, so mixed occupancy/length/sharing patterns share a single
step executable that compiles exactly once (recompile-monitor-asserted
across request waves); with prefill rows in its program it is the same
function at one more width, compiled once in ``warmup()``.

Per-request outputs are bit-identical to ``generation.generate`` (whose
contiguous static cache is the reference lane of the parity tests) with
the same sampling seed/params: the slot key chain reproduces generate's
``key, sub = split(key)`` walk, ``select_tokens`` is row-wise equal to
the config-static ``_select_token``, and the paged read path gathers the
exact same K/V values the contiguous cache holds (garbage beyond a row's
length is an exact no-op under the additive causal mask, just like the
contiguous cache's zeros).

Observability: every iteration that does work records ``engine.iter``
and its phases (``engine.admit`` / ``.prefill`` / ``.reserve`` /
``.dispatch`` / ``.wait`` / ``.emit``; ``engine.idle`` in the serving
loop) on the engine lane of ``observability.tracing`` and, while a
``jax.profiler`` session is active, on its host plane; an iteration (or
the gap between two) longer than ``tracing.STALL_NS`` also records the
instant ``engine.stall``, with its longest phase and the thread's CPU
time, and ``start()`` turns on the lane ``proc`` (the process's pauses
and the collector's passes); the counts the
benchmark reads are plain integers on the engine (``counters()``, O(1)
and lock-free; ``stats()`` adds the sections that cost). Then the
``paddle_tpu_serving_*`` instruments plus the
``paddle_tpu_kv_blocks_{total,in_use,shared}`` gauges (set once an
iteration) and
``paddle_tpu_prefix_cache_{hits,misses}_total`` counters; compiles are
attributed to ``serving.step`` / ``serving.prefill_chunk`` /
``serving.cow`` — a ``serving.step`` retrace after warmup is a bug and
the monitor flags it.
"""

from __future__ import annotations

import functools
import os
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..generation import (kv_cache_planes, latent_cache_width,
                          make_cached_runner,
                          make_paged_kv_pools, select_tokens,
                          spec_accept_length, split_key_levels, split_keys)
from ..observability import recompile as _recompile
from ..observability import tracing as _trace
from ..observability.recompile import entrypoint as _entrypoint
from . import metrics as _sm
from .block_pool import (DUMP_BLOCK, BlockPool, PoolExhaustedError,
                         PrefixCache, WindowedLayout)
from .kv_tier import DiskPrefixStore, KVTier, TierCostModel
from .request import Request, RequestStatus, SamplingParams
from .scheduler import Scheduler

__all__ = ["ServingConfig", "ServingEngine", "EngineStoppedError",
           "EngineDrainingError"]


class EngineStoppedError(RuntimeError):
    """``submit()`` after ``stop()``: the engine no longer admits work.
    Raised instead of silently enqueueing into a loop that will never
    run again (the old behavior hung the caller's ``result()``
    forever)."""


class EngineDrainingError(EngineStoppedError):
    """``submit()`` during drain: in-flight requests are finishing but
    no new work is admitted. A router should route the request to
    another replica; a direct caller should back off and retry once the
    replacement replica is up."""


# Rows up to which a bf16 prefill program shares its pass over the
# weights to any profit, on the chip this engine is written for. A v5e's
# peaks put the ridge at 197 TFLOP/s / 819 GB/s = 240 rows; the program
# alone at GPT-3 1.3B's widths (PERF.md section 6, PR 28) costs 3.7 ms
# at [1, 32], 4.8 at [4, 32], 6.6 at [8, 32] and 12.4 at [16, 32]: a
# chunk costs 3.7, 1.2, 0.82 and 0.77 ms, so past 256 rows a program's
# time doubles with its rows and a chunk gets no cheaper. A wider dtype
# streams more bytes a weight and so buys as many more rows.
_WEIGHT_PASS_ROWS_BF16 = 256


def prefill_batch_rows(chunk: int, dtype, max_slots: int) -> int:
    """P of the ``[P, chunk]`` prefill program: the largest power of two
    with ``P * chunk`` no more than the rows that share one pass over
    the weights (``_WEIGHT_PASS_ROWS_BF16``, scaled by the dtype's
    width), at least 1, at most ``max_slots``. A pure function of what
    the engine can see: nothing to configure."""
    rows = _WEIGHT_PASS_ROWS_BF16 * np.dtype(dtype).itemsize // 2
    p = 1
    while 2 * p <= min(rows // int(chunk), int(max_slots)):
        p *= 2
    return p


# What a row of a prefill program carries besides its table row and its
# token ids. The rows of one program reach it as ONE int32 host array
# [P, nb + C + len(_ROW_COLUMNS)] (a key's uint32 words and the float32
# values as their bits): every host argument of an executable is a
# transfer of its own, and twelve small ones a program were most of
# what the host did between a sync and the next enqueue.
_ROW_COLUMNS = ("pos0", "valid", "slot", "is_last", "last_idx", "ds", "tk",
                "key0", "key1", "temp", "tp")
_ONE_BITS = int(np.float32(1.0).view(np.int32))


def _unpack_rows(rows, nb: int, chunk: int):
    """A packed prefill batch on the device: ``(bt [P, nb], ids [P,
    chunk], fields)``, ``fields`` the per-row columns by name in their
    own dtypes, with ``key`` [P, 2] uint32 for the two key words."""
    col = dict(zip(_ROW_COLUMNS, jnp.moveaxis(rows[:, nb + chunk:], 1, 0)))
    f = {k: col[k] for k in ("pos0", "valid", "slot", "last_idx", "tk")}
    f.update(
        is_last=col["is_last"] != 0, ds=col["ds"] != 0,
        key=jax.lax.bitcast_convert_type(
            jnp.stack([col["key0"], col["key1"]], axis=1), jnp.uint32),
        temp=jax.lax.bitcast_convert_type(col["temp"], jnp.float32),
        tp=jax.lax.bitcast_convert_type(col["tp"], jnp.float32))
    return rows[:, :nb], rows[:, nb:nb + chunk], f


@dataclass
class ServingConfig:
    """Engine knobs.

    - ``max_slots``: the decode batch B — slots in flight at once.
    - ``max_len``: per-slot KV capacity; every request needs
      prompt_len + max_new_tokens <= max_len.
    - ``kv_mode``: ``"paged"``, the engine's one cache layout (block-pool
      KV, prefix sharing, chunked prefill). A field only because
      configuration files still name it; any other value is refused.
    - ``block_size``: tokens per KV block. Must divide
      ``max_len`` — the per-slot block table covers max_len in whole
      blocks.
    - ``num_blocks``: pool size INCLUDING the reserved dump block.
      Default ``max_slots * (max_len / block_size) + 1`` (worst case —
      paging can never run out); size it below that to oversubscribe
      slots against a fixed HBM budget (preemption keeps it safe).
    - ``prefill_chunk``: tokens per prefill chunk: a prompt
      advances at least this many tokens an iteration, between decode
      steps, and at most P times as many, so a long one never blocks
      the running requests for its length. One fixed
      ``[P, prefill_chunk]`` executable serves every prompt length: the
      next chunks of up to P prefilling slots are the rows of one
      program, so their one pass over the weights is shared, and the
      rows that the iteration's last program has left go to further
      chunks of the same slots, the earliest admitted first (a slot
      under a windowed layout takes none); an iteration's lone chunk
      rides the ``[1, prefill_chunk]`` form
      of the same body. P is not an option: ``prefill_batch_rows``
      reads it from this width, the weights' dtype and ``max_slots``
      (8 at the default 32 in bf16; 1 at 256).
    - ``prefix_caching``: reuse previously prefilled prompt prefixes
      (ref-counted, COW-protected). Disable for strictly independent
      workloads.
    - ``max_queue_depth``: admission backpressure bound
      (``QueueFullError`` beyond it).
    - ``pad_token_id``: right-pad filler for padded prefill — any valid
      token id works (padded positions are causally invisible, and
      their writes go to the dump block).
    - ``spec_k``: draft tokens per speculative round when the engine is
      built with a ``draft_model`` (the verify bundle is ``spec_k + 1``
      query positions through the paged kernel). Requests opt out (or
      shrink their k) per-request via ``SamplingParams.spec_k``; ignored
      without a draft model.
    - ``spec_tree``: per-level branching factors (e.g. ``[4, 2, 2]``)
      upgrading the speculative lane from a single draft chain to a
      token TREE: the draft proposes every branch, ONE verify scores
      the whole flattened tree (root + all nodes) through the paged
      kernel's ancestor-masked bundle path, and the deepest fully-
      matching root-to-leaf path is committed. Mutually exclusive with
      a non-default ``spec_k`` — one engine runs one lane. The node
      count (``spec_tree_width``) must fit the kernel's query window
      (``MAX_PAGED_Q_LEN``). ``SamplingParams.spec_k`` still applies
      per-request, clamping the tree DEPTH (0 = plain decode rows
      riding the bundle at width 1). Outputs stay bit-identical to
      non-speculative decode, greedy and sampled.
    - ``kv_format``: KV block storage — ``"bf16"`` keeps
      the model compute dtype (default); ``"int8"``/``"fp8"`` store the
      pool narrow with per-token-per-head absmax scale pools riding the
      same blocks: writes quantize in the scatter epilogue, the paged
      flash-decode kernel dequantizes in its prologue (XLA fallback at
      the gather), roughly doubling the tokens a fixed KV HBM budget
      holds. COW forks, prefix sharing, preemption-resume, and the
      spec-decode lane all operate on quantized blocks unchanged. fp8
      uses the e4m3 jnp dtype where available; int8 is the portable
      floor.
    - ``tp``: tensor-parallel degree — shard ONE model (and its KV
      pools, on the kv-heads axis) across ``tp`` devices via the
      ``distributed/partition.py`` rule tables; every executable runs
      under jit with explicit shardings over the TP mesh. Outputs are
      bit-identical to the tp=1 engine (greedy and sampled, spec and
      preemption lanes included); requires a
      model whose heads/kv-heads/intermediate/vocab divide by tp.
    - ``kv_tier``: hierarchical KV (``serving/kv_tier.py``) — prefix-
      cache eviction victims and preempted requests' blocks DEMOTE to a
      host-RAM tier (device->host at quantized width) instead of being
      freed, and a returning prefix re-admits via one jitted host->HBM
      block splice instead of prefill chunks. Defaults from the
      ``PADDLE_TPU_KV_TIER`` env var ("1" enables); requires
      prefix caching. Outputs stay bit-identical tier-on vs
      tier-off. ``kv_tier_host_blocks`` caps host residency (LRU);
      ``kv_tier_path`` (env ``PADDLE_TPU_KV_TIER_PATH``) adds the
      crash-safe disk tier below host, making cached prefixes persist
      across engine restarts; ``kv_tier_host_gbps`` (env
      ``PADDLE_TPU_KV_TIER_HOST_GBPS``) and ``kv_tier_safety`` feed the
      demote-vs-drop / readmit-vs-recompute cost model.
    """

    max_slots: int = 4
    max_len: int = 256
    max_queue_depth: int = 64
    pad_token_id: int = 0
    kv_mode: str = "paged"
    block_size: int = 16
    num_blocks: Optional[int] = None
    prefill_chunk: int = 32
    prefix_caching: bool = True
    spec_k: int = 4
    spec_tree: Optional[Sequence[int]] = None
    kv_format: str = "bf16"
    # tensor parallelism: shard ONE model over `tp` chips (Megatron
    # layout via distributed/partition.py rule tables; KV pools shard on
    # the kv-heads axis). Host-side scheduling/paging is tp-agnostic —
    # one allocator/prefix-cache/block-table drives every shard — and
    # outputs stay bit-identical to the tp=1 engine. Divisibility
    # against the model's heads/vocab is validated at engine build.
    tp: int = 1
    # background loop liveness: with work pending and no step boundary
    # for this long, /healthz flips to "stalled" (503) so a router's
    # probes can eject a HUNG replica — a wedged device dispatch looks
    # exactly like this, and without the detector it is invisible (the
    # loop thread is stuck, but every state read still says "ok")
    stall_timeout_s: float = 10.0
    # hierarchical KV tiers (host RAM + optional persistent disk under
    # the block pool); None resolves from the environment in
    # __post_init__ so a deployment can flip the tier on without code
    kv_tier: Optional[bool] = None
    kv_tier_host_blocks: int = 256
    kv_tier_path: Optional[str] = None
    kv_tier_host_gbps: Optional[float] = None
    kv_tier_safety: float = 1.5

    def __post_init__(self):
        if self.kv_mode != "paged":
            raise ValueError(
                f"kv_mode must be 'paged', the engine's one cache layout "
                f"(the contiguous mode is gone), got {self.kv_mode!r}")
        from ..quantization.intx import KV_FORMATS, format_dtype

        if self.kv_format not in KV_FORMATS:
            raise ValueError(
                f"kv_format must be one of {KV_FORMATS}, got "
                f"{self.kv_format!r}")
        if self.kv_format != "bf16":
            format_dtype(self.kv_format)  # actionable fp8-missing error
        from ..pallas_kernels.decode_attention import (
            MAX_PAGED_Q_LEN, MAX_SPEC_K, spec_tree_width)

        if not 0 <= int(self.spec_k) <= MAX_SPEC_K:
            raise ValueError(
                f"spec_k ({self.spec_k}) must be in [0, {MAX_SPEC_K}]: the "
                f"speculative verify scores spec_k + 1 bundle positions in "
                f"one paged flash-decode call, whose query window is "
                f"MAX_PAGED_Q_LEN = {MAX_SPEC_K + 1} — shrink spec_k (draft "
                f"win saturates long before that) or raise MAX_PAGED_Q_LEN "
                f"with the kernel's block budget in mind")
        if self.spec_tree is not None:
            factors = tuple(int(f) for f in self.spec_tree)
            if not factors or any(f < 1 for f in factors):
                raise ValueError(
                    f"spec_tree must be a non-empty sequence of branching "
                    f"factors >= 1 per draft level (e.g. [4, 2, 2]), got "
                    f"{self.spec_tree!r}")
            if int(self.spec_k) != 4:
                raise ValueError(
                    f"spec_tree ({list(factors)}) and a non-default spec_k "
                    f"({self.spec_k}) are mutually exclusive: one engine "
                    f"runs ONE speculative lane — the chain (spec_k drafts "
                    f"per round) or the tree (branching factors per level). "
                    f"Drop spec_k (per-request depth clamps still ride "
                    f"SamplingParams.spec_k) or drop spec_tree")
            wnodes = spec_tree_width(factors)
            if wnodes > MAX_PAGED_Q_LEN:
                raise ValueError(
                    f"spec_tree {list(factors)} flattens to {wnodes} nodes, "
                    f"but the verify bundle scores every node in one paged "
                    f"flash-decode call whose query window is "
                    f"MAX_PAGED_Q_LEN = {MAX_PAGED_Q_LEN} — shrink the "
                    f"branching factors or the depth (accept depth "
                    f"saturates long before that) or raise MAX_PAGED_Q_LEN "
                    f"with the kernel's block budget in mind")
            self.spec_tree = factors
        if int(self.tp) < 1:
            raise ValueError(f"tp ({self.tp}) must be >= 1")
        if self.block_size < 1 or self.max_len % self.block_size:
            raise ValueError(
                f"block_size ({self.block_size}) must divide max_len "
                f"({self.max_len}): the per-slot block table covers "
                f"max_len in whole KV blocks — pick a block_size that "
                f"divides max_len (e.g. 16) or round max_len up to a "
                f"multiple of block_size")
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}")
        if self.num_blocks is not None and self.num_blocks < 2:
            raise ValueError(
                f"num_blocks ({self.num_blocks}) must be >= 2: block 0 "
                f"is the reserved dump block, so at least one usable "
                f"block is needed")
        # hierarchical KV: env-resolved defaults, then validation
        if self.kv_tier is None:
            self.kv_tier = os.environ.get("PADDLE_TPU_KV_TIER", "") \
                not in ("", "0", "false", "False")
        self.kv_tier = bool(self.kv_tier)
        if self.kv_tier_path is None:
            self.kv_tier_path = \
                os.environ.get("PADDLE_TPU_KV_TIER_PATH") or None
        if self.kv_tier_host_gbps is None:
            self.kv_tier_host_gbps = float(
                os.environ.get("PADDLE_TPU_KV_TIER_HOST_GBPS", "12.0"))
        if self.kv_tier:
            if not self.prefix_caching:
                raise ValueError(
                    "kv_tier=True requires prefix_caching=True: tier "
                    "entries are keyed by the prefix cache's exact-token "
                    "keys and re-admission extends prefix matches — "
                    "enable prefix_caching or drop kv_tier")
            if self.kv_tier_host_blocks < 1:
                raise ValueError(
                    f"kv_tier_host_blocks ({self.kv_tier_host_blocks}) "
                    f"must be >= 1")
            if self.kv_tier_host_gbps <= 0 or self.kv_tier_safety <= 0:
                raise ValueError(
                    f"kv_tier_host_gbps ({self.kv_tier_host_gbps}) and "
                    f"kv_tier_safety ({self.kv_tier_safety}) must be > 0")

    def validate_draft(self, model_config, draft_config):
        """Speculative-lane compatibility checks between the target and
        draft models (called by the engine when ``draft_model`` is
        given; lives here so the error surface sits with the other
        config validation)."""
        if self.spec_k < 1:
            raise ValueError(
                f"spec_k ({self.spec_k}) must be >= 1 when a draft_model "
                f"is given — with 0 draft tokens per round the draft "
                f"model is dead weight; drop draft_model instead")
        if draft_config.vocab_size != model_config.vocab_size:
            raise ValueError(
                f"draft/target vocab mismatch: draft vocab_size "
                f"({draft_config.vocab_size}) != target vocab_size "
                f"({model_config.vocab_size}) — speculative verify "
                f"compares draft TOKEN IDS against target selections, so "
                f"both models must share one tokenizer/vocab (e.g. build "
                f"the draft with generation.truncated_draft)")
        if self.max_len > draft_config.max_position_embeddings:
            raise ValueError(
                f"max_len ({self.max_len}) exceeds the DRAFT model's "
                f"max_position_embeddings "
                f"({draft_config.max_position_embeddings}); the draft "
                f"decodes the same positions the target does — shrink "
                f"max_len or use a draft with a longer position table")

    def blocks_per_slot(self) -> int:
        return self.max_len // self.block_size

    def default_num_blocks(self) -> int:
        return self.max_slots * self.blocks_per_slot() + 1


@dataclass
class _PrefillJob:
    """Host-side progress of one chunked prefill: which tokens remain,
    the request's PRNG key (split ONCE, at the final chunk — generate's
    chain), and whether the final select's token was already delivered
    (preemption resume regenerates the last delivered token)."""

    req: Request
    tokens: np.ndarray           # prompt (+ replayed generation on resume)
    total: int
    done: int                    # tokens already in the cache (prefix hits
    key: np.ndarray              # + completed chunks); uint32 [2]
    skip: int                    # 1 on resume: final select re-derives an
    t0: float = field(default_factory=time.perf_counter)  # already-sent token

    def span(self, chunk: int, start: Optional[int] = None) -> tuple:
        """[start, end) of the chunk of ``chunk`` tokens at ``start``,
        the job's next one where None."""
        start = self.done if start is None else start
        return start, min(start + chunk, self.total)


class ServingEngine:
    """Request-level serving over one decoder model (llama / gpt — any
    model speaking the generation.py static-cache protocol).

    Drive it synchronously (``submit`` + ``step``/``run_until_idle`` —
    deterministic, what the tests do) or as a background thread
    (``start``/``stop``; ``submit`` then wakes the loop and callers wait
    on ``Request.result()`` / iterate ``Request.stream()``).
    """

    def __init__(self, model, config: Optional[ServingConfig] = None,
                 draft_model=None, **overrides):
        if config is None:
            config = ServingConfig(**overrides)
        elif overrides:
            raise ValueError("pass ServingConfig OR keyword overrides, not both")
        self.config = config
        self.model = model
        mcfg = model.config
        if config.max_len > mcfg.max_position_embeddings:
            raise ValueError(
                f"max_len ({config.max_len}) exceeds the model's "
                f"max_position_embeddings ({mcfg.max_position_embeddings})")
        # a model with chunked linearized attention (EVA) keeps exact
        # keys inside a window and summaries behind it: its slots give
        # blocks back in mid-sequence (WindowedLayout)
        self._layout: Optional[WindowedLayout] = None
        if getattr(mcfg, "attention_class", None) == "eva":
            self._layout = self._eva_layout(mcfg, config, draft_model)
        # a looped stack (its layers run ``total_ut_steps`` times a
        # token) keeps a K/V plane for every pass of every layer: a pool
        # block id covers them all, a layer's passes side by side in one
        # array (generation.make_paged_kv_pools)
        self._ut_steps = kv_cache_planes(mcfg) // int(mcfg.num_hidden_layers)
        if self._ut_steps > 1:
            self._refuse_for_loop(mcfg, config, draft_model)
        # a latent cache (MLA: one vector a position and layer, no
        # kv-heads axis) is the cache protocol's to shape, write and
        # read; the block pool and the tables see blocks as before
        if latent_cache_width(mcfg) is not None:
            self._refuse_for_latent(config, draft_model)
        # a model with routed experts hands back, with each program's
        # tokens, what it counted of its routing (``route_stats``); the
        # host reads the counts an iteration later, behind the tokens
        # of the step they rode with (``_read_routing``)
        self._routed = bool(getattr(mcfg, "n_routed_experts", 0))
        self._route_unread: List[tuple] = []
        self._route_totals = dict.fromkeys(
            ("programs", "pairs_here", "pairs", "experts_touched",
             "load_max"), 0)
        self.draft_model = draft_model
        self.spec = draft_model is not None
        if self.spec:
            config.validate_draft(mcfg, draft_model.config)
            self._spec_tree = (tuple(config.spec_tree)
                               if config.spec_tree is not None else None)
            if self._spec_tree is not None:
                from ..generation import spec_tree_plan
                self._tree = spec_tree_plan(self._spec_tree)
                # per-request SamplingParams.spec_k clamps the tree
                # DEPTH on the tree lane, so _spec_k doubles as the
                # depth bound and sizes the accept histogram (a round
                # accepts 0..depth draft nodes, one per path level)
                self._spec_k = int(self._tree["depth"])
            else:
                self._tree = None
                self._spec_k = int(config.spec_k)
            from ..pallas_kernels.decode_attention import \
                spec_verify_eligibility
            ok, reason = spec_verify_eligibility(
                self._spec_k,
                next(iter(model.parameters()))._data.dtype,
                spec_tree=self._spec_tree)
            # expected verify-bundle path, recorded once per engine: the
            # kernel serves q_len = spec_k + 1 (chain) or w-node (tree)
            # bundles, or the XLA gather fallback does (reason-counted
            # either way, under the spec_ / spec_tree_ prefix)
            self._spec_verify_kernel = ok
            _trace.instant("spec_verify_path", cat="engine",
                           args={"kernel": ok, "reason": reason,
                                 "k": self._spec_k,
                                 "tree": (list(self._spec_tree)
                                          if self._spec_tree else None)})
        B = int(config.max_slots)
        self.scheduler = Scheduler(config.max_queue_depth)

        self._dtype = next(iter(model.parameters()))._data.dtype
        params = {k: v._data for k, v in model.named_parameters_dict().items()}
        buffers = {k: v._data for k, v in model.named_buffers_dict().items()}
        self._pb = {**params, **buffers}
        self._mcfg = mcfg
        if self.spec:
            self._dcfg = draft_model.config
            self._ddtype = next(iter(draft_model.parameters()))._data.dtype
            self._dpb = {
                **{k: v._data
                   for k, v in draft_model.named_parameters_dict().items()},
                **{k: v._data
                   for k, v in draft_model.named_buffers_dict().items()}}
            self._spec_drafted = 0
            self._spec_accepted = 0
            self._spec_rounds = 0
            # engine-local accept-length histogram (0..k accepted per
            # round — on the tree lane k is the DEPTH, one accepted node
            # per path level): /stats percentiles come from THIS
            # engine's rounds; the registry Summary stays the fleet-wide
            # scrape surface
            self._accept_hist = [0] * (self._spec_k + 1)

        # per-slot decode state (last token, position, PRNG chain,
        # sampling params) lives on DEVICE across steps — the decode loop
        # transfers ONE [B] token vector per iteration (plus the tiny
        # int32 block table); admission updates a slot's state rows
        # inside the jitted chunk program.
        self._state = {
            "tokens": jnp.zeros(B, jnp.int32),     # last token per slot
            "pos": jnp.zeros(B, jnp.int32),        # next cache write index
            "keys": jnp.zeros((B, 2), jnp.uint32),  # per-slot PRNG chain
            "ds": jnp.zeros(B, bool),
            "temp": jnp.ones(B, jnp.float32),
            "tk": jnp.zeros(B, jnp.int32),
            "tp": jnp.ones(B, jnp.float32),
        }
        # tensor parallelism: rule-shard the params over the TP mesh and
        # pin the per-slot state replicated — the executables then run
        # under jit with explicit in/out shardings (see _init_paged), so
        # GSPMD inserts the Megatron collectives and the host-side
        # scheduler/paging logic below never notices the mesh.
        self._tp = int(config.tp)
        self._tp_mesh = None
        self._tp_pb_sh = self._tp_dpb_sh = None
        if self._tp > 1:
            from ..distributed import partition as _partition
            _partition.validate_tp(mcfg, self._tp)
            self._tp_mesh = _partition.tp_mesh(self._tp)
            self._pb, self._tp_pb_sh = _partition.shard_params(
                self._pb, self._tp_mesh,
                _partition.partition_rules_for(model))
            if self.spec:
                _partition.validate_tp(self._dcfg, self._tp,
                                       what="draft model")
                self._dpb, self._tp_dpb_sh = _partition.shard_params(
                    self._dpb, self._tp_mesh,
                    _partition.partition_rules_for(draft_model))
            rep = _partition.replicated(self._tp_mesh)
            self._state = {k: jax.device_put(v, rep)
                           for k, v in self._state.items()}

        self._slot_req: List[Optional[Request]] = [None] * B
        self._slot_sampling = [False] * B  # host mirror for the step cond
        self._decoding = [False] * B       # past prefill, in the step batch
        self._slot_seq = [0] * B           # admission order (victim pick)
        self._admit_seq = 0

        self._steps = 0
        self._occupancy_integral = 0
        self._outcomes = {}
        self._preempt_count = 0
        # event counters: plain ints bumped where the event happens
        # (under _step_lock), read lock-free by counters() and written
        # as deltas into the engine.* span args of the iteration; with
        # _preempt_count, _steps and _occupancy_integral above they are
        # all a metric reads (blocks, COW forks and chunks are counted
        # once already: pool.alloc_total, pool.cow_forks,
        # prefill_chunks_total)
        self._n_prompt_tokens = 0      # tokens admitted prefills cover
        self._n_prefix_hit_tokens = 0  # of those, adopted from the cache
        self._n_prefill_rows = 0       # chunks that rode a prefill program
        self._n_prefill_programs = 0   # prefill programs enqueued
        self._n_prefill_fill_rows = 0  # rows beyond a slot's first
        # the step in flight (``_ahead``, _step_impl)
        self._n_steps_ahead = 0    # steps enqueued with one still unread
        self._n_ahead_flushes = 0  # times something rare read it first
        self._n_dead_rows = 0      # rows whose request ended under them
        self._n_steps_fused = 0    # steps that carried prefill rows too
        self._n_loop_passes = 0    # passes of the steps enqueued
        # windowed layout (EVA) only
        self._n_window_rolls = 0       # slots that crossed into a window
        self._n_window_blocks_released = 0   # exact-key blocks given back
        self._n_summary_entries = 0    # chunks pooled into a summary
        # the iteration's phase spans (engine.iter and its children);
        # .seq numbers the iterations that did work
        self._phases = _trace.Phases("engine.iter", "engine", "engine")
        self._last_progress_ts = time.perf_counter()  # stall detector
        self._step_lock = threading.RLock()
        self._wake = threading.Condition()
        self._running = False
        self._thread: Optional[threading.Thread] = None
        self._crashed: Optional[str] = None  # repr of the fatal loop error
        self._draining = False   # no new admissions; in-flight finishing
        self._stopped = False    # terminal: drained (or aborted) + loop down
        self._warmed_up = False  # warmup() ran: executables AOT-compiled
        # supervisor crash-capture hook: called by _on_loop_crash (step
        # lock held, flight dump already taken, requests NOT yet failed)
        # so a supervisor can detach queued+running requests for requeue
        # on a rebuilt engine before _fail_inflight reaches them
        self._crash_hook = None
        _sm.engine_unhealthy.set(0)  # a fresh engine is the healthy one

        # /debug/requests keeps the tail of finished requests next to the
        # live ones; goodput is deadline-met tokens over a sliding window
        self._recent: deque = deque(maxlen=256)
        self._goodput_window: deque = deque()  # (finish_ts, tokens)
        self._goodput_span_s = 30.0
        # flight-recorder state provider: a crash dump carries this
        # engine's full stats() (pool accounting, per-slot phases, queue
        # depth) — weakref'd so a dead engine drops out of dumps
        ref = weakref.ref(self)
        _trace.register_state_provider(
            "serving_engine",
            lambda ref=ref: (ref().stats() if ref() is not None else None))

        run = make_cached_runner(model)
        self._run = run

        self._tier: Optional[KVTier] = None  # set by _init_paged(kv_tier)
        self._init_paged(B, run)
        self._register_memory_components()

    @staticmethod
    def _eva_layout(mcfg, config: ServingConfig, draft_model):
        """The table layout of an EVA model's slots, after refusing the
        options whose bookkeeping assumes that a slot keeps the exact
        keys of every position it has passed."""
        refused = [
            (config.prefix_caching,
             "prefix_caching=True: a slot forgets its exact keys behind "
             "the window, so a later prompt cannot adopt them; pass "
             "prefix_caching=False"),
            (draft_model is not None,
             "a draft_model: a verify bundle of a few tokens is no whole "
             "chunk, and rollback by position cannot take back a summary "
             "already pooled; drop the draft model"),
            (config.kv_tier,
             "kv_tier=True: tier entries are keyed by the prefix cache, "
             "which this model cannot use; drop kv_tier"),
            (int(config.tp) > 1,
             f"tp={config.tp}: the paged kernel that reads summaries and "
             f"window as one row declines under tp; serve it with tp=1"),
            (config.kv_format != "bf16",
             f"kv_format={config.kv_format!r}: summaries are pooled in the "
             f"model's dtype; use kv_format='bf16'"),
        ]
        for bad, why in refused:
            if bad:
                raise ValueError(
                    "an EVA model (chunked linearized attention) cannot be "
                    "served with " + why)
        W, c = int(mcfg.window_size), int(mcfg.chunk_size)
        C = int(config.prefill_chunk)
        if C % c or W % C:
            raise ValueError(
                f"prefill_chunk ({C}) must be a multiple of the model's "
                f"chunk_size ({c}) that divides its window_size ({W}), so "
                f"that a prefill chunk pools whole chunks and never "
                f"straddles a window")
        return WindowedLayout(config.block_size, W, c, config.max_len)

    @staticmethod
    def _refuse_for_loop(mcfg, config: ServingConfig, draft_model):
        """The options nobody has made work, and tested, with a looped
        stack: each is refused with its reason."""
        refused = [
            (float(getattr(mcfg, "early_exit_threshold", 1.0)) < 1.0,
             f"early_exit_threshold={mcfg.early_exit_threshold}: rows of "
             f"one batched step would leave the stack at different passes "
             f"while later tokens still need the planes of the passes they "
             f"skipped; serve it at the published threshold of 1"),
            (draft_model is not None,
             "a draft_model: the draft's pools share the target's block "
             "tables, and a verify bundle through the passes' planes is "
             "not built; drop the draft model"),
            (config.kv_tier,
             "kv_tier=True: a tier payload holds one plane a layer, not "
             "one a pass and layer; drop kv_tier"),
            (int(config.tp) > 1,
             f"tp={config.tp}: the pools' block axis carries the passes "
             f"and no sharded run of the loop has been tested; serve it "
             f"with tp=1"),
            (config.kv_format != "bf16",
             f"kv_format={config.kv_format!r}: the scale pools are not "
             f"carried through the loop; use kv_format='bf16'"),
        ]
        for bad, why in refused:
            if bad:
                raise ValueError(
                    "a looped stack (total_ut_steps "
                    f"{mcfg.total_ut_steps}) cannot be served with " + why)

    @staticmethod
    def _refuse_for_latent(config: ServingConfig, draft_model):
        """The options nobody has made work, and tested, over a latent
        cache: each is refused with its reason."""
        refused = [
            (draft_model is not None,
             "a draft_model: a verify bundle over the latent pool is not "
             "built; drop the draft model"),
            (config.kv_tier,
             "kv_tier=True: no tier payload of a latent block has been "
             "tested; drop kv_tier"),
            (int(config.tp) > 1,
             f"tp={config.tp}: the latent has no heads axis to shard and "
             f"the paged kernel cannot be partitioned; serve it with tp=1"),
            (config.kv_format != "bf16",
             f"kv_format={config.kv_format!r}: a latent cache is stored "
             f"unquantized; use kv_format='bf16'"),
        ]
        for bad, why in refused:
            if bad:
                raise ValueError(
                    "a latent (MLA) cache cannot be served with " + why)

    def _register_memory_components(self):
        """HBM-ledger attribution (``observability.perf.hbm_ledger``):
        the engine owns the KV pools and holds the model weights — the
        two footprints an OOM forensics dump most needs named. Weakref'd
        like the flight-recorder state provider; a dead engine drops
        out instead of pinning its pools."""
        from ..observability import perf as _perf

        ref = weakref.ref(self)

        def _pool_bytes(attr, ref=ref):
            eng = ref()
            pools = getattr(eng, attr, None) if eng is not None else None
            if pools is None:
                return None
            total = int(sum(arr.nbytes for c in pools for arr in c.values()))
            out = {"bytes": total, "kv_format": eng.config.kv_format,
                   "bytes_per_token": eng._kv_bytes_per_token,
                   "blocks": eng._nblocks}
            if eng._tp > 1:
                # jax .nbytes is the GLOBAL logical size; the pools
                # shard on the kv-heads axis, so each chip holds 1/tp
                out["tp"] = eng._tp
                out["bytes_per_device"] = total // eng._tp
            return out

        def _weight_bytes(ref=ref):
            eng = ref()
            if eng is None:
                return None
            n = int(sum(v.nbytes for v in eng._pb.values()))
            if eng.spec:
                n += int(sum(v.nbytes for v in eng._dpb.values()))
            out = {"bytes": n}
            if eng._tp > 1:
                # Megatron-sharded matmul weights split 1/tp; norms/rope
                # replicate — report the exact per-device residency from
                # the arrays' own shardings, not a naive division
                per_dev = 0
                for pb in ((eng._pb, eng._dpb) if eng.spec else (eng._pb,)):
                    for v in pb.values():
                        try:
                            shard = v.sharding.shard_shape(v.shape)
                            per_dev += int(np.prod(shard, dtype=np.int64)
                                           * v.dtype.itemsize)
                        except Exception:
                            per_dev += int(v.nbytes)
                out["tp"] = eng._tp
                out["bytes_per_device"] = per_dev
            return out

        _perf.register_memory_component(
            "serving_kv_pool", functools.partial(_pool_bytes, "_pools"))
        if self.spec:
            _perf.register_memory_component(
                "serving_draft_kv_pool",
                functools.partial(_pool_bytes, "_dpools"))
        _perf.register_memory_component("serving_model_weights",
                                        _weight_bytes)

    # -- executables --------------------------------------------------------
    def _init_paged(self, B: int, run):
        config = self.config
        mcfg = self._mcfg
        bs = config.block_size
        nb = config.blocks_per_slot() if self._layout is None \
            else self._layout.width
        self._nblocks = int(config.num_blocks or config.default_num_blocks())
        self.pool = BlockPool(self._nblocks, bs)
        self.prefix_cache = PrefixCache(self.pool) if config.prefix_caching \
            else None
        self._pools = make_paged_kv_pools(mcfg, self._nblocks, bs,
                                          self._dtype, config.kv_format)
        tpm = self._tp_mesh
        if tpm is not None:
            from ..distributed import partition as _partition
            self._pools, self._tp_pool_sh = _partition.shard_kv_pools(
                self._pools, tpm)
        # the executables below round-trip the pool dicts generically so
        # quantized pools (extra ks/vs scale arrays) ride every program
        # — chunk, step, COW, draft, verify — without a second variant
        pool_keys = tuple(self._pools[0].keys())
        self._pool_keys = pool_keys
        from ..generation import kv_cache_bytes_per_token
        self._kv_bytes_per_token = kv_cache_bytes_per_token(
            mcfg, config.kv_format, self._dtype)
        _sm.kv_bytes_per_token.labels(config.kv_format).set(
            self._kv_bytes_per_token)
        self._bt = np.zeros((B, nb), np.int32)           # host block tables
        self._slot_blocks: List[List[int]] = [[] for _ in range(B)]
        self._slot_len = [0] * B                         # host mirror of pos
        self._slot_win = [0] * B     # windowed layout: the row's window
        self._jobs: List[Optional[_PrefillJob]] = [None] * B
        # first tokens selected by a prompt's last chunk and not yet read
        # from the device: (a program's tokens, its last rows), read in
        # the NEXT iteration's engine.wait (_deliver_first_tokens)
        self._parked_tokens: List[tuple] = []
        # the decode step that is enqueued and not yet read, a pipeline
        # of depth one: ``(tokens on the device, [(slot, request)], the
        # time of its dispatch)``; and per slot the tokens selected on
        # the device for its occupant and not yet delivered (a parked
        # first token, a row of the step in flight), which with the
        # delivered ones decide ``max_new_tokens``
        self._ahead: Optional[tuple] = None
        self._slot_due = [0] * B
        self._sync_ns = 0     # when the last step's tokens reached the host
        self._unseated = 0    # requests _admit has popped and not seated
        # this engine's closures are NEW executables — their first
        # compiles are warmup, not retraces of a previous engine's
        C = int(config.prefill_chunk)
        P = prefill_batch_rows(C, self._dtype, B)
        # one program for the decode step and the iteration's last
        # prefill rows, so that an iteration reads the weights once:
        # where the step is one pass through one table layout with its
        # tokens on the device (a windowed row rolls between the two
        # halves, a looped stack would part inside its loop, the
        # speculative lane sizes its bundles from tokens on the host,
        # and a sharded step's in_shardings are one fixed tuple)
        self._fuses = (self._layout is None and self._ut_steps == 1
                       and not self.spec and tpm is None)
        # the widths prefill rows ride at: [P, C], and [1, C] for a lone
        # chunk. An engine that fuses keeps [P, C] alone, for the
        # prefill program and for the step that carries rows: every
        # executable costs warmup() its size, warm cache or not (on the
        # chip 1.9 s a plain program and 3.5 s a fused one at GPT-3
        # 1.3B's depth, PERF.md section 6, PR 38), and the fused [P, C]
        # program is worth more than the two [1, C] forms it displaces
        self._row_widths = [P] if self._fuses else sorted({1, P})
        self._chunk_entries = [self._chunk_entry(w)
                               for w in self._row_widths]
        self._fused_entries = [self._step_entry(P)] if self._fuses else []
        warm = ["serving.step", "serving.cow", *self._chunk_entries,
                *self._fused_entries]
        if self.spec:
            warm += ["serving.spec_draft", "serving.spec_verify"]
        if config.kv_tier:
            warm += ["serving.kv_demote", "serving.kv_splice"]
        _recompile.reset_warmup(*warm)
        if self.spec:
            # the draft model's KV pools mirror the target's block
            # structure and are addressed by the SAME per-slot block
            # tables, so one host-side allocator/prefix-cache/COW
            # bookkeeping drives both models' caches
            self._dpools = make_paged_kv_pools(
                self._dcfg, self._nblocks, bs, self._ddtype,
                config.kv_format)
            if tpm is not None:
                self._dpools, self._tp_dpool_sh = _partition.shard_kv_pools(
                    self._dpools, tpm)
            self._drun = make_cached_runner(self.draft_model)

        # executable wrapper: plain jit at tp=1; at tp>1 jit with
        # EXPLICIT in/out shardings — round-tripped trees (pools, state)
        # keep identical layouts on both sides so the compiled signature
        # is a fixpoint and the one-compile invariant survives sharding
        # — plus the trace-time tp context the Pallas decode dispatch
        # consults (a pallas_call cannot be GSPMD-partitioned; under
        # tp>1 attention takes the XLA gather path, which shards
        # cleanly on the kv-heads axis).
        if tpm is None:
            rep = pb_sh = pool_sh = state_sh = None

            def _wrap(fn, donate, in_s, out_s):
                return jax.jit(fn, donate_argnums=donate)
        else:
            rep = _partition.replicated(tpm)
            pb_sh = self._tp_pb_sh
            pool_sh = self._tp_pool_sh
            state_sh = {k: rep for k in self._state}

            def _wrap(fn, donate, in_s, out_s):
                return _partition.tp_jit(
                    fn, tp=self._tp, mesh=tpm, in_shardings=in_s,
                    out_shardings=out_s, donate_argnums=donate)
        self._tp_rep = rep
        self._tp_state_sh = state_sh
        self._tp_wrap = _wrap

        def _first_tokens(state, last, f):
            """The final-token select of a batch of chunks (``f``: the
            rows' fields, ``_unpack_rows``), each row with its own key
            chain (generate's, as ``_step`` walks it for the pool), and
            the state rows of the slots whose chunk ``is_last``. Every
            other row's write goes out of range and is dropped, so a row
            that carries nothing can name any slot."""
            key2, sub = split_keys(f["key"])
            token = jax.lax.cond(
                jnp.any(f["ds"]),
                lambda: select_tokens(last, sub, f["ds"], f["temp"],
                                      f["tk"], f["tp"]),
                lambda: jnp.argmax(last, axis=-1).astype(jnp.int32))
            at = jnp.where(f["is_last"], f["slot"], B)
            state = dict(state)
            for name, new in (("tokens", token),
                              ("pos", f["pos0"] + f["valid"]),
                              ("keys", key2), ("ds", f["ds"]),
                              ("temp", f["temp"]), ("tk", f["tk"]),
                              ("tp", f["tp"])):
                state[name] = state[name].at[at].set(
                    new.astype(state[name].dtype), mode="drop")
            return token, state

        routed = self._routed

        def _chunk_live(valid):
            """[P, C] bool: the tokens of a batch of chunks that are
            real (a routed model leaves the rest out of its experts'
            work and of its counts)."""
            return jnp.arange(C, dtype=jnp.int32)[None, :] < valid[:, None]

        def _chunk(pb, pools, state, rows):
            """The fixed-shape prefill program: the next chunk of up to
            P prefilling slots as the rows of ``rows`` (packed:
            ``_unpack_rows``), row r's ``ids`` [C] at offset ``pos0[r]``
            through table row ``bt[r]`` (writes scatter through it; pad
            tokens beyond ``valid[r]`` land in the dump block, and so
            does all of a row that carries no chunk: ``valid`` 0 and a
            zeroed table row). The head sees each row's ``last_idx``
            position alone. State rows are set only for rows whose
            ``is_last`` is set (traced: neither the chunk count nor the
            number of live rows retraces); the select itself is computed
            every time and simply unused until then. The one body is run
            at two widths: [P, C], and [1, C] for an iteration's lone
            chunk (``_enqueue_claimed``)."""
            bt, ids, f = _unpack_rows(rows, nb, C)
            caches = [dict(c, bt=bt, valid=f["valid"]) for c in pools]
            caches[0]["head_idx"] = f["last_idx"]
            if routed:
                caches[0]["live"] = _chunk_live(f["valid"])
            logits, newc = run(pb, ids, caches, f["pos0"])
            token, state = _first_tokens(state, logits[:, 0], f)
            pools_out = [{kk: c[kk] for kk in pool_keys} for c in newc]
            if routed:
                token = token, newc[0]["route_stats"]
            return token, pools_out, state

        _chunk = _wrap(_chunk, (1, 2), (pb_sh, pool_sh, state_sh, rep),
                       (rep, pool_sh, state_sh))

        def _step(pb, pools, state, bt, any_sampling, active, rows=None):
            """ONE decode iteration for the whole slot pool, reading and
            writing KV through the traced block tables ``bt`` [B, nb]
            (inactive rows are zeroed by the host -> their static-shape
            writes land in the dump block). Traced per-slot positions,
            sampling params and keys drive the per-row RoPE, cache write
            and batched sampler; the ``any_sampling`` cond skips the
            sampler for pure-argmax pools; free rows ride along pinned
            to pos 0. Compiles exactly once —
            occupancy, length mix, and SHARING patterns are all data.

            With ``rows`` (an engine that ``_fuses``: the iteration's
            last prefill rows, packed as ``_chunk`` takes them) the
            same program carries those chunks too: their
            tokens and the decode rows' are ONE batch of ``P * C + B``
            single-token rows, each at its own position, so the linear
            layers, the norms and the head read the weights once for
            all of them; ``generation.cached_attention`` parts them
            again at the seam (``chunk_bt``), and the head sees the P
            ``last_idx`` rows and the B decode rows. Each half then
            selects as it does alone, and the program also returns the
            chunks' first tokens. A slot is in one half or in neither:
            one whose last chunk rides here joins the NEXT step, which
            reads the state row written here. Two traces of one
            function (``rows`` absent, or [P, ..])."""
            if rows is None:
                caches = [dict(c, bt=bt) for c in pools]
                if routed:
                    caches[0]["live"] = active[:, None]
                logits, newc = run(pb, state["tokens"][:, None], caches,
                                   state["pos"])
                last = logits[:, 0]
            else:
                cbt, ids, f = _unpack_rows(rows, nb, C)
                n = rows.shape[0] * C
                caches = [dict(c, bt=bt, chunk_bt=cbt,
                               chunk_valid=f["valid"]) for c in pools]
                if routed:
                    caches[0]["live"] = jnp.concatenate(
                        [_chunk_live(f["valid"]).reshape(n), active])[:, None]
                caches[0]["head_pick"] = jnp.concatenate(
                    [jnp.arange(0, n, C, dtype=jnp.int32) + f["last_idx"],
                     n + jnp.arange(B, dtype=jnp.int32)])
                at = f["pos0"][:, None] + jnp.arange(C, dtype=jnp.int32)
                logits, newc = run(
                    pb, jnp.concatenate([ids.reshape(n),
                                         state["tokens"]])[:, None],
                    caches, jnp.concatenate([at.reshape(n), state["pos"]]))
                first, last = logits[:rows.shape[0], 0], \
                    logits[rows.shape[0]:, 0]
            new_keys, subs = split_keys(state["keys"])
            nxt = jax.lax.cond(
                any_sampling,
                lambda: select_tokens(last, subs, state["ds"], state["temp"],
                                      state["tk"], state["tp"]),
                lambda: jnp.argmax(last, axis=-1).astype(jnp.int32))
            state = dict(state)
            state["tokens"] = nxt
            state["pos"] = jnp.where(
                active,
                jnp.minimum(state["pos"] + 1, jnp.int32(config.max_len - 1)),
                jnp.int32(0))
            state["keys"] = new_keys
            pools_out = [{kk: c[kk] for kk in pool_keys} for c in newc]
            if routed:
                nxt = nxt, newc[0]["route_stats"]
            if rows is None:
                return nxt, pools_out, state
            # (after the step's own: a slot whose last chunk rides here
            # is no decode row of this step, and what the step leaves in
            # an inactive row is scratch)
            token, state = _first_tokens(state, first, f)
            return nxt, token, pools_out, state

        _step = _wrap(_step, (1, 2),
                      (pb_sh, pool_sh, state_sh, rep, rep, rep),
                      (rep, pool_sh, state_sh))

        # (locals, not ``self``: an executable that held the engine
        # would keep engine, model and weights alive in a cycle that
        # only the collector breaks)
        passes, nblocks = self._ut_steps, self._nblocks

        def _cow(pools, src, dst):
            """Copy-on-write fork: duplicate physical block ``src`` into
            ``dst`` across every layer's K and V pool (one dispatch;
            src/dst are traced so every fork shares the executable)."""
            if passes > 1:
                # a looped stack: the block of every pass's plane
                at = jnp.arange(passes, dtype=jnp.int32) * nblocks
                src, dst = src + at, dst + at
            out = []
            for c in pools:
                out.append({kk: c[kk].at[dst].set(c[kk][src])
                            for kk in c})
            return out

        _cow = _wrap(_cow, (0,), (pool_sh, rep, rep), pool_sh)

        self._chunk_fn = _chunk
        self._step_fn = _step
        self._cow_fn = _cow
        self._chunk_size = C
        self._chunk_rows = P
        # retrace warnings for the engine entries cite these defs
        for entry in ("serving.step", *self._fused_entries):
            _recompile.register_entry_location(entry, _step)
        for entry in self._chunk_entries:
            _recompile.register_entry_location(entry, _chunk)
        _recompile.register_entry_location("serving.cow", _cow)
        if config.kv_tier:
            self._init_kv_tier(pool_keys, _wrap, rep, pool_sh)
        if self.spec:
            self._init_spec(B, run, _first_tokens)
        if self._tp > 1:
            # per-shard perf-ledger rows: the sharded executables'
            # cost_analysis is captured from the PARTITIONED module, so
            # flops/bytes/MFU are already per-device — the mesh tag makes
            # that explicit in /stats and the roofline ledger
            from ..observability import perf as _perf
            for e in warm:
                _perf.note_entry_mesh(e, {"tp": self._tp})

    # -- hierarchical KV: host/disk tiers under the pool ---------------------
    def _init_kv_tier(self, pool_keys, _wrap, rep, pool_sh):
        """Two more one-compile executables plus the host-side tier
        state machine (``serving/kv_tier.py``):

        - ``serving.kv_demote``: gather ONE block's rows out of every
          pool (target + draft + int8/fp8 scale companions) — the
          device half of a device->host demotion. ``src`` is traced, so
          every demotion shares the executable.
        - ``serving.kv_splice``: scatter a demoted block's payload back
          into pool block ``dst`` (donated pools, traced ``dst``) — the
          re-admission that replaces that block's prefill chunks.

        Both run under jit with the same explicit-sharding wrapper as
        the other executables at tp>1 (payloads replicate; the pool
        sides keep the kv-heads sharding), so the zero-retrace
        invariant holds with tiering ON.
        """
        config = self.config
        spec = self.spec
        dpool_sh = self._tp_dpool_sh if (spec and rep is not None) else None

        if spec:
            def _kv_extract(pools, dpools, src):
                return ([{kk: c[kk][src] for kk in pool_keys}
                         for c in pools],
                        [{kk: c[kk][src] for kk in c} for c in dpools])

            def _kv_splice(pools, dpools, pay, dpay, dst):
                return ([{kk: c[kk].at[dst].set(pay[li][kk])
                          for kk in c} for li, c in enumerate(pools)],
                        [{kk: c[kk].at[dst].set(dpay[li][kk])
                          for kk in c} for li, c in enumerate(dpools)])

            ex_t = [{kk: rep for kk in pool_keys} for _ in self._pools]
            ex_d = [{kk: rep for kk in c} for c in self._dpools]
            _kv_extract = _wrap(_kv_extract, (),
                                (pool_sh, dpool_sh, rep), (ex_t, ex_d))
            _kv_splice = _wrap(_kv_splice, (0, 1),
                               (pool_sh, dpool_sh, ex_t, ex_d, rep),
                               (pool_sh, dpool_sh))
        else:
            def _kv_extract(pools, src):
                return [{kk: c[kk][src] for kk in pool_keys}
                        for c in pools]

            def _kv_splice(pools, pay, dst):
                return [{kk: c[kk].at[dst].set(pay[li][kk]) for kk in c}
                        for li, c in enumerate(pools)]

            ex_t = [{kk: rep for kk in pool_keys} for _ in self._pools]
            _kv_extract = _wrap(_kv_extract, (), (pool_sh, rep), ex_t)
            _kv_splice = _wrap(_kv_splice, (0,), (pool_sh, ex_t, rep),
                               pool_sh)
        self._kv_extract_fn = _kv_extract
        self._kv_splice_fn = _kv_splice
        _recompile.register_entry_location("serving.kv_demote", _kv_extract)
        _recompile.register_entry_location("serving.kv_splice", _kv_splice)

        # host bytes one demoted block costs (per-block rows across all
        # pools at quantized width) — the cost model's transfer size
        blk = sum(
            int(np.prod(c[kk].shape[1:], dtype=np.int64))
            * c[kk].dtype.itemsize
            for c in self._pools for kk in c)
        if spec:
            blk += sum(
                int(np.prod(c[kk].shape[1:], dtype=np.int64))
                * c[kk].dtype.itemsize
                for c in self._dpools for kk in c)
        self._tier_block_bytes = int(blk)

        def _prefill_rate():
            from ..observability import perf as _perf
            row = _perf.ledger_entry("serving.prefill_chunk")
            return row.get("items_per_s") if row else None

        cost = TierCostModel(host_gbps=config.kv_tier_host_gbps,
                             safety=config.kv_tier_safety,
                             prefill_rate_fn=_prefill_rate)
        disk = None
        if config.kv_tier_path:
            # re-admitting a foreign engine's bytes would be silent
            # corruption — the fingerprint pins everything that shapes
            # a block's payload or its interpretation
            disk = DiskPrefixStore(config.kv_tier_path, fingerprint={
                "kv_format": config.kv_format,
                "block_size": config.block_size,
                "bytes_per_token": self._kv_bytes_per_token,
                "dtype": str(np.dtype(self._dtype)),
                "spec": spec,
                "layers": kv_cache_planes(self._mcfg),
            })
        self._tier = KVTier(host_blocks=config.kv_tier_host_blocks,
                            block_size=config.block_size, cost=cost,
                            disk=disk)
        self.prefix_cache.on_evict = self._on_prefix_evict

    def _tier_extract(self, bid: int) -> dict:
        """Device->host copy of block ``bid``'s rows across every pool,
        as the tier's flat ``{"<layer>/<pool-key>": ndarray}`` payload
        (draft-model rows under ``d<layer>/``)."""
        t0 = time.perf_counter_ns()
        src = jnp.asarray(bid, jnp.int32)
        with _entrypoint("serving.kv_demote"):
            if self.spec:
                t, d = self._kv_extract_fn(self._pools, self._dpools, src)
            else:
                t, d = self._kv_extract_fn(self._pools, src), None
        payload = {}
        for li, c in enumerate(jax.device_get(t)):
            for kk, arr in c.items():
                payload[f"{li}/{kk}"] = np.asarray(arr)
        if d is not None:
            for li, c in enumerate(jax.device_get(d)):
                for kk, arr in c.items():
                    payload[f"d{li}/{kk}"] = np.asarray(arr)
        t1 = time.perf_counter_ns()
        _trace.complete("kv_demote", "engine", None, t0, t1 - t0,
                        {"block": bid})
        return payload

    def _tier_splice(self, bid: int, payload: dict):
        """Scatter a demoted payload back into pool block ``bid`` (the
        host->HBM re-admission; one jitted dispatch)."""
        t0 = time.perf_counter_ns()
        dst = jnp.asarray(bid, jnp.int32)
        pay = [{kk: jnp.asarray(payload[f"{li}/{kk}"])
                for kk in self._pool_keys}
               for li in range(len(self._pools))]
        with _entrypoint("serving.kv_splice"):
            if self.spec:
                dkeys = tuple(self._dpools[0].keys())
                dpay = [{kk: jnp.asarray(payload[f"d{li}/{kk}"])
                         for kk in dkeys}
                        for li in range(len(self._dpools))]
                self._pools, self._dpools = self._kv_splice_fn(
                    self._pools, self._dpools, pay, dpay, dst)
            else:
                self._pools = self._kv_splice_fn(self._pools, pay, dst)
        t1 = time.perf_counter_ns()
        _trace.complete("kv_splice", "engine", None, t0, t1 - t0,
                        {"block": bid})

    def _on_prefix_evict(self, key: bytes, bid: int, end: int) -> str:
        """PrefixCache eviction hook: copy the victim block down to the
        host tier when the cost model says the transfer beats the
        recompute it saves; the cache frees the device block either
        way."""
        tier = self._tier
        if tier is None:
            return "dropped"
        if not tier.cost.should_demote(tier.tokens_in_block(end),
                                       self._tier_block_bytes):
            return "dropped"
        tier.put(key, end, self._tier_extract(bid), reason="evict")
        return "demoted"

    def _demote_slot_blocks(self, slot: int, tokens: np.ndarray,
                            covered: int):
        """Preemption-side demotion: the victim slot's PRIVATE blocks
        (nobody else references them — shared ones survive in the
        prefix cache) demote to the host tier before ``_clear_slot``
        frees them, so the preempted request's resume prefill re-admits
        instead of recomputing."""
        tier = self._tier
        if tier is None or covered <= 0:
            return
        bs = self.config.block_size
        for i, bid in enumerate(self._slot_blocks[slot]):
            end = min((i + 1) * bs, covered)
            if end <= i * bs:
                break
            if self.pool.ref(bid) != 1:
                continue
            key = tier.key_of(tokens, end)
            if tier.has(key):
                continue
            if not tier.cost.should_demote(tier.tokens_in_block(end),
                                           self._tier_block_bytes):
                continue
            tier.put(key, end, self._tier_extract(bid), reason="preempt")

    def _flush_tier(self):
        """Drain-time persistence sweep (the restart contract): every
        still-cached prefix demotes to the host tier, then the whole
        host tier commits to the disk store. Best-effort — shutdown
        must never wedge on a full disk."""
        tier = self._tier
        if tier is None or tier.disk is None or self.prefix_cache is None:
            return
        try:
            for key, bid, end in self.prefix_cache.entries():
                if not tier.has(key):
                    tier.put(key, end, self._tier_extract(bid),
                             reason="flush")
            n = tier.flush()
            _trace.instant("kv_tier_flush", cat="engine",
                           args={"committed": n})
        except Exception as e:  # noqa: BLE001 — see docstring
            import warnings
            warnings.warn(f"kv_tier: drain-time flush failed "
                          f"(persistence skipped): {e!r}")

    # -- executables: speculative lane ---------------------------------------
    def _init_spec(self, B: int, run, first_tokens):
        """Draft + verify executables over the shared block tables.

        Two programs replace the plain decode step: ``spec_draft`` runs
        k cached draft-model forwards (q_len 1) proposing one token
        each, ``spec_verify`` scores the whole [B, k+1] bundle with the
        target in ONE paged flash-decode call and accepts the longest
        draft prefix matching the target's own selections. Every
        per-row quantity (positions, block tables, live bundle width
        ``spec_valid``, accept length) is traced data, so both compile
        exactly once whatever the accept-length pattern.

        PRNG contract: the draft proposes with the SAME chain subkeys
        the verify selects with (common-noise coupling), and the verify
        commits the chain at level ``n_emit`` — one split per EMITTED
        token, exactly the non-speculative chain, so outputs are
        bit-identical to plain decode (greedy AND sampled) and
        preemption's replay-by-token-count machinery works untouched.

        KV rollback is BY POSITION: rejected draft/target writes stay in
        the pool past the committed length; the next round's bundle
        lands on top of them before any in-length query can attend them
        (the same contract the contiguous cache's garbage rides on)."""
        config = self.config
        k = self._spec_k
        drun = self._drun
        pool_keys = self._pool_keys
        nb, C = self._bt.shape[1], self._chunk_size
        _wrap = self._tp_wrap
        rep = self._tp_rep
        pb_sh, dpb_sh = self._tp_pb_sh, self._tp_dpb_sh
        pool_sh = getattr(self, "_tp_pool_sh", None)
        dpool_sh = getattr(self, "_tp_dpool_sh", None)
        state_sh = self._tp_state_sh

        def _draft(dpb, dpools, state, bt, spec_valid, any_sampling):
            """k cached draft forwards proposing the bundle's draft
            tokens. ``spec_valid`` [B] is each row's live bundle width:
            draft writes beyond it are routed to the dump block (rows
            opted out of speculation still get their last token's draft
            KV at width 1, keeping the draft cache consistent for
            free)."""
            _, subs = split_key_levels(state["keys"], k)
            tok = state["tokens"]
            pos = state["pos"]
            drafts = []
            cur = dpools
            for j in range(k):
                caches = [dict(c, bt=bt,
                               valid=jnp.maximum(spec_valid - j, 0))
                          for c in cur]
                logits, newdc = drun(dpb, tok[:, None], caches, pos + j)
                last = logits[:, 0]
                sub_j = subs[:, j]
                tok = jax.lax.cond(
                    any_sampling,
                    lambda l=last, s=sub_j: select_tokens(
                        l, s, state["ds"], state["temp"], state["tk"],
                        state["tp"]),
                    lambda l=last: jnp.argmax(l, axis=-1).astype(jnp.int32))
                drafts.append(tok)
                cur = [{kk: c[kk] for kk in pool_keys} for c in newdc]
            # one write-only forward for the LAST draft token: on a
            # full accept the sequence advances past pos+k, and d_k's
            # draft KV was only ever an output — without this write the
            # next round's draft attends a hole there and falls off the
            # chain (accept rate halves; outputs are unaffected since
            # verify is target-authoritative). Dump-routed unless the
            # row's bundle really spans k+1 positions.
            caches = [dict(c, bt=bt, valid=jnp.maximum(spec_valid - k, 0))
                      for c in cur]
            _, newdc = drun(dpb, tok[:, None], caches, pos + k)
            cur = [{kk: c[kk] for kk in pool_keys} for c in newdc]
            return jnp.stack(drafts, axis=1), cur

        _draft = _wrap(_draft, (1,),
                       (dpb_sh, dpool_sh, state_sh, rep, rep, rep),
                       (rep, dpool_sh))

        def _verify(pb, pools, state, bt, drafts, spec_valid, any_sampling,
                    active):
            """ONE target forward over the [B, k+1] bundle (the paged
            kernel's q_len > 1 path), candidate selection for every
            position with that position's chain subkey, accept-length
            commit. Rows with ``spec_valid`` 1 ride as plain decode
            steps (their drafts are ignored), width-0 rows are inert —
            mixed spec/non-spec pools share this one executable."""
            bundle = jnp.concatenate([state["tokens"][:, None], drafts],
                                     axis=1)
            caches = [dict(c, bt=bt, valid=spec_valid) for c in pools]
            logits, newc = run(pb, bundle, caches, state["pos"])
            levels, subs = split_key_levels(state["keys"], k + 1)
            V = logits.shape[-1]
            flat = logits.reshape(B * (k + 1), V)

            def _rep(x):
                return jnp.broadcast_to(
                    x[:, None], (B, k + 1)).reshape(B * (k + 1))

            cand = jax.lax.cond(
                any_sampling,
                lambda: select_tokens(
                    flat, subs.reshape(B * (k + 1), 2), _rep(state["ds"]),
                    _rep(state["temp"]), _rep(state["tk"]),
                    _rep(state["tp"])),
                lambda: jnp.argmax(flat, axis=-1).astype(jnp.int32)
            ).reshape(B, k + 1)
            n_emit = spec_accept_length(drafts, cand, spec_valid)
            new_keys = jnp.take_along_axis(
                levels, n_emit[:, None, None], axis=1)[:, 0]
            last = jnp.take_along_axis(
                cand, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
            state = dict(state)
            state["tokens"] = jnp.where(n_emit > 0, last, state["tokens"])
            state["pos"] = jnp.where(
                active,
                jnp.minimum(state["pos"] + n_emit,
                            jnp.int32(config.max_len - 1)),
                jnp.int32(0))
            state["keys"] = new_keys
            pools_out = [{kk: c[kk] for kk in pool_keys} for c in newc]
            return cand, n_emit, pools_out, state

        _verify = _wrap(_verify, (1, 2),
                        (pb_sh, pool_sh, state_sh, rep, rep, rep, rep, rep),
                        (rep, rep, pool_sh, state_sh))

        if self._spec_tree is not None:
            # tree lane (ServingConfig.spec_tree): the chain pair above
            # is replaced before anything traces it — same entry names,
            # so warmup, recompile accounting, and the dispatch sites
            # stay lane-agnostic. The tree verify additionally owns the
            # draft pools (the accepted path's KV commits by position in
            # BOTH models' caches).
            _draft, _verify = self._build_tree_spec(B, run)

        def _chunk_spec(pb, dpb, pools, dpools, state, rows):
            """The prefill program with the draft model riding along:
            both models' paged caches take the chunks' writes through
            the one batch of table rows, so prefix-cached blocks carry
            BOTH models' KV and preemption-resume re-prefills both.
            Select and state are the plain program's."""
            bt, ids, f = _unpack_rows(rows, nb, C)
            caches = [dict(c, bt=bt, valid=f["valid"]) for c in pools]
            caches[0]["head_idx"] = f["last_idx"]
            dcaches = [dict(c, bt=bt, valid=f["valid"]) for c in dpools]
            # the draft's logits are read by nobody: one position a row
            dcaches[0]["head_idx"] = f["last_idx"]
            logits, newc = run(pb, ids, caches, f["pos0"])
            _, newdc = drun(dpb, ids, dcaches, f["pos0"])
            token, state = first_tokens(state, logits[:, 0], f)
            pools_out = [{kk: c[kk] for kk in pool_keys} for c in newc]
            dpools_out = [{kk: c[kk] for kk in pool_keys} for c in newdc]
            return token, pools_out, dpools_out, state

        _chunk_spec = _wrap(
            _chunk_spec, (2, 3, 4),
            (pb_sh, dpb_sh, pool_sh, dpool_sh, state_sh, rep),
            (rep, pool_sh, dpool_sh, state_sh))

        def _cow_spec(pools, dpools, src, dst):
            """COW fork across BOTH models' pools (same block ids)."""
            out, dout = [], []
            for c in pools:
                out.append({kk: c[kk].at[dst].set(c[kk][src])
                            for kk in c})
            for c in dpools:
                dout.append({kk: c[kk].at[dst].set(c[kk][src])
                             for kk in c})
            return out, dout

        _cow_spec = _wrap(_cow_spec, (0, 1),
                          (pool_sh, dpool_sh, rep, rep),
                          (pool_sh, dpool_sh))

        self._draft_fn = _draft
        self._verify_fn = _verify
        self._chunk_spec_fn = _chunk_spec
        self._cow_spec_fn = _cow_spec
        wd = int(self._tree["nodes"]) - 1 if self._spec_tree is not None \
            else k
        self._zero_drafts = jnp.zeros((B, wd), jnp.int32)
        _recompile.register_entry_location("serving.spec_draft", _draft)
        _recompile.register_entry_location("serving.spec_verify", _verify)
        for entry in self._chunk_entries:
            _recompile.register_entry_location(entry, _chunk_spec)
        _recompile.register_entry_location("serving.cow", _cow_spec)

    def _build_tree_spec(self, B: int, run):
        """TREE-speculative draft + verify executables
        (``ServingConfig.spec_tree``; Medusa/SpecInfer-class token-tree
        verification on this repo's paged substrate).

        ``spec_draft`` grows the token tree level by level, each forward
        re-feeding the WHOLE tree-so-far under the square ancestor mask
        (past-KV masking is untouched, so a rectangular new-nodes-only
        query is not expressible; earlier nodes' KV rewrites
        bit-identically). Branch 0 of every node proposes with the exact
        chain subkey for its depth — the non-speculative sampler's own
        draw — and branches r > 0 diversify via ``fold_in`` on the
        child's BFS index. ``spec_verify`` scores all w flattened nodes
        in ONE paged flash-decode call (the [B, w, w] ancestor mask
        rides the cache dicts the way per-slot sampling params ride the
        state), walks the deepest root-to-leaf path whose every node
        matches the target's selection for its parent, and commits that
        path's KV BY POSITION in both models' pools — a gather/scatter
        through the block tables where non-committed slots route back
        onto themselves (same-value no-op writes). Node i's cache slot
        is pos + i; its RoPE/positional index is pos + depth(i), carried
        by the ``tree_depth`` vector.

        PRNG contract: identical to the chain lane — all depth-t nodes
        verify with chain subkey ``subs[:, t]``, the chain commits at
        level ``n_emit`` (one split per EMITTED token), so outputs are
        bit-identical to non-speculative decode (greedy AND sampled) and
        preemption replay / failover requeue machinery never notices the
        tree. Every per-row quantity (positions, block tables, live
        BFS-prefix width ``spec_valid``, accept depth) is traced data:
        both programs compile exactly once; width-1 rows ride the bundle
        as plain decode steps."""
        config = self.config
        drun = self._drun
        pool_keys = self._pool_keys
        _wrap = self._tp_wrap
        rep = self._tp_rep
        pb_sh, dpb_sh = self._tp_pb_sh, self._tp_dpb_sh
        pool_sh = getattr(self, "_tp_pool_sh", None)
        dpool_sh = getattr(self, "_tp_dpool_sh", None)
        state_sh = self._tp_state_sh
        plan = self._tree
        D, w = int(plan["depth"]), int(plan["nodes"])
        off = [int(o) for o in plan["offsets"]]
        factors = plan["factors"]
        parent = jnp.asarray(plan["parent"])
        depth_vec = jnp.asarray(plan["depth_vec"])
        anc_idx = jnp.asarray(plan["anc_idx"])
        anc = jnp.asarray(plan["anc"])
        bs = config.block_size

        def _rep_bw(x, m):
            return jnp.broadcast_to(x[:, None], (B, m)).reshape(B * m)

        def _tree_caches(pools, bt, valid, n):
            tm = jnp.broadcast_to(anc[:n, :n][None], (B, n, n))
            return [dict(c, bt=bt, valid=valid, tree_mask=tm,
                         tree_depth=depth_vec[:n]) for c in pools]

        def _draft(dpb, dpools, state, bt, spec_valid, any_sampling):
            """D level forwards + one write-only full-width forward.
            ``spec_valid`` [B] is each row's live node width (a BFS
            prefix): writes beyond it route to the dump block, so rows
            opted down to plain decode still get their root token's
            draft KV at width 1 (draft cache stays consistent for
            free)."""
            _, subs = split_key_levels(state["keys"], D + 1)
            tok_tree = jnp.zeros((B, w), jnp.int32).at[:, 0].set(
                state["tokens"])
            pos = state["pos"]
            cur = dpools
            for t in range(D):
                n = off[t + 1]
                caches = _tree_caches(
                    cur, bt, jnp.minimum(spec_valid, jnp.int32(n)), n)
                logits, newdc = drun(dpb, tok_tree[:, :n], caches, pos)
                cur = [{kk: c[kk] for kk in pool_keys} for c in newdc]
                lvl = logits[:, off[t]:n]            # [B, w_t, V]
                f = factors[t]
                w_next = off[t + 2] - off[t + 1]
                # greedy: branch 0 = argmax EXPLICITLY (bit-parity with
                # the verify selection under any top_k tie-break),
                # branches r>0 = the r-th ranked token
                tk = jax.lax.top_k(lvl, f)[1].astype(jnp.int32)
                tk = tk.at[:, :, 0].set(
                    jnp.argmax(lvl, axis=-1).astype(jnp.int32))
                greedy = tk.reshape(B, w_next)

                def _samp(lvl=lvl, t=t, f=f, w_next=w_next, greedy=greedy):
                    V = lvl.shape[-1]
                    base = subs[:, t]                # the chain subkey
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
                        _rep_bw(state["ds"], w_next),
                        _rep_bw(state["temp"], w_next),
                        _rep_bw(state["tk"], w_next),
                        _rep_bw(state["tp"], w_next)).reshape(B, w_next)
                    return jnp.where(state["ds"][:, None], sampled, greedy)

                children = jax.lax.cond(any_sampling, _samp,
                                        lambda g=greedy: g)
                tok_tree = tok_tree.at[:, off[t + 1]:off[t + 2]].set(
                    children)
            # write-only forward at full width: leaf KV, so a deep
            # accept never leaves the next round's draft attending a
            # hole (outputs are unaffected either way — the verify is
            # target-authoritative)
            caches = _tree_caches(cur, bt, spec_valid, w)
            _, newdc = drun(dpb, tok_tree, caches, pos)
            cur = [{kk: c[kk] for kk in pool_keys} for c in newdc]
            return tok_tree[:, 1:], cur

        _draft = _wrap(_draft, (1,),
                       (dpb_sh, dpool_sh, state_sh, rep, rep, rep),
                       (rep, dpool_sh))

        def _kv_path_move(pools, bt, src_tok, dst_tok):
            """Commit-walk scatter: flat pool index = physical block
            (via the row's table) * block_size + offset; every path
            slot's payload is gathered BEFORE any write lands, and
            duplicate destinations only ever carry identical values
            (non-committed entries route onto their own source)."""
            nb_cols = bt.shape[1]
            sblk = jnp.clip(src_tok // bs, 0, nb_cols - 1)
            dblk = jnp.clip(dst_tok // bs, 0, nb_cols - 1)
            fsrc = (jnp.take_along_axis(bt, sblk, axis=1) * bs
                    + src_tok % bs).reshape(-1)
            fdst = (jnp.take_along_axis(bt, dblk, axis=1) * bs
                    + dst_tok % bs).reshape(-1)
            out = []
            for c in pools:
                nc = {}
                for kk in c:
                    p = c[kk]
                    fl = p.reshape((p.shape[0] * p.shape[1],)
                                   + p.shape[2:])
                    fl = fl.at[fdst].set(fl[fsrc])
                    nc[kk] = fl.reshape(p.shape)
                out.append(nc)
            return out

        def _verify(pb, pools, dpools, state, bt, drafts, spec_valid,
                    any_sampling, active):
            """ONE target forward over the [B, w] flattened tree, per-
            node candidate selection with the node's DEPTH subkey, the
            deepest-path accept walk, and the by-position KV commit in
            both pools."""
            bundle = jnp.concatenate([state["tokens"][:, None], drafts],
                                     axis=1)
            caches = _tree_caches(pools, bt, spec_valid, w)
            logits, newc = run(pb, bundle, caches, state["pos"])
            levels, subs = split_key_levels(state["keys"], D + 1)
            node_keys = jnp.take(subs, depth_vec, axis=1)   # [B, w, 2]
            V = logits.shape[-1]
            flat = logits.reshape(B * w, V)
            cand = jax.lax.cond(
                any_sampling,
                lambda: select_tokens(
                    flat, node_keys.reshape(B * w, 2),
                    _rep_bw(state["ds"], w), _rep_bw(state["temp"], w),
                    _rep_bw(state["tk"], w), _rep_bw(state["tp"], w)),
                lambda: jnp.argmax(flat, axis=-1).astype(jnp.int32)
            ).reshape(B, w)
            # a node survives iff its token matches the target's
            # selection for its PARENT and every ancestor survives
            # (D parent-AND sweeps); the BFS-prefix width gates rows
            match = jnp.concatenate(
                [jnp.ones((B, 1), bool),
                 bundle[:, 1:] == jnp.take(cand, parent[1:], axis=1)],
                axis=1)
            acc = match & (jnp.arange(w)[None, :] < spec_valid[:, None])
            for _ in range(D):
                acc = acc & jnp.take(acc, parent, axis=1)
            score = jnp.where(acc, depth_vec[None, :] + 1, 0)
            best = jnp.argmax(score, axis=1)
            n_emit = jnp.take_along_axis(score, best[:, None],
                                         axis=1)[:, 0]
            path = jnp.take(anc_idx, best, axis=0)          # [B, D+1]
            emitted = jnp.take_along_axis(cand, path, axis=1)
            new_keys = jnp.take_along_axis(
                levels, n_emit[:, None, None], axis=1)[:, 0]
            last = jnp.take_along_axis(cand, best[:, None], axis=1)[:, 0]
            pos = state["pos"]
            # commit slot pos+t <- slot pos+path[t] for 1 <= t < n_emit
            # in BOTH pools; everything else routes onto itself
            tt = jnp.arange(D + 1)[None, :]
            src_tok = pos[:, None] + path
            dst_tok = pos[:, None] + tt
            commit = (tt < n_emit[:, None]) & (tt >= 1)
            dst_tok = jnp.where(commit, dst_tok, src_tok)
            pools_out = _kv_path_move(
                [{kk: c[kk] for kk in pool_keys} for c in newc],
                bt, src_tok, dst_tok)
            dpools_out = _kv_path_move(dpools, bt, src_tok, dst_tok)
            state = dict(state)
            state["tokens"] = jnp.where(n_emit > 0, last,
                                        state["tokens"])
            state["pos"] = jnp.where(
                active,
                jnp.minimum(pos + n_emit,
                            jnp.int32(config.max_len - 1)),
                jnp.int32(0))
            state["keys"] = new_keys
            return emitted, n_emit, pools_out, dpools_out, state

        _verify = _wrap(_verify, (1, 2, 3),
                        (pb_sh, pool_sh, dpool_sh, state_sh,
                         rep, rep, rep, rep, rep),
                        (rep, rep, pool_sh, dpool_sh, state_sh))
        return _draft, _verify

    # -- warmup: AOT-compile every executable before taking traffic ----------
    def warmup(self) -> dict:
        """Compile every executable this engine will dispatch — the
        pool-wide decode step alone and, on an engine that fuses, with
        ``[P, C]`` prefill rows in its program (or the spec draft+verify
        pair), the prefill program at its widths (``_row_widths``), and
        the COW fork
        — by running each once with inert inputs: zeroed block tables
        route every write to the reserved dump block, ``valid``/``active``
        masks are all-off, and ``is_last`` is False, so no slot state a
        future request relies on is touched (free rows' tokens/keys are
        scratch that admission rewrites anyway).

        A replica that warms up before registering with the router
        serves its FIRST request with zero compiles — the recompile
        monitor asserts it (the warmup runs inside
        ``recompile.warmup_scope`` so a second in-process replica's
        expected compiles never count as retraces of the first's
        entries). Requires an idle engine; idempotent. Returns
        ``{"entries": [...], "compiles": n, "wall_s": t}``."""
        t0 = time.perf_counter()
        before = _recompile.total_compiles()
        with self._step_lock:
            self._flush_ahead()
            if self.busy_slots() or self.scheduler.depth:
                raise RuntimeError(
                    "warmup() requires an idle engine: it dispatches "
                    "every executable with inert (dump-block-routed) "
                    "inputs — warm up before submitting traffic")
            with _recompile.warmup_scope():
                entries = self._warmup_paged()
            self._warmed_up = True
        return {"entries": entries,
                "compiles": _recompile.total_compiles() - before,
                "wall_s": round(time.perf_counter() - t0, 4)}

    def _warmup_paged(self) -> list:
        B = self.config.max_slots
        nb = self._bt.shape[1]
        btB = jnp.zeros((B, nb), jnp.int32)
        off = jnp.zeros(B, bool)
        zero_i = jnp.asarray(0, jnp.int32)
        entries = ["serving.cow"]
        for width in self._row_widths:
            # no row carries a chunk
            entries.append(self._enqueue_chunks(
                self._chunk_args((), width))[1])
        if self.spec:
            # a spec engine never traces the plain step — its decode
            # round is the draft+verify pair
            entries += ["serving.spec_draft", "serving.spec_verify"]
            sv0 = jnp.zeros(B, jnp.int32)
            with _entrypoint("serving.spec_draft"):
                _, self._dpools = self._draft_fn(
                    self._dpb, self._dpools, self._state, btB, sv0,
                    jnp.asarray(False))
            with _entrypoint("serving.spec_verify"):
                if self._spec_tree is not None:
                    _, _, self._pools, self._dpools, self._state = \
                        self._verify_fn(
                            self._pb, self._pools, self._dpools,
                            self._state, btB, self._zero_drafts, sv0,
                            jnp.asarray(False), off)
                else:
                    _, _, self._pools, self._state = self._verify_fn(
                        self._pb, self._pools, self._state, btB,
                        self._zero_drafts, sv0, jnp.asarray(False), off)
        else:
            # the step alone and, on an engine that fuses, with prefill
            # rows in its program, no row carrying a chunk
            entries.append(self._enqueue_step(
                btB, jnp.asarray(False), off)[2])
            if self._fuses:
                entries.append(self._enqueue_step(
                    btB, jnp.asarray(False), off,
                    self._chunk_args((), self._chunk_rows))[2])
        with _entrypoint("serving.cow"):
            if self.spec:
                self._pools, self._dpools = self._cow_spec_fn(
                    self._pools, self._dpools, zero_i, zero_i)
            else:
                self._pools = self._cow_fn(self._pools, zero_i, zero_i)
        if self._tier is not None:
            # inert tier round trip: extract the dump block's rows and
            # splice the same payload back into it (dump content is
            # never meaningfully read) — compiles both tier executables
            entries += ["serving.kv_demote", "serving.kv_splice"]
            self._tier_splice(DUMP_BLOCK, self._tier_extract(DUMP_BLOCK))
        return entries

    # -- submission ----------------------------------------------------------
    def submit(self, prompt, deadline_s: Optional[float] = None,
               on_token=None, params: Optional[SamplingParams] = None,
               **sampling) -> Request:
        """Enqueue one request; returns its handle immediately.

        ``prompt`` is a 1-D sequence of token ids; ``sampling`` takes
        the ``SamplingParams`` fields (``max_new_tokens``, ``do_sample``,
        ``temperature``, ``top_k``, ``top_p``, ``eos_token_id``,
        ``seed``), or pass a prebuilt ``params``. Raises ``ValueError``
        for requests that cannot fit a slot and ``QueueFullError`` under
        backpressure."""
        if self._crashed is not None:
            raise RuntimeError(
                f"serving engine has crashed ({self._crashed}); create a "
                f"fresh engine — this one's decode state is gone")
        if self._stopped:
            raise EngineStoppedError(
                "serving engine is stopped; submit() refused — build a "
                "fresh engine (and warmup() it before taking traffic)")
        if self._draining:
            raise EngineDrainingError(
                "serving engine is draining: in-flight requests are "
                "finishing but no new work is admitted — route this "
                "request to another replica")
        if params is None:
            params = SamplingParams(**sampling)
        elif sampling:
            raise ValueError("pass params OR sampling kwargs, not both")
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        L = int(prompt.shape[0])
        if L < 1:
            raise ValueError("empty prompt")
        if params.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if L + params.max_new_tokens > self.config.max_len:
            raise ValueError(
                f"prompt ({L}) + max_new_tokens ({params.max_new_tokens}) "
                f"exceeds the slot KV capacity max_len="
                f"{self.config.max_len}")
        bs = self.config.block_size
        worst = -(-(L + params.max_new_tokens - 1) // bs) \
            if self._layout is None \
            else self._layout.peak(L + params.max_new_tokens - 1)
        if worst > self.pool.usable_blocks:
            raise ValueError(
                f"prompt ({L}) + max_new_tokens "
                f"({params.max_new_tokens}) needs up to {worst} KV "
                f"blocks of {bs} tokens, but the pool only has "
                f"{self.pool.usable_blocks} usable blocks — raise "
                f"num_blocks or shrink the request")
        req = Request(prompt, params, deadline_s=deadline_s, on_token=on_token)
        self.scheduler.submit(req)  # may raise QueueFullError
        with self._wake:
            self._wake.notify_all()
        return req

    def cancel(self, req: Request) -> bool:
        return self.scheduler.cancel(req)

    # -- slot bookkeeping ----------------------------------------------------
    def busy_slots(self) -> int:
        return sum(r is not None for r in self._slot_req)

    def _update_occupancy_gauges(self):
        busy = self.busy_slots()
        _sm.slots_busy.set(busy)
        _sm.slot_occupancy.set(busy / max(1, self.config.max_slots))

    def _clear_slot(self, slot: int):
        """Reset every host-side trace of a slot's occupant (shared by
        free and preempt paths)."""
        self._slot_req[slot] = None
        self._slot_sampling[slot] = False
        self._decoding[slot] = False
        self._jobs[slot] = None
        for b in self._slot_blocks[slot]:
            self.pool.decref(b)
        self._slot_blocks[slot] = []
        self._bt[slot, :] = 0
        self._slot_len[slot] = 0
        self._slot_due[slot] = 0
        self._slot_win[slot] = 0

    def _note_admission(self, req: Request, now: float,
                        resumed: bool = False):
        """Queue-wait digest + trace transitions of an admission:
        the ``queued`` span ends, ``admitted`` (and ``resume`` for a
        preempted request) lands, and the wait feeds the p50/p95/p99
        digest."""
        wait = max(now - req.queued_since_ts, 0.0)
        req.queue_wait_total_s += wait
        req.admitted_ts = now
        _sm.queue_wait_seconds.observe(wait)
        req._tr_end("queued", wait_s=round(wait, 6))
        if resumed:
            req._tr_event("resume", generated=len(req.output_tokens))
        req._tr_event("admitted", slot=req.slot)
        req._tr_begin("prefill")

    def _note_goodput(self, req: Request, now: float):
        """Completed within deadline (or no deadline): its tokens count
        toward the goodput gauge over the sliding window."""
        if req.deadline_ts is not None and now > req.deadline_ts:
            return
        w = self._goodput_window
        w.append((now, len(req.output_tokens)))
        horizon = now - self._goodput_span_s
        while w and w[0][0] < horizon:
            w.popleft()
        span = max(now - w[0][0], 1e-9) if len(w) > 1 \
            else self._goodput_span_s
        _sm.goodput_tokens_per_second.set(
            sum(n for _, n in w) / max(span, 1e-9))

    def _free_slot(self, slot: int, status: str, outcome: str,
                   error: Optional[str] = None):
        req = self._slot_req[slot]
        self._clear_slot(slot)
        if req is not None:
            req.finish(status, error=error)
            _sm.requests_total.labels(outcome).inc()
            self._outcomes[outcome] = self._outcomes.get(outcome, 0) + 1
            self._recent.append(req)
            if outcome == "completed":
                self._note_goodput(req, req.finish_ts)
        self._update_occupancy_gauges()

    def _finish_or_keep(self, slot: int, req: Request, token: int,
                        now: float) -> bool:
        """Terminal checks after a delivered token; True when freed."""
        p = req.params
        if req.cancel_requested:
            self._free_slot(slot, RequestStatus.CANCELLED, "cancelled")
            return True
        if req.deadline_ts is not None and now > req.deadline_ts:
            self._free_slot(slot, RequestStatus.EXPIRED, "expired",
                            error="deadline passed during decode")
            return True
        if p.eos_token_id is not None and token == p.eos_token_id:
            self._free_slot(slot, RequestStatus.COMPLETED, "completed")
            return True
        if len(req.output_tokens) >= p.max_new_tokens:
            self._free_slot(slot, RequestStatus.COMPLETED, "completed")
            return True
        return False

    # -- pool pressure (eviction -> preemption) ------------------------------
    def _reclaim_alloc(self, n: int, requester: int,
                       allow_preempt: bool = True) -> List[int]:
        """Allocate ``n`` blocks, reclaiming under pressure: first evict
        prefix-cache entries nobody references, then (decode/COW paths
        only) preempt the latest-admitted OTHER request. Admission never
        preempts — a request that cannot be admitted without violence
        waits at the queue front instead (no admission/preemption
        thrash). Preemption waits for what is in flight: its tokens are
        emitted first (``_flush_ahead``), which may itself free the
        blocks, or end the requester (the error then stands: there is
        nobody to allocate for)."""
        while True:
            try:
                return self.pool.alloc(n)
            except PoolExhaustedError:
                deficit = max(1, n - self.pool.free_blocks)
                if self.prefix_cache is not None \
                        and self.prefix_cache.evict(deficit) > 0:
                    continue
                if not allow_preempt:
                    raise
                if self.in_flight:
                    occupant = self._slot_req[requester]
                    self._flush_ahead()
                    if self._slot_req[requester] is not occupant:
                        raise
                    continue
                victim = self._pick_victim(exclude=requester)
                if victim is None:
                    raise
                self._preempt(victim)

    def _pick_victim(self, exclude: int) -> Optional[int]:
        """Latest-admitted busy slot (other than ``exclude``) whose
        release would actually free at least one block. Oldest requests
        are never victimized first, so the head of the line always makes
        progress and preemption terminates."""
        best, best_seq = None, -1
        for slot in range(self.config.max_slots):
            if slot == exclude or self._slot_req[slot] is None:
                continue
            if not any(self.pool.ref(b) == 1 for b in self._slot_blocks[slot]):
                continue  # all shared: releasing frees nothing
            if self._slot_seq[slot] > best_seq:
                best, best_seq = slot, self._slot_seq[slot]
        return best

    def _build_resume(self, slot: int):
        """Seed-deterministic resume state for the slot's occupant (the
        recipe both preemption and supervised restart replay): mid-
        prefill restarts the same chunk job; mid-decode folds the
        generated tokens into the next prefill with the PRNG chain
        split back to the right link, and the one token the resumed
        prefill's final select re-derives is skipped, never
        re-delivered. The resumed decode is bit-identical — on THIS
        engine after a preemption or on a fresh one after a crash.
        Returns ``(tokens, recompute_len)`` for the caller's block
        bookkeeping (``(None, 0)`` when nothing ran yet: a fresh
        prefill replays everything)."""
        req = self._slot_req[slot]
        job = self._jobs[slot]
        if job is not None:
            # mid-prefill: nothing delivered yet; restart the same job
            req._resume = (job.tokens, job.key, job.skip)
            return job.tokens, job.done
        g = len(req.output_tokens)
        if g == 0:
            # claimed but never prefilled (crash between admission
            # bookkeeping and the first chunk): full replay
            req._resume = None
            return None, 0
        key = jax.random.PRNGKey(req.params.seed)
        for _ in range(g - 1):
            key, _ = jax.random.split(key)
        tokens = np.concatenate(
            [req.prompt,
             np.asarray(req.output_tokens[:g - 1], np.int32)])
        req._resume = (tokens, key, 1)
        return tokens, self._slot_len[slot]

    def _preempt(self, slot: int):
        """Preemption by recompute: release the slot's blocks and push
        the request back to the QUEUE FRONT with its generated tokens
        folded into the next prefill and its PRNG chain replayed — the
        resumed decode is bit-identical, and the one token the resumed
        prefill's select re-derives is skipped, never re-delivered.
        What is in flight is emitted first, so the resume state is built
        from every token the device has selected; a slot whose request
        that ends has nothing left to preempt."""
        self._flush_ahead()
        req = self._slot_req[slot]
        if req is None:
            return
        tokens, recompute_len = self._build_resume(slot)
        if tokens is not None:
            # the resume prefill recomputes exactly tokens[:recompute_
            # len]; demoting the private blocks now lets it re-admit
            # them through the tier instead of re-running the chunks
            self._demote_slot_blocks(slot, tokens, recompute_len)
        req.slot = None
        req.preempt_count += 1
        # whichever lifecycle span is open (prefill or decode) ends at
        # the preemption boundary; requeue() opens the next queued span
        req._tr_end("prefill")
        req._tr_end("decode")
        req._tr_event("preempted", slot=slot,
                      generated=len(req.output_tokens))
        self._clear_slot(slot)
        self.scheduler.requeue(req)
        self._preempt_count += 1
        _sm.preemptions_total.inc()
        self._update_occupancy_gauges()

    def _reserve_write(self, slot: int, start: int, end: int,
                       allow_preempt: bool = True):
        """Make the slot's table ready for a write of positions
        ``[start, end)``: allocate what the write crosses into, COW-fork
        what it would dirty of a shared block. Under a windowed layout
        the row is rolled first when ``start`` opens a new window, and
        the summary blocks of the chunks the write completes are
        allocated with the exact keys' (nothing is ever shared there).
        Pool pressure preempts the latest-admitted other request, unless
        the write is one the slot can do without (``allow_preempt``
        False: a prefill program's spare row); ``PoolExhaustedError`` when that
        does not help."""
        lay = self._layout
        if lay is None:
            bs = self.config.block_size
            for bi in range(start // bs, (end - 1) // bs + 1):
                if bi >= len(self._slot_blocks[slot]):
                    nid = self._reclaim_alloc(1, slot, allow_preempt)[0]
                    self._slot_blocks[slot].append(nid)
                    self._bt[slot, bi] = nid
                else:
                    self._ensure_writable(slot, bi, allow_preempt)
            return
        if start // lay.window > self._slot_win[slot]:
            self._roll_window(slot)
        for e in lay.entries(start, end):
            if not self._bt[slot, e]:
                nid = self._reclaim_alloc(1, slot, allow_preempt)[0]
                self._slot_blocks[slot].append(nid)
                self._bt[slot, e] = nid
        self._n_summary_entries += end // lay.chunk - start // lay.chunk

    def _roll_window(self, slot: int):
        """The slot's position crossed a multiple of the window: give
        the window's exact-key blocks back to the pool, move the
        window's own summary blocks down beside the older summaries,
        and start the window's part of the row again. Host work only:
        the device derives the same layout from the position."""
        lay = self._layout
        row = self._bt[slot]
        lo = self._slot_win[slot] * lay.per_window
        mid, hi = lo + lay.window_blocks, lo + lay.window_blocks \
            + lay.per_window
        released = {int(b) for b in row[lo:mid] if b}
        summaries = row[mid:hi].copy()
        row[lo:hi] = 0
        row[lo:lo + lay.per_window] = summaries
        for b in released:
            self.pool.decref(b)
        self._slot_blocks[slot] = [b for b in self._slot_blocks[slot]
                                   if b not in released]
        self._slot_win[slot] += 1
        self._n_window_rolls += 1
        self._n_window_blocks_released += len(released)

    def _ensure_writable(self, slot: int, block_idx: int,
                         allow_preempt: bool = True):
        """COW: the first write into a SHARED block forks it — allocate
        a fresh block, copy the shared content (one jitted dispatch),
        repoint the slot's table, drop the shared reference."""
        bid = self._slot_blocks[slot][block_idx]
        if self.pool.ref(bid) <= 1:
            return
        new_id = self._reclaim_alloc(1, slot, allow_preempt)[0]
        with _entrypoint("serving.cow"):
            if self.spec:
                self._pools, self._dpools = self._cow_spec_fn(
                    self._pools, self._dpools,
                    jnp.asarray(bid, jnp.int32),
                    jnp.asarray(new_id, jnp.int32))
            else:
                self._pools = self._cow_fn(self._pools,
                                           jnp.asarray(bid, jnp.int32),
                                           jnp.asarray(new_id, jnp.int32))
        self.pool.decref(bid)
        self._slot_blocks[slot][block_idx] = new_id
        self._bt[slot, block_idx] = new_id
        self.pool.note_cow_fork()
        _sm.cow_forks_total.inc()
        req = self._slot_req[slot]
        if req is not None:
            req._tr_event("cow_fork", block=block_idx, src=bid, dst=new_id)

    # -- paged: admission + chunked prefill ----------------------------------
    def _begin_prefill(self, req: Request, slot: int):
        """Claim the slot: match the prompt against the prefix cache,
        allocate the remaining prompt blocks, and queue the chunk job.
        No model work happens here — chunks run interleaved with decode
        steps in ``step()``."""
        resume = req._resume
        if resume is not None:
            tokens, key, skip = resume
        else:
            tokens, key, skip = req.prompt, \
                jax.random.PRNGKey(req.params.seed), 0
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        total = int(tokens.shape[0])
        bs = self.config.block_size
        n_blocks = -(-total // bs)
        if self._layout is not None:
            # blocks come chunk by chunk, as windows give theirs back on
            # the way; admission asks that the pool could hold the
            # prefill's peak now
            if self.pool.free_blocks < self._layout.peak(total):
                raise PoolExhaustedError(
                    f"KV block pool: {self._layout.peak(total)} blocks for "
                    f"a prefill of {total}, {self.pool.free_blocks} free")
            n_blocks = 0
        matched_tok, mblocks = 0, []
        if self.prefix_cache is not None:
            matched_tok, mblocks = self.prefix_cache.match(tokens, total - 1)
        # hierarchical KV re-admission: extend the prefix-cache match
        # through the host/disk tiers — each hit allocates a fresh
        # block and SPLICES the demoted payload back instead of running
        # that block's prefill chunks. A partial tier entry is always
        # the last extension.
        covered, tier_blocks, tier_tok = matched_tok, [], 0
        if self._tier is not None and covered % bs and mblocks:
            # partial-tail upgrade: the cache match ended mid-block, but
            # the tier may hold a LONGER demoted copy of that same
            # block (a preempted request's COW fork demotes keyed at
            # the boundary). Swap the partial cache block for a spliced
            # tier block — this is what re-aligns coverage so the
            # aligned loop below can keep extending through the
            # preempted request's decode blocks. The entry must end
            # inside the SAME block: a longer key's payload would be
            # the next block, not a replacement for this one.
            ceil = ((covered // bs) + 1) * bs
            ent = self._tier.match_next(tokens, covered,
                                        min(ceil, total - 1))
            if ent is not None and self._tier.cost.should_readmit(
                    ent[0] - covered, self._tier_block_bytes):
                end, payload, src = ent
                try:
                    nid = self._reclaim_alloc(1, slot,
                                              allow_preempt=False)[0]
                except PoolExhaustedError:
                    nid = None
                if nid is not None:
                    self._tier_splice(nid, payload)
                    self.pool.decref(mblocks.pop())  # drop partial tail
                    matched_tok = (matched_tok // bs) * bs
                    tier_blocks.append(nid)
                    tier_tok += end - covered
                    _sm.kv_tier_readmitted_blocks.labels(src).inc()
                    covered = end
        if self._tier is not None and covered % bs == 0:
            while covered < total - 1:
                ent = self._tier.match_next(tokens, covered, total - 1)
                if ent is None:
                    break
                end, payload, src = ent
                if not self._tier.cost.should_readmit(
                        end - covered, self._tier_block_bytes):
                    break
                try:
                    nid = self._reclaim_alloc(1, slot,
                                              allow_preempt=False)[0]
                except PoolExhaustedError:
                    break  # re-admit what fits; prefill does the rest
                self._tier_splice(nid, payload)
                tier_blocks.append(nid)
                tier_tok += end - covered
                _sm.kv_tier_readmitted_blocks.labels(src).inc()
                covered = end
                if end % bs:
                    break
        try:
            fresh = self._reclaim_alloc(
                n_blocks - len(mblocks) - len(tier_blocks), slot,
                allow_preempt=False)
        except PoolExhaustedError:
            # admission retries later — the resume state MUST survive
            # this attempt, or a requeued preempted request would
            # restart as fresh and re-deliver its tokens
            for b in mblocks + tier_blocks:
                self.pool.decref(b)
            raise
        req._resume = None  # consumed only once admission is certain
        self._n_prompt_tokens += total
        self._n_prefix_hit_tokens += matched_tok
        if self.prefix_cache is not None:
            self.prefix_cache.note(len(mblocks), n_blocks - len(mblocks))
            _sm.prefix_cache_hits.inc(len(mblocks))
            _sm.prefix_cache_misses.inc(n_blocks - len(mblocks))
            if matched_tok:
                _sm.tokens_total.labels("prompt_cached").inc(matched_tok)
            if mblocks:
                req._tr_event("prefix_cache_hit", blocks=len(mblocks),
                              tokens=matched_tok)
            else:
                req._tr_event("prefix_cache_miss", blocks=n_blocks)
        if tier_tok:
            self._tier.note_readmit(len(tier_blocks), tier_tok)
            _sm.kv_tier_readmitted_tokens.inc(tier_tok)
            _sm.tokens_total.labels("prompt_tier").inc(tier_tok)
            req._tr_event("kv_tier_readmit", blocks=len(tier_blocks),
                          tokens=tier_tok)
        blocks = mblocks + tier_blocks + fresh
        self._slot_blocks[slot] = blocks
        self._bt[slot, :] = 0
        self._bt[slot, :len(blocks)] = blocks
        self._slot_len[slot] = 0
        self._decoding[slot] = False
        self._slot_req[slot] = req
        self._admit_seq += 1
        self._slot_seq[slot] = self._admit_seq
        req.slot = slot
        req.status = RequestStatus.RUNNING
        self._note_admission(req, time.perf_counter(),
                             resumed=resume is not None)
        # the key on the host, once a request: it rides its chunks as a
        # row of the program's [P, 2] host array
        self._jobs[slot] = _PrefillJob(req=req, tokens=tokens, total=total,
                                       done=covered, key=np.asarray(key),
                                       skip=skip)
        self._update_occupancy_gauges()

    def _claim_chunk(self, slot: int, job: _PrefillJob) -> Optional[tuple]:
        """Make the slot's next chunk ready to ride this iteration's
        prefill program: the cancel and deadline checks (None when one
        of them freed the slot), then the blocks its write needs;
        returns the chunk's ``(start, end)``. ``PoolExhaustedError``
        where the pool cannot give them."""
        req = job.req
        if req.cancel_requested:
            self._free_slot(slot, RequestStatus.CANCELLED, "cancelled")
            return None
        if req.deadline_ts is not None \
                and time.perf_counter() > req.deadline_ts:
            # the deadline can expire BETWEEN admission and the first
            # (or any) prefill chunk — free the blocks now instead of
            # burning chunk dispatches on a request nobody will read
            self._free_slot(slot, RequestStatus.EXPIRED, "expired",
                            error="deadline passed during prefill")
            return None
        span = job.span(self._chunk_size)
        self._reserve_write(slot, *span)
        return span

    def _claim_spare_rows(self, claimed):
        """Give the rows that the iteration's last prefill program has
        left (``claimed``, its rows so far, ``(slot, job, start,
        end)``) to FURTHER chunks of the prefilling slots (each has
        claimed its next chunk this iteration, or lost its job), the
        earliest admitted first, each taking as many of its remaining
        chunks as rows are left. The program costs what its width costs
        whatever rows are live (PERF.md section 6, PR 28), so an empty
        row is paid for and a prompt in it reaches its first token an
        iteration sooner. Rows of one slot in one program are ordinary
        rows: every row's K/V is scattered into the pool before any row
        reads it (``generation.cached_attention``), which is how a
        chunk's own positions already see each other. A spare row is
        one the slot can do without: its blocks come without preempting
        anybody, and ``PoolExhaustedError`` ends that slot's share.

        What it adapts to is what it can see: at P = 1 no program has a
        spare row, and a slot under a windowed layout takes none,
        because ``_roll_window`` gives blocks back to the pool that an
        earlier row of the same program still reads through its copy of
        the table row."""
        spare = self._chunk_rows - len(claimed)
        if not spare or self._layout is not None:
            return
        C = self._chunk_size
        for slot in sorted((i for i, job in enumerate(self._jobs)
                            if job is not None),
                           key=self._slot_seq.__getitem__):
            job = self._jobs[slot]
            start = job.done + C
            while spare and start < job.total:
                span = job.span(C, start)
                try:
                    self._reserve_write(slot, *span, allow_preempt=False)
                except PoolExhaustedError:
                    break
                claimed.append((slot, job, *span))
                start += C
                spare -= 1
            if not spare:
                return

    @staticmethod
    def _chunk_entry(width: int) -> str:
        """The recompile monitor's name for the prefill program at
        ``width`` rows, one name an executable: the ``[1, C]`` form
        keeps the name it always had."""
        return "serving.prefill_chunk" if width == 1 \
            else f"serving.prefill_chunk[{width}]"

    def _chunk_args(self, rows, width: int) -> np.ndarray:
        """The host argument of one ``[width, C]`` prefill program for
        ``rows``, at most ``width`` rows ``(slot, job, start, end)``, a
        chunk ``[start, end)`` of the job's tokens: one int32 array, a
        row of it a program row's table row, token ids and
        ``_ROW_COLUMNS``. A row past ``rows`` carries nothing: ``valid``
        0 and a zeroed table row, so it writes the dump block alone. A
        fresh array every call, handed over as it is: the call moves it
        with its other arguments, where a ``jnp.asarray`` is a dispatch
        and a transfer of its own (PERF.md, PR 27), and the backend may
        alias a host array, so one still in flight must not be written
        again (the table rows are copies for the same reason: a
        windowed row is rolled while earlier chunks are in flight)."""
        C, nb = self._chunk_size, self._bt.shape[1]
        packed = np.zeros((width, nb + C + len(_ROW_COLUMNS)), np.int32)
        packed[:, nb:nb + C] = self.config.pad_token_id
        packed[:, -2:] = _ONE_BITS          # temp, tp
        for r, (slot, job, start, end) in enumerate(rows):
            p = job.req.params
            row = packed[r]
            row[:nb] = self._bt[slot]
            row[nb:nb + end - start] = job.tokens[start:end]
            # pos0, valid, slot, is_last, last_idx (a padded tail's
            # position, clipped, is read by nobody), ds, tk, key
            row[nb + C:-2] = (start, end - start, slot, end == job.total,
                              min(job.total - 1 - start, C - 1),
                              p.do_sample, p.top_k, *job.key.view(np.int32))
            row[-2:] = np.asarray((p.temperature, p.top_p),
                                  np.float32).view(np.int32)
        return packed

    def _enqueue_chunks(self, packed) -> tuple:
        """One prefill program over ``_chunk_args``' host array, at its
        width; returns its first tokens, one a row and still on the
        device, and the entry it ran under."""
        entry = self._chunk_entry(packed.shape[0])
        with _entrypoint(entry):
            if self.spec:
                token, self._pools, self._dpools, self._state = \
                    self._chunk_spec_fn(self._pb, self._dpb, self._pools,
                                        self._dpools, self._state, packed)
            else:
                token, self._pools, self._state = self._chunk_fn(
                    self._pb, self._pools, self._state, packed)
        return self._note_routing(token, False), entry

    def _enqueue_claimed(self, claimed, first):
        """One ``serving.prefill_chunk`` program for ``claimed``, at
        most P rows ``(slot, job, start, end)``; None where nothing was
        enqueued. A set that is not the iteration's last goes out as
        soon as its rows are claimed, so the device works while the
        host claims the next program's (at P = 1 every chunk is
        enqueued before the next slot's blocks are reserved); the last
        set too, on an engine whose step cannot carry it or in an
        iteration with no decode row, and otherwise it is held and
        rides the step (``_step_impl``, ``_enqueue_step``). The rows'
        bookkeeping waits for ``_book_chunks``. ``first``:
        ``_note_prefill_program``'s.

        A lone chunk rides the ``[1, C]`` form of the same body where
        the engine keeps one (``_row_widths``): on the chip ``[8, 32]``
        takes 2.1 ms longer than ``[1, 32]`` whatever rows are live (its
        256 rows are computed, PERF.md section 6, PR 28), which every
        iteration with one prefilling slot would pay; from two chunks
        on the wide program is the cheaper."""
        rows = self._live_rows(claimed)
        if not rows:
            return None
        tc0 = time.perf_counter_ns()
        try:
            # the first row's request is the active trace, so an XLA
            # compile fired here (the one serving.prefill_chunk warmup,
            # or a would-be-retrace bug) lands in a timeline
            with _trace.trace_context(rows[0][1].req.trace):
                token, entry = self._enqueue_chunks(self._pack(rows))
        except Exception as e:  # noqa: BLE001 — engine must survive
            for slot in {row[0] for row in rows}:
                self._free_slot(slot, RequestStatus.FAILED, "failed",
                                error=repr(e))
            return None
        self._note_prefill_program(rows, first)
        return rows, token, entry, tc0, time.perf_counter_ns()

    def _pack(self, rows) -> np.ndarray:
        """``_chunk_args`` of the live ``rows`` at the width they ride:
        ``[P, C]``, and where the engine keeps it (``_row_widths``) the
        ``[1, C]`` form for a lone row."""
        return self._chunk_args(
            rows, self._row_widths[0 if len(rows) == 1 else -1])

    def _live_rows(self, claimed) -> list:
        """The rows of ``claimed`` that still carry their chunk: a row
        whose slot a later reservation preempted (a later row's, or a
        decode row's while the set was held for the step) carries
        nothing, its blocks may be that row's by now."""
        return [row for row in claimed if self._jobs[row[0]] is row[1]]

    def _note_prefill_program(self, rows, first: set):
        """Count one program that carries the live prefill ``rows``: a
        prefill program, or the step they ride. ``first``: the slots
        that have had a row in this iteration (a further row of one is
        a spare row's), these rows' now among them."""
        self._n_prefill_rows += len(rows)
        self._n_prefill_programs += 1
        for slot, *_ in rows:
            self._n_prefill_fill_rows += slot in first
            first.add(slot)

    @staticmethod
    def _step_entry(width: int) -> str:
        """The recompile monitor's name for the step that carries
        ``width`` prefill rows, one name an executable."""
        return f"serving.step+chunk[{width}]"

    def _enqueue_step(self, bt_step, any_sampling, active_mask,
                      packed=None) -> tuple:
        """The decode step, its arguments host arrays handed over as
        they are; with ``packed`` (``_chunk_args``: the prefill rows
        that ride it, an engine that ``_fuses``) the program that
        carries those rows too. Returns the step's tokens, the rows'
        first tokens (None without rows), both still on the device, and
        the entry it ran under."""
        entry = "serving.step" if packed is None \
            else self._step_entry(packed.shape[0])
        first = None
        with _entrypoint(entry):
            if packed is None:
                toks, self._pools, self._state = self._step_fn(
                    self._pb, self._pools, self._state, bt_step,
                    any_sampling, active_mask)
            else:
                toks, first, self._pools, self._state = self._step_fn(
                    self._pb, self._pools, self._state, bt_step,
                    any_sampling, active_mask, packed)
        return self._note_routing(toks, True), first, entry

    def _note_routing(self, out, step: bool):
        """A program's tokens. A model with routed experts returns them
        with its routing counts (``distributed/moe_serving.ROUTE_STATS``,
        on the device); those wait in ``_route_unread`` until the tokens
        of a step enqueued behind them are read."""
        if not self._routed:
            return out
        toks, stats = out
        self._route_unread.append((step, stats))
        return toks

    def _read_routing(self, n: int, dispatch_args, prefill_args):
        """Read the counts of the oldest ``n`` programs (they have run:
        the step whose tokens were just read was enqueued behind them)
        into the engine's counters, and into the args of this
        iteration's ``engine.dispatch`` (the steps among them) and
        ``engine.prefill`` (the prefill programs), where it has them:
        a span tells the routing of the programs READ in its iteration,
        the ones the iteration before enqueued."""
        from ..distributed.moe_serving import ROUTE_STATS

        read, self._route_unread = (self._route_unread[:n],
                                    self._route_unread[n:])
        for args, step in ((dispatch_args, True), (prefill_args, False)):
            mine = [np.asarray(stats) for was, stats in read if was == step]
            if not mine:
                continue
            got = dict(zip(ROUTE_STATS, np.sum(mine, axis=0).tolist()))
            self._route_totals["programs"] += len(mine)
            for key, val in got.items():
                self._route_totals[key] += val
            if args is not None:
                args.update(expert_pairs=got["pairs_here"],
                            experts_touched=got["experts_touched"])

    def _book_chunks(self, ran):
        """The bookkeeping of the rows of programs that are enqueued
        (``_enqueue_claimed``'s records, and the step's where it
        carried rows; None for a program that failed). A chunk that
        ends its prompt also selected the first token (generate's key
        chain) and wrote it into the slot's state row on the device, so
        the slot flips into the decode batch here, on the host's side
        alone (``_finish_prefill``), and its token is parked: the
        host's copy is read in the next iteration's ``engine.wait``
        (``_deliver_first_tokens``), so the device never drains between
        a prompt's last chunk and the step behind it. WHEN it is called
        decides which step the slot joins: the prefill programs are
        booked before the iteration's decode rows are chosen, so their
        slots join this iteration's step; rows that rode the step are
        booked behind it, and their slots join the next (inside one
        pass over the layers a decode row cannot read the token that
        the same program selects)."""
        from ..observability import perf as _perf
        for rows, token, entry, tc0, tc1 in filter(None, ran):
            _sm.prefill_chunk_seconds.observe((tc1 - tc0) / 1e9)
            last = []
            for r, (slot, job, start, end) in enumerate(rows):
                if self._jobs[slot] is not job:
                    # preempted, after its program went out, by the
                    # reservation of a later program's row: recomputed
                    continue
                job.done = end
                # the rows of one program share its two clock reads
                _trace.complete(
                    "prefill_chunk", "request", job.req.trace, tc0,
                    tc1 - tc0, {"slot": slot, "start": start, "end": end,
                                "last": end == job.total,
                                "iter": self._phases.seq})
                _sm.prefill_chunks_total.inc()
                _sm.tokens_total.labels("prompt").inc(end - start)
                _perf.note_entry_items(entry, end - start)
                if end < job.total:
                    continue
                try:
                    self._finish_prefill(slot, job)
                    last.append((r, slot, job))
                except Exception as e:  # noqa: BLE001 — that slot alone
                    self._free_slot(slot, RequestStatus.FAILED, "failed",
                                    error=repr(e))
            if last:
                self._parked_tokens.append((token, last))

    def _finish_prefill(self, slot: int, job: _PrefillJob):
        """The slot's last chunk is enqueued: the host's side of its
        flip into the decode batch. The prompt's blocks are registered
        with the prefix cache BEFORE any decode write can dirty them
        (COW keeps them pristine): the step's reservation comes after
        this."""
        req = job.req
        if self.prefix_cache is not None:
            bs = self.config.block_size
            n_reg = min(int(req.prompt.shape[0]), job.total)
            self.prefix_cache.insert(
                job.tokens, n_reg,
                self._slot_blocks[slot][:-(-n_reg // bs)])
        self._jobs[slot] = None
        self._decoding[slot] = True
        self._slot_len[slot] = job.total
        # the chunk selected the first token; a resumed prompt's is one
        # the request already has
        self._slot_due[slot] = 0 if job.skip else 1
        self._slot_sampling[slot] = bool(req.params.do_sample)

    def _deliver_first_tokens(self, n: Optional[int] = None):
        """Read the first ``n`` parked first tokens (``_book_chunks``;
        all of them by default), one device-to-host read a program, and
        deliver each to its request. ``_step_impl`` reads those of the
        iteration before, in ``engine.wait``: this iteration's programs
        are enqueued by then, the prefill program that selected them
        ran before the step in flight, and they are read before that
        step's tokens, so a first token waits for its own program and
        for no decode step it does not depend on, and a request's
        tokens arrive in order. Until then the slot is decoding with no
        output token yet (``_slot_due`` counts the one parked). A slot
        that was cancelled since its chunk was booked gets nothing; a
        request that ends on its first token frees its slot here, and
        the rows it has in the steps enqueued meanwhile are dead
        (``_emit_ahead``)."""
        parked = self._parked_tokens[:n]
        del self._parked_tokens[:n]
        for token, last in parked:
            toks = None
            for r, slot, job in last:
                if self._slot_req[slot] is not job.req:
                    continue
                try:
                    if toks is None:
                        toks = np.asarray(token)
                    self._first_token(slot, job, int(toks[r]))
                except Exception as e:  # noqa: BLE001 — that slot alone
                    self._free_slot(slot, RequestStatus.FAILED, "failed",
                                    error=repr(e))

    def _first_token(self, slot: int, job: _PrefillJob, tok0: int):
        """The slot's last chunk selected ``tok0``, now on the host."""
        req = job.req
        now = time.perf_counter()
        _sm.prefill_seconds.observe(now - job.t0)
        req.prefill_done_ts = now
        req._tr_end("prefill", tokens=job.total)
        req._tr_begin("decode")
        if job.skip:
            return  # resumed: tok0 re-derives the last delivered token
        self._slot_due[slot] -= 1
        req.push_token(tok0, now)
        req._tr_event("first_token")
        _sm.ttft_seconds.observe(req.ttft_s)
        _sm.ttft_summary.observe(req.ttft_s)
        _sm.tokens_generated.inc()
        self._finish_or_keep(slot, req, tok0, now)
        self._update_occupancy_gauges()

    def _admit(self):
        """Fill every free slot FCFS from the queue; runs at the top of
        each iteration so a slot freed by EOS is refilled before the
        next decode step. Admission only claims blocks and queues the
        chunk job; the chunks run in the iteration's prefill phase."""
        # quarantine-probe isolation: a crash SUSPECT the supervisor
        # requeued runs ALONE — admitted only into an idle pool, with
        # nothing admitted beside it. A repeat crash then implicates
        # exactly one request instead of smearing suspicion over
        # innocent co-runners (which is what would let a single poison
        # request quarantine its whole cohort).
        if any(r is not None and r.quarantine_probe for r in self._slot_req):
            return
        # a request between the queue and its slot is in neither count:
        # raised before the pop and lowered after the seating, so that
        # drain(), which reads the queue, then this, then the slots,
        # without the step lock, never sees it nowhere
        self._unseated += 1
        try:
            for slot in range(self.config.max_slots):
                while self._slot_req[slot] is None:
                    req = self.scheduler.pop_ready()
                    if req is None:
                        return
                    if req.quarantine_probe and self.busy_slots():
                        # the probe waits at the queue front for an idle
                        # pool (admission-backoff requeue: same wait
                        # window), and blocks everything behind it — brief,
                        # bounded by the in-flight requests' decode
                        self.scheduler.requeue(req)
                        return
                    try:
                        self._begin_prefill(req, slot)
                    except PoolExhaustedError:
                        # not enough free blocks even after cache eviction:
                        # FCFS holds — the request waits at the queue front
                        # until decode completions release blocks
                        self.scheduler.requeue(req)
                        return
                    except Exception as e:  # noqa: BLE001 — engine must survive
                        self._clear_slot(slot)
                        req.finish(RequestStatus.FAILED, error=repr(e))
                        _sm.requests_total.labels("failed").inc()
                        self._outcomes["failed"] = self._outcomes.get("failed", 0) + 1
                    else:
                        if req.quarantine_probe:
                            return  # solo: nothing is admitted beside it
        finally:
            self._unseated -= 1

    # -- the iteration -------------------------------------------------------
    def step(self) -> bool:
        """One engine iteration: admit into free slots, advance every
        in-flight chunked prefill by one chunk (and, in the rows its
        prefill program has left, the earliest-admitted ones by
        further chunks), then (if any slot is decoding) enqueue the
        single jitted decode step for the whole pool (the iteration's
        last prefill rows in its program, where the engine fuses), and
        only then
        read and deliver the tokens of the step the iteration BEFORE
        enqueued: the host stays one step ahead of the tokens it reads,
        so the device has this iteration's programs queued while the
        host emits and builds the next. On return at most one step and
        the first tokens of the prompts that ended in this iteration
        are in flight; the next ``step()`` delivers both, and whatever
        needs the tokens' values first (``_flush_ahead``) reads them
        early. A speculative engine reads before it returns, as it
        always did. Returns True when any work happened or anything is
        still in flight, so ``while eng.step()`` ends with every token
        delivered.

        A ``PoolExhaustedError`` escaping the iteration (every in-loop
        exhaustion is normally absorbed by eviction/preemption — an
        escape means the reclaim logic is stuck) snapshots the flight
        recorder before propagating: the dump carries the pool/slot
        state that produced the wedge."""
        try:
            return self._step_impl()
        except PoolExhaustedError as e:
            # a pool-exhaustion escape IS an allocation failure: the dump
            # carries the OOM forensics payload (HBM ledger + top
            # temp-byte executables) on top of the usual state snapshot
            from ..observability import perf as _perf

            try:
                extra = {"error": repr(e), **_perf.oom_report()}
            except Exception:  # noqa: BLE001 — dump must not crash twice
                extra = {"error": repr(e)}
            _trace.flight_dump("pool_exhausted", extra=extra)
            raise

    def _step_impl(self) -> bool:
        """The iteration, under ``_step_lock``: admit, advance prefills,
        reserve blocks for the decode rows, dispatch step N+1, wait for
        step N's tokens, emit them, each a phase of ``engine.iter``: a
        pipeline of depth one. What goes out when: every set of P
        claimed prefill rows that is not the iteration's last, as a
        prefill program, at once; the last set with the step, in ONE
        program, where the engine ``_fuses`` and a slot is decoding
        (else as the prefill program it is, before the decode rows are
        chosen); the step, with or without rows, once its rows' blocks
        are reserved. A slot whose last chunk rode the step joins the
        next step. Nothing the host does before a dispatch
        needs the values of the tokens in flight: the step reads its
        inputs from ``self._state`` on the device, and the table, the
        active mask and ``any_sampling`` follow from lengths and counts,
        which move when a step is ENQUEUED (``_slot_len``,
        ``_slot_due``). A row whose last token by count is in flight
        gets no further row; one that ends on a value (end of sequence,
        a deadline, a cancel seen at emit) already has one, which is
        dead (``_emit_ahead``). Whatever needs the values first, or
        hands requests over, reads what is in flight first
        (``_flush_ahead``). The speculative lane sizes its bundles from
        tokens on the host and stays synchronous.

        Every ``ph.mark`` is the one clock read of a phase boundary; the
        step histogram and the ``serving.step`` span reuse those readings
        (with tracing disabled a mark reads no clock, and the step's
        two edges are read here)."""
        with self._step_lock:
            ph = self._phases
            n_hit, n_prompt, n_pre = (self._n_prefix_hit_tokens,
                                      self._n_prompt_tokens,
                                      self._preempt_count)
            n_rows, n_programs, n_fill = (self._n_prefill_rows,
                                          self._n_prefill_programs,
                                          self._n_prefill_fill_rows)
            self._last_progress_ts = ph.open("engine.admit") / 1e9
            worked = False
            dispatch_args = None   # of engine.dispatch, while it is open
            n_route = len(self._route_unread)   # programs run by the wait
            try:
                self._admit()
                ph.mark("engine.prefill", ph.on and {
                    "prefix_hit_tokens": self._n_prefix_hit_tokens - n_hit,
                    "prompt_tokens": self._n_prompt_tokens - n_prompt})
                # every prefilling slot advances one chunk, in slot
                # order; the chunks ride one program, P rows each,
                # which goes out as soon as its rows are claimed; the
                # rows the last program has left carry further chunks.
                # Where the step can carry prefill rows (``_fuses``) the
                # iteration's LAST set is ``held`` for it until the
                # decode rows are reserved: a full set goes out at once
                # only where a later slot still has a chunk to claim
                fuses, P = self._fuses, self._chunk_rows
                held, ran = [], []
                first = set()   # the slots that have a row out already
                for slot in range(self.config.max_slots):
                    job = self._jobs[slot]
                    if job is None:
                        continue
                    worked = True
                    try:
                        span = self._claim_chunk(slot, job)
                        if span:
                            held.append((slot, job, *span))
                    except PoolExhaustedError:
                        self._preempt(slot)  # retried from the queue front
                    except Exception as e:  # noqa: BLE001
                        self._free_slot(slot, RequestStatus.FAILED,
                                        "failed", error=repr(e))
                    if len(held) == P and (
                            not fuses or any(self._jobs[slot + 1:])):
                        ran.append(self._enqueue_claimed(held, first))
                        held = []
                if held:
                    self._claim_spare_rows(held)
                # the first tokens parked before this iteration's: read
                # in engine.wait, behind this iteration's enqueues
                n_parked = len(self._parked_tokens)
                # the slots whose prompt ended in a program that is out
                # join this iteration's step
                self._book_chunks(ran)
                if held and not (fuses and self._rows_to_step()):
                    # no step that could carry them, or no decode row to
                    # ride with: the program they are, and its slots
                    # join the step as well
                    self._book_chunks([self._enqueue_claimed(held, first)])
                    held = []
                if self.spec:
                    # the speculative lane sizes its bundles from what a
                    # request has been given: it reads before it dispatches
                    self._deliver_first_tokens()
                # engine.prefill's args, filled in once the iteration's
                # last prefill rows are out (they may ride the step)
                prefill_args = {} if ph.on else None
                ph.mark("engine.reserve", prefill_args)
                active = self._rows_to_step()
                # cancellation between steps: drop flagged slots without
                # paying another decode step for them, once what they
                # have in flight is emitted
                if any(self._slot_req[i].cancel_requested for i in active):
                    self._flush_ahead()
                    for i in self._rows_to_step():
                        if self._slot_req[i].cancel_requested:
                            self._free_slot(i, RequestStatus.CANCELLED,
                                            "cancelled")
                    active = self._rows_to_step()

                # every active row writes this step's K/V at its current
                # length — or, speculatively, at its whole verify-bundle
                # window [len, len + spec_len): cross a block boundary
                # -> allocate; write into a shared (prefix-cached) block
                # -> COW fork. Allocation pressure preempts the
                # latest-admitted request, which can shrink `active`
                # (and first emits what is in flight, which can too).
                bs = self.config.block_size
                for i in active:
                    if self._slot_req[i] is None or not self._decoding[i]:
                        continue  # preempted by an earlier row's reclaim
                    # _row_spec_len is a pure function of host state that
                    # does not change between here and the dispatch, so
                    # the bundle can never write past this coverage
                    m = self._row_spec_len(i) if self.spec else 1
                    try:
                        self._reserve_write(i, self._slot_len[i],
                                            self._slot_len[i] + m)
                    except PoolExhaustedError:
                        self._preempt(i)
                if active:
                    worked = True
                    active = [i for i in active
                              if self._slot_req[i] is not None
                              and self._decoding[i]]
                # a flush on the way (a cancel, pool pressure) has read
                # this iteration's first tokens too
                n_parked = min(n_parked, len(self._parked_tokens))
                # the rows the step carries: those still live (a decode
                # row's reservation may have preempted a slot that holds
                # one), where there is a step; else they go out as the
                # prefill program they are
                riding = self._live_rows(held) if active else []
                booked = None
                if riding:
                    self._note_prefill_program(riding, first)
                elif held:
                    booked = self._enqueue_claimed(held, first)
                if prefill_args is not None \
                        and self._n_prefill_programs > n_programs:
                    # rows, programs and spare rows filled, on the
                    # iterations that enqueued any
                    prefill_args.update(
                        rows=self._n_prefill_rows - n_rows,
                        programs=self._n_prefill_programs - n_programs,
                        fill=self._n_prefill_fill_rows - n_fill)
                enqueued = None
                if active:
                    ahead = self._ahead is not None
                    # the pool blocks the step's attention reads: each
                    # active row's, up to the end of what it writes
                    if self._layout is None:
                        dispatch_args = ph.on and {"kv_blocks": sum(
                            -(-(self._slot_len[i] + (self._row_spec_len(i)
                                                     if self.spec else 1))
                              // bs) for i in active)}
                    else:
                        # exact keys of the window, and summaries behind it
                        read = ph.on and [self._layout.read_blocks(
                            self._slot_len[i] + 1) for i in active]
                        dispatch_args = ph.on and {
                            "kv_blocks": sum(r[0] for r in read),
                            "summary_blocks": sum(r[1] for r in read)}
                    if dispatch_args:
                        # whether the step before was still unread, and
                        # the prefill rows this step's program carries
                        dispatch_args.update(
                            ahead=int(ahead), fused=int(bool(riding)),
                            prefill_rows=len(riding))
                    if dispatch_args and self._ut_steps > 1:
                        # the passes the enqueued step runs
                        dispatch_args["ut_steps"] = self._ut_steps
                    t0_ns = ph.mark("engine.dispatch") \
                        or time.perf_counter_ns()
                    any_sampling = any(self._slot_sampling[i]
                                       for i in active)
                    active_mask = np.zeros(self.config.max_slots, bool)
                    active_mask[active] = True
                    if self.spec:
                        self._spec_step(active, active_mask, any_sampling,
                                        t0_ns, ph, dispatch_args)
                        dispatch_args = None
                        return True
                    bt_step = self._bt.copy()
                    bt_step[~active_mask] = 0  # inactive -> dump block
                    toks, tok0, entry = self._enqueue_step(
                        bt_step, np.asarray(any_sampling, bool),
                        active_mask, self._pack(riding) if riding else None)
                    if riding:
                        booked = (riding, tok0, entry, t0_ns,
                                  time.perf_counter_ns())
                        self._n_steps_fused += 1
                    # enqueued: the rows' lengths and counts move now,
                    # the next reservation needs them
                    rows = []
                    for i in active:
                        rows.append((i, self._slot_req[i]))
                        self._slot_len[i] = min(self._slot_len[i] + 1,
                                                self.config.max_len - 1)
                        self._slot_due[i] += 1
                    self._n_steps_ahead += ahead
                    self._n_loop_passes += self._ut_steps
                    enqueued = toks, rows, t0_ns, entry
                # a slot whose prompt ended in the held rows joins the
                # NEXT step
                self._book_chunks([booked])
                prev = self._ahead
                if prev is None and not n_parked:
                    self._ahead = enqueued
                    return worked   # nothing to read: the pipeline fills
                worked = True
                ph.mark("engine.wait", dispatch_args)
                wait_args, dispatch_args = dispatch_args or None, None
                # this iteration's programs are queued behind the step
                # in flight: now the host reads. First the first tokens
                # the iteration before parked (their program ran before
                # that step), then the step's ONE device->host sync
                self._deliver_first_tokens(n_parked)
                toks_np = np.asarray(prev[0]) if prev else None
                # (had that read failed, the step behind it would have
                # gone with it: a request's tokens arrive in order)
                self._ahead = enqueued
                if prev and n_route:
                    self._read_routing(n_route, wait_args, prefill_args)
                now_ns = ph.mark("engine.emit") or time.perf_counter_ns()
                if prev:
                    self._emit_ahead(prev, toks_np, now_ns)
                return True
            except BaseException:
                # whatever ended the iteration takes nothing with it: the
                # tokens in flight are delivered, or dropped where the
                # device no longer gives them
                self._flush_ahead(or_drop=True)
                raise
            finally:
                self._update_occupancy_gauges()
                self.pool.set_gauges()
                # an iteration that only admitted (and lost the request
                # again) is recorded too: its engine.admit has tokens
                ph.close(worked or self._n_prompt_tokens > n_prompt,
                         dispatch_args,
                         ph.on and {"preempted":
                                    self._preempt_count - n_pre})

    def _rows_to_step(self) -> List[int]:
        """The slots the next decode step carries: decoding, and with a
        token still to come by count, those delivered and those in
        flight taken together (a row whose last token is in flight has
        its slot until that token is emitted, and no further row)."""
        return [i for i, r in enumerate(self._slot_req)
                if r is not None and self._decoding[i]
                and len(r.output_tokens) + self._slot_due[i]
                < r.params.max_new_tokens]

    @property
    def in_flight(self) -> bool:
        """A step is enqueued and unread, or a first token is parked."""
        return self._ahead is not None or bool(self._parked_tokens)

    def _emit_ahead(self, step: tuple, toks_np, now_ns: int):
        """Deliver the tokens of a step that was in flight (``step``,
        its ``_ahead`` record; ``toks_np``, read at ``now_ns``) to the
        requests that were given its rows at dispatch, never through
        the slot as it stands now: a row whose request has left its slot
        since (it ended on an earlier token's value) is dead, its token
        is dropped and counted. The step's span and the perf ledger's
        interval run from sync to sync (from its dispatch where the
        device had drained), the one interval a pipelined step has."""
        _, rows, t0_ns, entry = step
        now = now_ns / 1e9
        live = [(i, req) for i, req in rows if self._slot_req[i] is req]
        self._n_dead_rows += len(rows) - len(live)
        t0_ns = max(t0_ns, self._sync_ns)
        self._sync_ns = now_ns
        step_s = (now_ns - t0_ns) / 1e9
        _sm.steps_total.inc()
        _sm.step_seconds.observe(step_s)
        # the engine-lane step span reuses the boundaries' timestamps:
        # no extra clock read on the hot path
        _trace.complete("serving.step", "engine", "engine", t0_ns,
                        now_ns - t0_ns,
                        {"active": len(live), "step": self._steps})
        self._steps += 1
        self._occupancy_integral += len(live)
        from ..observability import perf as _perf
        _perf.note_entry_items(entry, len(live))
        _perf.note_entry_time(entry, step_s)
        for i, req in live:
            self._slot_due[i] -= 1
            t = int(toks_np[i])
            prev = req.last_token_ts
            req.push_token(t, now)
            _sm.tokens_generated.inc()
            if prev is not None:
                _sm.tpot_seconds.observe(now - prev)
                _sm.tpot_summary.observe(now - prev)
            self._finish_or_keep(i, req, t, now)

    def _flush_ahead(self, or_drop: bool = False):
        """Read and emit what is in flight, ahead of the iteration that
        would have: the parked first tokens, then the step. Everything
        rare that needs the tokens' values, or hands requests over,
        calls this first and then goes on as an engine with nothing in
        flight does: preemption and the resume state, a cancel between
        steps, ``stop``, ``drain``, ``run_until_idle``'s return, the
        export and the failing of requests, a crashed iteration. Caller
        holds the step lock. ``or_drop``: where the device no longer
        gives the tokens (the program that made them failed), what is
        in flight is dropped and the requests keep what they were
        given, which is what their resume state is built from."""
        if not self.in_flight:
            return
        self._n_ahead_flushes += 1
        step, self._ahead = self._ahead, None
        try:
            self._deliver_first_tokens()
            if step is not None:
                self._emit_ahead(step, np.asarray(step[0]),
                                 time.perf_counter_ns())
            if self._route_unread:
                self._read_routing(len(self._route_unread), None, None)
        except Exception:  # noqa: BLE001 — or_drop: the crash path's own
            del self._parked_tokens[:]
            del self._route_unread[:]
            if not or_drop:
                raise

    # -- the speculative iteration -------------------------------------------
    def _row_spec_len(self, slot: int) -> int:
        """Live bundle width for one decoding slot this round: 1 + the
        row's draft count, clamped by the request's own ``spec_k``
        (opt-out = 0 -> width 1 = a plain decode step riding the
        bundle), its remaining token budget (drafting past
        ``max_new_tokens`` is pure waste), and the slot's KV capacity
        (the bundle writes ``width`` positions through the table)."""
        req = self._slot_req[slot]
        p = req.params
        k_req = self._spec_k if p.spec_k is None \
            else max(0, min(int(p.spec_k), self._spec_k))
        remaining = p.max_new_tokens - len(req.output_tokens)
        room = self.config.max_len - self._slot_len[slot]
        if self._spec_tree is not None:
            # tree lane: k_req clamps the DEPTH; the bundle width is
            # the BFS node count of the clamped tree (an accepted path
            # emits at most depth+1 tokens, so depth caps at
            # remaining-1), then clips to the slot's KV room — any
            # BFS prefix is a valid (ragged) tree
            depth_cap = max(0, min(k_req, remaining - 1))
            width = int(self._tree["offsets"][depth_cap + 1])
            return max(1, min(width, room))
        return max(1, min(k_req + 1, remaining, room))

    def _spec_step(self, active, active_mask, any_sampling, t0_ns: int,
                   ph, dispatch_args) -> None:
        """One speculative iteration for the whole pool: ONE jitted
        draft program (k draft-model forwards), ONE jitted verify
        (target scores the k+1-wide bundle through the paged kernel,
        accepts the longest matching prefix, bumps each row's position
        by its own accept length through the block tables). The draft
        program is skipped — host-side, no recompile — when no live row
        wants more than a plain step this round. Entered in
        ``engine.dispatch`` (since ``t0_ns``); leaves ``engine.emit``
        open."""
        B = self.config.max_slots
        k = self._spec_k
        spec_valid = np.zeros(B, np.int32)
        for i in active:
            spec_valid[i] = self._row_spec_len(i)
        bt_step = self._bt.copy()
        bt_step[~active_mask] = 0
        bt_j = jnp.asarray(bt_step)
        sv_j = jnp.asarray(spec_valid)
        as_j = jnp.asarray(any_sampling)
        tree = self._spec_tree is not None
        need_draft = bool((spec_valid > 1).any())
        if need_draft:
            td0 = time.perf_counter()
            with _entrypoint("serving.spec_draft"):
                drafts, self._dpools = self._draft_fn(
                    self._dpb, self._dpools, self._state, bt_j, sv_j, as_j)
            td1 = time.perf_counter()
            _trace.complete("serving.spec_draft", "engine", "engine",
                            int(td0 * 1e9), int((td1 - td0) * 1e9),
                            {"active": len(active), "k": k,
                             **({"tree": list(self._spec_tree),
                                 "nodes": int(self._tree["nodes"])}
                                if tree else {})})
        else:
            drafts = self._zero_drafts
        tv0 = time.perf_counter()
        with _entrypoint("serving.spec_verify"):
            if tree:
                cand, n_emit, self._pools, self._dpools, self._state = \
                    self._verify_fn(
                        self._pb, self._pools, self._dpools, self._state,
                        bt_j, drafts, sv_j, as_j,
                        jnp.asarray(active_mask))
            else:
                cand, n_emit, self._pools, self._state = self._verify_fn(
                    self._pb, self._pools, self._state, bt_j, drafts,
                    sv_j, as_j, jnp.asarray(active_mask))
        ph.mark("engine.wait", dispatch_args)
        cand_np = np.asarray(cand)   # the round's device->host sync
        n_np = np.asarray(n_emit)
        now_ns = ph.mark("engine.emit") or time.perf_counter_ns()
        now = now_ns / 1e9
        _sm.steps_total.inc()
        _sm.step_seconds.observe((now_ns - t0_ns) / 1e9)
        _trace.complete("serving.spec_verify", "engine", "engine",
                        int(tv0 * 1e9), int((now - tv0) * 1e9),
                        {"active": len(active), "step": self._steps,
                         **({"tree": list(self._spec_tree)}
                            if tree else {})})
        self._steps += 1
        self._occupancy_integral += len(active)
        self._spec_rounds += 1
        from ..observability import perf as _perf
        if need_draft:
            _perf.note_entry_items("serving.spec_draft",
                                   int((spec_valid - 1).clip(0).sum()))
        _perf.note_entry_items("serving.spec_verify",
                               int(n_np[active].sum()))
        # verify dispatch to its tokens on the host: a synced interval
        _perf.note_entry_time("serving.spec_verify", now - tv0)

        for i in active:
            req = self._slot_req[i]
            n = int(n_np[i])
            drafted = int(spec_valid[i]) - 1
            accepted = n - 1
            if drafted > 0:
                self._spec_drafted += drafted
                self._spec_accepted += accepted
                req.spec_drafted += drafted
                req.spec_accepted += accepted
                _sm.spec_drafted_tokens.inc(drafted)
                _sm.spec_accepted_tokens.inc(accepted)
                _sm.spec_rejected_tokens.inc(drafted - accepted)
                _sm.spec_accept_len.observe(accepted)
                if tree:
                    # node accounting + the per-depth accept histogram
                    # (on the tree lane `accepted` IS the accepted path
                    # depth: one draft node per committed level)
                    _sm.spec_tree_nodes_drafted.inc(drafted)
                    _sm.spec_tree_nodes_accepted.inc(accepted)
                    _sm.spec_accept_depth.observe(accepted)
                self._accept_hist[accepted] += 1
                # accepted-k instant on the request's PR-7 trace lane
                req._tr_event("spec_accept", drafted=drafted,
                              accepted=accepted, emitted=n)
            self._slot_len[i] = min(self._slot_len[i] + n,
                                    self.config.max_len - 1)
            prev = req.last_token_ts
            interval = (now - prev) if prev is not None else None
            pushed = 0
            for j in range(n):
                t = int(cand_np[i, j])
                req.push_token(t, now)
                pushed += 1
                if interval is not None:
                    # the round's wall time amortized over its tokens —
                    # the honest per-token cadence of a multi-token step
                    _sm.tpot_seconds.observe(interval / n)
                    _sm.tpot_summary.observe(interval / n)
                if self._finish_or_keep(i, req, t, now):
                    break
            _sm.tokens_generated.inc(pushed)

    def run_until_idle(self, max_steps: int = 1_000_000) -> int:
        """Drive ``step()`` until queue and slots are empty (the
        synchronous serving loop); returns iterations executed.
        Returns with nothing in flight, ``max_steps`` or not."""
        n = 0
        while n < max_steps and self.has_work():
            if not self.step():
                break
            n += 1
        with self._step_lock:
            self._flush_ahead()
        # admission may have drained the queue into terminal states
        # without any decode work; one more pass clears stragglers
        self._admit()
        return n

    def has_work(self) -> bool:
        """A request is queued, between the queue and its slot, or in a
        slot, or a step is in flight (its rows may all be dead, with no
        slot left to show for it). Read without the step lock, in the
        order in which a request moves, so that one on its way is
        always seen somewhere."""
        return bool(self.scheduler.depth or self._unseated
                    or self.busy_slots() or self.in_flight)

    # -- background loop -----------------------------------------------------
    def start(self):
        """Run the serving loop on a daemon thread (the HTTP front end
        and ``Request.result()`` consumers use this mode)."""
        if self._stopped:
            raise EngineStoppedError(
                "stopped engines don't restart: the drain already "
                "refused new work — build a fresh engine (warmup() it "
                "before taking traffic)")
        with self._wake:
            if self._running:
                return self
            self._running = True
            self._thread = threading.Thread(
                target=self._serve_loop, name="paddle-tpu-serving", daemon=True)
            self._thread.start()
        # what the whole process does while the loop runs (the lane
        # ``proc``): started here, after warm-up, never at import
        _trace.watch_process()
        return self

    def _serve_loop(self):
        # the per-request try in _admit guards prefill failures; anything
        # escaping step() itself (a poisoned pool program, OOM, a bug) is
        # fatal to the WHOLE pool — without this guard the thread died
        # silently and every result() caller hung forever
        try:
            while self._running:
                if self.step():
                    # straight into the next iteration: a long gap from
                    # here to its open() is time the thread lost
                    self._phases.follows = True
                else:
                    with self._wake:
                        if self._running and not self.has_work():
                            with _trace.profiled_span("engine.idle", "engine",
                                                      "engine"):
                                self._wake.wait(0.05)
        except BaseException as e:  # noqa: BLE001 — loop-level crash
            self._on_loop_crash(e)

    def _on_loop_crash(self, exc: BaseException):
        """Decode-loop death: fail EVERY running and queued request with
        the exception (so ``result()``/``stream()`` callers return
        instead of hanging), flip health to unhealthy, and count it."""
        err = repr(exc)
        with self._step_lock:
            self._crashed = err
            self._running = False
            _sm.engine_crashes_total.inc()
            _sm.engine_unhealthy.set(1)
            # post-mortem first, while the slot/queue state still shows
            # what the engine was doing when it died (the dump's state
            # provider reads stats() — before the requests are failed).
            # A death that looks like a device allocation failure gets
            # the OOM forensics dump instead: same flight recorder, but
            # the extra names the top temp-byte executable — the OOM
            # names its culprit instead of dying with an XLA backtrace.
            from ..observability import perf as _perf

            if _perf.is_oom_error(exc):
                _perf.dump_oom(exc)
            else:
                _trace.flight_dump("engine_crash", extra={"error": err})
            # supervised engines: the supervisor's capture hook runs
            # AFTER the post-mortem (the dump shows the true in-flight
            # state) and BEFORE _fail_inflight (finish() is idempotent
            # and irreversible — anything the hook does not detach is
            # failed below, exactly the unsupervised semantics)
            hook = self._crash_hook
            if hook is not None:
                try:
                    hook(self, exc)
                except Exception:  # noqa: BLE001 — the crash path must
                    pass           # survive a broken supervisor
            self._fail_inflight(f"engine loop crashed: {err}")
        with self._wake:
            self._wake.notify_all()

    def _fail_inflight(self, error: str):
        """Fail every running slot and queued request with ``error`` so
        their ``result()``/``stream()`` callers return instead of
        hanging (crash / abort / drain-timeout paths; caller holds the
        step lock). What is in flight is emitted first: no token that
        the device selected before the hand-over is lost, and none
        arrives after it."""
        self._flush_ahead(or_drop=True)
        for slot in range(self.config.max_slots):
            if self._slot_req[slot] is not None:
                self._free_slot(slot, RequestStatus.FAILED, "failed",
                                error=error)
        while True:  # drain the queue; pop_ready finishes
            req = self.scheduler.pop_ready()  # cancelled/expired itself
            if req is None:
                break
            req.finish(RequestStatus.FAILED, error=error)
            _sm.requests_total.labels("failed").inc()
            self._outcomes["failed"] = self._outcomes.get("failed", 0) + 1
        self.pool.set_gauges()  # slots freed outside an iteration

    def _export_inflight(self) -> tuple:
        """Detach every running and queued request WITHOUT finishing
        them — the supervised-restart capture (caller holds the step
        lock, normally from inside ``_crash_hook``). Returns
        ``(running, queued)`` in FCFS admission order. This engine is
        presumed dead: no pool bookkeeping happens (the pools die with
        the engine); only host-side request state is rebuilt, via the
        same ``_build_resume`` recipe preemption uses, so a FRESH
        engine resumes each running request bit-identically. Queued
        requests were never touched by the crashing step and carry no
        resume state at all. What is in flight is emitted first where
        the device still gives it, and dropped where not: the resume
        state is built from what each request was given, and nothing
        reaches a request after its capture."""
        self._flush_ahead(or_drop=True)
        running = []
        order = sorted(
            (slot for slot in range(self.config.max_slots)
             if self._slot_req[slot] is not None),
            key=lambda s: self._slot_seq[s])
        for slot in order:
            req = self._slot_req[slot]
            self._build_resume(slot)
            req.slot = None
            req._tr_end("prefill")
            req._tr_end("decode")
            req._tr_event("captured", slot=slot,
                          generated=len(req.output_tokens))
            self._slot_req[slot] = None
            self._decoding[slot] = False
            self._jobs[slot] = None
            running.append(req)
        return running, self.scheduler.detach_all()

    @property
    def crashed(self) -> Optional[str]:
        return self._crashed

    @property
    def healthy(self) -> bool:
        return self._crashed is None

    @property
    def draining(self) -> bool:
        return self._draining and not self._stopped

    @property
    def stopped(self) -> bool:
        return self._stopped

    @property
    def warmed_up(self) -> bool:
        return self._warmed_up

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Stop admitting new requests and let the in-flight ones finish
        (the graceful half of ``stop()``; a router calls this before
        taking a replica out of rotation). ``submit()`` raises
        ``EngineDrainingError`` from the moment this is called. Returns
        True when every in-flight request reached a terminal state on
        its own; on ``timeout_s`` expiry the stragglers are FAILED with
        an explicit drain-timeout error (never silently dropped) and
        False is returned. Idempotent; a crashed engine is already
        drained (everything was failed by the crash path)."""
        with self._wake:
            self._draining = True
            self._wake.notify_all()
        deadline = (time.perf_counter() + timeout_s
                    if timeout_s is not None else None)
        while self.has_work():
            if self._crashed is not None:
                return False  # crash path failed everything already
            if deadline is not None and time.perf_counter() > deadline:
                with self._step_lock:
                    self._fail_inflight(
                        f"drain timed out after {timeout_s}s; request "
                        f"aborted at engine stop — retry on another "
                        f"replica")
                return False
            if self._thread is None:
                # sync engine (nobody runs the loop): drive it inline —
                # draining blocks submits, so the backlog is finite
                self.run_until_idle()
            else:
                time.sleep(0.005)
        return True

    def stop(self, abort: bool = False,
             drain_timeout_s: Optional[float] = 30.0):
        """Stop serving. DRAINS by default: new submits are refused
        (``EngineDrainingError`` now, ``EngineStoppedError`` once
        stopped), in-flight requests finish (or are explicitly FAILED
        at ``drain_timeout_s``), then the loop stops. ``abort=True``
        keeps the old fail-fast shutdown, minus its silent data loss:
        every queued and running request is FAILED immediately with an
        actionable error instead of being abandoned with ``result()``
        hanging forever."""
        with self._wake:
            self._draining = True
        if abort:
            with self._step_lock:
                self._fail_inflight(
                    "engine stopped (abort=True); request aborted "
                    "mid-flight — resubmit to another replica")
        elif self._crashed is None:
            self.drain(timeout_s=drain_timeout_s)
        if self._crashed is None:
            # persist the prefix cache across the restart (disk tier)
            # BEFORE the terminal flip: the engine is drained, so the
            # pool blocks are stable under the step lock
            with self._step_lock:
                self._flush_tier()
        self._stopped = True
        self._running = False
        with self._wake:
            self._wake.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        ph = self._phases
        if ph.stalls:
            # each was logged as it came (the first few); the totals
            _trace.logger.warning(
                "engine stalled %d time(s), %.2f s in all", ph.stalls,
                ph.stall_ns / 1e9)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- introspection -------------------------------------------------------
    @property
    def mean_occupancy(self) -> Optional[float]:
        if not self._steps:
            return None
        return self._occupancy_integral / (self._steps * self.config.max_slots)

    def spec_stats(self) -> dict:
        """Speculative-lane accounting for ``/stats`` and the flight
        recorder: engine-lifetime drafted/accepted/rejected totals, the
        pool-wide accept rate, and the accept-length digest."""
        if not self.spec:
            return {"enabled": False}
        count = sum(self._accept_hist)
        total = sum(i * n for i, n in enumerate(self._accept_hist))

        def _pct(p):
            # exact percentile over the engine's own rounds (the hist is
            # tiny: one bucket per accept length 0..k)
            target = p * count
            seen = 0
            for i, n in enumerate(self._accept_hist):
                seen += n
                if seen >= target:
                    return float(i)
            return float(len(self._accept_hist) - 1)

        out = {
            "enabled": True,
            "mode": "tree" if self._spec_tree is not None else "chain",
            "k": self._spec_k,
            "verify_kernel": self._spec_verify_kernel,
            "rounds": self._spec_rounds,
            "drafted_tokens": self._spec_drafted,
            "accepted_tokens": self._spec_accepted,
            "rejected_tokens": self._spec_drafted - self._spec_accepted,
            "accept_rate": (self._spec_accepted / self._spec_drafted
                            if self._spec_drafted else None),
            "queue_spec_opted_out": self.scheduler.depth_spec_opted_out(),
            "accept_len": {
                **({f"p{round(p * 100)}": _pct(p)
                    for p in (0.5, 0.95, 0.99)} if count else {}),
                "hist": list(self._accept_hist),
                "mean": (total / count) if count else None,
                "count": count},
        }
        if self._spec_tree is not None:
            # tree lane: the drafted/accepted totals above count NODES
            # (the whole flattened tree verifies; most siblings lose by
            # construction), so accept_rate is structurally low — the
            # per-round accepted PATH depth is the useful signal
            out["tree"] = {
                "factors": list(self._spec_tree),
                "depth": int(self._tree["depth"]),
                "nodes": int(self._tree["nodes"]),
                "drafted_nodes": self._spec_drafted,
                "accepted_nodes": self._spec_accepted,
                # +1: the root token always commits alongside the path
                "mean_accepted_path_len":
                    (total / count) + 1.0 if count else None,
            }
        return out

    def kv_block_stats(self) -> dict:
        """Pool utilization + internal fragmentation (allocated token
        slots the slots' sequences do not fill), with the quantization
        accounting: the storage format, bytes per cached token (values +
        scales, all layers), and the capacity multiplier vs a bf16 pool
        of the same HBM budget."""
        from ..generation import kv_cache_bytes_per_token

        stats = self.pool.stats()
        bs = self.config.block_size
        frag = 0
        for slot in range(self.config.max_slots):
            if self._slot_req[slot] is None:
                continue
            used = self._jobs[slot].done if self._jobs[slot] is not None \
                else self._slot_len[slot]
            if self._layout is not None:
                # entries in use: the window's exact keys and a summary
                # for every whole chunk so far
                used = (used - 1) % self._layout.window + 1 \
                    + used // self._layout.chunk if used else 0
            frag += len(self._slot_blocks[slot]) * bs - used
        stats["internal_fragmentation_tokens"] = frag
        stats["kv_format"] = self.config.kv_format
        stats["bytes_per_token"] = self._kv_bytes_per_token
        stats["effective_capacity_tokens"] = self.pool.usable_blocks * bs
        bf16 = kv_cache_bytes_per_token(self._mcfg, "bf16", self._dtype)
        stats["capacity_vs_bf16"] = round(
            bf16 / max(1, self._kv_bytes_per_token), 3)
        return stats

    def debug_requests(self) -> dict:
        """The live per-request state table (``GET /debug/requests``):
        every queued and running request plus the recent-finished tail,
        each as a ``Request.debug_row`` (+ slot-phase and KV-block
        accounting for running ones)."""
        queued = [r.debug_row() for r in self.scheduler.snapshot()]
        running = []
        for slot, r in enumerate(self._slot_req):
            if r is None:
                continue
            row = r.debug_row()
            job = self._jobs[slot]
            row["phase"] = "prefill" if job is not None else "decode"
            row["tokens_in_cache"] = (job.done if job is not None
                                      else self._slot_len[slot])
            row["kv_blocks"] = len(self._slot_blocks[slot])
            running.append(row)
        recent = [r.debug_row() for r in list(self._recent)]
        return {"ts": time.time(), "queued": queued, "running": running,
                "recent": recent}

    def health(self) -> tuple:
        """``(http_status, payload)`` for ``/healthz`` — and the probe
        surface a router's health-gating reads. The 503 states are
        DISTINCT (a saturated replica used to be indistinguishable from
        a dead one):

        - ``ok`` (200): admitting traffic.
        - ``crashed`` (503): the decode loop died; every request was
          failed; only a fresh engine recovers. ``crashed`` carries the
          error repr.
        - ``draining`` (503): no new admissions, in-flight requests
          finishing (graceful shutdown in progress) — route elsewhere,
          don't retry here.
        - ``stopped`` (503): drain complete, loop down.
        - ``saturated`` (503): alive but the admission queue is full;
          ``retry_after_s`` (derived from the queue-wait digest's p50)
          says when a slot is likely to free — back off, don't eject.
        - ``stalled`` (503): the background loop has work pending but
          hasn't reached a step boundary for ``stall_timeout_s`` — a
          hung device dispatch; probes should treat it like a crash.
          One clock, two thresholds: ``_last_progress_ts`` is the
          iteration's ``Phases.open``, the clock against which an
          iteration longer than ``tracing.STALL_NS`` (250 ms) is
          recorded as ``engine.stall`` and counted in ``counters()``
          once it ENDS; this state is for the one that has not ended
          after ``stall_timeout_s`` (10 s), and keeps no record.
        """
        payload = {
            "ts": time.time(),
            "slots_busy": self.busy_slots(),
            "slots_total": self.config.max_slots,
            "queue_depth": self.scheduler.depth,
            "max_queue_depth": self.scheduler.max_queue_depth,
            "warmed_up": self._warmed_up,
            "crashed": self._crashed,
        }
        kv = self.kv_block_stats()
        payload["kv_blocks_in_use"] = kv["in_use"]
        payload["kv_blocks_total"] = kv["usable"]
        payload["kv_blocks_shared"] = kv["shared"]
        payload["kv_block_utilization"] = round(kv["utilization"], 4)
        if self._crashed is not None:
            payload["status"] = "crashed"
            return 503, payload
        if self._stopped:
            payload["status"] = "stopped"
            return 503, payload
        if self._draining:
            payload["status"] = "draining"
            payload["in_flight"] = (payload["slots_busy"]
                                    + payload["queue_depth"])
            return 503, payload
        stalled_s = time.perf_counter() - self._last_progress_ts
        if self._running and stalled_s > self.config.stall_timeout_s \
                and (payload["slots_busy"] or payload["queue_depth"]):
            payload["status"] = "stalled"
            payload["stalled_s"] = round(stalled_s, 3)
            return 503, payload
        if payload["queue_depth"] >= self.scheduler.max_queue_depth:
            payload["status"] = "saturated"
            payload["retry_after_s"] = _sm.queue_wait_retry_after()
            return 503, payload
        payload["status"] = "ok"
        return 200, payload

    def counters(self) -> dict:
        """The engine's running counts, O(1): plain integers read
        without ``_step_lock``, the pool lock, a digest or the perf
        ledger, so a caller may poll this while the engine serves (the
        queue depth takes the scheduler's own short lock). Each is
        bumped where its event happens; the ``engine.admit`` and
        ``engine.iter`` spans carry the token and preemption counts per
        iteration. ``slot_steps`` is the occupancy integral: decode rows
        summed over ``steps``. Both count a step when its tokens are
        emitted, ``steps_ahead`` when it is enqueued: with a step in
        flight the latter already holds it and the former do not yet.
        Blocks, COW forks and chunks are counted by the pool
        (``stats()["kv_blocks"]``) and the metrics registry."""
        out = {
            "steps": self._steps,
            "slots": self.config.max_slots,
            "slot_steps": self._occupancy_integral,
            "queue_depth": self.scheduler.depth,
            "prompt_tokens": self._n_prompt_tokens,
            "prefix_hit_tokens": self._n_prefix_hit_tokens,
            "preemptions": self._preempt_count,
            # chunks that rode a prefill program, the programs, and
            # the chunks that rode as a slot's second or later row of
            # its iteration (the last program's spare rows)
            "prefill_rows": self._n_prefill_rows,
            "prefill_programs": self._n_prefill_programs,
            "prefill_fill_rows": self._n_prefill_fill_rows,
            # decode steps enqueued while the step before was unread
            # (of ``steps``, once both are emitted), the times something
            # rare read what was in flight ahead of its iteration, and
            # the rows of a step whose request had ended by its emit
            "steps_ahead": self._n_steps_ahead,
            "ahead_flushes": self._n_ahead_flushes,
            "dead_rows": self._n_dead_rows,
            # decode steps whose program carried prefill rows too (the
            # sum of the dispatch spans' ``fused``; counted, as
            # ``steps_ahead`` is, when the step is enqueued)
            "steps_fused": self._n_steps_fused,
            # iterations that worked, and gaps between two with no idle
            # wait, longer than ``tracing.STALL_NS``, and their lengths
            # summed (the ``engine.stall`` instants and their ``ms``;
            # counted only while tracing is on)
            "stalls": self._phases.stalls,
            "stall_ns": self._phases.stall_ns,
        }
        if self._ut_steps > 1:
            # a looped stack: the passes of every decode step enqueued
            # (the sum of the dispatch spans' ``ut_steps``)
            out["loop_passes"] = self._n_loop_passes
        if self._routed:
            # routed experts, over the programs whose counts were read
            # (``_read_routing``): the chosen (token, expert) pairs of
            # live rows, summed over the expert layers; those whose
            # expert is held and was computed here; those left out
            # because their expert is on another share; held experts
            # with a pair; and the busiest one's pairs, a layer
            t = self._route_totals
            out.update(
                route_programs=t["programs"], expert_pairs=t["pairs"],
                expert_pairs_here=t["pairs_here"],
                expert_pairs_absent=t["pairs"] - t["pairs_here"],
                experts_touched=t["experts_touched"],
                expert_load_max=t["load_max"])
        if self._layout is not None:
            # slots that crossed into a new window, the exact-key blocks
            # those rolls gave back, chunks pooled into a summary
            out.update(
                window_rolls=self._n_window_rolls,
                window_blocks_released=self._n_window_blocks_released,
                summary_entries_written=self._n_summary_entries)
        return out

    def stats(self) -> dict:
        """``counters()`` plus the sections that cost: latency digests
        (sorts of up to 4,096 samples each), the perf ledger, pool and
        prefix-cache statistics (their own locks). None of it takes
        ``_step_lock``; it is pure-Python work beside the engine's
        thread, so call it at a scrape's grain, not a step's."""
        c = self.counters()
        out = {
            "kv_mode": self.config.kv_mode,
            "slots": c["slots"],
            "slots_busy": self.busy_slots(),
            "queue_depth": c["queue_depth"],
            "max_len": self.config.max_len,
            "steps": c["steps"],
            "mean_occupancy": ((c["slot_steps"] / (c["steps"] * c["slots"]))
                               if c["steps"] else None),
            "counters": c,
            "outcomes": dict(self._outcomes),
            "running": self._running,
            "healthy": self.healthy,
            "crashed": self._crashed,
            "draining": self.draining,
            "stopped": self._stopped,
            "warmed_up": self._warmed_up,
            "max_queue_depth": self.scheduler.max_queue_depth,
            "latency_digests": _sm.latency_digests(),
            "goodput_tokens_per_s": _sm.goodput_tokens_per_second.value(),
            "preemptions": c["preemptions"],
            "tp": self._tp,
        }
        # the performance ledger for this engine's executables: per-entry
        # flops/bytes/intensity/roofline + MFU when peaks are known (the
        # /stats block the acceptance criteria read)
        from ..observability import perf as _perf
        out["perf"] = {"ledger": _perf.ledger(prefix="serving."),
                       "peaks": _perf.peak_specs()}
        out["spec"] = self.spec_stats()
        out["block_size"] = self.config.block_size
        out["prefill_chunk"] = self.config.prefill_chunk
        out["kv_format"] = self.config.kv_format
        out["kv_blocks"] = self.kv_block_stats()
        out["prefix_cache"] = (self.prefix_cache.stats()
                               if self.prefix_cache is not None else None)
        out["kv_tier"] = (self._tier.stats()
                          if self._tier is not None else None)
        out["requests"] = [
            {"request_id": r.id, "slot": slot,
             "tokens_in_cache": (self._jobs[slot].done
                                 if self._jobs[slot] is not None
                                 else self._slot_len[slot]),
             "kv_blocks": len(self._slot_blocks[slot]),
             "phase": ("prefill" if self._jobs[slot] is not None
                       else "decode")}
            for slot, r in enumerate(self._slot_req) if r is not None]
        return out
