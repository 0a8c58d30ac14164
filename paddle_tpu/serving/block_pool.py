"""Host-side KV block allocator + prefix cache for the paged serving
engine.

The paged KV design (PagedAttention / vLLM allocation model, RadixAttention
prefix reuse, translated to this repo's static-shape substrate): device
HBM holds ONE fixed pool of ``num_blocks`` KV blocks of ``block_size``
tokens each (per layer, [num_blocks, block_size, kv_heads, head_dim]);
every slot's logical cache is a small int32 block table indexing the
pool. All allocation POLICY lives here on the host — the device only
ever sees block tables as traced arrays, so occupancy/sharing patterns
never retrace the decode step.

- ``BlockPool``: free-list allocator with per-block reference counts.
  Block 0 is permanently reserved as the *dump* block: inactive slot
  rows in the pool-wide decode step still execute their (static-shape)
  cache write, and routing those writes at physical block 0 keeps them
  from ever dirtying a live block. A block with refcount > 1 is SHARED
  (prefix cache and/or several requests); writers must copy-on-write
  fork it first (`ServingEngine._ensure_writable`).
- ``PrefixCache``: exact-prefix reuse map ``prompt[:end] -> block id``
  with LRU eviction. A request whose prompt starts with an already-
  prefilled prefix adopts those blocks by reference instead of
  recomputing them — a shared system prompt is prefilled once, ever.
  Partial (non-block-aligned) tails are cached too; the first divergent
  write into one triggers the COW fork, which is what makes sharing
  safe.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import List, Sequence, Tuple

import numpy as np

from . import metrics as _sm

__all__ = ["BlockPool", "PrefixCache", "PoolExhaustedError",
           "BlockPoolError", "DUMP_BLOCK", "WindowedLayout"]

# physical block 0: the write sink for inactive/padded rows. Never
# allocated, never freed, never cached.
DUMP_BLOCK = 0


class PoolExhaustedError(RuntimeError):
    """No free KV blocks. Callers evict the prefix cache / preempt a
    running request and retry, or surface admission backpressure."""


class BlockPoolError(RuntimeError):
    """Allocator invariant violation (double free, bad block id) — a
    bug in the caller, never load-dependent."""


class WindowedLayout:
    """Where the blocks of a slot sit in its table when the model keeps
    exact keys only inside a window and one summary entry per chunk of
    every window behind it (EVA; ``generation.eva_virtual_position`` is
    the device's side of the same layout). In table entries of
    ``block_size`` positions, a slot in window ``g`` holds

        [ g * per_window summary blocks | up to window_blocks of exact
          keys | up to per_window blocks of window g's own summaries ]

    and when its position crosses into window ``g + 1`` the engine
    rolls the row: the exact keys' blocks are released, window ``g``'s
    summary blocks move down beside the older ones, and the window
    starts again. Pure arithmetic on the host; the pool and the table
    stay the engine's."""

    def __init__(self, block_size: int, window: int, chunk: int,
                 max_len: int):
        if window % chunk or window % block_size \
                or (window // chunk) % block_size:
            raise ValueError(
                f"a window of {window} positions must be whole chunks of "
                f"{chunk}, whole blocks of {block_size}, and its "
                f"{window // chunk} summaries must fill whole blocks: pick a "
                f"block_size that divides {window // chunk}")
        self.block_size, self.window, self.chunk = block_size, window, chunk
        self.window_blocks = window // block_size
        self.per_window = window // chunk // block_size
        self.width = self.per_window * ((max_len - 1) // window + 1) \
            + self.window_blocks

    def entries(self, start: int, end: int) -> List[int]:
        """Table indices that writing positions ``[start, end)`` (inside
        one window) touches: the exact keys' blocks and the summary
        blocks of every chunk the write completes."""
        bs, g = self.block_size, start // self.window
        base = g * self.per_window
        lo, hi = start % self.window, (end - 1) % self.window
        out = list(range(base + lo // bs, base + hi // bs + 1))
        # chunk m is completed by this write when start < (m+1)*chunk <= end
        first_c = start // self.chunk % (self.window // self.chunk)
        n_done = end // self.chunk - start // self.chunk
        if n_done > 0:
            sb = base + self.window_blocks
            out += range(sb + first_c // bs,
                         sb + (first_c + n_done - 1) // bs + 1)
        return out

    def held(self, n: int) -> int:
        """Blocks a slot holds once positions ``[0, n)`` are written
        (before the roll that the write of position ``n`` would bring)."""
        if n <= 0:
            return 0
        g, inw = (n - 1) // self.window, (n - 1) % self.window + 1
        bs = self.block_size
        return g * self.per_window + -(-inw // bs) \
            + -(-(inw // self.chunk) // bs)

    def peak(self, n: int) -> int:
        """The most blocks the slot holds on its way to ``n`` positions:
        at ``n``, or at the end of the last whole window before it."""
        return max(self.held(n), self.held(n // self.window * self.window))

    def read_blocks(self, n: int):
        """(window blocks, summary blocks) that attention reads for a
        query at position ``n - 1``."""
        g, inw = (n - 1) // self.window, (n - 1) % self.window + 1
        return -(-inw // self.block_size), g * self.per_window


class BlockPool:
    """Ref-counted free-list allocator over ``num_blocks`` KV blocks.

    Thread-safe (one lock; every operation is O(1) or O(n_requested),
    the count of shared blocks included: it is kept as references
    cross 1 <-> 2, never reduced over the pool). The ``kv_blocks_*``
    gauges are not written by alloc/incref/decref: their owner calls
    ``set_gauges()`` at its own grain (the serving engine once an
    iteration), which is finer than any scrape.
    Allocation is all-or-nothing: ``alloc(n)`` either returns ``n``
    block ids or raises ``PoolExhaustedError`` leaving the pool
    untouched. The free list is LIFO so tests and replays are
    deterministic.
    """

    # pt-analysis lock discipline: every mutable piece of allocator
    # state is touched only under self._lock (methods below either take
    # it or are '# holds-lock' helpers whose callers do)
    GUARDED_BY = {
        "_free": "_lock",
        "_ref": "_lock",
        "_shared": "_lock",
        "alloc_total": "_lock",
        "free_total": "_lock",
        "cow_forks": "_lock",
        "high_watermark": "_lock",
    }

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is the reserved dump "
                f"block), got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._lock = threading.Lock()
        # LIFO free list; low ids first out for deterministic layouts
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._ref = np.zeros(num_blocks, np.int64)
        self._ref[DUMP_BLOCK] = 1  # pinned forever
        self._shared = 0     # live blocks with more than one reference
        self.alloc_total = 0
        self.free_total = 0
        self.cow_forks = 0   # engine reports forks via note_cow_fork()
        self.high_watermark = 0
        self.set_gauges()

    # -- core ops ------------------------------------------------------------
    def alloc(self, n: int = 1) -> List[int]:
        """Take ``n`` fresh blocks (refcount 1 each). All-or-nothing."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        with self._lock:
            if n > len(self._free):
                raise PoolExhaustedError(
                    f"KV block pool exhausted: need {n} block(s), "
                    f"{len(self._free)} free of {self.usable_blocks} usable "
                    f"(block_size={self.block_size})")
            ids = [self._free.pop() for _ in range(n)]
            for b in ids:
                self._ref[b] = 1
            self.alloc_total += n
            self.high_watermark = max(self.high_watermark,
                                      self._used_unlocked())
            return ids

    def incref(self, block_id: int) -> None:
        """Adopt a shared reference to a live block."""
        with self._lock:
            self._check_live(block_id)
            self._ref[block_id] += 1
            if self._ref[block_id] == 2:
                self._shared += 1

    def decref(self, block_id: int) -> bool:
        """Drop one reference; returns True when the block was freed."""
        with self._lock:
            self._check_live(block_id)
            self._ref[block_id] -= 1
            left = self._ref[block_id]
            if left == 0:
                self._free.append(block_id)
                self.free_total += 1
                return True
            if left == 1:
                self._shared -= 1
            return False

    def ref(self, block_id: int) -> int:
        with self._lock:
            if not (0 <= block_id < self.num_blocks):
                raise BlockPoolError(f"bad block id {block_id}")
            return int(self._ref[block_id])

    def _check_live(self, block_id: int):  # holds-lock: _lock
        if not (0 < block_id < self.num_blocks):
            raise BlockPoolError(
                f"bad block id {block_id} (usable ids are "
                f"1..{self.num_blocks - 1}; 0 is the reserved dump block)")
        if self._ref[block_id] <= 0:
            raise BlockPoolError(
                f"block {block_id} is not allocated (double free / "
                f"use-after-free)")

    def note_cow_fork(self) -> None:
        """Engine-side fork accounting (the fork itself is the engine's
        jitted copy; only the counter lives behind the pool lock)."""
        with self._lock:
            self.cow_forks += 1

    # -- accounting ----------------------------------------------------------
    # The public properties take the lock (they are read from the HTTP
    # stats/health threads while the engine allocates); the *_unlocked
    # helpers are for use inside an operation that already holds it.
    def _free_unlocked(self) -> int:  # holds-lock: _lock
        return len(self._free)

    def _used_unlocked(self) -> int:  # holds-lock: _lock
        return self.usable_blocks - len(self._free)

    def _shared_unlocked(self) -> int:  # holds-lock: _lock
        return self._shared

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1  # minus the dump block (immutable)

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return self._free_unlocked()

    @property
    def used_blocks(self) -> int:
        with self._lock:
            return self._used_unlocked()

    @property
    def shared_blocks(self) -> int:
        """Blocks referenced by more than one owner (COW-protected)."""
        with self._lock:
            return self._shared_unlocked()

    def stats(self) -> dict:
        """Fragmentation/utilization accounting for /stats and tests —
        one lock hold, so the snapshot is internally consistent."""
        with self._lock:
            used = self._used_unlocked()
            return {
                "num_blocks": self.num_blocks,
                "block_size": self.block_size,
                "usable": self.usable_blocks,
                "in_use": used,
                "free": self._free_unlocked(),
                "shared": self._shared_unlocked(),
                "utilization": used / max(1, self.usable_blocks),
                "high_watermark": self.high_watermark,
                "alloc_total": self.alloc_total,
                "free_total": self.free_total,
                "cow_forks": self.cow_forks,
            }

    def set_gauges(self) -> None:
        """Publish ``paddle_tpu_kv_blocks_{total,in_use,shared}``: three
        stores under one lock hold, no reduction."""
        with self._lock:
            _sm.kv_blocks_total.set(self.usable_blocks)
            _sm.kv_blocks_in_use.set(self._used_unlocked())
            _sm.kv_blocks_shared.set(self._shared_unlocked())


class PrefixCache:
    """Exact token-prefix -> KV block map with LRU eviction.

    One entry per cached block: the key is the request prompt's bytes up
    to and including the tokens that block covers, so a hit guarantees
    both the block's own tokens AND its entire left context match —
    K/V entries are position- and context-dependent, a content-only
    match would be wrong. The cache holds its own reference on every
    registered block; eviction (LRU, only blocks nobody else references)
    releases it back to the pool.
    """

    GUARDED_BY = {"_map": "_lock", "hits": "_lock", "misses": "_lock"}

    def __init__(self, pool: BlockPool):
        self.pool = pool
        # key -> (block_id, covered_end); ordered for LRU (oldest first)
        self._map: "OrderedDict[bytes, Tuple[int, int]]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0       # block-level hit/miss tallies (also metrics)
        self.misses = 0
        # Eviction hook: called as on_evict(key, block_id, covered_end)
        # BEFORE the block is released, returning "demoted" when it
        # copied the KV somewhere (the hierarchical host tier rides
        # this, serving/kv_tier.py) or "dropped" to free outright. None
        # (the default) keeps the legacy drop-on-evict behavior. A
        # raising hook counts as "dropped": eviction must reclaim
        # blocks even when the tier misbehaves.
        self.on_evict = None

    @staticmethod
    def _key(tokens: np.ndarray, end: int) -> bytes:
        return np.ascontiguousarray(tokens[:end], dtype=np.int32).tobytes()

    def __len__(self) -> int:
        with self._lock:
            return len(self._map)

    def note(self, hit_blocks: int, miss_blocks: int) -> None:
        """Admission-side hit/miss accounting (the engine calls this
        once per admission; keeping the tallies behind the cache lock
        means a /stats scrape never reads a torn update)."""
        with self._lock:
            self.hits += hit_blocks
            self.misses += miss_blocks

    def match(self, tokens: np.ndarray, limit: int) -> Tuple[int, List[int]]:
        """Longest reusable prefix of ``tokens`` covering at most
        ``limit`` tokens (callers pass ``len(prompt) - 1`` so at least
        the last prompt token is always recomputed for its logits).
        Increfs every matched block on behalf of the caller; returns
        ``(n_tokens_covered, block_ids)``."""
        bs = self.pool.block_size
        matched: List[int] = []
        covered = 0
        with self._lock:
            while covered < limit:
                hit = None
                # longest cached span first: the full next block, then
                # every shorter partial tail down to one extra token
                top = min(covered + bs, limit)
                for end in range(top, covered, -1):
                    ent = self._map.get(self._key(tokens, end))
                    if ent is not None:
                        hit = (end, ent[0])
                        break
                if hit is None:
                    break
                end, bid = hit
                self.pool.incref(bid)
                self._map.move_to_end(self._key(tokens, end))
                matched.append(bid)
                covered = end
                if end % bs:
                    break  # a partial block is always the last reusable one
        return covered, matched

    def insert(self, tokens: np.ndarray, length: int,
               block_ids: Sequence[int]) -> int:
        """Register the blocks covering ``tokens[:length]`` after a
        prefill completes. Already-present keys are left alone (the
        first writer wins; no duplicate references). Returns the number
        of NEW entries."""
        bs = self.pool.block_size
        added = 0
        with self._lock:
            for i, bid in enumerate(block_ids):
                end = min((i + 1) * bs, length)
                if end <= i * bs:
                    break
                key = self._key(tokens, end)
                if key in self._map:
                    self._map.move_to_end(key)
                    continue
                self.pool.incref(bid)
                self._map[key] = (bid, end)
                added += 1
        return added

    def evict(self, n: int) -> int:
        """Free up to ``n`` blocks by dropping LRU entries whose block
        nobody else references (cache-only blocks). Returns how many
        blocks were actually freed."""
        freed = 0
        with self._lock:
            for key in list(self._map.keys()):
                if freed >= n:
                    break
                bid, end = self._map[key]
                if self.pool.ref(bid) == 1:  # cache holds the only ref
                    del self._map[key]
                    outcome = "dropped"
                    if self.on_evict is not None:
                        # the block is still live (our ref) — the hook
                        # may copy it device->host before the decref
                        # below hands it back to the pool
                        try:
                            if self.on_evict(key, bid, end) == "demoted":
                                outcome = "demoted"
                        except Exception:  # noqa: BLE001 — see __init__
                            pass
                    self.pool.decref(bid)
                    freed += 1
                    _sm.prefix_cache_evictions.labels(outcome).inc()
        return freed

    def forget(self, block_id: int) -> None:
        """Drop every entry pointing at ``block_id`` (engine-side
        invalidation; releases the cache's reference)."""
        with self._lock:
            for key in [k for k, (b, _) in self._map.items()
                        if b == block_id]:
                del self._map[key]
                self.pool.decref(block_id)

    def entries(self) -> List[Tuple[bytes, int, int]]:
        """Consistent ``(key, block_id, covered_end)`` snapshot in LRU
        order (oldest first) — the drain-time tier flush walks this to
        persist every still-cached prefix."""
        with self._lock:
            return [(k, bid, end) for k, (bid, end) in self._map.items()]

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._map), "hits": self.hits,
                    "misses": self.misses}
