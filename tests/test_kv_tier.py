"""Hierarchical KV cache: host-RAM block tier + crash-safe persistent
prefix store (serving/kv_tier.py).

Oracles:
- OUTPUT PARITY: engine outputs are BIT-IDENTICAL (greedy and sampled)
  with the host tier on vs off — through forced prefix-cache eviction +
  re-admission, preemption-demote-resume, and an engine restart that
  re-admits a disk-persisted prefix. The reference is always
  ``generation.generate``.
- ONE EXECUTABLE: with tiering ON, ``serving.kv_demote`` and
  ``serving.kv_splice`` each compile exactly once (warmup) and never
  retrace across demote/readmit waves; the step/chunk invariants hold
  unchanged.
- TIER STATE MACHINE: LRU capacity, demote-vs-drop accounting, the
  eviction-callback contract on PrefixCache (no-op default preserved),
  and the cost model's measured-vs-unmeasured decisions are exact.
- CRASH SAFETY: a kill at EVERY stage of the spill commit protocol
  (tmp-write / fsync / marker / replace) leaves no half-visible entry —
  restart re-admits ONLY committed entries, corrupt spill files are
  skipped with a counted warning, and the engine falls back to prefill
  recompute with correct output (mirrors the test_fault_tolerance
  checkpoint matrix).
"""

import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import generation, serving
from paddle_tpu.distributed.checkpoint import atomic as _atomic
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import recompile
from paddle_tpu.serving import metrics as _sm
from paddle_tpu.serving.block_pool import BlockPool, PrefixCache
from paddle_tpu.serving.kv_tier import (DiskPrefixStore, KVTier,
                                        TierCostModel, payload_nbytes)

SEED = 4242


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    cfg = LlamaConfig.tiny(max_position_embeddings=256)
    return LlamaForCausalLM(cfg), cfg


def _prompt(rng, cfg, n):
    return rng.randint(1, cfg.vocab_size, n).astype("int32")


def _ref(model, prompt, **params):
    return generation.generate(
        model, prompt[None], **params).numpy()[0, len(prompt):]


def _payload(seed=0, nbytes=64):
    rng = np.random.RandomState(seed)
    return {"0/k": rng.rand(nbytes // 8, 2).astype(np.float32)}


def _engine(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 8)
    return serving.ServingEngine(model, **kw)


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


class TestCostModel:
    def test_unmeasured_defaults_to_keeping_the_work(self):
        cm = TierCostModel(prefill_rate_fn=None)
        assert cm.should_demote(8, 1 << 20)
        assert cm.should_readmit(8, 1 << 20)
        assert cm.snapshot()["decisions"] == {
            "demote": 1, "drop": 0, "readmit": 1, "recompute": 0}

    def test_measured_rate_decides_both_ways(self):
        # recompute 16 tokens at 1e6 tok/s = 16us; moving 1 MiB at
        # 12 GB/s = ~87us * 1.5 safety -> recompute wins -> drop
        cm = TierCostModel(host_gbps=12.0, safety=1.5,
                           prefill_rate_fn=lambda: 1e6)
        assert not cm.should_demote(16, 1 << 20)
        assert not cm.should_readmit(16, 1 << 20)
        # a slow measured prefill (1k tok/s -> 16ms) flips it
        cm2 = TierCostModel(host_gbps=12.0, safety=1.5,
                            prefill_rate_fn=lambda: 1e3)
        assert cm2.should_demote(16, 1 << 20)
        assert cm2.decisions["demote"] == 1

    def test_broken_rate_fn_never_decides(self):
        cm = TierCostModel(prefill_rate_fn=lambda: 1 / 0)
        assert cm.prefill_tokens_per_s() is None
        assert cm.should_readmit(4, 1 << 30)  # falls back to keep

    def test_validation(self):
        with pytest.raises(ValueError, match="host_gbps"):
            TierCostModel(host_gbps=0)
        with pytest.raises(ValueError, match="safety"):
            TierCostModel(safety=-1)


# ---------------------------------------------------------------------------
# host tier state machine (no engine, no device)
# ---------------------------------------------------------------------------


class TestKVTierUnit:
    def _tier(self, host_blocks=2, disk=None):
        return KVTier(host_blocks=host_blocks, block_size=8,
                      cost=TierCostModel(), disk=disk)

    def test_lru_capacity_drops_without_disk(self):
        t = self._tier(host_blocks=2)
        for i in range(3):
            t.put(bytes([i]), end=8, payload=_payload(i))
        st = t.stats()
        assert st["host_entries"] == 2 and st["demoted_blocks"] == 3
        assert st["dropped_blocks"] == 1           # LRU victim, no disk
        assert t.lookup(bytes([0])) is None        # the evicted oldest
        assert t.lookup(bytes([2]))[2] == "host"

    def test_lookup_refreshes_lru(self):
        t = self._tier(host_blocks=2)
        t.put(b"a", 8, _payload(1))
        t.put(b"b", 8, _payload(2))
        assert t.lookup(b"a") is not None          # refresh: a is now MRU
        t.put(b"c", 8, _payload(3))
        assert t.lookup(b"b") is None and t.lookup(b"a") is not None

    def test_match_next_longest_first_within_limit(self):
        t = self._tier(host_blocks=8)
        toks = np.arange(100, 120, dtype=np.int32)
        t.put(KVTier.key_of(toks, 8), 8, _payload(1))
        t.put(KVTier.key_of(toks, 14), 14, _payload(2))
        end, _, src = t.match_next(toks, covered=8, limit=19)
        assert end == 14 and src == "host"
        # limit below the entry's end hides it
        assert t.match_next(toks, covered=8, limit=13) is None
        assert t.match_next(toks, covered=14, limit=19) is None

    def test_spill_to_disk_and_promote_back(self, tmp_path):
        disk = DiskPrefixStore(str(tmp_path), fingerprint={"v": 1})
        t = self._tier(host_blocks=1, disk=disk)
        pay = _payload(7)
        t.put(b"old", 8, pay)
        t.put(b"new", 8, _payload(8))              # evicts -> spills
        assert len(disk) == 1 and disk.end_for(b"old") == 8
        end, got, src = t.lookup(b"old")
        assert src == "disk" and end == 8
        np.testing.assert_array_equal(got["0/k"], pay["0/k"])
        # promoted back into host (evicting "new" -> spilled too)
        assert t.lookup(b"old")[2] == "host"

    def test_payload_nbytes(self):
        p = _payload(0, nbytes=64)
        assert payload_nbytes(p) == p["0/k"].nbytes


# ---------------------------------------------------------------------------
# PrefixCache eviction-callback hook (satellite)
# ---------------------------------------------------------------------------


class TestEvictionHook:
    def _cache_with_entry(self):
        pool = BlockPool(num_blocks=6, block_size=4)
        cache = PrefixCache(pool)
        toks = np.arange(50, 58, dtype=np.int32)
        blocks = pool.alloc(2)
        cache.insert(toks, 8, blocks)
        for b in blocks:
            pool.decref(b)  # cache holds the only refs now
        return pool, cache, toks, blocks

    def test_default_no_hook_counts_dropped(self):
        pool, cache, _, _ = self._cache_with_entry()
        before = _sm.prefix_cache_evictions.labels("dropped").value()
        assert cache.on_evict is None
        assert cache.evict(2) == 2
        assert pool.used_blocks == 0
        assert _sm.prefix_cache_evictions.labels("dropped").value() \
            == before + 2

    def test_hook_sees_live_block_and_counts_demoted(self):
        pool, cache, toks, blocks = self._cache_with_entry()
        seen = []

        def hook(key, bid, end):
            assert pool.ref(bid) == 1          # still live for the copy
            seen.append((key, bid, end))
            return "demoted"

        cache.on_evict = hook
        before = _sm.prefix_cache_evictions.labels("demoted").value()
        assert cache.evict(2) == 2
        assert pool.used_blocks == 0            # freed either way
        assert _sm.prefix_cache_evictions.labels("demoted").value() \
            == before + 2
        assert [s[1] for s in seen] == blocks
        assert seen[0][0] == np.ascontiguousarray(
            toks[:4], np.int32).tobytes()
        assert [s[2] for s in seen] == [4, 8]

    def test_raising_hook_still_frees_and_counts_dropped(self):
        pool, cache, _, _ = self._cache_with_entry()
        cache.on_evict = lambda *a: 1 / 0
        before = _sm.prefix_cache_evictions.labels("dropped").value()
        assert cache.evict(2) == 2
        assert pool.used_blocks == 0
        assert _sm.prefix_cache_evictions.labels("dropped").value() \
            == before + 2

    def test_entries_snapshot_is_lru_ordered(self):
        pool, cache, toks, blocks = self._cache_with_entry()
        ents = cache.entries()
        assert [(b, e) for _, b, e in ents] == [(blocks[0], 4),
                                                (blocks[1], 8)]


# ---------------------------------------------------------------------------
# engine integration: parity, preemption, zero-retrace
# ---------------------------------------------------------------------------


def _run_workload(model, cfg, *, kv_tier, evict_between=True, path=None,
                  num_blocks=None, **tier_kw):
    """One scripted multi-request workload (greedy + sampled, shared
    prefix) with a forced full prefix-cache eviction between requests,
    so with the tier ON every later request must re-admit from host."""
    eng = _engine(model, kv_tier=kv_tier, kv_tier_path=path,
                  num_blocks=num_blocks, kv_tier_host_blocks=32, **tier_kw)
    eng.warmup()
    rng = np.random.RandomState(SEED)
    pfx = _prompt(rng, cfg, 16)
    outs = []
    for i in range(4):
        p = np.concatenate([pfx, _prompt(rng, cfg, 4)])
        params = dict(max_new_tokens=8, seed=i)
        if i % 2:
            params.update(do_sample=True, temperature=0.8, top_k=16)
        r = eng.submit(p, **params)
        eng.run_until_idle(max_steps=2000)
        assert r.status == serving.RequestStatus.COMPLETED
        outs.append((p, params, np.asarray(r.result(timeout=5.0))))
        if evict_between:
            eng.prefix_cache.evict(100)  # LRU-evict every cached block
    st = eng.stats()
    eng.stop()
    return outs, st


class TestEngineParity:
    def test_bit_identical_tier_on_vs_off_and_vs_generate(self, tiny_model):
        model, cfg = tiny_model
        off, _ = _run_workload(model, cfg, kv_tier=False)
        on, st = _run_workload(model, cfg, kv_tier=True)
        for (p, params, a), (_, _, b) in zip(off, on):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(b, _ref(model, p, **params))
        tier = st["kv_tier"]
        assert tier["demoted_blocks"] > 0        # evictions demoted...
        assert tier["readmitted_blocks"] > 0     # ...and came back
        assert tier["readmitted_tokens"] >= 8
        assert tier["cost_model"]["decisions"]["readmit"] > 0

    def test_preempt_demote_resume_bit_identical(self, tiny_model):
        """A mid-decode preemption demotes the victim's private blocks;
        the resume prefill re-admits them (host tier) instead of
        recomputing — and the output stays bit-identical to generate,
        greedy AND sampled."""
        model, cfg = tiny_model
        eng = _engine(model, max_len=128, kv_tier=True,
                      kv_tier_host_blocks=64, prefix_caching=True)
        eng.warmup()
        rng = np.random.RandomState(SEED + 1)
        pa = _prompt(rng, cfg, 40)
        pb = _prompt(rng, cfg, 55)
        sb = dict(max_new_tokens=30, do_sample=True, top_k=8,
                  temperature=0.9, seed=7)
        ra = eng.submit(pa, max_new_tokens=40)
        rb = eng.submit(pb, **sb)
        while len(rb.output_tokens) < 16:
            eng.step()
        demoted0 = eng._tier.stats()["demoted_blocks"]
        with eng._step_lock:
            eng._preempt(rb.slot)
        st = eng._tier.stats()
        assert st["demoted_blocks"] > demoted0   # preempt-path demotion
        eng.run_until_idle(max_steps=5000)
        np.testing.assert_array_equal(
            np.asarray(ra.result(timeout=5.0)),
            _ref(model, pa, max_new_tokens=40))
        np.testing.assert_array_equal(
            np.asarray(rb.result(timeout=5.0)), _ref(model, pb, **sb))
        assert eng._tier.stats()["readmitted_blocks"] > 0
        eng.stop()

    def test_one_compile_zero_retrace_with_tier_on(self, tiny_model):
        model, cfg = tiny_model
        eng = _engine(model, kv_tier=True, kv_tier_host_blocks=32)
        info = eng.warmup()
        assert "serving.kv_demote" in info["entries"]
        assert "serving.kv_splice" in info["entries"]
        rng = np.random.RandomState(SEED + 2)
        pfx = _prompt(rng, cfg, 24)
        for wave in range(3):
            reqs = [eng.submit(
                np.concatenate([pfx, _prompt(rng, cfg, 3 + wave + i)]),
                max_new_tokens=3 + i % 3, do_sample=bool(i % 2), seed=i,
                top_k=5) for i in range(4)]
            eng.run_until_idle(max_steps=2000)
            assert all(r.status == serving.RequestStatus.COMPLETED
                       for r in reqs)
            eng.prefix_cache.evict(100)          # demote + readmit churn
        stats = recompile.entry_stats()
        for entry in ("serving.step", *eng._chunk_entries,
                      *eng._fused_entries,
                      "serving.kv_demote", "serving.kv_splice"):
            assert stats[entry]["retraces"] == 0, entry
        assert stats["serving.kv_demote"]["compiles"] >= 1
        assert stats["serving.kv_splice"]["compiles"] >= 1
        assert eng._tier.stats()["readmitted_blocks"] > 0
        eng.stop()

    def test_config_validation(self, tiny_model):
        model, _ = tiny_model
        with pytest.raises(ValueError, match="prefix_caching"):
            serving.ServingConfig(kv_tier=True, prefix_caching=False)
        with pytest.raises(ValueError, match="kv_tier_host_blocks"):
            serving.ServingConfig(kv_tier=True, kv_tier_host_blocks=0)

    def test_env_knob_resolution(self, monkeypatch, tmp_path):
        monkeypatch.setenv("PADDLE_TPU_KV_TIER", "1")
        monkeypatch.setenv("PADDLE_TPU_KV_TIER_PATH", str(tmp_path))
        monkeypatch.setenv("PADDLE_TPU_KV_TIER_HOST_GBPS", "7.5")
        cfg = serving.ServingConfig()
        assert cfg.kv_tier is True
        assert cfg.kv_tier_path == str(tmp_path)
        assert cfg.kv_tier_host_gbps == 7.5
        monkeypatch.setenv("PADDLE_TPU_KV_TIER", "0")
        assert serving.ServingConfig().kv_tier is False

    def test_stats_and_router_carry_tier_state(self, tiny_model):
        model, cfg = tiny_model
        eng = _engine(model, kv_tier=True)
        st = eng.stats()
        assert st["kv_tier"]["host_capacity"] > 0
        assert st["kv_tier"]["cost_model"]["decisions"] is not None
        router = serving.Router([eng])
        rep = router._replicas["r0"]
        router._refresh_load(rep, time.perf_counter() + 1e6)
        row = rep.row()
        assert row["load"]["kv_tier"]["host_capacity"] \
            == st["kv_tier"]["host_capacity"]
        router.stop()

    def test_tier_off_engine_has_no_tier(self, tiny_model):
        model, _ = tiny_model
        eng = _engine(model, kv_tier=False)
        assert eng._tier is None
        assert eng.stats()["kv_tier"] is None
        assert eng.prefix_cache.on_evict is None


# ---------------------------------------------------------------------------
# persistence across restarts (disk tier)
# ---------------------------------------------------------------------------


class TestPersistence:
    def test_restart_readmits_persisted_prefix_bit_identical(
            self, tiny_model, tmp_path):
        model, cfg = tiny_model
        d = str(tmp_path / "tier")
        out1, st1 = _run_workload(model, cfg, kv_tier=True, path=d)
        # stop() flushed the cache: committed entries on disk
        assert any(n.startswith("e_") for n in os.listdir(d))
        out2, st2 = _run_workload(model, cfg, kv_tier=True, path=d,
                                  evict_between=False)
        for (p, params, a), (_, _, b) in zip(out1, out2):
            np.testing.assert_array_equal(a, b)
        assert st2["kv_tier"]["disk"]["loads"] > 0
        assert st2["kv_tier"]["readmitted_blocks"] > 0

    def test_incompatible_fingerprint_skipped_not_trusted(
            self, tiny_model, tmp_path):
        d = str(tmp_path / "tier")
        store = DiskPrefixStore(d, fingerprint={"kv_format": "bf16"})
        store.put(b"\x01\x02", 8, _payload(1))
        other = DiskPrefixStore(d, fingerprint={"kv_format": "int8"})
        assert len(other) == 0
        assert other.incompatible_skipped == 1
        # the original fingerprint still sees it
        assert len(DiskPrefixStore(d, {"kv_format": "bf16"})) == 1

    def test_corrupt_spill_skipped_with_counted_warning(
            self, tiny_model, tmp_path):
        """Byte-flip a committed payload: the deep verify catches it at
        load, warns, counts, drops it from the index — and the ENGINE
        falls back to prefill recompute with a correct output."""
        model, cfg = tiny_model
        d = str(tmp_path / "tier")
        out1, _ = _run_workload(model, cfg, kv_tier=True, path=d)
        # flip a byte in every committed payload file
        for name in os.listdir(d):
            p = os.path.join(d, name)
            if not os.path.isdir(p):
                continue
            with open(os.path.join(p, "a0.bin"), "r+b") as f:
                b = bytearray(f.read())
                b[0] ^= 0xFF
                f.seek(0)
                f.write(b)
        with pytest.warns(UserWarning, match="corrupt spill"):
            out2, st2 = _run_workload(model, cfg, kv_tier=True, path=d,
                                      evict_between=False)
        for (p, params, a), (_, _, b) in zip(out1, out2):
            np.testing.assert_array_equal(a, b)   # recompute fallback
        assert st2["kv_tier"]["disk"]["corrupt_skipped"] > 0


# ---------------------------------------------------------------------------
# kill-mid-spill matrix (mirrors test_fault_tolerance's checkpoint matrix)
# ---------------------------------------------------------------------------


class TestKillMidSpillMatrix:
    """Inject a failure at every stage of the spill commit protocol;
    assert the store never serves a half-committed entry and restart
    scans re-admit only prior COMMITTED entries."""

    FP = {"v": 1}

    def _store_with_committed(self, root):
        store = DiskPrefixStore(root, fingerprint=self.FP)
        assert store.put(b"good", 8, _payload(1))
        return store

    def _assert_only_good_survives(self, root):
        """THE invariant: a fresh scan sees exactly the prior committed
        entry; every dir it trusts verifies deeply."""
        fresh = DiskPrefixStore(root, fingerprint=self.FP)
        assert len(fresh) == 1
        end, pay = fresh.get(b"good")
        assert end == 8
        np.testing.assert_array_equal(pay["0/k"], _payload(1)["0/k"])
        for name in os.listdir(root):
            p = os.path.join(root, name)
            if os.path.isdir(p) and ".tmp-" not in name:
                _atomic.verify_checkpoint(p, deep=True)

    def test_kill_at_tmp_write(self, tmp_path, monkeypatch):
        store = self._store_with_committed(str(tmp_path))

        def boom(*a, **k):
            raise OSError("disk full mid tmp write")

        import paddle_tpu.serving.kv_tier as kvt
        monkeypatch.setattr(kvt.json, "dump", boom)
        with pytest.raises(OSError):
            store.put(b"half", 8, _payload(2))
        monkeypatch.undo()
        assert store.end_for(b"half") is None
        self._assert_only_good_survives(str(tmp_path))

    def test_kill_at_fsync(self, tmp_path, monkeypatch):
        store = self._store_with_committed(str(tmp_path))

        def boom(path):
            raise OSError("killed at fsync")

        monkeypatch.setattr(_atomic, "_fsync_file", boom)
        with pytest.raises(OSError):
            store.put(b"half", 8, _payload(2))
        monkeypatch.undo()
        assert store.end_for(b"half") is None
        self._assert_only_good_survives(str(tmp_path))

    def test_kill_at_marker_write(self, tmp_path, monkeypatch):
        store = self._store_with_committed(str(tmp_path))
        # one put() does two json.dump calls: #1 is the entry's
        # meta.json (inside the scratch dir), #2 is commit_dir's
        # COMMITTED marker — fail exactly the marker write
        calls = {"n": 0}
        real = _atomic.json.dump

        def boom(obj, fh, **kw):
            calls["n"] += 1
            if calls["n"] >= 2:
                raise OSError("killed writing COMMITTED marker")
            return real(obj, fh, **kw)

        monkeypatch.setattr(_atomic.json, "dump", boom)
        with pytest.raises(OSError):
            store.put(b"half", 8, _payload(2))
        monkeypatch.undo()
        assert store.end_for(b"half") is None
        self._assert_only_good_survives(str(tmp_path))

    def test_kill_at_replace(self, tmp_path, monkeypatch):
        store = self._store_with_committed(str(tmp_path))

        def boom(src, dst):
            raise OSError("killed at atomic rename")

        monkeypatch.setattr(_atomic.os, "replace", boom)
        with pytest.raises(OSError):
            store.put(b"half", 8, _payload(2))
        monkeypatch.undo()
        assert store.end_for(b"half") is None
        self._assert_only_good_survives(str(tmp_path))

    def test_pre_rename_tmp_debris_swept_on_restart(self, tmp_path):
        root = str(tmp_path)
        self._store_with_committed(root)
        debris = os.path.join(root, "e_deadbeef.tmp-dead0")
        os.makedirs(debris)
        with open(os.path.join(debris, "a0.bin"), "wb") as f:
            f.write(b"half a block")
        self._assert_only_good_survives(root)
        assert not os.path.exists(debris)  # cleanup_stale_tmp swept it

    def test_missing_marker_skipped_with_counted_warning(self, tmp_path):
        root = str(tmp_path)
        store = self._store_with_committed(root)
        store.put(b"second", 8, _payload(3))
        victim = os.path.join(root, DiskPrefixStore._entry_dir(b"second"))
        os.remove(os.path.join(victim, _atomic.COMMITTED_MARKER))
        with pytest.warns(UserWarning, match="uncommitted/corrupt"):
            fresh = DiskPrefixStore(root, fingerprint=self.FP)
        assert fresh.end_for(b"second") is None
        assert fresh.end_for(b"good") == 8
        assert fresh.corrupt_skipped == 1

    def test_truncated_payload_caught_at_load(self, tmp_path):
        root = str(tmp_path)
        store = self._store_with_committed(root)
        victim = os.path.join(root, DiskPrefixStore._entry_dir(b"good"))
        with open(os.path.join(victim, "a0.bin"), "r+b") as f:
            f.truncate(4)
        with pytest.warns(UserWarning, match="corrupt spill"):
            assert store.get(b"good") is None
        assert store.end_for(b"good") is None  # dropped from the index
        assert store.corrupt_skipped == 1

    def test_put_is_idempotent_for_committed_keys(self, tmp_path):
        store = self._store_with_committed(str(tmp_path))
        assert store.put(b"good", 8, _payload(9)) is False
        assert store.spills == 1
