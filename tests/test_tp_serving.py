"""Tensor-parallel sharded serving: one model spanning devices.

Oracles:
- RULE TABLE: ``distributed/partition.py`` rule matching reproduces the
  Megatron layout the ad-hoc ``llama_shard_fn`` placements encode —
  column-parallel q/k/v/gate/up, row-parallel o/down, vocab-parallel
  embeddings — proved by cross-checking the two on the real tiny-llama
  parameter names.
- OUTPUT PARITY: a ``tp=2`` (and ``tp=4``) engine produces EXACTLY the
  tokens the ``tp=1`` engine produces for the same prompts + seeds —
  greedy and sampled, speculative decoding, quantized KV blocks, and
  preemption-by-recompute included. The psum reduction order perturbs
  logits at float epsilon; token streams must still be bit-identical.
- ONE EXECUTABLE: with tp>1 the pool-wide decode step and the [1, C]
  prefill chunk each compile exactly once across ≥3 ragged waves —
  explicit in/out shardings keep the round-tripped pool layouts a
  fixpoint (no call-two retrace).
- WARMUP: ``engine.warmup()`` on a tp>1 engine AOT-compiles every
  sharded executable; the first request after it triggers ZERO compiles
  (the replacement-TP-replica boot path under the router).

The host-side mesh comes from conftest.py: 8 virtual XLA:CPU devices,
so tp=2/tp=4 run in the normal CPU test lane.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import generation, serving
from paddle_tpu.distributed import partition
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                               LlamaForCausalLM)
from paddle_tpu.observability import perf, recompile

SEED = 4321


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    cfg = LlamaConfig.tiny(max_position_embeddings=256)
    return LlamaForCausalLM(cfg), cfg


@pytest.fixture(scope="module")
def tiny_gpt():
    paddle.seed(1)
    cfg = GPTConfig.tiny()
    return GPTForCausalLM(cfg), cfg


@pytest.fixture(scope="module")
def draft_model(tiny_model):
    _, cfg = tiny_model
    paddle.seed(99)
    return LlamaForCausalLM(cfg)


def _prompt(rng, cfg, n):
    return rng.randint(1, cfg.vocab_size, n).astype("int32")


def _run_engine(model, prompts, specs, tp, draft=None, **cfg_kw):
    cfg_kw.setdefault("max_len", 128)
    eng = serving.ServingEngine(model, draft_model=draft, max_slots=3,
                                tp=tp, **cfg_kw)
    reqs = [eng.submit(p, **s) for p, s in zip(prompts, specs)]
    eng.run_until_idle(max_steps=5000)
    outs = []
    for r in reqs:
        assert r.status == serving.RequestStatus.COMPLETED
        outs.append(np.asarray(r.result(timeout=1.0)))
    return outs, eng


# ---------------------------------------------------------------------------
# rule matching
# ---------------------------------------------------------------------------


class TestPartitionRules:
    def test_llama_rules_match_expected_layout(self, tiny_model):
        from jax.sharding import PartitionSpec as PS
        model, _ = tiny_model
        params = {k: v._data for k, v in model.named_parameters_dict().items()}
        specs = partition.match_partition_rules(
            partition.LLAMA_PARTITION_RULES(), params)
        assert set(specs) == set(params)
        for name, spec in specs.items():
            if any(k in name for k in ("q_proj", "k_proj", "v_proj",
                                       "gate_proj", "up_proj")):
                assert spec == PS(None, "tp"), name
            elif any(k in name for k in ("o_proj", "down_proj")):
                assert spec == PS("tp", None), name
            elif "embed_tokens" in name:
                assert spec == PS("tp", None), name
            elif "lm_head" in name:
                assert spec == PS(None, "tp"), name
            else:  # norms and any scalar: replicated
                assert spec == PS(), name

    def test_rules_agree_with_legacy_llama_shard_fn(self, tiny_model):
        """The rule table is the unification of the ad-hoc shard fns:
        on every real tiny-llama parameter the regex table must place
        the SAME axis ``llama_shard_fn``'s substring matching shards."""
        from paddle_tpu.models.llama import llama_shard_fn  # noqa: F401
        from jax.sharding import PartitionSpec as PS
        model, _ = tiny_model
        params = {k: v._data for k, v in model.named_parameters_dict().items()}
        specs = partition.match_partition_rules(
            partition.LLAMA_PARTITION_RULES(), params)
        for name, spec in specs.items():
            if not name.endswith("weight") or param_ndim(params[name]) != 2:
                continue
            layer = name.rsplit(".", 1)[0]
            col = any(k in layer for k in ("q_proj", "k_proj", "v_proj",
                                           "gate_proj", "up_proj"))
            row = any(k in layer for k in ("o_proj", "down_proj"))
            if col:        # Shard(1) in llama_shard_fn == PS(None, tp)
                assert spec == PS(None, "tp"), name
            elif row:      # Shard(0) == PS(tp, None)
                assert spec == PS("tp", None), name
            elif "lm_head" in layer:   # Shard(1)
                assert spec == PS(None, "tp"), name
            elif "embed_tokens" in layer:  # Shard(0) on vocab rows
                assert spec == PS("tp", None), name

    def test_gpt_rules_cover_all_params(self, tiny_gpt):
        from jax.sharding import PartitionSpec as PS
        model, _ = tiny_gpt
        params = {k: v._data for k, v in model.named_parameters_dict().items()}
        specs = partition.match_partition_rules(
            partition.GPT_PARTITION_RULES(), params)
        assert set(specs) == set(params)
        # biases of column-parallel projections shard with the out dim
        for name, spec in specs.items():
            if "q_proj.bias" in name or "fc_in.bias" in name:
                assert spec == PS("tp"), name
            if "out_proj.bias" in name or "fc_out.bias" in name:
                assert spec == PS(), name  # row-parallel bias replicated

    def test_first_match_wins_and_catchall(self):
        from jax.sharding import PartitionSpec as PS
        rules = [("a/weight", PS("tp")), (".*", PS())]
        specs = partition.match_partition_rules(
            rules, {"x.a.weight": np.zeros((4,)),
                    "x.b.weight": np.zeros((4,))})
        assert specs["x.a.weight"] == PS("tp")
        assert specs["x.b.weight"] == PS()

    def test_unknown_architecture_raises(self):
        with pytest.raises(ValueError, match="partition rule table"):
            partition.partition_rules_for("resnet50")

    def test_validate_tp_rejects_nondividing(self, tiny_model):
        _, cfg = tiny_model
        # tiny llama has 2 kv heads: tp=4 can't split the KV pools
        with pytest.raises(ValueError, match="tp"):
            partition.validate_tp(cfg, 4)
        partition.validate_tp(cfg, 2)  # divides everything

    def test_tp_mesh_rejects_too_few_devices(self):
        with pytest.raises(ValueError, match="devices"):
            partition.tp_mesh(1024)

    def test_serving_config_validation(self, tiny_model):
        model, _ = tiny_model
        with pytest.raises(ValueError, match="tp"):
            serving.ServingConfig(tp=0)
        with pytest.raises(ValueError, match="tp"):
            serving.ServingEngine(model, max_slots=2, max_len=64, tp=4)


def param_ndim(arr):
    return getattr(arr, "ndim", len(getattr(arr, "shape", ())))


# ---------------------------------------------------------------------------
# output parity: tp=N engine == tp=1 engine, bit for bit
# ---------------------------------------------------------------------------


class TestTpParity:
    def test_tp2_greedy_and_sampled_match_tp1(self, tiny_model):
        model, cfg = tiny_model
        rng = np.random.RandomState(SEED)
        prompts = [_prompt(rng, cfg, n) for n in (5, 11, 3)]
        specs = [dict(max_new_tokens=8),
                 dict(max_new_tokens=10, do_sample=True, temperature=0.8,
                      top_k=8, seed=5),
                 dict(max_new_tokens=6, do_sample=True, top_p=0.9, seed=9)]
        ref, _ = _run_engine(model, prompts, specs, tp=1)
        got, eng = _run_engine(model, prompts, specs, tp=2)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        assert eng.stats()["tp"] == 2

    def test_tp4_gpt_matches_tp1(self, tiny_gpt):
        """tp=4 on the GPT tiny (4 heads, no GQA) — learned position
        embeddings and biased projections through the same rule table."""
        model, cfg = tiny_gpt
        rng = np.random.RandomState(SEED + 1)
        prompts = [_prompt(rng, cfg, n) for n in (4, 9)]
        specs = [dict(max_new_tokens=6),
                 dict(max_new_tokens=7, do_sample=True, temperature=1.1,
                      top_k=12, seed=3)]
        ref, _ = _run_engine(model, prompts, specs, tp=1, max_len=64)
        got, _ = _run_engine(model, prompts, specs, tp=4, max_len=64)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)

    def test_tp2_quantized_kv_matches_tp1(self, tiny_model):
        model, cfg = tiny_model
        rng = np.random.RandomState(SEED + 2)
        prompts = [_prompt(rng, cfg, n) for n in (6, 13)]
        specs = [dict(max_new_tokens=8),
                 dict(max_new_tokens=8, do_sample=True, top_k=8, seed=7)]
        ref, _ = _run_engine(model, prompts, specs, tp=1, kv_format="int8")
        got, _ = _run_engine(model, prompts, specs, tp=2, kv_format="int8")
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)

    def test_tp2_spec_decode_matches_tp1(self, tiny_model, draft_model):
        model, cfg = tiny_model
        rng = np.random.RandomState(SEED + 3)
        prompts = [_prompt(rng, cfg, n) for n in (5, 9)]
        specs = [dict(max_new_tokens=10),
                 dict(max_new_tokens=10, do_sample=True, temperature=0.9,
                      top_k=8, seed=11)]
        ref, _ = _run_engine(model, prompts, specs, tp=1,
                             draft=draft_model, spec_k=3)
        got, _ = _run_engine(model, prompts, specs, tp=2,
                             draft=draft_model, spec_k=3)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)

    def test_tp2_preemption_resume_matches_tp1(self, tiny_model):
        """An oversubscribed pool forces preemption-by-recompute; the
        replayed PRNG chain and re-prefilled blocks must land the tp=2
        engine on the exact tp=1 token streams."""
        model, cfg = tiny_model
        rng = np.random.RandomState(SEED + 4)
        prompts = [_prompt(rng, cfg, n) for n in (40, 55, 33)]
        specs = [dict(max_new_tokens=25),
                 dict(max_new_tokens=25, do_sample=True, top_k=8,
                      temperature=0.9, seed=7),
                 dict(max_new_tokens=25)]
        ref, _ = _run_engine(model, prompts, specs, tp=1, num_blocks=13)
        got, eng = _run_engine(model, prompts, specs, tp=2, num_blocks=13)
        assert eng._preempt_count >= 1
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)

    def test_generate_tp_oracle_matches_tp1(self, tiny_model):
        """Offline generate(tp=2): same contract as kv_format= /
        draft_model= — an oracle flag, bit-identical output."""
        model, cfg = tiny_model
        rng = np.random.RandomState(SEED + 5)
        p = _prompt(rng, cfg, 7)
        for kw in (dict(max_new_tokens=10),
                   dict(max_new_tokens=10, do_sample=True, temperature=0.8,
                        top_k=8, seed=5),
                   dict(max_new_tokens=8, loop_mode="python")):
            a = generation.generate(model, p[None], **kw).numpy()
            b = generation.generate(model, p[None], tp=2, **kw).numpy()
            np.testing.assert_array_equal(a, b)

    def test_generate_tp_rejects_draft_model(self, tiny_model, draft_model):
        model, cfg = tiny_model
        rng = np.random.RandomState(SEED + 6)
        p = _prompt(rng, cfg, 5)
        with pytest.raises(ValueError, match="tp"):
            generation.generate(model, p[None], max_new_tokens=4,
                                draft_model=draft_model, tp=2)


# ---------------------------------------------------------------------------
# one-compile invariant under tp
# ---------------------------------------------------------------------------


class TestTpOneCompile:
    def test_one_decode_step_compile_across_ragged_waves(self, tiny_model):
        """3 waves of ragged requests through ONE tp=2 engine: exactly
        one ``serving.step`` compile and one ``serving.prefill_chunk``
        compile — the explicit in/out shardings keep every round-tripped
        pool layout identical call-to-call (no GSPMD re-layout retrace)."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=3, max_len=128, tp=2)
        rng = np.random.RandomState(SEED + 7)

        def wave(lens, new):
            reqs = [eng.submit(_prompt(rng, cfg, n), max_new_tokens=new)
                    for n in lens]
            eng.run_until_idle(max_steps=5000)
            return reqs

        before = {k: (v["compiles"], v["retraces"])
                  for k, v in recompile.entry_stats().items()}
        wave((5, 11, 3), 6)
        wave((17, 2), 5)
        wave((9, 23, 7), 8)
        after = recompile.entry_stats()
        for entry in ("serving.step", "serving.prefill_chunk"):
            b = before.get(entry, (0, 0))
            assert after[entry]["compiles"] - b[0] == 1, entry
            assert after[entry]["retraces"] - b[1] == 0, entry

    def test_warmup_zero_compiles_on_first_request(self, tiny_model):
        """The replacement-replica boot path: warmup() AOT-compiles the
        sharded executables; the first real request is compile-free."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64, tp=2)
        info = eng.warmup()
        assert info["compiles"] >= 2
        rng = np.random.RandomState(SEED + 8)
        before = recompile.total_compiles()
        r = eng.submit(_prompt(rng, cfg, 6), max_new_tokens=5)
        eng.run_until_idle(max_steps=2000)
        assert r.status == serving.RequestStatus.COMPLETED
        assert recompile.total_compiles() - before == 0


# ---------------------------------------------------------------------------
# per-shard observability
# ---------------------------------------------------------------------------


class TestTpObservability:
    def test_ledger_rows_carry_mesh_and_hbm_divides(self, tiny_model):
        model, cfg = tiny_model
        assert perf.perf_enabled()
        eng = serving.ServingEngine(model, max_slots=2, max_len=64, tp=2)
        rng = np.random.RandomState(SEED + 9)
        r = eng.submit(_prompt(rng, cfg, 5), max_new_tokens=4)
        eng.run_until_idle(max_steps=2000)
        assert r.status == serving.RequestStatus.COMPLETED

        row = perf.ledger_entry("serving.step")
        assert row is not None and row["mesh"] == {"tp": 2}
        if row.get("flops"):  # cost analysis is per-DEVICE (GSPMD
            # captures the partitioned module); mesh_flops is the
            # whole-mesh total
            assert row["mesh_flops"] == row["flops"] * 2

        comps = perf.hbm_ledger()["components"]
        kv = comps["serving_kv_pool"]
        assert kv["tp"] == 2
        assert kv["bytes_per_device"] == kv["bytes"] // 2
        wt = comps["serving_model_weights"]
        # column/row-sharded weights: per-device strictly below total
        assert wt["bytes_per_device"] < wt["bytes"]

    def test_stats_surface_tp(self, tiny_model):
        model, _ = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64, tp=2)
        assert eng.stats()["tp"] == 2
        eng1 = serving.ServingEngine(model, max_slots=2, max_len=64)
        assert eng1.stats()["tp"] == 1
