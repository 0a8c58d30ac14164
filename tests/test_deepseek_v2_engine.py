"""DeepSeek-V2 (``test_deepseek_v2.py``) through ``ServingEngine``:
chunked prefill then decode through the latent pool against the plain
reference's full forward on logits, with prompts that span several
chunks and blocks, under a forced preemption and resume, with decode
rows and prefill rows in one iteration (the fused step), with the prefix
cache on; the routing counts in the spans and the counters; the pool's
bytes and the gauge; the options the engine refuses over a latent
cache; and a freed engine leaving nothing behind. Float32, tiny sizes,
share 1 of 4."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.observability import tracing
from perfbench.references import deepseek_v2 as ref
from test_deepseek_v2 import build, sizes, tokens

CFG = sizes(1, 4)


def engine_for(model, **kw):
    how = dict(max_slots=3, max_len=96, block_size=8, prefill_chunk=8)
    how.update(kw)
    return serving.ServingEngine(model, **how)


@pytest.fixture(scope="module")
def pair():
    return build(ep_rank=1, ep_size=4)


def gaps(params, prompt, out):
    """How far each emitted token's logit lies below the reference's
    best at the position before it (0: the reference's own choice)."""
    ids = np.zeros(96, np.int32)
    n = len(prompt) + len(out)
    ids[:n] = np.concatenate([prompt, np.asarray(out, np.int32)])
    lg = np.asarray(ref.logit_rows(params, jnp.asarray(ids), 0, 96, CFG))
    rows = lg[len(prompt) - 1:n - 1]
    return rows.max(-1) - rows[np.arange(len(out)), out]


@pytest.fixture(scope="module")
def served(pair):
    """One engine for the module, prefix cache on, a pool too small for
    three requests at their peaks: what it served and what it counted."""
    model, params = pair
    eng = engine_for(model, num_blocks=19, prefix_caching=True)
    tracing.enable_tracing()
    tracing.clear()
    first = tokens(27, seed=11)
    done = eng.submit(first, max_new_tokens=6)
    eng.run_until_idle()
    # one that shares three blocks with the first, and two of their own
    # that need more blocks than the pool has left at their peaks
    prompts = [np.concatenate([first[:24], tokens(9, seed=12)]),
               tokens(45, seed=13), tokens(38, seed=14)]
    n_new = [20, 34, 30]
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, n_new)]
    eng.run_until_idle()
    return dict(params=params, eng=eng, prompts=[first] + prompts,
                reqs=[done] + reqs, n_new=[6] + n_new,
                dispatches=tracing.events(trace="engine",
                                          name="engine.dispatch"),
                prefills=tracing.events(trace="engine",
                                        name="engine.prefill"))


def test_every_request_got_the_references_greedy_tokens(served):
    for req, prompt, n in zip(served["reqs"], served["prompts"],
                              served["n_new"]):
        assert req.status == "completed" and len(req.output_tokens) == n
        assert gaps(served["params"], prompt, req.output_tokens).max() < 1e-4


def test_chunks_blocks_a_preemption_a_prefix_hit_and_fused_steps_all_happened(
        served):
    c = served["eng"].counters()
    assert served["eng"]._fuses
    assert c["preemptions"] >= 1
    assert c["prefix_hit_tokens"] >= 24
    # prompts of 27 to 45 tokens in chunks of 8 over blocks of 8
    assert c["prefill_rows"] >= sum(-(-len(p) // 8)
                                    for p in served["prompts"][:1])
    assert c["steps_fused"] >= 1
    fused = [e["args"] for e in served["dispatches"] if e["args"]["fused"]]
    assert fused and all(a["prefill_rows"] >= 1 for a in fused)


def test_the_spans_and_the_counters_tell_the_routing(served):
    c = served["eng"].counters()
    # every program's counts were read: three experts a token in each of
    # two expert layers, a quarter of the experts held
    assert c["expert_pairs"] % 6 == 0 and c["expert_pairs"] > 0
    assert c["expert_pairs_here"] + c["expert_pairs_absent"] \
        == c["expert_pairs"]
    assert 0.1 < c["expert_pairs_here"] / c["expert_pairs"] < 0.45
    assert c["route_programs"] >= c["steps"]
    assert 0 < c["experts_touched"] <= 8 * c["route_programs"]
    told = [e["args"] for e in served["dispatches"]
            if "expert_pairs" in e["args"]]
    assert len(told) >= len(served["dispatches"]) - 4
    for a in told:
        assert 0 <= a["expert_pairs"] <= 6 * (3 + 3 * 8)
        assert 0 <= a["experts_touched"] <= 8
    assert any("expert_pairs" in e["args"] for e in served["prefills"])
    # the spans' sums are the counters', step programs and prefill
    # programs together
    summed = sum(e["args"].get("expert_pairs", 0)
                 for e in served["dispatches"] + served["prefills"])
    assert 0 < summed <= c["expert_pairs_here"]


@pytest.mark.parametrize("kernels", ["0", "1"])
def test_a_lone_prompt_in_one_chunk_and_one_by_one(pair, kernels,
                                                   monkeypatch):
    """The same prompt through chunks of 32 (one chunk, decompressed by
    the shapes' rule: 16 rows or more share their positions) and of 4
    (absorbed): the same tokens, through XLA and through the paged
    kernel (interpreted; it attends both chunk lengths absorbed)."""
    monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", kernels)
    model, params = pair
    prompt = tokens(30, seed=21)
    outs = []
    for chunk in (32, 4):
        eng = engine_for(model, max_slots=2, prefill_chunk=chunk)
        req = eng.submit(prompt, max_new_tokens=8)
        eng.run_until_idle()
        assert gaps(params, prompt, req.output_tokens).max() < 1e-4
        outs.append(list(req.output_tokens))
    assert outs[0] == outs[1]


def test_the_pool_and_the_gauge_count_the_latent(pair):
    model, _ = pair
    eng = engine_for(model, num_blocks=24)
    # 16 + 4 float32 values a position in each of 3 layers
    assert eng._kv_bytes_per_token == 20 * 4 * 3
    assert eng.stats()["kv_bytes_per_token"] == 240 \
        if "kv_bytes_per_token" in eng.stats() else True
    assert [list(c) for c in eng._pools] == [["c"]] * 3
    assert eng._pools[0]["c"].shape == (24, 8, 128)
    from paddle_tpu import observability
    fam = observability.snapshot()["metrics"]["paddle_tpu_kv_bytes_per_token"]
    assert 240.0 in [s["value"] for s in fam["samples"]]


@pytest.mark.parametrize("bad, why", [
    (dict(tp=2), "tp=2"), (dict(kv_format="int8"), "kv_format='int8'"),
    (dict(kv_tier=True), "kv_tier=True")])
def test_what_nobody_has_tested_over_a_latent_cache_is_refused(pair, bad,
                                                               why):
    model, _ = pair
    with pytest.raises(ValueError, match="latent .MLA. cache cannot be "
                       "served with " + why):
        engine_for(model, **bad)


def test_a_draft_model_is_refused(pair):
    model, _ = pair
    with pytest.raises(ValueError, match="a draft_model"):
        serving.ServingEngine(model, draft_model=model, max_slots=2,
                              max_len=96, block_size=8, prefill_chunk=8)


def test_a_freed_engine_goes_without_the_collector():
    """As ``test_ouro_engine.py``'s: engine, model and weights go by
    reference count when the benchmark frees the program."""
    import gc
    import weakref

    from perfbench.programs import gpt_engine

    model, _ = build(ep_rank=1, ep_size=4)
    eng = engine_for(model, max_slots=2)
    eng.submit(tokens(9), max_new_tokens=3)
    eng.run_until_idle()
    alive = [weakref.ref(model), weakref.ref(eng),
             weakref.ref(next(iter(model.parameters())))]
    gc.collect()
    gc.disable()
    try:
        gpt_engine.free(eng)
        del eng, model
        assert [r() is None for r in alive] == [True, True, True]
    finally:
        gc.enable()
