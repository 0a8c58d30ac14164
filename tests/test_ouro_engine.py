"""Ouro (a looped stack, ``test_ouro.py``) through ``ServingEngine``:
one engine for the module with prefix caching on, a shared prefix, a COW
fork, spare prefill rows and a forced preemption and resume, held to the
plain reference's greedy tokens; the pool's bytes at T * L planes a
position; the ``ut_steps`` arg and the ``loop_passes`` counter; and the
options the engine refuses to combine with a loop. Float32, tiny sizes,
T = 3."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.generation import generate_uncached, make_kv_caches
from paddle_tpu.models import GPTConfig
from paddle_tpu.observability import tracing
from perfbench.references import ouro as ref
from test_ouro import L, SIZES, T, build, engine_for, tokens


@pytest.fixture(scope="module")
def pair():
    return build()


def is_greedy(params, prompt, out):
    """The reference's forward over prompt and answer (padded to one
    length, so one program): every emitted token is pass T's best at
    the position before it."""
    ids = np.zeros(96, np.int32)
    n = len(prompt) + len(out)
    ids[:n] = np.concatenate([prompt, np.asarray(out, np.int32)])
    lg = np.asarray(ref.logit_rows(params, jnp.asarray(ids), 0, 96, SIZES))
    return lg.argmax(-1)[len(prompt) - 1:n - 1].tolist() == list(out)


@pytest.fixture(scope="module")
def served(pair):
    """One engine for the module, prefix cache on, a pool too small for
    three requests at their peaks: what it served and what it counted."""
    model, params = pair
    eng = engine_for(model, slots=3, max_len=96, num_blocks=30,
                     prefix_caching=True)
    tracing.enable_tracing()
    tracing.clear()
    first = tokens(25, seed=11)
    done = eng.submit(first, max_new_tokens=6)
    eng.run_until_idle()
    # the same prompt again (every block adopted, the last one forked on
    # its first write), one that shares five blocks, and two of their own
    prompts = [first, np.concatenate([first[:20], tokens(9, seed=12)]),
               tokens(30, seed=13), tokens(27, seed=14)]
    n_new = [6, 30, 34, 28]
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, n_new)]
    eng.run_until_idle()
    events = tracing.events(trace="engine", name="engine.dispatch")
    return dict(model=model, params=params, eng=eng, prompts=[first] + prompts,
                reqs=[done] + reqs, n_new=[6] + n_new, dispatches=events)


def test_the_engine_returns_the_uncached_forwards_tokens(served):
    for p, r, n in zip(served["prompts"], served["reqs"], served["n_new"]):
        assert r.status == "completed" and len(r.output_tokens) == n
        assert is_greedy(served["params"], p, r.output_tokens)
    first = served["prompts"][0]
    want = np.asarray(generate_uncached(
        served["model"], paddle.to_tensor(first[None]), 3)._data)[0, 25:]
    assert list(served["reqs"][0].output_tokens)[:3] == want.tolist()
    assert list(served["reqs"][1].output_tokens) \
        == list(served["reqs"][0].output_tokens)


def test_prefix_hits_a_fork_spare_rows_and_a_preemption_all_happened(served):
    eng = served["eng"]
    c = eng.counters()
    assert c["prefix_hit_tokens"] >= 24 + 20
    assert eng.pool.stats()["cow_forks"] >= 1
    assert c["prefill_fill_rows"] >= 1          # a slot's second chunk rode
    assert c["preemptions"] >= 1
    assert c["steps_ahead"] > 0
    # nothing is held once everything ended but what the prefix cache keeps
    assert eng.busy_slots() == 0 and not eng.has_work()


def test_the_pools_bytes_count_every_pass_and_layer(served):
    eng = served["eng"]
    per_token = T * L * 2 * SIZES["hidden_size"] * 4        # float32
    assert eng._kv_bytes_per_token == per_token
    st = eng.stats()
    assert st["kv_blocks"]["bytes_per_token"] == per_token
    assert st["kv_blocks"]["num_blocks"] == 30       # blocks, not planes
    total = sum(arr.nbytes for c in eng._pools for arr in c.values())
    assert total == 30 * 4 * per_token                # 30 blocks of 4
    assert len(eng._pools) == L


def test_dispatch_spans_carry_the_passes_and_the_counter_adds_up(served):
    events = served["dispatches"]
    assert events and all(e["args"]["ut_steps"] == T for e in events)
    assert set(events[0]["args"]) == {"iter", "kv_blocks", "ahead",
                                      "fused", "prefill_rows", "ut_steps"}
    # a looped stack keeps its pair of programs an iteration
    assert not any(e["args"]["fused"] or e["args"]["prefill_rows"]
                   for e in events)
    c = served["eng"].counters()
    assert c["steps_fused"] == 0
    assert c["loop_passes"] == T * len(events)
    assert c["loop_passes"] >= T * c["steps"]


def test_an_engine_of_a_plain_stack_names_no_passes():
    from paddle_tpu.models import GPTForCausalLM

    eng = serving.ServingEngine(GPTForCausalLM(GPTConfig.tiny()),
                                max_slots=1, max_len=32, block_size=4)
    assert "loop_passes" not in eng.counters()
    assert eng._ut_steps == 1


REFUSED = {
    "kv_tier": (dict(prefix_caching=True, kv_tier=True), "kv_tier"),
    "tp2": (dict(tp=2), "tp=2"),
    "int8_pool": (dict(kv_format="int8"), "kv_format"),
}


@pytest.mark.parametrize("options,said", REFUSED.values(), ids=REFUSED.keys())
def test_what_nobody_made_work_with_a_loop_is_refused_with_a_sentence(
        pair, options, said):
    with pytest.raises(ValueError, match=f"looped stack.*{said}"):
        serving.ServingEngine(pair[0], serving.ServingConfig(
            max_slots=1, max_len=32, block_size=4, **options))


def test_a_draft_model_and_a_lower_threshold_are_refused(pair):
    cfg = serving.ServingConfig(max_slots=1, max_len=32, block_size=4)
    with pytest.raises(ValueError, match="looped stack.*draft_model"):
        serving.ServingEngine(pair[0], cfg, draft_model=pair[0])
    early, _ = build(early_exit_threshold=0.5)
    with pytest.raises(ValueError, match="looped stack.*early_exit_threshold"):
        serving.ServingEngine(early, cfg)
    ids = paddle.to_tensor(tokens(5)[None])
    with pytest.raises(ValueError, match="runs every pass"):
        early(ids, kv_caches=make_kv_caches(early.config, 1, 8, jnp.float32))


def test_a_freed_engine_goes_without_the_collector():
    """The benchmark frees the program and makes the reference's float32
    weights at once: engine, model and weights have to go by reference
    count when the last name does. An executable of the engine's that
    held the engine itself (``_cow`` once read ``self._ut_steps``) kept
    all three alive until the collector next ran: 2.9 to 5.4 GB "still
    in use" in every served cell (PERF.md section 6, PR 37)."""
    import gc
    import weakref

    from perfbench.programs import gpt_engine

    model, _ = build()
    eng = engine_for(model, slots=2)
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
