"""The Ouro cell (a looped stack: its layers run ``total_ut_steps`` times
a token, a K/V plane for every pass and layer) at tiny sizes on the CPU:
the run end to end (``correct``, the int8 control failing its limits,
the passes read from the dispatch spans), an answer longer than the 256
rows ``logit_gaps`` asks for, ``ops_loop``'s counts against
``ops_bytes``'s, and the two new readers on synthetic facts. The tiny
checkout gets the cell from ``perfbench_tiny_ouro`` (no file the
benchmark already had is edited); the synthetic rings are
``test_perfbench_spans``'s. Times read here mean nothing."""

import json
import os

import numpy as np
import pytest

import perfbench_tiny as tiny
import perfbench_tiny_evabyte as tiny_eva
import perfbench_tiny_ouro as tiny_ouro
import test_perfbench_spans as base

from perfbench import limits as limits_tool
from perfbench import manifest, ops_bytes, ops_loop, run
from perfbench.drivers import serve
from perfbench.references import ouro as ref
from test_perfbench_spans import (man, no_persistent_cache,  # noqa: F401
                                  ring, root)   # (fixtures)

CELL = tiny_ouro.TINY_CELL
REAL = manifest.Manifest(tiny.REPO)
OURO = [m["name"] for m in REAL.data["per_layer"]
        if m["name"].endswith(".ouro")]
PUBLISHED = dict(hidden_size=2048, intermediate_size=5632,
                 num_hidden_layers=48, num_attention_heads=16,
                 num_key_value_heads=16, vocab_size=49152, total_ut_steps=4)


@pytest.fixture(scope="module")
def ouro_run(root):
    return run.run_cell(root, CELL, 2147483931, 3, 0, on_chip=False)


# -- the manifest ---------------------------------------------------------------

def test_the_tiny_checkout_holds_the_sixth_cell(root):
    assert manifest.problems(root) == []
    man_ = manifest.Manifest(root)
    assert man_.cell(CELL)["config"] == "tiny-ouro"
    cfg = man_.config("tiny-ouro")
    assert cfg["total_ut_steps"] == 3 and cfg["early_exit_threshold"] == 1
    assert cfg["dtype"] == "float32" and cfg["program"] == "ouro_engine"
    # what test_perfbench_evabyte.py asserts beside its count of three
    # configurations (tests/conftest.py says why that test is marked)
    assert {c["name"] for c in man_.data["configs"]} == {
        "tiny-gpt", "tiny-mistral", "tiny-evabyte", "tiny-ouro"}
    eva = man_.config("tiny-evabyte")
    assert eva["window_size"] == 64 and eva["chunk_size"] == 16
    assert man_.traffic("tiny-doc-bytes")["prompt_quantiles"][0][1] \
        > 3 * eva["window_size"]


def test_the_benchmark_gained_one_configuration_and_one_cell_on_one_chip(root):
    """Six cells, one of them on four chips, the new one last; and what
    ``test_perfbench_evabyte.py`` asserts beside its count of five
    (marked in ``tests/conftest.py``): only ``train_tok_s`` and the
    collectives' share list the four-chip cell without a ``.dp2mp2``
    twin, and the tiny checkout holds each cell once."""
    real = REAL.data
    assert manifest.problems(tiny.REPO) == []
    cells = [w["name"] for w in real["workloads"]]
    assert len(cells) == 6 and cells[-2:] == [tiny_eva.CELL, tiny_ouro.CELL]
    assert sum(w["chips"] == 4 for w in real["workloads"]) == 1
    assert REAL.cell(tiny_ouro.CELL)["chips"] == 1
    listing = [m["name"] for m in real["end_to_end"] + real["per_layer"]
               if base.NEW_CELL in m.get("workloads", [])
               and not m["name"].endswith(".dp2mp2")]
    assert listing == ["train_tok_s", "collective_exposed_share"]
    names = [w["name"] for w in manifest.Manifest(root).data["workloads"]]
    assert len(names) == len(set(names)) == len(cells)
    assert names.count(tiny.TRAIN4) == names.count(CELL) \
        == names.count(tiny_eva.TINY_CELL) == 1


def test_the_configuration_is_the_published_one_whole():
    cfg = REAL.config("ouro-2p6b")
    entry = next(c for c in REAL.data["configs"] if c["name"] == "ouro-2p6b")
    assert entry["reduced"] == [] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and "Ouro-2.6B" in cfg["source"]
    for key, value in PUBLISHED.items():
        assert cfg[key] == value
    assert cfg["head_dim"] == 128 and cfg["early_exit_threshold"] == 1
    assert cfg["rope_theta"] == 1000000 and cfg["rms_norm_eps"] == 1e-6
    assert cfg["max_position_embeddings"] == 65536
    assert cfg["tie_word_embeddings"] is False
    assert cfg["layer_types"] == ["full_attention"] * 48
    assert {"norm placement", "final norm", "gate", "cache planes",
            "attention"} <= set(cfg["assumed"])
    sv = cfg["serving"]
    # every slot can reach its longest request at once
    assert sv["max_slots"] * sv["max_len"] == sv["num_blocks"] * 16
    tr = REAL.traffic("reason-closed")
    assert tr["clients"] == sv["max_slots"] and tr["kind"] == "closed"
    assert tr["prompt_quantiles"][-1][1] + tr["output_quantiles"][-1][1] \
        == sv["max_len"]


def test_every_ouro_metric_is_data_beside_the_accepted_ones():
    """Sixteen entries at the end of ``per_layer``, in the order they
    were added, each listing the one cell and moving ``serve_tok_s``,
    each with a metric file whose reader exists."""
    per_layer = REAL.data["per_layer"]
    assert [m["name"] for m in per_layer[-len(OURO):]] == OURO
    assert len(OURO) == 16
    assert {"loop_passes_per_step.ouro", "loop_attn_roofline.ouro",
            "loop_step_roofline.ouro"} <= set(OURO)
    for m in per_layer[-len(OURO):]:
        assert m["workloads"] == [tiny_ouro.CELL]
        assert m["moves"] == "serve_tok_s"
        mf = REAL.metric_file(m["name"])
        assert mf["name"] == m["name"] and mf["unit"] == m["unit"]
        assert os.path.isfile(os.path.join(
            tiny.REPO, "perfbench", "readers", mf["reader"] + ".py"))
    serve_cells = next(m["workloads"] for m in REAL.data["end_to_end"]
                       if m["name"] == "serve_tok_s")
    assert serve_cells[-1] == tiny_ouro.CELL and len(serve_cells) == 3


# -- the run --------------------------------------------------------------------

def test_the_cell_runs_and_is_correct(ouro_run):
    res, ctx = ouro_run
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tok_s", "setup_s"}
    facts = ctx["facts"]
    assert facts["compiles_in_window"] == 0
    a, b = facts["engine_start"], facts["engine_end"]
    assert b["preemptions"] == 0
    # three passes for every decode step enqueued
    assert b["loop_passes"] - a["loop_passes"] \
        >= 3 * (b["engine_steps"] - a["engine_steps"]) > 0


def test_int8_control_fails_the_limits(ouro_run):
    _, ctx = ouro_run
    sound, limits = ctx["checks"].values(), ctx["limits"]
    control = limits_tool.control_values(ctx)
    assert sound["served_logit_gap_mean"] <= limits["served_logit_gap_mean"]
    assert control["served_logit_gap_mean"] \
        > 3 * limits["served_logit_gap_mean"]


def test_a_traced_run_reads_the_passes_and_invents_no_roofline(root, capsys):
    run.main(["--workload", CELL, "--seed", "2147483777", "--seconds", "4",
              "--trace", "1"], root=root, on_chip=False)
    lines = capsys.readouterr().out.strip().splitlines()
    res = json.loads(lines[-1])
    got = res["metrics"]
    assert res["correct"] is True, [ln for ln in lines if "check" in ln]
    assert got["loop_passes_per_step.ouro"]["value"] == 3.0
    assert got["preemptions.ouro"]["value"] == 0
    assert got["compiles_in_window.ouro"]["value"] == 0
    assert got["steps_ahead_per_step.ouro"]["value"] > 0.9
    # four slots of at most 128 / 8 blocks: blocks, not planes
    assert 0 < got["decode_live_blocks_per_step.ouro"]["value"] <= 64
    # no TPU trace and no table of peaks on the CPU: none is invented
    assert "loop_attn_roofline.ouro" not in got
    assert "loop_step_roofline.ouro" not in got


def test_an_answer_of_448_tokens_goes_through_logit_gaps():
    """``logit_gaps`` asks for 256 rows and slices ``out_len``: the
    reference returns every row from ``start`` on, so the cell's longest
    answer is compared whole."""
    import jax.numpy as jnp

    from perfbench import weights

    cfg = dict(vocab_size=64, hidden_size=64, intermediate_size=128,
               num_hidden_layers=1, num_attention_heads=2,
               rms_norm_eps=1e-6, rope_theta=1000000.0, total_ut_steps=2,
               early_exit_threshold=1)
    params = weights.make(ref.param_spec(cfg), 5, jnp.float32)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 64, size=224).astype(np.int32)
    rec = {"prompt": prompt, "prompt_len": 224, "out_len": 448,
           "tokens": rng.integers(1, 64, size=448).tolist()}
    gaps = serve.logit_gaps(ref, params, cfg, [rec])
    assert len(gaps) == 448 and min(gaps) >= 0 and max(gaps) > 0
    ids = np.zeros(768, np.int32)
    ids[:224] = prompt
    assert np.asarray(ref.logit_rows(params, jnp.asarray(ids), 512, 256,
                                     cfg)).shape == (256, 64)
    with pytest.raises(ValueError, match="early_exit_threshold of 1"):
        ref.logit_rows(params, jnp.asarray(ids), 0, 256,
                       dict(cfg, early_exit_threshold=0.5))


# -- operations and bytes -------------------------------------------------------

@pytest.mark.parametrize("passes", [4, 1])
def test_ops_loop_counts_a_plane_for_every_pass_and_layer(passes):
    cfg = dict(PUBLISHED, total_ut_steps=passes)
    plain = {k: v for k, v in PUBLISHED.items() if k != "total_ut_steps"}
    flops, nbytes = ops_bytes.decode_attention_cost(plain, 3000)
    assert ops_loop.decode_attention_cost(cfg, 3000) \
        == (passes * flops, passes * nbytes)
    # a configuration that names no passes is a plain stack
    assert ops_loop.decode_attention_cost(plain, 3000) == (flops, nbytes)
    assert ops_loop.passes(plain) == 1
    if passes == 4:
        # 192 planes of 2 x 2048 bfloat16: 1.5 MiB a position
        assert nbytes * passes == 3000 * 1536 * 1024


def test_a_decode_step_reads_the_layers_once_a_pass_and_the_head_once():
    layers = ops_loop.layer_params(PUBLISHED)
    assert layers == 48 * (4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048)
    head = 2048 * 49152
    flops, nbytes = ops_loop.decode_step_cost(PUBLISHED, 10, 80, 30000)
    a_flops, a_bytes = ops_loop.decode_attention_cost(PUBLISHED, 30000)
    assert nbytes == 10 * (4 * layers + head) * 2 + a_bytes
    assert flops == 80 * 2 * (4 * layers + head) + a_flops
    # ISSUE 37's reckoning: 19.9 GB of weights a step
    assert 19.8e9 < (4 * layers + head) * 2 < 20.0e9
    one = dict(PUBLISHED, total_ut_steps=1)
    assert ops_loop.decode_step_cost(one, 10, 80, 0)[1] \
        == 10 * (layers + head) * 2


# -- the readers on synthetic facts ----------------------------------------------

KERNEL = "ouro_pass_q1_custom-call"


def _traced_facts(kernel_s, step_s=2.0, steps=50):
    """Two requests whose tokens arrive inside a traced window of a
    second, one a step, on a kernel that took ``kernel_s`` in ``steps``
    step programs of ``step_s`` in all."""
    lo = 200.0
    reqs = [{"prompt_len": 200, "times": [lo - 1 + 0.01 * j
                                          for j in range(150)]},
            {"prompt_len": 100, "times": [lo + 0.02 * j for j in range(50)]}]
    return {"requests": reqs, "config": dict(PUBLISHED),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"host_window": (lo, lo + 1.0), "busy_s": 0.99,
                      "op_s": {KERNEL: kernel_s, "while": 1.9,
                               "ouro_pass_q32_custom-call": 0.2,
                               "fusion": 0.3},
                      "module_s": {"jit__step": step_s, "jit__chunk": 0.1},
                      "module_calls": {"jit__step": steps, "jit__chunk": 2}}}


def _attended(facts):
    return [r["prompt_len"] + j for r in facts["requests"]
            for j, t in enumerate(r["times"]) if j >= 1 and 200.0 <= t < 201.0]


def test_the_metric_files_name_the_decode_steps_kernel_alone(man):   # noqa: F811
    for name in ("loop_attn_roofline.ouro", "decode_attn_share.ouro"):
        assert man.metric_file(name)["args"]["match"] == f"^{KERNEL}$"
    assert man.metric_file("loop_step_roofline.ouro")["args"]["module"] \
        == "jit__step"
    facts = _traced_facts(0.25)
    # the prefill program's kernel calls are not the decode step's
    assert base.reading(man, "decode_attn_share.ouro", facts) \
        == pytest.approx(100 * 0.25 / 0.99)


def test_loop_attn_roofline_is_every_planes_bytes_over_kernel_time(man):   # noqa: F811
    facts = _traced_facts(0.25)
    ctx = _attended(facts)
    assert len(ctx) == 50 + 49
    want = 100.0 * (192 * sum(ctx) * 2 * 2048 * 2 / 819e9) / 0.25
    got = base.reading(man, "loop_attn_roofline.ouro", facts)
    assert got == pytest.approx(want) and 0 < got < 100
    # four times what the plain stack's reader would count
    plain = ops_bytes.decode_attention_cost(PUBLISHED, sum(ctx))[1]
    assert got == pytest.approx(100.0 * 4 * plain / 819e9 / 0.25)


def test_loop_step_roofline_is_the_steps_least_time_over_the_programs(man):   # noqa: F811
    facts = _traced_facts(0.25, step_s=2.0, steps=50)
    ctx = _attended(facts)
    weights_bytes = (4 * ops_loop.layer_params(PUBLISHED) + 2048 * 49152) * 2
    want = 100.0 * ((50 * weights_bytes + 192 * sum(ctx) * 2 * 2048 * 2)
                    / 819e9) / 2.0
    got = base.reading(man, "loop_step_roofline.ouro", facts)
    assert got == pytest.approx(want) and 50 < got < 100
    # the kernel's time does not enter it
    assert base.reading(man, "loop_step_roofline.ouro",
                        _traced_facts(0.5)) == pytest.approx(got)


@pytest.mark.parametrize("name", ["loop_attn_roofline.ouro",
                                  "loop_step_roofline.ouro"])
def test_a_roofline_share_gives_nothing_without_its_sources(man, name):   # noqa: F811
    facts = _traced_facts(0.25)
    for drop in ("trace", "peaks", "requests"):
        assert base.reading(man, name, {k: v for k, v in facts.items()
                                        if k != drop}) is None
    # a configuration that names no passes (the cells of the other
    # models, and the parent of the PR that brought these readers)
    plain = {k: v for k, v in PUBLISHED.items() if k != "total_ut_steps"}
    assert base.reading(man, name, dict(facts, config=plain)) is None
    # a trace in which the step and its kernel never ran
    idle = _traced_facts(0.0, step_s=0.0, steps=0)
    assert base.reading(man, name, idle) is None


def test_passes_per_step_are_a_mean_over_the_windows_steps(man, ring):   # noqa: F811
    events = base.steady()
    for e in events:
        if e["name"] == "engine.dispatch":
            e["args"]["ut_steps"] = 4
    events.append(base.ev("engine.dispatch", -50_000.0, 2.0, iter=-1,
                          ut_steps=1))
    facts = ring(base.facts_for(events))
    assert base.reading(man, "loop_passes_per_step.ouro", facts) == 4.0


def test_a_dispatch_span_without_the_passes_gives_nothing(man, ring):   # noqa: F811
    """An engine whose model has no loop, and the parent of the PR that
    added the arg."""
    facts = ring(base.facts_for(base.steady()))
    assert base.reading(man, "loop_passes_per_step.ouro", facts) is None
