"""The EvaByte cell at tiny sizes on the CPU: the run end to end
(``correct``, the int8 control failing its limits, an altered token
caught), an answer longer than the 256 rows ``logit_gaps`` asks for,
``ops_eva``'s counts against a walk over every position, and the new
readers on synthetic facts. The tiny checkout gets the cell from
``perfbench_tiny_evabyte`` (no file the benchmark already had is
edited); the synthetic rings are ``test_perfbench_spans``'s. Times read
here mean nothing."""

import json

import numpy as np
import pytest

import perfbench_tiny as tiny
import perfbench_tiny_evabyte as tiny_eva
import test_perfbench_spans as base

from perfbench import limits as limits_tool
from perfbench import manifest, ops_eva, run
from perfbench.drivers import serve
from perfbench.references import evabyte as ref
from test_perfbench_spans import (man, no_persistent_cache,  # noqa: F401
                                  ring, root)   # (fixtures)

CELL = tiny_eva.TINY_CELL
EVA = ["eva_summary_blocks_per_step.eva", "decode_live_blocks_per_step.eva"]


@pytest.fixture(scope="module")
def eva_run(root):
    return run.run_cell(root, CELL, 2147483931, 4, 0, on_chip=False)


def test_the_tiny_checkout_holds_the_fifth_cell(root):
    assert manifest.problems(root) == []
    man_ = manifest.Manifest(root)
    assert man_.cell(CELL)["config"] == "tiny-evabyte"
    cfg = man_.config("tiny-evabyte")
    assert cfg["window_size"] == 64 and cfg["chunk_size"] == 16
    assert cfg["dtype"] == "float32" and cfg["program"] == "evabyte_engine"
    assert {c["name"] for c in man_.data["configs"]} == {
        "tiny-gpt", "tiny-mistral", "tiny-evabyte"}
    # prompts past three windows
    tr = man_.traffic("tiny-doc-bytes")
    assert tr["prompt_quantiles"][0][1] > 3 * cfg["window_size"]


def test_the_four_chip_cell_is_still_listed_where_pr_25_put_it(root):
    """What ``test_perfbench_spans.py`` asserts beside its count of four
    cells (``tests/conftest.py`` says why that count is marked): only
    ``train_tok_s`` and the collectives' share list the four-chip cell
    without a ``.dp2mp2`` twin, one cell takes four chips, and the tiny
    checkout holds each of the five cells once."""
    real = manifest.Manifest(tiny.REPO).data
    listing = [m["name"] for m in real["end_to_end"] + real["per_layer"]
               if base.NEW_CELL in m.get("workloads", [])
               and not m["name"].endswith(".dp2mp2")]
    assert listing == ["train_tok_s", "collective_exposed_share"]
    assert sum(w["chips"] == 4 for w in real["workloads"]) == 1
    assert [w["name"] for w in real["workloads"]][-1] == tiny_eva.CELL
    names = [w["name"] for w in manifest.Manifest(root).data["workloads"]]
    assert len(names) == len(set(names)) == len(real["workloads"]) == 5
    assert names.count(tiny.TRAIN4) == names.count(CELL) == 1


def test_the_cell_runs_and_is_correct(eva_run):
    res, ctx = eva_run
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tok_s", "setup_s"}
    facts = ctx["facts"]
    assert facts["compiles_in_window"] == 0
    a, b = facts["engine_start"], facts["engine_end"]
    # every request rolls at least three windows, and a roll gives back
    # the window's 64 / 4 blocks
    rolls = b["window_rolls"] - a["window_rolls"]
    assert rolls >= 3 * res["attempted"]
    assert b["window_blocks_released"] - a["window_blocks_released"] \
        == 16 * rolls
    assert b["summary_entries_written"] > a["summary_entries_written"]


def test_int8_control_fails_the_limits(eva_run):
    _, ctx = eva_run
    sound, limits = ctx["checks"].values(), ctx["limits"]
    control = limits_tool.control_values(ctx)
    assert sound["served_logit_gap_mean"] <= limits["served_logit_gap_mean"]
    assert control["served_logit_gap_mean"] \
        > 3 * limits["served_logit_gap_mean"]


def test_an_altered_token_is_caught(root, monkeypatch):
    from paddle_tpu.serving.request import Request

    real = Request.push_token

    def push(self, token, now):
        n = len(self.output_tokens)
        return real(self, token + 1 if n % 5 == 4 else token, now)

    monkeypatch.setattr(Request, "push_token", push)
    res, ctx = run.run_cell(root, CELL, 23, 3, 0, on_chip=False)
    assert res["failed"] == 0 and res["correct"] is False
    assert ctx["checks"].values()["served_logit_gap_mean"] \
        > ctx["limits"]["served_logit_gap_mean"]


def test_a_traced_run_reads_the_new_metrics(root, capsys):
    run.main(["--workload", CELL, "--seed", "2147483777", "--seconds", "7",
              "--trace", "1"], root=root, on_chip=False)
    lines = capsys.readouterr().out.strip().splitlines()
    res = json.loads(lines[-1])
    got = res["metrics"]
    assert res["correct"] is True, [ln for ln in lines if "check" in ln]
    assert got["eva_window_rolls.eva"]["value"] > 0
    assert got["preemptions.eva"]["value"] == 0
    # four slots: at most 16 window blocks each, and one summary block
    # for each of at most four windows behind
    assert 0 < got["decode_live_blocks_per_step.eva"]["value"] <= 64
    assert 0 < got["eva_summary_blocks_per_step.eva"]["value"] <= 16
    # no TPU trace and no table of peaks on the CPU: none is invented
    assert "eva_attn_roofline.eva" not in got


def test_an_answer_of_2048_tokens_goes_through_logit_gaps():
    """``logit_gaps`` asks for 256 rows and slices ``out_len``: the
    reference returns every row from ``start`` on, so an answer of any
    length is compared whole."""
    import jax.numpy as jnp

    from perfbench import weights

    cfg = dict(vocab_size=64, hidden_size=64, intermediate_size=128,
               num_hidden_layers=1, num_attention_heads=2,
               chunk_size=16, window_size=256, num_pred_heads=2,
               rms_norm_eps=1e-5, rope_theta=100000.0)
    params = weights.make(ref.param_spec(cfg), 5, jnp.float32)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 64, size=100).astype(np.int32)
    ids = np.concatenate([prompt, np.zeros(2048, np.int32)])
    logits = np.asarray(ref.logit_rows(params, jnp.asarray(ids), 99, 256, cfg))
    assert logits.shape == (2049, 64)
    rec = {"prompt": prompt, "prompt_len": 100, "out_len": 2048,
           "tokens": rng.integers(1, 64, size=2048).tolist()}
    gaps = serve.logit_gaps(ref, params, cfg, [rec])
    assert len(gaps) == 2048 and min(gaps) >= 0 and max(gaps) > 0


@pytest.mark.parametrize("window,chunk", [(64, 16), (2048, 16)])
def test_ops_eva_counts_against_a_walk(window, chunk):
    for pos in list(range(3 * window + 5)) if window == 64 else (
            0, 2047, 2048, 2049, 6143, 6144, 18431):
        exact = sum(1 for j in range(pos + 1) if j // window == pos // window)
        summaries = sum(1 for m in range(pos // chunk + 1)
                        if m * chunk // window < pos // window)
        assert ops_eva.attended(pos, window, chunk) == (exact, summaries)
    # a request's j-th token (j >= 1) was produced by the query at
    # prompt + j - 1
    assert ops_eva.decode_entries(100, 1, window, chunk) \
        == sum(ops_eva.attended(100, window, chunk))
    cfg = {"hidden_size": 4096, "num_hidden_layers": 12}
    flops, nbytes = ops_eva.attention_cost(cfg, 1000)
    assert nbytes == 12 * 1000 * 2 * 4096 * 2 and flops == 12 * 1000 * 4 * 4096


def _traced_facts(kernel_s):
    """Two requests whose tokens arrive inside a traced window of a
    second, one a step, on a kernel that took ``kernel_s``."""
    lo = 200.0
    reqs = [{"prompt_len": 5000, "times": [lo - 1 + 0.01 * j
                                           for j in range(150)]},
            {"prompt_len": 100, "times": [lo + 0.01 * j for j in range(50)]}]
    return {"requests": reqs,
            "config": {"hidden_size": 4096, "num_hidden_layers": 12,
                       "window_size": 2048, "chunk_size": 16},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"host_window": (lo, lo + 1.0), "busy_s": 0.9,
                      "op_s": {"_step_custom-call": kernel_s,
                               "_chunk_custom-call": 0.2, "fusion": 0.3}}}


def test_eva_roofline_share_is_entries_read_over_kernel_time(man):   # noqa: F811
    facts = _traced_facts(0.05)
    entries = sum(ops_eva.decode_entries(r["prompt_len"], j, 2048, 16)
                  for r in facts["requests"]
                  for j, t in enumerate(r["times"])
                  if j >= 1 and 200.0 <= t < 201.0)
    # the long request is two windows behind: 256 summaries a query
    assert entries > 50 * (256 + 900)
    want = 100.0 * (12 * entries * 2 * 4096 * 2 / 819e9) / 0.05
    got = base.reading(man, "eva_attn_roofline.eva", facts)
    assert got == pytest.approx(want) and 0 < got < 100


def test_eva_roofline_share_gives_nothing_without_its_sources(man):   # noqa: F811
    facts = _traced_facts(0.05)
    for drop in ("trace", "peaks", "requests"):
        assert base.reading(man, "eva_attn_roofline.eva",
                            {k: v for k, v in facts.items() if k != drop}) \
            is None
    # a configuration with no window (the cells of the other models),
    # and a trace in which the step's kernel never ran
    other = dict(facts, config={"hidden_size": 2048, "num_hidden_layers": 24})
    assert base.reading(man, "eva_attn_roofline.eva", other) is None
    idle = _traced_facts(0.0)
    assert base.reading(man, "eva_attn_roofline.eva", idle) is None


def test_window_rolls_are_a_counters_difference(man):   # noqa: F811
    facts = {"engine_start": {"window_rolls": 7, "engine_steps": 1},
             "engine_end": {"window_rolls": 180, "engine_steps": 900}}
    assert base.reading(man, "eva_window_rolls.eva", facts) == 173
    # the parent of the PR that added the counter, and a run that never
    # read its counters
    assert base.reading(man, "eva_window_rolls.eva", {
        "engine_start": {"engine_steps": 1},
        "engine_end": {"engine_steps": 900}}) is None
    assert base.reading(man, "eva_window_rolls.eva", {}) is None


@pytest.mark.parametrize("name", EVA)
def test_block_counts_are_a_mean_over_the_windows_steps(man, ring, name):   # noqa: F811
    key = man.metric_file(name)["args"]["key"]
    events = base.steady()
    n = 0
    for e in events:
        if e["name"] == "engine.dispatch":
            e["args"][key] = 500 + 2 * e["args"]["iter"]
            n += 1
    events.append(base.ev("engine.dispatch", -50_000.0, 2.0, iter=-1,
                          **{key: 10_000}))
    facts = ring(base.facts_for(events))
    assert base.reading(man, name, facts) == pytest.approx(500 + (n - 1))


@pytest.mark.parametrize("name", EVA)
def test_a_dispatch_span_without_the_count_gives_nothing(man, ring, name):   # noqa: F811
    facts = ring(base.facts_for(base.steady()))
    assert base.reading(man, name, facts) is None
