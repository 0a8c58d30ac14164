"""The DeepSeek-V2 cell (a latent cache, absorbed and decompressed
attention over it, one share of the routed experts) at tiny sizes on the
CPU: the run end to end (``correct``, the int8 control failing its
limits, an altered token caught, the routing counts read from the
dispatch spans and the engine's counters), ``ops_mla_moe``'s counts
against the issue's arithmetic, and the new readers on synthetic facts.
The tiny checkout gets the cell from ``perfbench_tiny_deepseek_v2`` (no
file the benchmark already had is edited); the synthetic rings are
``test_perfbench_spans``'s. Times read here mean nothing.

Then what the accepted tests that this PR's entries push out of place
(``tests/conftest.py`` marks them, with the reason) said of
``BENCHMARK.json`` and the tiny checkout, held at the count the manifest
has now: seven cells, five tiny configurations, 128 per-layer metrics
(all the contract allows) of which the last ten are this cell's."""

import json
import os

import numpy as np
import pytest

import perfbench_tiny as tiny
import perfbench_tiny_deepseek_v2 as tiny_dsv2
import perfbench_tiny_evabyte as tiny_eva
import perfbench_tiny_ouro as tiny_ouro
import test_perfbench_grad_update as grad
import test_perfbench_spans as base
import test_perfbench_stalls as stalls
import test_perfbench_steps_fused as fused_base

from perfbench import limits as limits_tool
from perfbench import manifest, ops_mla_moe, run
from perfbench.drivers import serve
from perfbench.references import deepseek_v2 as ref
from test_perfbench_spans import (man, no_persistent_cache,  # noqa: F401
                                  ring, root)   # (fixtures)

CELL = tiny_dsv2.TINY_CELL
REAL = manifest.Manifest(tiny.REPO)
DSV2 = [m["name"] for m in REAL.data["per_layer"]
        if m["name"].endswith(".dsv2")]
ENGINE = "engine executables (serving.step, serving.prefill_chunk)"
KERNELS = "decode kernels (pallas_kernels/decode_attention.py)"
EXPERTS = "expert layer (distributed/moe_serving.py)"
GMM, MLA = "^gmm_custom-call$", "^mla_absorbed_q1_custom-call$"
DISPATCH = {"trace": "engine", "span": "engine.dispatch"}
# name -> (unit, better, source, layer, reader, the reader's arguments)
# of the entries that are no copy of an accepted ``.ouro``/``.doc`` one
MINE = {
    "expert_pairs_per_step.dsv2": (
        "pairs", "higher", "program_counter", EXPERTS, "span_arg_mean",
        dict(DISPATCH, key="expert_pairs")),
    "experts_touched_per_step.dsv2": (
        "experts", "lower", "program_counter", EXPERTS, "span_arg_mean",
        dict(DISPATCH, key="experts_touched")),
    "expert_matmul_share.dsv2": (
        "%", "lower", "device_trace", EXPERTS, "op_share", {"match": GMM}),
    "expert_matmul_roofline.dsv2": (
        "%", "higher", "device_trace", EXPERTS, "expert_matmul_roofline",
        {"match": GMM}),
    "mla_decode_attn_share.dsv2": (
        "%", "lower", "device_trace", KERNELS, "op_share", {"match": MLA}),
    "mla_decode_attn_roofline.dsv2": (
        "%", "higher", "device_trace", KERNELS, "mla_decode_attn_roofline",
        {"match": MLA}),
    "dsv2_step_roofline.dsv2": (
        "%", "higher", "device_trace", ENGINE, "dsv2_step_roofline",
        {"module": "jit__step", "span": "engine.dispatch"}),
}
# the accepted entry each of the others copies, under the new suffix
COPIES = {name: name[:-len(".dsv2")] + ".ouro"
          for name in DSV2 if name not in MINE}
PUBLISHED = REAL.config("deepseek-v2-cut")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def dsv2_run(root):
    return run.run_cell(root, CELL, 2147483931, 3, 0, on_chip=False)


# -- the manifest: this PR's entries ----------------------------------------

def test_the_tiny_checkout_holds_the_seventh_cell(root):
    """And what ``test_perfbench_ouro.py`` asserts beside its count of
    four configurations (``tests/conftest.py`` says why that test is
    marked)."""
    assert manifest.problems(root) == []
    man_ = manifest.Manifest(root)
    assert man_.cell(CELL)["config"] == "tiny-dsv2"
    cfg = man_.config("tiny-dsv2")
    assert cfg["dtype"] == "float32" and cfg["program"] == "deepseek_v2_engine"
    assert cfg["reference"] == "deepseek_v2"
    assert ref.held_experts(cfg) == [4, 5, 6, 7] \
        == cfg["expert_parallel"]["held_experts"]
    assert ref.router_experts(cfg) == 16 and cfg["n_routed_experts"] == 4
    assert {c["name"] for c in man_.data["configs"]} == {
        "tiny-gpt", "tiny-mistral", "tiny-evabyte", "tiny-ouro", "tiny-dsv2"}
    ouro = man_.config("tiny-ouro")
    assert ouro["total_ut_steps"] == 3 and ouro["early_exit_threshold"] == 1
    assert ouro["dtype"] == "float32" and ouro["program"] == "ouro_engine"
    assert man_.cell(tiny_ouro.TINY_CELL)["config"] == "tiny-ouro"
    eva = man_.config("tiny-evabyte")
    assert eva["window_size"] == 64 and eva["chunk_size"] == 16
    assert man_.traffic("tiny-doc-bytes")["prompt_quantiles"][0][1] \
        > 3 * eva["window_size"]


def test_the_benchmark_gained_one_configuration_and_one_cell_on_one_chip(root):
    """Seven cells, one of them on four chips, the new one last; and
    what ``test_perfbench_ouro.py`` asserts beside its count of six."""
    real = REAL.data
    assert manifest.problems(tiny.REPO) == []
    cells = [w["name"] for w in real["workloads"]]
    assert len(cells) == 7 and cells[-3:] == [
        tiny_eva.CELL, tiny_ouro.CELL, tiny_dsv2.CELL]
    assert [c["name"] for c in real["configs"]][-1] == "deepseek-v2-cut"
    assert len(real["configs"]) == 5
    assert sum(w["chips"] == 4 for w in real["workloads"]) == 1
    assert REAL.cell(tiny_dsv2.CELL)["chips"] == 1
    assert REAL.cell(tiny_ouro.CELL)["chips"] == 1
    listing = [m["name"] for m in real["end_to_end"] + real["per_layer"]
               if base.NEW_CELL in m.get("workloads", [])
               and not m["name"].endswith(".dp2mp2")]
    assert listing == ["train_tok_s", "collective_exposed_share"]
    names = [w["name"] for w in manifest.Manifest(root).data["workloads"]]
    assert len(names) == len(set(names)) == len(cells)
    assert names.count(tiny.TRAIN4) == names.count(CELL) \
        == names.count(tiny_ouro.TINY_CELL) \
        == names.count(tiny_eva.TINY_CELL) == 1
    serve_cells = next(m["workloads"] for m in real["end_to_end"]
                       if m["name"] == "serve_tok_s")
    assert serve_cells[-2:] == [tiny_ouro.CELL, tiny_dsv2.CELL]
    assert len(serve_cells) == 4


def test_the_configuration_is_the_published_one_cut_to_a_share():
    cfg = PUBLISHED
    entry = next(c for c in REAL.data["configs"]
                 if c["name"] == "deepseek-v2-cut")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] and "DeepSeek-V2" in cfg["source"]
    catalog = dict(
        hidden_size=5120, intermediate_size=12288, moe_intermediate_size=1536,
        num_attention_heads=128, num_key_value_heads=128, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, first_k_dense_replace=1, n_shared_experts=2,
        n_group=8, topk_group=3, num_experts_per_tok=6,
        routed_scaling_factor=16, max_position_embeddings=163840,
        rms_norm_eps=1e-6, rope_theta=10000, moe_layer_freq=1,
        norm_topk_prob=False, scoring_func="softmax",
        topk_method="group_limited_greedy", tie_word_embeddings=False,
        attention_bias=False, hidden_act="silu", seq_aux=True,
        model_type="deepseek_v2")
    for key, value in catalog.items():
        assert cfg[key] == value, key
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    # the cut, and the published numbers beside it
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 20, 12800)
    assert cfg["published"] == {"num_hidden_layers": 60,
                                "n_routed_experts": 160, "vocab_size": 102400}
    assert cfg["router_experts"] == 160
    assert ref.held_experts(cfg) == list(range(20)) \
        == cfg["expert_parallel"]["held_experts"]
    assert cfg["expert_parallel"]["size"] == cfg["n_group"]
    assert {"rotary pairing", "init", "router precision"} <= set(
        cfg["assumed"])
    assert "3.145B" in cfg["memory"] and "8 chips" in cfg["deployment"]
    sv = cfg["serving"]
    # every slot can reach its longest request at once
    assert sv["max_slots"] * sv["max_len"] == sv["num_blocks"] * 16
    assert (sv["max_slots"], sv["max_len"], sv["block_size"],
            sv["prefix_caching"]) == (64, 17408, 16, False)


def test_the_traffic_is_the_issues_table():
    tr = REAL.traffic("longdoc-closed")
    assert tr["kind"] == "closed"
    assert (tr["clients"], tr["requests_per_client"]) == (64, 4)
    assert tr["prompt_quantiles"] == [[0.0, 2048], [1.0, 16384]]
    assert tr["output_quantiles"] == [[0.0, 256], [1.0, 1024]]
    assert (tr["greedy"], tr["lead_in_s"], tr["trace_window_s"],
            tr["check_sample"]) == (True, 10, 5, 6)
    sv = PUBLISHED["serving"]
    assert tr["clients"] == sv["max_slots"]
    assert tr["prompt_quantiles"][-1][1] + tr["output_quantiles"][-1][1] \
        == sv["max_len"]
    cell = REAL.cell(tiny_dsv2.CELL)
    assert (cell["config"], cell["traffic"]) == ("deepseek-v2-cut",
                                                 "longdoc-closed")
    lim = REAL.limits(tiny_dsv2.CELL)
    assert lim["token_count_mismatches"] == 0
    assert set(lim) == {"token_count_mismatches", "served_logit_gap_max",
                        "served_logit_gap_mean"}


@pytest.mark.parametrize("name", DSV2)
def test_every_dsv2_metric_is_data_beside_the_accepted_ones(name):
    """Ten entries at the end of ``per_layer`` (the contract allows 128
    and 118 were there), each listing the one cell and moving
    ``serve_tok_s``, each with a metric file whose reader exists: a copy
    of an accepted entry's reader and arguments under the new suffix, or
    one of this PR's seven."""
    per_layer = REAL.data["per_layer"]
    assert [m["name"] for m in per_layer[-10:]] == DSV2 and len(DSV2) == 10
    assert len(MINE) == 7 and len(COPIES) == 3
    entry = next(m for m in per_layer if m["name"] == name)
    mf = REAL.metric_file(name)
    assert entry["workloads"] == [tiny_dsv2.CELL]
    assert entry["moves"] == mf["moves"] == "serve_tok_s"
    assert (mf["name"], mf["unit"], mf["layer"]) == (
        name, entry["unit"], entry["layer"])
    assert os.path.isfile(os.path.join(
        tiny.REPO, "perfbench", "readers", mf["reader"] + ".py"))
    if name in MINE:
        unit, better, source, layer, reader, args = MINE[name]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"]) == (unit, better, source, layer)
        assert (mf["reader"], mf["args"]) == (reader, args)
        return
    was = next(m for m in per_layer if m["name"] == COPIES[name])
    old = REAL.metric_file(COPIES[name])
    assert (mf["reader"], mf.get("args", {})) == (old["reader"],
                                                  old.get("args", {}))
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == was[key]


def test_the_expert_layer_is_the_one_new_layer_and_the_rooflines_are_named():
    per_layer = REAL.data["per_layer"]
    before = {m["layer"] for m in per_layer if m["name"] not in DSV2}
    mine = {m["layer"] for m in per_layer if m["name"] in DSV2}
    assert mine - before == {EXPERTS}
    rooflines = [n for n in DSV2 if "roofline" in n]
    assert rooflines == ["expert_matmul_roofline.dsv2",
                         "mla_decode_attn_roofline.dsv2",
                         "dsv2_step_roofline.dsv2"]
    for n in rooflines:
        assert next(m for m in per_layer if m["name"] == n)["unit"] == "%"


# -- the manifest: what the marked tests said, at today's count ---------------

@pytest.mark.parametrize("name", grad.NAMES)
def test_pr_41s_three_stand_where_they_stood(name):
    """``test_perfbench_grad_update.py``'s facts of each of its entries,
    with the place they have now: the three before this PR's
    twenty-five."""
    unit, better, source, layer, reader, args = grad.MINE[name]
    mf = REAL.metric_file(name)
    assert (mf["name"], mf["reader"], mf["args"]) == (name, reader, args)
    assert os.path.isfile(os.path.join(
        tiny.REPO, "perfbench", "readers", reader + ".py"))
    names = [m["name"] for m in REAL.data["per_layer"]]
    entry = REAL.data["per_layer"][names.index(name)]
    assert entry["workloads"] == [grad.CELL]
    assert entry["moves"] == "train_tok_s"
    assert entry["layer"] == mf["layer"] == layer
    assert (entry["unit"], entry["better"], entry["source"]) \
        == (mf["unit"], better, source) == (unit, better, source)
    assert names[-13:-10] == grad.NAMES and names[-10:] == DSV2
    (e2e,) = [m for m in REAL.data["end_to_end"]
              if m["name"] == "train_tok_s"]
    assert grad.CELL in e2e["workloads"]


def test_the_manifest_with_every_prs_entries_meets_the_static_rules():
    """``test_perfbench_grad_update.py``'s static-rule test at today's
    count (118 before this PR's ten, and no room for more): the
    optimizer's layer is named by PR 41's entries alone."""
    assert manifest.problems(tiny.REPO) == []
    per_layer = REAL.data["per_layer"]
    assert len(per_layer) == 128 == 118 + len(DSV2)
    before = {m["layer"] for m in per_layer if m["name"] not in grad.NAMES}
    assert grad.ENTRY in before and grad.UPDATE not in before


@pytest.mark.parametrize("name", stalls.NAMES)
def test_pr_40s_twenty_stand_where_they_stood(name):
    """``test_perfbench_stalls.py``'s facts of each of its entries, with
    the place they have now: the twenty before PR 41's three and this
    PR's ten."""
    kind, suf = name.rsplit(".", 1)
    _, layer, reader, args = stalls.KINDS[kind]
    cell, moves = stalls.CELLS[suf]
    mf = REAL.metric_file(name)
    assert (mf["name"], mf["reader"], mf["args"]) == (name, reader, args)
    names = [m["name"] for m in REAL.data["per_layer"]]
    entry = REAL.data["per_layer"][names.index(name)]
    assert entry["workloads"] == [cell] and entry["moves"] == moves
    assert entry["layer"] == mf["layer"] == layer
    assert (entry["unit"], entry["better"], entry["source"]) \
        == (mf["unit"], "lower", "program_span") and mf["unit"] == "s"
    assert len(stalls.NAMES) == 20 and names[-33:-13] == stalls.NAMES
    (e2e,) = [m for m in REAL.data["end_to_end"] if m["name"] == moves]
    assert cell in e2e["workloads"]


def test_the_ouro_entries_stay_together_where_pr_37_put_them():
    per_layer = REAL.data["per_layer"]
    names = [m["name"] for m in per_layer]
    mine = [i for i, m in enumerate(per_layer)
            if m.get("workloads") == [tiny_ouro.CELL]]
    at, new = mine[:16], mine[16:]
    assert at == list(range(at[0], at[0] + 16))
    assert names[at[-1] + 1:at[-1] + 3] == fused_base.NAMES
    assert names[at[-1] + 3:] == stalls.NAMES + grad.NAMES + DSV2
    assert [names[i] for i in new] == [n for n in stalls.NAMES
                                       if n.endswith(".ouro")]
    for i in at + new:
        m = per_layer[i]
        assert m["moves"] == "serve_tok_s"
        mf = REAL.metric_file(m["name"])
        assert mf["name"] == m["name"] and mf["unit"] == m["unit"]
        assert os.path.isfile(os.path.join(
            tiny.REPO, "perfbench", "readers", mf["reader"] + ".py"))


# -- the run ------------------------------------------------------------------

def test_the_cell_runs_and_is_correct(dsv2_run):
    res, ctx = dsv2_run
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tok_s", "setup_s"}
    facts = ctx["facts"]
    assert facts["compiles_in_window"] == 0
    a, b = facts["engine_start"], facts["engine_end"]
    assert b["preemptions"] == 0
    pairs = b["expert_pairs"] - a["expert_pairs"]
    here = b["expert_pairs_here"] - a["expert_pairs_here"]
    absent = b["expert_pairs_absent"] - a["expert_pairs_absent"]
    # 3 experts a token in each of 2 expert layers; the share holds 4 of
    # 16, so about a quarter of the pairs are computed here
    assert pairs > 0 and pairs % 6 == 0 and here + absent == pairs
    assert 0.1 < here / pairs < 0.45
    assert 0 < b["experts_touched"] - a["experts_touched"] \
        <= 8 * (b["route_programs"] - a["route_programs"])


def test_int8_control_fails_the_limits(dsv2_run):
    _, ctx = dsv2_run
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


def test_a_traced_run_reads_every_metric_a_cpu_can_and_invents_no_roofline(
        root, capsys):
    run.main(["--workload", CELL, "--seed", "2147483777", "--seconds", "4",
              "--trace", "1"], root=root, on_chip=False)
    lines = capsys.readouterr().out.strip().splitlines()
    res = json.loads(lines[-1])
    got = res["metrics"]
    assert res["correct"] is True, [ln for ln in lines if "check" in ln]
    assert got["compiles_in_window.dsv2"]["value"] == 0
    # a step of at most 4 decode rows and 4 x 16 prefill tokens: 3
    # experts a token in 2 layers, a quarter of them held
    assert 0 < got["expert_pairs_per_step.dsv2"]["value"] <= 68 * 6
    assert 0 < got["experts_touched_per_step.dsv2"]["value"] <= 8
    # no TPU trace and no table of peaks on the CPU: none is invented
    for name in DSV2:
        if REAL.metric_file(name)["reader"] in (
                "op_share", "module_ms", "device_idle_share",
                "expert_matmul_roofline", "mla_decode_attn_roofline",
                "dsv2_step_roofline"):
            assert name not in got


def test_an_answer_of_1024_tokens_goes_through_logit_gaps():
    """``logit_gaps`` asks for 256 rows and slices ``out_len``: the
    reference returns every row from ``start`` on, so the cell's longest
    answer is compared whole."""
    import jax.numpy as jnp

    from perfbench import weights

    cfg = dict(tiny._load("perfbench/configs/deepseek-v2-cut.json"),
               **tiny_dsv2.TINY_DSV2)
    cfg.update(vocab_size=64, hidden_size=32, intermediate_size=48,
               moe_intermediate_size=16, num_hidden_layers=2,
               num_attention_heads=2, q_lora_rank=16, kv_lora_rank=16,
               qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)
    params = weights.make(ref.param_spec(cfg), 5, jnp.float32)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 64, size=200).astype(np.int32)
    rec = {"prompt": prompt, "prompt_len": 200, "out_len": 1024,
           "tokens": rng.integers(1, 64, size=1024).tolist()}
    gaps = serve.logit_gaps(ref, params, cfg, [rec])
    assert len(gaps) == 1024 and min(gaps) >= 0 and max(gaps) > 0


# -- operations and bytes -------------------------------------------------------

def test_a_position_costs_1152_bytes_and_278_kflop_a_layer():
    flops, nbytes = ops_mla_moe.decode_attention_cost(PUBLISHED, 1000)
    layers = PUBLISHED["num_hidden_layers"]
    assert ops_mla_moe.latent_width(PUBLISHED) == 576
    assert nbytes == 1000 * layers * 1152
    assert flops == 1000 * layers * 128 * (576 + 512) * 2 \
        == 1000 * layers * 278528
    # on the v5e's ridge: 242 flop a byte against 197e12 / 819e9 = 240
    assert 241 < flops / nbytes < 243


def test_the_parameter_counts_are_the_issues():
    cfg = PUBLISHED
    assert round(ops_mla_moe.attention_params(cfg) / 1e6, 2) == 149.23
    assert round(ops_mla_moe.expert_params(cfg) / 1e6, 2) == 23.59
    # 4 x 197.24M + the dense layer's 337.97M + the head's 65.5M, the
    # embedding (a lookup) left out
    want = 4 * (149.23e6 + 47.19e6 + 0.82e6) + 337.97e6 + 65.54e6
    assert abs(ops_mla_moe.unrouted_params(cfg) - want) < 0.05e6
    spec = ref.param_spec(cfg)
    total = sum(int(np.prod(shape)) for shape, _, _ in spec.values())
    assert round(total / 1e9, 3) == 3.145
    assert total == ops_mla_moe.unrouted_params(cfg) \
        + 4 * 20 * ops_mla_moe.expert_params(cfg) + 5120 * 12800


def test_a_pair_multiplies_three_matrices_and_a_touched_expert_is_read_once():
    flops, nbytes = ops_mla_moe.expert_cost(PUBLISHED, 100, 30)
    assert flops == 100 * 3 * 2 * 5120 * 1536
    assert nbytes == 30 * 3 * 5120 * 1536 * 2
    s_flops, s_bytes = ops_mla_moe.decode_step_cost(
        PUBLISHED, 10, 640, 5_000_000, 900, 700)
    a_flops, a_bytes = ops_mla_moe.decode_attention_cost(PUBLISHED, 5_000_000)
    e_flops, e_bytes = ops_mla_moe.expert_cost(PUBLISHED, 900, 700)
    base_ = ops_mla_moe.unrouted_params(PUBLISHED)
    assert s_bytes == 10 * base_ * 2 + a_bytes + e_bytes
    assert s_flops == 640 * 2 * base_ + a_flops + e_flops


# -- the readers on synthetic facts ----------------------------------------------

KERNEL = "mla_absorbed_q1_custom-call"


def _traced_facts(ring, kernel_s=0.25, gmm_s=0.4, step_s=2.0, steps=50,
                  args=True):
    """Two requests whose tokens arrive inside a traced window of a
    second, and fifty iterations there whose dispatch spans carry the
    routing counts of a step (and whose prefill spans those of a
    program)."""
    t0_ms = (base.S0 - base.T0) * 1e3 + 100.0   # inside the session
    events = [e for i in range(50)
              for e in base.iteration(i, t0_ms + 10.0 * i, prefill=1.0)]
    for e in events:
        if not args:
            break
        if e["name"] == "engine.dispatch":
            e["args"].update(expert_pairs=60, experts_touched=40)
        elif e["name"] == "engine.prefill":
            e["args"].update(expert_pairs=200, experts_touched=80)
    facts = base.facts_for(events)
    lo, hi = facts["trace"]["host_window"]
    reqs = [{"prompt_len": 6000, "times": [lo - 1 + 0.01 * j
                                           for j in range(150)]},
            {"prompt_len": 3000, "times": [lo + 0.02 * j for j in range(40)]}]
    facts.update(requests=reqs, config=dict(PUBLISHED), peaks=PEAKS)
    facts["trace"].update(
        busy_s=0.99,
        op_s={KERNEL: kernel_s, "gmm_custom-call": gmm_s, "fusion": 0.3,
              "mla_absorbed_q64_custom-call": 0.2},
        module_s={"jit__step": step_s, "jit__chunk": 0.1},
        module_calls={"jit__step": steps, "jit__chunk": 2})
    return ring(facts)


def _in_session(facts, name):
    lo, hi = facts["trace"]["host_window"]
    return [e for e in facts["_events"] if e["name"] == name
            and lo * 1e9 <= e["ts_ns"] < hi * 1e9]


def _attended(facts):
    lo, hi = facts["trace"]["host_window"]
    return [r["prompt_len"] + j for r in facts["requests"]
            for j, t in enumerate(r["times"]) if j >= 1 and lo <= t < hi]


def test_the_shares_name_their_kernels_alone(man, ring):   # noqa: F811
    facts = _traced_facts(ring)
    assert base.reading(man, "mla_decode_attn_share.dsv2", facts) \
        == pytest.approx(100 * 0.25 / 0.99)
    assert base.reading(man, "expert_matmul_share.dsv2", facts) \
        == pytest.approx(100 * 0.4 / 0.99)


def test_mla_decode_attn_roofline_is_the_latents_bytes_over_kernel_time(
        man, ring):   # noqa: F811
    facts = _traced_facts(ring)
    ctx = _attended(facts)
    assert len(ctx) > 40
    flops, nbytes = sum(ctx) * 5 * 278528, sum(ctx) * 5 * 1152
    want = 100.0 * max(flops / 197e12, nbytes / 819e9) / 0.25
    got = base.reading(man, "mla_decode_attn_roofline.dsv2", facts)
    assert got == pytest.approx(want) and 0 < got < 100


def test_expert_matmul_roofline_is_the_traced_pairs_least_time(man, ring):   # noqa: F811
    facts = _traced_facts(ring)
    n_d = len(_in_session(facts, "engine.dispatch"))
    n_p = len(_in_session(facts, "engine.prefill"))
    assert n_d > 5 and n_p > 5
    flops, nbytes = ops_mla_moe.expert_cost(
        PUBLISHED, 60 * n_d + 200 * n_p, 40 * n_d + 80 * n_p)
    want = 100.0 * max(flops / 197e12, nbytes / 819e9) / 0.4
    got = base.reading(man, "expert_matmul_roofline.dsv2", facts)
    assert got == pytest.approx(want) and got > 0


def test_dsv2_step_roofline_is_the_whole_steps_least_time(man, ring):   # noqa: F811
    facts = _traced_facts(ring, step_s=2.0, steps=50)
    ctx = _attended(facts)
    n_d = len(_in_session(facts, "engine.dispatch"))
    flops, nbytes = ops_mla_moe.decode_step_cost(
        PUBLISHED, 50, len(ctx), sum(ctx), 60 * n_d, 40 * n_d)
    want = 100.0 * max(flops / 197e12, nbytes / 819e9) / 2.0
    got = base.reading(man, "dsv2_step_roofline.dsv2", facts)
    assert got == pytest.approx(want) and 0 < got < 100
    # the kernels' times do not enter it
    assert base.reading(man, "dsv2_step_roofline.dsv2", _traced_facts(
        ring, kernel_s=0.5, gmm_s=0.1)) == pytest.approx(got)


@pytest.mark.parametrize("name", ["expert_matmul_roofline.dsv2",
                                  "mla_decode_attn_roofline.dsv2",
                                  "dsv2_step_roofline.dsv2"])
def test_a_roofline_share_gives_nothing_without_its_sources(man, ring, name):   # noqa: F811
    facts = _traced_facts(ring)
    for drop in ("trace", "peaks"):
        assert base.reading(man, name, {k: v for k, v in facts.items()
                                        if k != drop}) is None
    # a configuration with no latent cache and no routed experts (the
    # cells of the other models)
    plain = {"hidden_size": 2048, "num_hidden_layers": 24,
             "num_attention_heads": 16, "vocab_size": 50304}
    assert base.reading(man, name, dict(facts, config=plain)) is None
    # a trace in which the step and its kernels never ran
    idle = _traced_facts(ring, kernel_s=0.0, gmm_s=0.0, step_s=0.0, steps=0)
    assert base.reading(man, name, idle) is None
    if name != "mla_decode_attn_roofline.dsv2":
        # a program that writes no routing counts into its spans: the
        # parent of the PR that added them
        assert base.reading(man, name, _traced_facts(ring, args=False)) \
            is None


def test_the_routing_args_are_means_over_the_windows_steps(man, ring):   # noqa: F811
    events = base.steady()
    for e in events:
        if e["name"] == "engine.dispatch":
            e["args"].update(expert_pairs=48, experts_touched=70)
    facts = ring(base.facts_for(events))
    assert base.reading(man, "expert_pairs_per_step.dsv2", facts) == 48
    assert base.reading(man, "experts_touched_per_step.dsv2", facts) == 70
    # an engine whose model routes nothing, and the parent
    plain = ring(base.facts_for(base.steady()))
    for name in ("expert_pairs_per_step.dsv2",
                 "experts_touched_per_step.dsv2"):
        assert base.reading(man, name, plain) is None
