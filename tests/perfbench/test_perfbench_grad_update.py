"""``grad_update_share``, ``grad_update_roofline`` and
``fused_update_leaves.train`` (PR 41): the operations that make the
linear weights' gradients and apply AdamW to them (XLA's
``subtract_convert_fusion`` family: each leaf's weight-gradient matmul
with the update as its epilogue), as a share of busy time and held to
the least time the configuration's leaves need
(``perfbench/ops_grad_update.py``), and the count of linear weights
whose gradient the step makes from factors written once, from
``train.dispatch``'s ``fused_leaves``. On synthetic facts with known
answers, against a program that does not write the arg (the parent),
and the tiny training cell end to end. Then what the twenty-one tests
of ``test_perfbench_stalls.py`` that these three entries push out of
place (``tests/conftest.py`` marks them) said of ``per_layer``, held by
the entries' order."""

import json
import os
import re

import pytest

import perfbench_tiny as tiny
import perfbench_tiny_ouro as tiny_ouro
import test_perfbench_spans as base
import test_perfbench_stalls as stalls
import test_perfbench_steps_fused as fused_base

from perfbench import manifest, ops_bytes, ops_grad_update, run
from perfbench.programs import observe
from test_perfbench_spans import man, ring   # noqa: F401  (fixtures)

CELL = "mistral-7b-cut.pretrain-4k"
UPDATE = "optimizer update (optimizer/functional.py)"
ENTRY = "train entry (distributed/engine.py)"
# name -> (unit, better, source, layer, reader, the reader's arguments)
MINE = {
    "grad_update_share": ("%", "lower", "device_trace", UPDATE, "op_share",
                          {"match": "^subtract_convert_fusion$"}),
    "grad_update_roofline": ("%", "higher", "device_trace", UPDATE,
                             "grad_update_roofline",
                             {"match": "^subtract_convert_fusion$",
                              "module": "jit_step"}),
    "fused_update_leaves.train": ("leaves", "higher", "program_counter",
                                  ENTRY, "span_arg_mean",
                                  {"trace": "train", "span": "train.dispatch",
                                   "key": "fused_leaves"}),
}
NAMES = list(MINE)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# -- the entries -------------------------------------------------------------


@pytest.mark.parametrize("name", NAMES)
def test_each_metric_is_an_entry_behind_the_accepted_ones(name):
    real = manifest.Manifest(tiny.REPO)
    unit, better, source, layer, reader, args = MINE[name]
    mf = real.metric_file(name)
    assert (mf["name"], mf["reader"], mf["args"]) == (name, reader, args)
    assert os.path.isfile(os.path.join(
        tiny.REPO, "perfbench", "readers", reader + ".py"))
    names = [m["name"] for m in real.data["per_layer"]]
    entry = real.data["per_layer"][names.index(name)]
    assert entry["workloads"] == [CELL] and entry["moves"] == "train_tok_s"
    assert entry["layer"] == mf["layer"] == layer
    assert (entry["unit"], entry["better"], entry["source"]) \
        == (mf["unit"], better, source) == (unit, better, source)
    assert names[-3:] == NAMES
    (e2e,) = [m for m in real.data["end_to_end"]
              if m["name"] == "train_tok_s"]
    assert CELL in e2e["workloads"]


def test_the_manifest_with_the_three_entries_meets_the_static_rules():
    assert manifest.problems(tiny.REPO) == []
    real = manifest.Manifest(tiny.REPO)
    before = {m["layer"] for m in real.data["per_layer"]
              if m["name"] not in NAMES}
    # the entry is a layer the benchmark had; the optimizer's is new
    assert ENTRY in before and UPDATE not in before
    assert len(real.data["per_layer"]) == 118


@pytest.mark.parametrize("name", stalls.NAMES)
def test_pr_40s_twenty_stand_where_they_stood(name):
    """``test_perfbench_stalls.py``'s facts of each of its entries, with
    the place they have now: the twenty before this PR's three."""
    real = manifest.Manifest(tiny.REPO)
    kind, suf = name.rsplit(".", 1)
    _, layer, reader, args = stalls.KINDS[kind]
    cell, moves = stalls.CELLS[suf]
    mf = real.metric_file(name)
    assert (mf["name"], mf["reader"], mf["args"]) == (name, reader, args)
    names = [m["name"] for m in real.data["per_layer"]]
    entry = real.data["per_layer"][names.index(name)]
    assert entry["workloads"] == [cell] and entry["moves"] == moves
    assert entry["layer"] == mf["layer"] == layer
    assert (entry["unit"], entry["better"], entry["source"]) \
        == (mf["unit"], "lower", "program_span") and mf["unit"] == "s"
    assert len(stalls.NAMES) == 20 and names[-23:-3] == stalls.NAMES
    (e2e,) = [m for m in real.data["end_to_end"] if m["name"] == moves]
    assert cell in e2e["workloads"]


def test_the_ouro_entries_stay_together_where_pr_37_put_them():
    real = manifest.Manifest(tiny.REPO)
    per_layer = real.data["per_layer"]
    names = [m["name"] for m in per_layer]
    mine = [i for i, m in enumerate(per_layer)
            if m.get("workloads") == [tiny_ouro.CELL]]
    at, new = mine[:16], mine[16:]
    assert at == list(range(at[0], at[0] + 16))
    assert names[at[-1] + 1:at[-1] + 3] == fused_base.NAMES
    assert names[at[-1] + 3:] == stalls.NAMES + NAMES
    assert [names[i] for i in new] == [n for n in stalls.NAMES
                                       if n.endswith(".ouro")]
    for i in at + new:
        m = per_layer[i]
        assert m["moves"] == "serve_tok_s"
        mf = real.metric_file(m["name"])
        assert mf["name"] == m["name"] and mf["unit"] == m["unit"]
        assert os.path.isfile(os.path.join(
            tiny.REPO, "perfbench", "readers", mf["reader"] + ".py"))


def test_no_served_cell_and_not_the_four_chip_cell_list_them(man):
    for cell in man.data["workloads"]:
        got = {m["name"] for m in man.metrics_of(cell["name"], "per_layer")}
        assert bool(got & set(NAMES)) == (cell["name"] == CELL)


# -- what the leaves need ----------------------------------------------------


def test_the_cells_leaves_from_its_configuration(man):
    cfg = man.config("mistral-7b-cut")
    leaves = ops_grad_update.linear_leaves(cfg)
    assert len(leaves) == 22
    assert set(leaves) == {(4096, 4096), (4096, 1024), (4096, 14336),
                           (14336, 4096), (4096, 32000)}
    # every matmul parameter, which is what ``mfu`` counts
    assert sum(i * o for i, o in leaves) == ops_bytes.matmul_params(cfg) \
        == 785_383_424
    tied = dict(cfg, tie_word_embeddings=True)
    assert len(ops_grad_update.linear_leaves(tied)) == 21


def test_a_leaf_is_its_contraction_and_one_pass_over_its_state():
    flops, nbytes = ops_grad_update.leaf_cost(4096, 14336, 4096)
    assert flops == 2 * 4096 * 4096 * 14336
    # bf16 p and f32 m, v read and written; x and dy read once
    assert nbytes == 20 * 4096 * 14336 + 2 * 4096 * (4096 + 14336)
    _, f32 = ops_grad_update.leaf_cost(8, 16, 4, dtype_bytes=4)
    assert f32 == 24 * 8 * 16 + 4 * 4 * (8 + 16)


def test_the_least_time_is_each_leaf_at_its_larger_roof(man):
    cfg = man.config("mistral-7b-cut")
    least = ops_grad_update.least_seconds(cfg, 1, 4096, PEAKS)
    # at 4096 tokens every leaf is bound by the MXU: 6.43 TFLOP
    assert least == pytest.approx(2 * 785_383_424 * 4096 / 197e12)
    # at 64 tokens by its state's bytes
    few = ops_grad_update.least_seconds(cfg, 1, 64, PEAKS)
    assert few == pytest.approx(
        (20 * 785_383_424 + 2 * 64 * sum(
            i + o for i, o in ops_grad_update.linear_leaves(cfg))) / 819e9)


# -- the readers -------------------------------------------------------------


def traced(man, op_s, steps=19, busy_s=3.0):
    """Facts of a traced training run whose window held ``steps`` whole
    steps and the operations ``op_s`` (name -> seconds)."""
    return {"trace": {"op_s": op_s, "busy_s": busy_s,
                      "module_calls": {"jit_step": steps}},
            "peaks": PEAKS, "step_ends": [0.0], "window": (0.0, 3.0),
            "config": man.config("mistral-7b-cut"),
            "traffic": man.traffic("pretrain-4k")}


def test_the_share_and_the_roofline_of_a_known_trace(man):
    least = ops_grad_update.least_seconds(
        man.config("mistral-7b-cut"), 1, 4096, PEAKS)
    ops = {"subtract_convert_fusion": 19 * least / 0.8, "fusion": 1.4,
           "multiply_subtract_fusion": 0.5,      # another family: not mine
           "transpose_jvp____custom-call": 0.21, "jvp___custom-call": 0.08}
    facts = traced(man, ops)
    assert base.reading(man, "grad_update_roofline", facts) \
        == pytest.approx(80.0)
    assert base.reading(man, "grad_update_share", facts) \
        == pytest.approx(100 * 19 * least / 0.8 / 3.0)
    # operations exactly at their roofs read 100, never more
    facts = traced(man, {"subtract_convert_fusion": 19 * least})
    assert base.reading(man, "grad_update_roofline", facts) \
        == pytest.approx(100.0)


def test_the_parents_trace_reads_what_the_ledger_says(man):
    """PR 40's line for the cell (ledger): 1.1176 s of
    ``subtract_convert_fusion`` over 19.1 steps of a 2.9636 s busy
    window. The leaves' 32.66 ms a step are 55.8% of that."""
    ops = {"fusion": 1.4225, "subtract_convert_fusion": 1.1176,
           "transpose_jvp____custom-call": 0.2123,
           "jvp___custom-call": 0.0844}
    facts = traced(man, ops, steps=19.1, busy_s=2.9636)
    assert base.reading(man, "grad_update_roofline", facts) \
        == pytest.approx(55.8, abs=0.1)
    assert base.reading(man, "grad_update_share", facts) \
        == pytest.approx(37.7, abs=0.1)


def test_the_flash_metrics_and_these_share_no_operation(man):
    flash = man.metric_file("flash_attn_share")["args"]["match"]
    mine = MINE["grad_update_share"][5]["match"]
    assert not re.search(flash, "subtract_convert_fusion")
    for name in ("transpose_jvp____custom-call", "jvp___custom-call",
                 "multiply_subtract_fusion", "fusion",
                 "subtract_convert_fusion_bitcast"):
        assert not re.search(mine, name)


@pytest.mark.parametrize("name", ["grad_update_share",
                                  "grad_update_roofline"])
def test_a_run_without_a_trace_or_the_operations_gives_nothing(man, name):
    ops = {"fusion": 1.42, "all-reduce": 0.67}
    assert base.reading(man, name, traced(man, ops)) is None
    # an untraced run, and a served cell's facts
    assert base.reading(man, name, {"trace": None}) is None
    assert base.reading(man, name, {}) is None


def dispatches(n=300, **args):
    return [base.ev("train.dispatch", 160.0 * k, 3.0, trace="train",
                    cat="train", step=k, **args) for k in range(n)]


def test_fused_leaves_are_read_off_the_dispatch_span(man, ring):
    facts = ring(base.facts_for(dispatches(
        fused_leaves=22, fused_param_share=0.857)))
    assert base.reading(man, "fused_update_leaves.train", facts) == 22
    # a step on the dense path says so: zero, not nothing
    facts = ring(base.facts_for(dispatches(fused_leaves=0,
                                           fused_param_share=0.0)))
    assert base.reading(man, "fused_update_leaves.train", facts) == 0
    assert base.reading(man, "train_dispatch_ms", facts) \
        == pytest.approx(3.0)


def test_the_parents_dispatch_span_gives_nothing(man, ring):
    """The parent writes ``step`` alone: the accepted metric reads, the
    new one has nothing to read and the line leaves it out."""
    facts = ring(base.facts_for(dispatches()))
    assert base.reading(man, "train_dispatch_ms", facts) \
        == pytest.approx(3.0)
    assert base.reading(man, "fused_update_leaves.train", facts) is None
    # a ring that no longer reaches back to the window's start
    evicted = ring(base.facts_for(dispatches(fused_leaves=22)[5:],
                                  evicted=True))
    assert base.reading(man, "fused_update_leaves.train", evicted) is None


# -- the tiny training cell, read end to end ---------------------------------


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    mp = pytest.MonkeyPatch()
    mp.setattr(observe, "enable_compile_cache", lambda: "off (tests)")
    yield
    mp.undo()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("checkout")))


def _run(root, capsys, *argv):
    run.main(list(argv), root=root, on_chip=False)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_tiny_training_cell_routes_its_linears(
        root, capsys):
    """One device, AdamW, nothing clipped or sharded: the step makes its
    linears' weight gradients from factors written once, the reference
    comparison holds, and the line carries the count."""
    res = _run(root, capsys, "--workload", "tiny-mistral.tiny-train",
               "--seed", "2147484201", "--seconds", "6", "--trace", "1")
    assert res["correct"] is True
    got = res["metrics"]["fused_update_leaves.train"]
    layers = tiny.TINY_MISTRAL["num_hidden_layers"]
    assert got == {"value": 7.0 * layers + 1, "unit": "leaves"}
    # the operations' time needs a TPU's trace
    assert "grad_update_roofline" not in res["metrics"]
    res0 = _run(root, capsys, "--workload", "tiny-mistral.tiny-train",
                "--seed", "2147484202", "--seconds", "2", "--trace", "0")
    assert set(res0["metrics"]) == {"train_tok_s", "setup_s"}


def test_the_four_device_cell_stays_on_the_plain_step(root, capsys):
    """dp 2 x mp 2: the mesh shards batch and parameters, so the step
    is traced as it always was and says so."""
    res = _run(root, capsys, "--workload", tiny.TRAIN4, "--seed",
               "2147484203", "--seconds", "6", "--trace", "1")
    assert res["correct"] is True
    assert res["metrics"]["fused_update_leaves.train"]["value"] == 0.0


def test_a_program_without_the_arg_prints_none_and_fails_nothing(
        root, capsys, monkeypatch):
    """The parent under this PR's benchmark files: ``train.dispatch``
    carries ``step`` alone."""
    from paddle_tpu.distributed import engine

    def step_alone(self, inputs, labels, _step=engine.ShardedTrainStep.step):
        self._fused = {}
        return _step(self, inputs, labels)

    monkeypatch.setattr(engine.ShardedTrainStep, "_one_program_one_device",
                        lambda self: False)
    monkeypatch.setattr(engine.ShardedTrainStep, "step", step_alone)
    res = _run(root, capsys, "--workload", "tiny-mistral.tiny-train",
               "--seed", "2147484204", "--seconds", "6", "--trace", "1")
    assert res["correct"] is True and res["failed"] == 0
    assert "train_dispatch_ms" in res["metrics"]
    assert not set(res["metrics"]) & set(NAMES)
