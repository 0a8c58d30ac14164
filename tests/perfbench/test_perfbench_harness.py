"""The harness end to end on the CPU at tiny sizes: the result line,
adding a cell and a metric without editing a file, the controls, and
the timed path broken underneath. Times read here mean nothing."""

import json
import os

import numpy as np
import pytest

import perfbench_tiny as tiny

from perfbench import limits as limits_tool
from perfbench import manifest, run
from perfbench.programs import observe

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "checks"}


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A run turns the program's persistent compile cache on for the
    whole process; the other tests of this worker must not inherit it."""
    mp = pytest.MonkeyPatch()
    mp.setattr(observe, "enable_compile_cache", lambda: "off (tests)")
    yield
    mp.undo()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("checkout")))


@pytest.fixture(scope="module")
def chat_run(root):
    return run.run_cell(root, "tiny-gpt.tiny-chat", 5, 3, 0, on_chip=False)


@pytest.fixture(scope="module")
def train_run(root):
    return run.run_cell(root, "tiny-mistral.tiny-train", 7, 2, 0,
                        on_chip=False)


def last_line(capsys, err=None):
    """The result object; standard error's lines go into ``err``."""
    got = capsys.readouterr()
    if err is not None:
        err.extend(got.err.strip().splitlines())
    return json.loads(got.out.strip().splitlines()[-1])


def test_tiny_manifest_meets_the_static_rules(root):
    assert manifest.problems(root) == []


def test_refuses_to_run_without_a_tpu(root, capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "tiny-gpt.tiny-chat", "--seed", "1",
                  "--seconds", "1"], root=root)
    assert e.value.code not in (0, None)
    assert not any(ln.startswith("{")
                   for ln in capsys.readouterr().out.splitlines())


def test_last_line_of_an_untraced_run(root, capsys):
    rc = run.main(["--workload", "tiny-gpt.tiny-doc", "--seed", "2147483659",
                   "--seconds", "3", "--trace", "0"], root=root,
                  on_chip=False)
    err = []
    res = last_line(capsys, err)
    assert rc == 0 and set(res) == RESULT_KEYS
    assert set(res["metrics"]) == {"serve_tok_s", "setup_s"}
    assert all(set(m) == {"value", "unit"} for m in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["metrics"]["serve_tok_s"]["value"] > 0
    # each number compared beside its limit: the line's last key, and
    # the last lines of standard error
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"token_count_mismatches",
                                  "served_logit_gap_max",
                                  "served_logit_gap_mean"}
    assert all(0 <= c["value"] <= c["limit"] for c in res["checks"].values())
    assert err[-1] == "correct True"
    assert [ln.split()[0] for ln in err[-4:-1]] == list(res["checks"])


def test_last_line_of_a_traced_run(root, capsys):
    run.main(["--workload", "tiny-mistral.tiny-train4", "--seed", "3",
              "--seconds", "2", "--trace", "1"], root=root, on_chip=False)
    res = last_line(capsys)
    assert set(res) == RESULT_KEYS | {"breakdown"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    per_layer = {m["name"] for m in manifest.Manifest(root).metrics_of(
        "tiny-mistral.tiny-train4", "per_layer")}
    # readers that need a TPU trace or the table of peaks find nothing
    # to read here and are left out; none may be invented
    assert {"train_step_ms", "compiles_in_window.train",
            "backend_compiles_setup"} <= set(res["metrics"]) <= per_layer
    assert res["metrics"]["compiles_in_window.train"]["value"] == 0
    assert res["correct"] is True


def test_chat_cell_counts_and_checks(chat_run):
    res, ctx = chat_run
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 24  # 8 req/s x 3 s, whole blocks of 8
    assert set(res["metrics"]) == {"ttft_p75_ms", "itl_p99_ms", "setup_s"}
    assert ctx["facts"]["compiles_in_window"] == 0
    recs = [r for r in ctx["facts"]["requests"] if r["counted"]]
    assert all(len(r["tokens"]) == r["out_len"] == len(r["times"])
               for r in recs)
    assert sum(r["extends"] for r in recs) == len(recs) // 2


def test_adding_a_cell_and_a_metric_edits_no_file(root, tmp_path):
    """What a later PR does: a traffic file, a limits file, a metric
    file and a reader, each new, plus entries in BENCHMARK.json."""
    import shutil

    mine = str(tmp_path / "checkout")
    shutil.copytree(root, mine)
    before = tiny.tree_digest(mine)
    pb = os.path.join(mine, "perfbench")
    spec = manifest.load_json(os.path.join(pb, "traffic", "tiny-chat.json"))
    spec.update(rate_rps=4, share_gap_slots=2)
    cell = "tiny-gpt.tiny-chat-slow"
    tiny._dump(spec, os.path.join(pb, "traffic", "tiny-chat-slow.json"))
    tiny._dump(tiny.LIMITS["tiny-chat"],
               os.path.join(pb, "limits", cell + ".json"))
    tiny._dump({"name": "follow_up_share.slow", "unit": "%",
                "reader": "follow_up_share", "args": {}},
               os.path.join(pb, "metrics", "follow_up_share.slow.json"))
    with open(os.path.join(pb, "readers", "follow_up_share.py"), "w") as fh:
        fh.write("def read(facts):\n"
                 "    rs = [r for r in facts['requests'] if r['counted']]\n"
                 "    return 100.0 * sum(r['extends'] for r in rs) / len(rs)\n")
    bench = manifest.load_json(os.path.join(mine, "BENCHMARK.json"))
    bench["workloads"].append({
        "name": cell, "config": "tiny-gpt", "traffic": "tiny-chat-slow",
        "chips": 1, "why": "the chat mix at half the rate"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p75_ms", "itl_p99_ms"):
            m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "follow_up_share.slow", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "traffic generator (perfbench)",
        "moves": "ttft_p75_ms", "workloads": [cell]})
    tiny._dump(bench, os.path.join(mine, "BENCHMARK.json"))
    assert manifest.problems(mine) == []
    res, _ = run.run_cell(mine, cell, 11, 4, 1, on_chip=False)
    assert res["correct"] is True
    assert res["metrics"]["follow_up_share.slow"] == {"value": 50.0,
                                                      "unit": "%"}
    assert "backend_compiles_setup" in res["metrics"]  # lists no cells
    res0, _ = run.run_cell(mine, cell, 12, 4, 0, on_chip=False)
    assert set(res0["metrics"]) == {"ttft_p75_ms", "itl_p99_ms", "setup_s"}
    after = tiny.tree_digest(mine)
    changed = {k for k in before if before[k] != after.get(k)}
    assert changed == {"BENCHMARK.json"}


def test_int8_control_fails_the_serving_limits(chat_run):
    """The reference in int8 weights, put in the program's place, must
    come out as not correct where the program itself passes."""
    _, ctx = chat_run
    sound, limits = ctx["checks"].values(), ctx["limits"]
    control = limits_tool.control_values(ctx)
    assert sound["served_logit_gap_mean"] <= limits["served_logit_gap_mean"]
    assert control["served_logit_gap_mean"] \
        > 3 * limits["served_logit_gap_mean"]


def test_fp8_control_fails_the_training_limits(train_run):
    res, ctx = train_run
    assert res["correct"] is True
    sound, limits = ctx["checks"].values(), ctx["limits"]
    control = limits_tool.control_values(ctx)
    assert sound["first_grad_norm_gap"] <= limits["first_grad_norm_gap"]
    assert control["first_grad_norm_gap"] > 3 * limits["first_grad_norm_gap"]
    assert any(control[k] > limits[k] for k in control)


def test_an_altered_token_is_caught(root, monkeypatch):
    """The timed path broken where a token is produced: every fifth
    token the engine hands over is off by one."""
    from paddle_tpu.serving.request import Request

    real = Request.push_token

    def push(self, token, now):
        n = len(self.output_tokens)
        return real(self, token + 1 if n % 5 == 4 else token, now)

    monkeypatch.setattr(Request, "push_token", push)
    res, ctx = run.run_cell(root, "tiny-gpt.tiny-chat", 21, 3, 0,
                            on_chip=False)
    assert res["failed"] == 0 and res["correct"] is False
    assert ctx["checks"].values()["served_logit_gap_mean"] \
        > ctx["limits"]["served_logit_gap_mean"]


def test_a_step_that_leaves_its_state_unchanged_is_caught(root, monkeypatch):
    import paddle_tpu as paddle

    monkeypatch.setattr(paddle.optimizer.AdamW, "get_lr", lambda self: 0.0)
    res, ctx = run.run_cell(root, "tiny-mistral.tiny-train", 22, 2, 0,
                            on_chip=False)
    vals, limits = ctx["checks"].values(), ctx["limits"]
    assert res["correct"] is False
    assert vals["param_change_norm_gap"] > 0.9
    assert vals["first_grad_norm_gap"] <= limits["first_grad_norm_gap"]


def test_a_part_of_the_batch_left_out_is_caught(root, monkeypatch):
    from perfbench.programs import llama_trainer

    real = llama_trainer.Trainer.step

    def step(self, ids):
        return real(self, np.concatenate([ids[:1]] * len(ids)))

    monkeypatch.setattr(llama_trainer.Trainer, "step", step)
    res, ctx = run.run_cell(root, "tiny-mistral.tiny-train", 23, 2, 0,
                            on_chip=False)
    vals, limits = ctx["checks"].values(), ctx["limits"]
    assert res["correct"] is False
    assert vals["loss_rel_gap_step1"] > limits["loss_rel_gap_step1"]
