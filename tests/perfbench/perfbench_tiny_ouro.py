"""The sixth cell in the tiny checkout, the way ``perfbench_tiny_evabyte``
brought the fifth: ``perfbench_tiny.make_root`` maps every cell of
``BENCHMARK.json`` through ``CELLS`` and rewrites ``configs``, so the
cell of a further configuration needs its mapping before the call and
its configuration, traffic and ``configs`` entry after it.
``perfbench_tiny.py`` is part of the accepted benchmark and is not
edited: this module wraps its ``make_root`` and is imported by
``tests/conftest.py`` so that every file of this directory sees it."""

import os

import perfbench_tiny as tiny

CELL = "ouro-2p6b.reason-closed"
TINY_CELL = "tiny-ouro.tiny-reason"
# three passes, so that nothing passes by the symmetry of two; a
# vocabulary of 2048, as tiny-gpt's, so that the int8 control changes a
# token in sixty
TINY_OURO = dict(vocab_size=2048, hidden_size=256, intermediate_size=512,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=4, head_dim=64,
                 max_position_embeddings=256, total_ut_steps=3,
                 layer_types=["full_attention"] * 2, max_window_layers=2)
SERVING = dict(max_slots=4, max_len=128, kv_mode="paged", block_size=8,
               prefill_chunk=16, prefix_caching=False, num_blocks=65,
               max_queue_depth=64)

tiny.CELLS[CELL] = ("tiny-ouro", "tiny-reason")
# as perfbench_tiny.LIMITS: loose against float32 rounding, tight
# against the int8 control (test_perfbench_ouro.py reads both)
tiny.LIMITS["tiny-reason"] = {
    "token_count_mismatches": 0, "served_logit_gap_max": 1e-3,
    "served_logit_gap_mean": 2e-5}
_make_root = tiny.make_root


def make_root(root):
    _make_root(root)
    pb = os.path.join(root, "perfbench")
    cfg = tiny._load("perfbench/configs/ouro-2p6b.json")
    cfg.update(TINY_OURO, name="tiny-ouro", dtype="float32", serving=SERVING)
    tiny._dump(cfg, os.path.join(pb, "configs", "tiny-ouro.json"))
    tr = tiny._load("perfbench/traffic/reason-closed.json")
    tr.update(clients=4, requests_per_client=2, lead_in_s=1, check_sample=4,
              prompt_quantiles=[[0, 12], [1, 40]],
              output_quantiles=[[0, 16], [1, 40]], trace_window_s=1)
    tiny._dump(tr, os.path.join(pb, "traffic", "tiny-reason.json"))
    path = os.path.join(root, "BENCHMARK.json")
    bench = tiny._load(path)
    real = next(c for c in tiny._load("BENCHMARK.json")["configs"]
                if c["name"] == "ouro-2p6b")
    if all(c["name"] != "tiny-ouro" for c in bench["configs"]):
        bench["configs"].append(dict(
            real, name="tiny-ouro", file="perfbench/configs/tiny-ouro.json"))
    tiny._dump(bench, path)
    return root


tiny.make_root = make_root
