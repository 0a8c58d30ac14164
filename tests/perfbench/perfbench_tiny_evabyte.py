"""The fifth cell in the tiny checkout. ``perfbench_tiny.make_root``
maps every cell of ``BENCHMARK.json`` through ``CELLS`` and rewrites
``configs`` to its two tiny configurations, so a cell of a third
configuration needs its mapping before the call and its configuration,
traffic and ``configs`` entry after it. ``perfbench_tiny.py`` is part of
the accepted benchmark and is not edited: this module wraps its
``make_root`` the way ``conftest.py`` does, and is imported by
``tests/conftest.py`` so that every file of this directory sees it."""

import os

import perfbench_tiny as tiny

CELL = "evabyte-6p5b-cut.doc-bytes-closed"
TINY_CELL = "tiny-evabyte.tiny-doc-bytes"
# window 64 in chunks of 16: four summaries a window, which must fill
# whole pool blocks, hence blocks of 4 (the real cell: 128 and 16). A
# vocabulary of 2048, as tiny-gpt's: among 320 logits the best two lie
# too far apart for the int8 control to change a token in sixty
TINY_EVABYTE = dict(vocab_size=2048, hidden_size=512, intermediate_size=1024,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=4, max_position_embeddings=512,
                    window_size=64, chunk_size=16)
SERVING = dict(max_slots=4, max_len=320, kv_mode="paged", block_size=4,
               prefill_chunk=32, prefix_caching=False, max_queue_depth=64)

tiny.CELLS[CELL] = ("tiny-evabyte", "tiny-doc-bytes")
# as perfbench_tiny.LIMITS: loose against float32 rounding, tight
# against the int8 control (test_perfbench_evabyte.py reads both)
tiny.LIMITS["tiny-doc-bytes"] = {
    "token_count_mismatches": 0, "served_logit_gap_max": 1e-3,
    "served_logit_gap_mean": 2e-5}
_make_root = tiny.make_root


def make_root(root):
    _make_root(root)
    pb = os.path.join(root, "perfbench")
    cfg = tiny._load("perfbench/configs/evabyte-6p5b-cut.json")
    cfg.update(TINY_EVABYTE, name="tiny-evabyte", dtype="float32",
               serving=SERVING)
    tiny._dump(cfg, os.path.join(pb, "configs", "tiny-evabyte.json"))
    doc = tiny._load("perfbench/traffic/doc-bytes-closed.json")
    # prompts past three windows, so every request rolls at least thrice
    doc.update(clients=4, requests_per_client=2, lead_in_s=1, check_sample=8,
               prompt_quantiles=[[0, 200], [1, 260]],
               output_quantiles=[[0, 24], [1, 48]], trace_window_s=1)
    tiny._dump(doc, os.path.join(pb, "traffic", "tiny-doc-bytes.json"))
    path = os.path.join(root, "BENCHMARK.json")
    bench = tiny._load(path)
    real = next(c for c in tiny._load("BENCHMARK.json")["configs"]
                if c["name"] == "evabyte-6p5b-cut")
    if all(c["name"] != "tiny-evabyte" for c in bench["configs"]):
        bench["configs"].append(dict(
            real, name="tiny-evabyte",
            file="perfbench/configs/tiny-evabyte.json"))
    tiny._dump(bench, path)
    return root


tiny.make_root = make_root
