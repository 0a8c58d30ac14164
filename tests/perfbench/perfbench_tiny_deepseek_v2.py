"""The seventh cell in the tiny checkout, the way ``perfbench_tiny_ouro``
brought the sixth: ``perfbench_tiny.make_root`` maps every cell of
``BENCHMARK.json`` through ``CELLS`` and rewrites ``configs``, so the
cell of a further configuration needs its mapping before the call and
its configuration, traffic and ``configs`` entry after it.
``perfbench_tiny.py`` is part of the accepted benchmark and is not
edited: this module wraps its ``make_root`` and is imported by
``tests/conftest.py`` so that every file of this directory sees it."""

import os

import perfbench_tiny as tiny

CELL = "deepseek-v2-cut.longdoc-closed"
TINY_CELL = "tiny-dsv2.tiny-longdoc"
# a share of 4 of 16 routed experts (rank 1 of 4, so that "held" is no
# prefix of the ids), 4 groups of which 2 stay, 3 experts a token; one
# dense layer and two expert layers; a vocabulary of 2048, as tiny-gpt's,
# so that the int8 control changes a token in sixty
TINY_DSV2 = dict(vocab_size=2048, hidden_size=256, intermediate_size=512,
                 moe_intermediate_size=128, num_hidden_layers=3,
                 num_attention_heads=4, num_key_value_heads=4,
                 q_lora_rank=96, kv_lora_rank=64, qk_nope_head_dim=32,
                 qk_rope_head_dim=16, v_head_dim=32,
                 n_routed_experts=4, router_experts=16, n_group=4,
                 topk_group=2, num_experts_per_tok=3,
                 max_position_embeddings=256,
                 expert_parallel={"rank": 1, "size": 4,
                                  "held_experts": [4, 5, 6, 7]})
SERVING = dict(max_slots=4, max_len=128, kv_mode="paged", block_size=8,
               prefill_chunk=16, prefix_caching=False, num_blocks=65,
               max_queue_depth=64)

tiny.CELLS[CELL] = ("tiny-dsv2", "tiny-longdoc")
# as perfbench_tiny.LIMITS: loose against float32 rounding, tight
# against the int8 control (test_perfbench_deepseek_v2.py reads both)
tiny.LIMITS["tiny-longdoc"] = {
    "token_count_mismatches": 0, "served_logit_gap_max": 1e-3,
    "served_logit_gap_mean": 2e-5}
_make_root = tiny.make_root


def make_root(root):
    _make_root(root)
    pb = os.path.join(root, "perfbench")
    cfg = tiny._load("perfbench/configs/deepseek-v2-cut.json")
    cfg.update(TINY_DSV2, name="tiny-dsv2", dtype="float32", serving=SERVING)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=64)
    tiny._dump(cfg, os.path.join(pb, "configs", "tiny-dsv2.json"))
    tr = tiny._load("perfbench/traffic/longdoc-closed.json")
    tr.update(clients=4, requests_per_client=2, lead_in_s=1, check_sample=4,
              prompt_quantiles=[[0, 20], [1, 80]],
              output_quantiles=[[0, 12], [1, 40]], trace_window_s=1)
    tiny._dump(tr, os.path.join(pb, "traffic", "tiny-longdoc.json"))
    path = os.path.join(root, "BENCHMARK.json")
    bench = tiny._load(path)
    real = next(c for c in tiny._load("BENCHMARK.json")["configs"]
                if c["name"] == "deepseek-v2-cut")
    if all(c["name"] != "tiny-dsv2" for c in bench["configs"]):
        bench["configs"].append(dict(
            real, name="tiny-dsv2", file="perfbench/configs/tiny-dsv2.json"))
    tiny._dump(bench, path)
    return root


tiny.make_root = make_root
