"""Auto-tuner measured-mode worker: one candidate config, launched as a
real process by AutoTuner.run() through the launch CLI.

Parity: the reference tuner launches each candidate as a real
distributed job and reads metrics back
(python/paddle/distributed/auto_tuner/tuner.py:21, utils.py log parsing).
Here the worker builds the candidate's dp x mp mesh, trains a Llama of
the tuner's model_cfg for a few steps through ShardedTrainStep, and
writes measured tokens/sec to --out as JSON (file handoff instead of
log scraping — the launcher already redirects stdout).

Run via:  python -m paddle_tpu.distributed.launch --nproc_per_node 1 \
              .../auto_tuner_worker.py --config cand.json --out out.json
"""

from __future__ import annotations

import argparse
import json
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.config) as f:
        cfg = json.load(f)

    import jax

    if cfg.get("platform") == "cpu":
        # CI / virtual-mesh mode: must run before any backend init
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", int(cfg["world_size"]))

    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.engine import ShardedTrainStep
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   llama_pretrain_loss, llama_shard_fn)

    cand = cfg["candidate"]
    mc = cfg["model_cfg"]
    ws = int(cfg["world_size"])
    dp_total = cand["dp_degree"] * cand["sharding_degree"]
    mp = cand["mp_degree"]
    assert cand["pp_degree"] == 1, "measured mode covers dp/mp/sharding candidates"
    assert dp_total * mp == ws, (dp_total, mp, ws)

    h = int(mc.get("hidden_size", 256))
    llama_cfg = LlamaConfig(
        vocab_size=int(mc.get("vocab_size", 32000)),
        hidden_size=h,
        intermediate_size=int(mc.get("intermediate_size", 4 * h)),
        num_hidden_layers=int(mc.get("num_layers", 2)),
        num_attention_heads=int(mc.get("num_attention_heads", 4)),
        num_key_value_heads=int(mc.get("num_attention_heads", 4)),
        max_position_embeddings=int(mc.get("seq_length", 128)),
    )
    paddle.seed(0)
    model = LlamaForCausalLM(llama_cfg)
    mesh = dist.ProcessMesh(np.arange(ws).reshape(dp_total, mp), ["dp", "mp"])
    if mp > 1:
        dist.shard_layer(model, mesh, llama_shard_fn(mesh, mp_axis="mp"))

    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = ShardedTrainStep(
        model, llama_pretrain_loss, opt, mesh,
        dp_axis="dp" if dp_total > 1 else None,
        shard_optimizer_states=cand["sharding_degree"] > 1,
        remat=bool(cand.get("use_recompute", False)))

    gbs = int(mc.get("global_batch_size", 8))
    seq = int(mc.get("seq_length", 128))
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, llama_cfg.vocab_size, (gbs, seq)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.randint(0, llama_cfg.vocab_size, (gbs, seq)).astype(np.int32))

    steps = int(cfg.get("steps", 3))
    warmup = int(cfg.get("warmup", 1))
    loss = None
    for _ in range(warmup):
        loss = step.step(ids, labels)
    _ = float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step.step(ids, labels)
    final = float(loss)
    dt = time.perf_counter() - t0

    with open(args.out, "w") as f:
        json.dump({"ips": gbs * seq * steps / dt, "final_loss": final,
                   "candidate": cand}, f)


if __name__ == "__main__":
    main()
