"""The system under test for training cells: ``LlamaForCausalLM``
(which carries Mistral's grouped-query block) under
``ShardedTrainStep`` with ``llama_pretrain_loss`` and AdamW. The only
place of the benchmark that touches these program classes."""

from __future__ import annotations

import gc

from . import install_weights

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "max_position_embeddings",
              "rms_norm_eps", "rope_theta")


class Trainer:
    def __init__(self, config, traffic, spec, leaves, chips):
        import numpy as np

        import paddle_tpu as paddle
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed.engine import ShardedTrainStep
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       llama_pretrain_loss, llama_shard_fn)

        self._paddle = paddle
        dp, mp = traffic["mesh"]["dp"], traffic["mesh"]["mp"]
        if dp * mp != chips:
            raise SystemExit(f"perfbench: mesh {dp}x{mp} on {chips} chips")
        cfg = LlamaConfig(
            use_flash_attention=bool(traffic["flash_attention"]),
            dtype=config["dtype"], **{k: config[k] for k in MODEL_KEYS})
        model = LlamaForCausalLM(cfg)
        install_weights(model, spec, leaves)
        mesh = dist.ProcessMesh(np.arange(chips).reshape(dp, mp),
                                ["dp", "mp"])
        if mp > 1:
            dist.shard_layer(model, mesh, llama_shard_fn(mesh, mp_axis="mp"))
        hy = config["training"]
        opt = paddle.optimizer.AdamW(
            learning_rate=hy["lr"], beta1=hy["beta1"], beta2=hy["beta2"],
            epsilon=hy["epsilon"], weight_decay=hy["weight_decay"],
            parameters=model.parameters())
        self.step_obj = ShardedTrainStep(
            model, llama_pretrain_loss, opt, mesh,
            dp_axis="dp" if dp > 1 else None,
            shard_optimizer_states=dp > 1)
        self.model, self.opt = model, opt

    def step(self, ids):
        """One optimizer step on ``ids [batch, seq]`` (numpy int32); the
        loss comes to the host, which is the step's one sync."""
        t = self._paddle.to_tensor(ids)
        return float(self.step_obj.step(t, t))

    def params(self):
        return self.step_obj.params

    def first_moment(self):
        """Adam's first moment by parameter name (after one step it is
        (1 - beta1) times the gradient the optimizer was given)."""
        return self.step_obj.opt_state["m"]

    def free(self):
        self.step_obj.params = self.step_obj.opt_state = None
        self.step_obj = self.model = self.opt = None
        gc.collect()
