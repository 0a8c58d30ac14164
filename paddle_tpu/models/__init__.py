"""Model families (large-model kit; reference analogue: the PaddleNLP-facing
capability surface built on fleet + fused kernels)."""

from .llama import (
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
    llama_pretrain_loss,
    llama_shard_fn,
    moe_aux_loss,
    moe_pretrain_loss,
)
from .gpt import GPTConfig, GPTForCausalLM
from .bert import BertConfig, BertForPretraining, BertModel
from .evabyte import EvaByteConfig, EvaByteForCausalLM
from .ouro import OuroConfig, OuroForCausalLM


# DeepSeek-V2 (latent attention, a share of the routed experts) loads
# with its first use: the other families' engines import nothing of it
_LAZY = {"DeepseekV2Config": "deepseek_v2",
         "DeepseekV2ForCausalLM": "deepseek_v2"}


def __getattr__(name):
    if name in _LAZY:
        import importlib

        return getattr(importlib.import_module(f".{_LAZY[name]}", __name__),
                       name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
