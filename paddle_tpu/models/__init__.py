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
