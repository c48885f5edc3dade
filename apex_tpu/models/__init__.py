"""apex_tpu.models — flagship model zoo (TP/SP-parallel flax).

Mirrors the reference's ``apex/transformer/testing/{standalone_gpt,
standalone_bert}.py`` toy models and the BASELINE.json workload configs
(BERT-Large north star, GPT-2 1.3B TP), built on the parallel
transformer core.
"""

from apex_tpu.models.transformer import (
    TransformerConfig,
    ParallelTransformer,
    ParallelTransformerLayer,
    ParallelAttention,
    ParallelMLP,
)
from apex_tpu.models.gpt import (GPTConfig, GPTModel, gpt_loss_fn,
                                 moe_aux_loss)
from apex_tpu.models.llama import LlamaConfig, LlamaModel
from apex_tpu.models.falcon_h1 import FalconH1Config, FalconH1Model
from apex_tpu.models.afmoe import AfmoeConfig, AfmoeModel
from apex_tpu.models.bert import BertConfig, BertModel, bert_mlm_loss_fn
from apex_tpu.models.resnet import ResNetConfig, ResNet, resnet50, resnet18
from apex_tpu.models.vit import ViTConfig, ViTModel

__all__ = [
    "load_torch_gpt2",
    "load_torch_llama",
    "TransformerConfig",
    "ParallelTransformer",
    "ParallelTransformerLayer",
    "ParallelAttention",
    "ParallelMLP",
    "GPTConfig",
    "GPTModel",
    "gpt_loss_fn",
    "moe_aux_loss",
    "LlamaConfig",
    "LlamaModel",
    "FalconH1Config",
    "FalconH1Model",
    "AfmoeConfig",
    "AfmoeModel",
    "BertConfig",
    "BertModel",
    "bert_mlm_loss_fn",
    "ResNetConfig", "ResNet", "resnet50", "resnet18",
    "ViTConfig", "ViTModel",
]
from apex_tpu.models.torch_import import (  # noqa: E402
    load_torch_gpt2,
    load_torch_llama,
)
from apex_tpu.models.generate import generate, init_cache  # noqa: E402

__all__ += ["generate", "init_cache"]
