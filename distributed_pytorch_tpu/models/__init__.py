"""Model zoo: the reference workload's MLP plus the evaluation-ladder
models (ResNet-18, Transformer LM, MoE Transformer LM) and the compiled
KV-cache generation path."""
from . import generate, mlp, moe_lm, resnet, transformer
from .generate import (KVCache, decode_step, init_cache, make_generate_fn,
                       prefill)
from .generate import generate as generate_tokens
from .mlp import DummyModel
from .moe_lm import MoETransformerLM
from .resnet import ResNet18
from .transformer import TransformerLM
