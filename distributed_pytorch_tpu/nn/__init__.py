"""Functional nn modules (pytree params, pure apply)."""
from . import attention, block, conv, core, hyper, latent, paged
from .attention import (MultiHeadAttention, TransformerBlock, dense_attention)
from .block import Block
from .conv import BatchNorm2d, Conv2d, global_avg_pool, max_pool
from .core import (Dropout, Embedding, GatedMLP, LayerNorm, Linear, Module,
                   Params, RMSNorm, Sequential, gelu, relu)
from .hyper import HyperConnection
from .latent import LatentAttention
