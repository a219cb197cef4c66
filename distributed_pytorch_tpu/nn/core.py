"""Functional neural-net modules: params are pytrees, apply is pure.

The reference builds models from ``torch.nn`` (``min_DDP.py:41-49``). This
framework's module system is deliberately functional — ``init(key)`` returns
a params pytree, ``apply(params, x)`` is a pure function — because that is
what compiles cleanly under ``jit``/``pjit``: parameters are explicit inputs
the sharding machinery can annotate (replicated for DP, axis-sharded for TP),
and a whole training step closes over nothing.

The modules a transformer is made of put their kind into the JAX name
stack (``jax.named_scope``: ``embed``, ``norm``): names only, the
computation is the same, and a profiler trace can then say which layer a
fused device operation belongs to (PERF.md section 3 lists every scope).

Initialization follows the same fan-in uniform scheme torch's ``Linear``
uses (U(-1/sqrt(fan_in), 1/sqrt(fan_in)) for both weight and bias), so
model-quality behavior matches the reference workload's.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


class Module:
    """Base: subclasses define ``init(key) -> params`` and
    ``apply(params, x, **kw) -> out``."""

    def init(self, key) -> Params:
        raise NotImplementedError

    def apply(self, params: Params, x, **kwargs):
        raise NotImplementedError

    def __call__(self, params: Params, x, **kwargs):
        return self.apply(params, x, **kwargs)


class Linear(Module):
    """Affine map ``x @ W + b`` (the reference model's only layer type,
    ``min_DDP.py:44-45``). Weight stored as (in, out) — the layout the MXU
    wants for ``x @ W`` without a transpose."""

    def __init__(self, in_dim: int, out_dim: int, bias: bool = True,
                 dtype=jnp.float32):
        self.in_dim = in_dim
        self.out_dim = out_dim
        self.bias = bias
        self.dtype = dtype

    def init(self, key) -> Params:
        kw, kb = jax.random.split(key)
        bound = 1.0 / math.sqrt(self.in_dim)
        p = {"w": jax.random.uniform(kw, (self.in_dim, self.out_dim),
                                     self.dtype, -bound, bound)}
        if self.bias:
            p["b"] = jax.random.uniform(kb, (self.out_dim,), self.dtype,
                                        -bound, bound)
        return p

    def apply(self, params: Params, x, **_):
        # params may hold the weight int8-quantized ({"w_q","w_scale"},
        # ops/quant.py); the dequant fuses into the matmul so HBM streams
        # the int8 bytes
        from ..ops.quant import resolve_weight
        y = jnp.matmul(x, resolve_weight(params, "w", self.dtype))
        if self.bias:
            y = y + params["b"]
        return y


class Embedding(Module):
    def __init__(self, vocab: int, dim: int, std: float = 1.0,
                 dtype=jnp.float32):
        self.vocab = vocab
        self.dim = dim
        # N(0, std). The default keeps historical behavior; models whose
        # table doubles as the output projection (tied embeddings) MUST
        # use a small std — at std=1 the tied logits come out with
        # ~sqrt(dim) scale and the loss diverges within a few steps
        # (TransformerLM passes dim**-0.5 for its tables).
        self.std = std
        self.dtype = dtype

    def init(self, key) -> Params:
        return {"emb": self.std * jax.random.normal(
            key, (self.vocab, self.dim)).astype(self.dtype)}

    def apply(self, params: Params, ids, **_):
        with jax.named_scope("embed"):
            if "emb" in params:
                return jnp.take(params["emb"], ids, axis=0)
            # int8 table (ops/quant.py): gather the int8 rows, dequantize
            # only what was looked up
            rows = jnp.take(params["emb_q"], ids,
                            axis=0).astype(self.dtype)
            return rows * params["emb_scale"].astype(self.dtype)


class LayerNorm(Module):
    def __init__(self, dim: int, eps: float = 1e-5, dtype=jnp.float32,
                 scope: str = "norm"):
        self.dim = dim
        self.eps = eps
        self.dtype = dtype
        self.scope = scope      # its name in the JAX name stack

    def init(self, key) -> Params:
        del key
        return {"scale": jnp.ones((self.dim,), self.dtype),
                "bias": jnp.zeros((self.dim,), self.dtype)}

    def apply(self, params: Params, x, **_):
        with jax.named_scope(self.scope):
            mu = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.var(x, axis=-1, keepdims=True)
            y = (x - mu) * jax.lax.rsqrt(var + self.eps)
            return y * params["scale"] + params["bias"]


class RMSNorm(Module):
    """``x / sqrt(mean(x^2) + eps) * scale``: no mean, no bias. The
    statistic is float32 whatever the activations are; the result comes
    back in the input's type."""

    def __init__(self, dim: int, eps: float = 1e-6, dtype=jnp.float32,
                 scope: str = "norm"):
        self.dim = dim
        self.eps = eps
        self.dtype = dtype
        self.scope = scope

    def init(self, key) -> Params:
        del key
        return {"scale": jnp.ones((self.dim,), self.dtype)}

    def apply(self, params: Params, x, **_):
        with jax.named_scope(self.scope):
            xf = x.astype(jnp.float32)
            y = xf * jax.lax.rsqrt(
                jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + self.eps)
            return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


class GatedMLP(Module):
    """``down(silu(gate(x)) * up(x))`` of any width, no biases (SwiGLU).
    The feed-forward of a dense layer and of one expert alike; it does
    not norm its input."""

    def __init__(self, dim: int, hidden: int, dtype=jnp.float32):
        self.dim = dim
        self.hidden = hidden
        self.gate = Linear(dim, hidden, bias=False, dtype=dtype)
        self.up = Linear(dim, hidden, bias=False, dtype=dtype)
        self.down = Linear(hidden, dim, bias=False, dtype=dtype)

    def init(self, key) -> Params:
        kg, ku, kd = jax.random.split(key, 3)
        return {"gate": self.gate.init(kg), "up": self.up.init(ku),
                "down": self.down.init(kd)}

    def apply(self, params: Params, x, **_):
        with jax.named_scope("mlp"):
            h = jax.nn.silu(self.gate.apply(params["gate"], x)) \
                * self.up.apply(params["up"], x)
            return self.down.apply(params["down"], h)


class Dropout(Module):
    """Stateless dropout: pass ``rng=`` and ``train=True`` to drop."""

    def __init__(self, rate: float):
        self.rate = rate

    def init(self, key) -> Params:
        del key
        return {}

    def apply(self, params: Params, x, *, rng=None, train: bool = False, **_):
        del params
        if not train or self.rate <= 0.0 or rng is None:
            return x
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0)


class Sequential(Module):
    """Named chain of modules; params nest under each layer's name."""

    def __init__(self, layers: Sequence[Tuple[str, Module]]):
        self.layers = list(layers)

    def init(self, key) -> Params:
        keys = jax.random.split(key, max(len(self.layers), 1))
        return {name: mod.init(k)
                for (name, mod), k in zip(self.layers, keys)}

    def apply(self, params: Params, x, **kwargs):
        for name, mod in self.layers:
            x = mod.apply(params[name], x, **kwargs)
        return x


def relu(x):
    return jnp.maximum(x, 0)


def gelu(x):
    return jax.nn.gelu(x)
