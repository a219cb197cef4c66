"""GPT-2 (Radford et al. 2019; openai-community/gpt2-xl) as plain float32
``jax.numpy``: learned positions, pre-norm LayerNorm, biased fused QKV,
multi-head causal attention, tanh-GELU MLP at 4x, tied output head.

Independent of the program under test: it imports nothing of it and
computes on weights the benchmark makes from the seed. ``mm`` is the
matrix product every layer uses; the control passes one that rounds its
operands to a lower precision (chipbench/lowprec.py)."""

import math

import jax.numpy as jnp


def leaf_specs(cfg):
    """(name, shape, init, std) of every weight. ``init``: ``normal`` is
    N(0, std), ``gain`` is 1 + N(0, std)."""
    d, v, p = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    s = cfg["initializer_range"]
    sr = s / math.sqrt(2 * cfg["n_layer"])
    return {
        "globals": [("wte", (v, d), "normal", s), ("wpe", (p, d), "normal", s),
                    ("lnf_g", (d,), "gain", s), ("lnf_b", (d,), "normal", s)],
        "layer": [("ln1_g", (d,), "gain", s), ("ln1_b", (d,), "normal", s),
                  ("w_qkv", (d, 3 * d), "normal", s),
                  ("b_qkv", (3 * d,), "normal", s),
                  ("w_o", (d, d), "normal", sr), ("b_o", (d,), "normal", s),
                  ("ln2_g", (d,), "gain", s), ("ln2_b", (d,), "normal", s),
                  ("w_fc", (d, 4 * d), "normal", s),
                  ("b_fc", (4 * d,), "normal", s),
                  ("w_proj", (4 * d, d), "normal", sr),
                  ("b_proj", (d,), "normal", s)],
    }


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def embed(g, tokens, cfg):
    """tokens (B, S) -> (B, S, D)."""
    return g["wte"][tokens] + g["wpe"][jnp.arange(tokens.shape[1])]


def block(w, x, cfg, mm=jnp.matmul):
    b, s, d = x.shape
    h, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    dh = d // h
    y = layer_norm(x, w["ln1_g"], w["ln1_b"], eps)
    q, k, v = jnp.split(mm(y, w["w_qkv"]) + w["b_qkv"], 3, axis=-1)
    q, k, v = (t.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
               for t in (q, k, v))
    scores = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(dh)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    scores = scores - jnp.max(scores, -1, keepdims=True)
    p = jnp.exp(scores)
    p = p / jnp.sum(p, -1, keepdims=True)
    o = mm(p, v).transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + mm(o, w["w_o"]) + w["b_o"]
    y = layer_norm(x, w["ln2_g"], w["ln2_b"], eps)
    y = gelu_new(mm(y, w["w_fc"]) + w["b_fc"])
    return x + mm(y, w["w_proj"]) + w["b_proj"]


def head(g, x, cfg, mm=jnp.matmul):
    """Final LayerNorm and the tied output head: (..., D) -> (..., V)."""
    y = layer_norm(x, g["lnf_g"], g["lnf_b"], cfg["layer_norm_epsilon"])
    return mm(y, g["wte"].T)
