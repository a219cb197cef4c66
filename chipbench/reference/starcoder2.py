"""StarCoder2 (Lozhkov et al. 2024; bigcode/starcoder2-3b) as plain
float32 ``jax.numpy``: rotary positions (half-split pairing, theta from
the config), pre-norm LayerNorm, biased projections, grouped-query causal
attention inside the published sliding window, tanh-GELU MLP, tied head.

Independent of the program under test (see reference/gpt2.py). The
program is built with no window; this reference has the published one, so
agreement shows it never binds at the contexts the cells send."""

import math

import jax.numpy as jnp


def leaf_specs(cfg):
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    dh = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * dh
    s = cfg["initializer_range"]
    return {
        "globals": [("wte", (v, d), "normal", s),
                    ("lnf_g", (d,), "gain", s), ("lnf_b", (d,), "normal", s)],
        "layer": [("ln1_g", (d,), "gain", s), ("ln1_b", (d,), "normal", s),
                  ("w_qkv", (d, d + 2 * kv), "normal", s),
                  ("b_qkv", (d + 2 * kv,), "normal", s),
                  ("w_o", (d, d), "normal", s), ("b_o", (d,), "normal", s),
                  ("ln2_g", (d,), "gain", s), ("ln2_b", (d,), "normal", s),
                  ("w_fc", (d, f), "normal", s), ("b_fc", (f,), "normal", s),
                  ("w_proj", (f, d), "normal", s),
                  ("b_proj", (d,), "normal", s)],
    }


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def rotate(x, theta):
    """Rotary embedding on (B, H, S, Dh): dims [0, Dh/2) pair with
    [Dh/2, Dh) (the ``rotate_half`` convention of the published code)."""
    s, dh = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def embed(g, tokens, cfg):
    return g["wte"][tokens]


def block(w, x, cfg, mm=jnp.matmul):
    b, s, d = x.shape
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, eps = d // h, cfg["norm_epsilon"]
    y = layer_norm(x, w["ln1_g"], w["ln1_b"], eps)
    qkv = mm(y, w["w_qkv"]) + w["b_qkv"]
    q, k, v = jnp.split(qkv, [d, d + hkv * dh], axis=-1)
    q = q.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
    q, k = rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"])
    k, v = (jnp.repeat(t, h // hkv, axis=1) for t in (k, v))
    scores = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(dh)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (j <= i) & (j > i - cfg["sliding_window"])
    scores = jnp.where(seen, scores, -jnp.inf)
    scores = scores - jnp.max(scores, -1, keepdims=True)
    p = jnp.exp(scores)
    p = p / jnp.sum(p, -1, keepdims=True)
    o = mm(p, v).transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + mm(o, w["w_o"]) + w["b_o"]
    y = layer_norm(x, w["ln2_g"], w["ln2_b"], eps)
    y = gelu_tanh(mm(y, w["w_fc"]) + w["b_fc"])
    return x + mm(y, w["w_proj"]) + w["b_proj"]


def head(g, x, cfg, mm=jnp.matmul):
    y = layer_norm(x, g["lnf_g"], g["lnf_b"], cfg["norm_epsilon"])
    return mm(y, g["wte"].T)
