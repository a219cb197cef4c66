"""Xing4.0-29B-A4B (XingChen-AGI, ``config.json``) as plain float32
``jax.numpy``: multi-head latent attention (DeepSeek-V2/V3's MLA: low-rank
queries and keys/values, one rotary key part shared by all heads, YaRN
frequencies), a leading dense SwiGLU layer, then expert layers (sigmoid
scores, bias-corrected top-k, renormalised and scaled, one shared expert),
all under four residual streams mixed by manifold-constrained
hyper-connections (mHC; Hyper-Connections, Zhu et al. 2024, with the
stream mix projected onto the doubly stochastic matrices by Sinkhorn).

Written from the description in ``ISSUE.md`` (PR 28), not from the
program: no cache, no absorbed form (keys and values are widened from the
latent for every token), no sorting (every expert runs over every token
and a mask keeps what the router chose), one request at a time so that
the float32 scores of 2560 tokens fit. It imports nothing of the program.

How it meets the harness (``chipbench/weights.py``, ``serve_logits.py``),
each a departure from "one spec a layer, one block a layer":

- the harness walks ``cfg["n_layer"]`` layers of ONE leaf spec through
  ``block(w, x, cfg, mm)`` with no layer index. Those are the expert
  layers. The leading dense layer's leaves live in ``globals`` under the
  prefix ``d_`` and run inside ``embed``; ``embed`` takes no ``mm``, so the
  lower-precision control leaves that one layer unrounded;
- the four streams travel flattened, ``(B, S, 4 * hidden)``;
- ``mm`` (the control's rounded product) is every product of the bfloat16
  part of the deployment: projections, scores, values, experts, head. The
  router's scores and the hyper-connections' coefficients are float32 in
  the deployment and stay plain float32 products here.

Assumed where the published config is silent (the configuration file
lists them): the head reads the SUM of the streams; the coefficient norm
has no gain; the clamp is on the logits before ``exp``; ``hc_eps`` is in
the norm and in both of Sinkhorn's divisions; rotary pairs interleave."""

import math

import jax
import jax.numpy as jnp

N_STREAMS_KEY = "hc_mult"


def _attn_specs(cfg, pre):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    gs, m = cfg["init_norm_gain_std"], cfg["init_matrix_gain"]
    std = lambda fan: m / math.sqrt(fan)
    return [(pre + "ln1_g", (d,), "gain", gs),
            (pre + "w_qa", (d, qr), "normal", std(d)),
            (pre + "qa_g", (qr,), "gain", gs),
            (pre + "w_qb", (qr, h * (dn + dr)), "normal", std(qr)),
            (pre + "w_kva", (d, kr + dr), "normal", std(d)),
            (pre + "kva_g", (kr,), "gain", gs),
            (pre + "w_kvb", (kr, h * (dn + dv)), "normal", std(kr)),
            (pre + "w_o", (h * dv, d), "normal", std(h * dv))]


def _hc_specs(cfg, pre):
    n, nd = cfg[N_STREAMS_KEY], cfg[N_STREAMS_KEY] * cfg["hidden_size"]
    out = []
    for sub in ("hc1_", "hc2_"):
        p = pre + sub
        std = cfg["init_matrix_gain"] / math.sqrt(nd)
        out += [(p + "p_pre", (nd, n), "normal", std),
                (p + "p_post", (nd, n), "normal", std),
                (p + "p_res", (nd, n * n), "normal",
                 cfg["init_hc_res_gain"] / math.sqrt(nd)),
                (p + "a_pre", (), "gain", cfg["init_hc_scale_std"]),
                (p + "a_post", (), "gain", cfg["init_hc_scale_std"]),
                (p + "a_res", (), "gain", cfg["init_hc_scale_std"]),
                (p + "b_pre", (n,), "normal", cfg["init_hc_bias_std"]),
                (p + "b_post", (n,), "normal", cfg["init_hc_bias_std"]),
                (p + "b_res", (n, n), "normal", cfg["init_hc_res_bias_std"])]
    return out


def leaf_specs(cfg):
    """(name, shape, init, std) of every weight. ``init``: ``normal`` is
    N(0, std), ``gain`` is 1 + N(0, std). A matrix's std is
    ``init_matrix_gain / sqrt(fan in)``, the down projections' smaller
    (``init_down_gain``, ``init_shared_down_gain``), so that a sublayer
    adds a tenth to a third of the stream's RMS, as in a trained network,
    and a routed expert's smaller again (``init_expert_down_gain``), so
    that one expert swapped for another at a near-tie of the router moves
    the stream by a few percent: random experts are independent, a
    trained router's near-ties are between experts that do alike."""
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    e, fe = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    gs, m = cfg["init_norm_gain_std"], cfg["init_matrix_gain"]
    std = lambda fan, gain=m: gain / math.sqrt(fan)
    dense = _attn_specs(cfg, "d_") + [
        ("d_ln2_g", (d,), "gain", gs),
        ("d_w_gate", (d, f), "normal", std(d)),
        ("d_w_up", (d, f), "normal", std(d)),
        ("d_w_down", (f, d), "normal", std(f, cfg["init_down_gain"])),
    ] + _hc_specs(cfg, "d_")
    ge = cfg["init_expert_down_gain"]
    expert = _attn_specs(cfg, "") + [
        ("ln2_g", (d,), "gain", gs),
        ("w_router", (d, e), "normal", std(d)),
        ("b_router", (e,), "normal", cfg["init_router_bias_std"]),
        ("we_gate", (e, d, fe), "normal", std(d)),
        ("we_up", (e, d, fe), "normal", std(d)),
        ("we_down", (e, fe, d), "normal", std(fe, ge)),
        ("ws_gate", (d, fs), "normal", std(d)),
        ("ws_up", (d, fs), "normal", std(d)),
        ("ws_down", (fs, d), "normal",
         std(fs, cfg["init_shared_down_gain"])),
    ] + _hc_specs(cfg, "")
    return {
        "globals": [("wte", (v, d), "normal", cfg["init_embed_std"]),
                    ("lnf_g", (d,), "gain", gs),
                    ("w_head", (d, v), "normal", std(d))] + dense,
        "layer": expert,
    }


# -- the equations ------------------------------------------------------------

def rms(v, g, eps):
    return v / jnp.sqrt(jnp.mean(jnp.square(v), -1, keepdims=True) + eps) * g


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def logistic(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def yarn_frequencies(cfg):
    """The rotary part's ``rope / 2`` frequencies under YaRN."""
    y, dr, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]
    orig = y["original_max_position_embeddings"]
    i = jnp.arange(dr // 2, dtype=jnp.float32)
    f = base ** (-2.0 * i / dr)

    def turns_at(b):
        return dr * math.log(orig / (2 * math.pi * b)) / (2 * math.log(base))

    lo = max(math.floor(turns_at(y["beta_fast"])), 0)
    hi = min(math.ceil(turns_at(y["beta_slow"])), dr - 1)
    r = jnp.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return f / y["factor"] * r + f * (1.0 - r)


def mscale(factor, t):
    return 0.1 * t * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotate(x, cfg):
    """x (..., S, rope) by position along axis -2, interleaved pairs."""
    y = cfg["rope_scaling"]
    mult = mscale(y["factor"], y["mscale"]) \
        / mscale(y["factor"], y["mscale_all_dim"])
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] \
        * yarn_frequencies(cfg)[None, :]
    cos, sin = jnp.cos(ang) * mult, jnp.sin(ang) * mult
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape)


def attention(w, p, u, cfg, mm):
    """One request: u (S, D) -> (S, D). ``p`` prefixes the leaf names."""
    s = u.shape[0]
    h = cfg["num_attention_heads"]
    kr = cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps, y = cfg["rms_norm_eps"], cfg["rope_scaling"]
    un = rms(u, w[p + "ln1_g"], eps)
    c_q = rms(mm(un, w[p + "w_qa"]), w[p + "qa_g"], eps)
    q = mm(c_q, w[p + "w_qb"]).reshape(s, h, dn + dr).transpose(1, 0, 2)
    q_n, q_r = q[..., :dn], rotate(q[..., dn:], cfg)            # (H, S, .)
    ckr = mm(un, w[p + "w_kva"])
    c = rms(ckr[:, :kr], w[p + "kva_g"], eps)
    k_r = rotate(ckr[:, kr:], cfg)                               # (S, rope)
    kv = mm(c, w[p + "w_kvb"]).reshape(s, h, dn + dv).transpose(1, 0, 2)
    k_n, v = kv[..., :dn], kv[..., dn:]
    scale = (dn + dr) ** -0.5 * mscale(y["factor"], y["mscale_all_dim"]) ** 2
    scores = (mm(q_n, k_n.transpose(0, 2, 1))
              + mm(q_r, k_r.T[None])) * scale                    # (H, S, S)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    scores = jnp.where(j <= i, scores, -jnp.inf)
    scores = scores - jnp.max(scores, -1, keepdims=True)
    pr = jnp.exp(scores)
    pr = pr / jnp.sum(pr, -1, keepdims=True)
    o = mm(pr, v).transpose(1, 0, 2).reshape(s, h * dv)
    return mm(o, w[p + "w_o"])


def dense_ffn(w, p, u, cfg, mm):
    un = rms(u, w[p + "ln2_g"], cfg["rms_norm_eps"])
    return mm(silu(mm(un, w[p + "w_gate"])) * mm(un, w[p + "w_up"]),
              w[p + "w_down"])


def expert_ffn(w, p, u, cfg, mm):
    """Every expert over every token; the router's choice is a mask."""
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    un = rms(u, w[p + "ln2_g"], cfg["rms_norm_eps"])
    g = logistic(jnp.matmul(un, w[p + "w_router"]))              # (S, E)
    biased = g + w[p + "b_router"]
    # the k largest: an expert is chosen where fewer than k are larger
    # (ties to the lower index, as a sort would have it)
    larger = (biased[:, None, :] > biased[:, :, None]) | (
        (biased[:, None, :] == biased[:, :, None])
        & (jnp.arange(e)[None, None, :] < jnp.arange(e)[None, :, None]))
    chosen = jnp.sum(larger, -1) < k                             # (S, E)
    top = jnp.where(chosen, g, 0.0)
    weight = top / (jnp.sum(top, -1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]

    def one(acc, xs):
        wg, wu, wd, col = xs
        y = mm(silu(mm(un, wg)) * mm(un, wu), wd)
        return acc + col[:, None] * y, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (w[p + "we_gate"], w[p + "we_up"], w[p + "we_down"], weight.T))
    shared = mm(silu(mm(un, w[p + "ws_gate"])) * mm(un, w[p + "ws_up"]),
                w[p + "ws_down"])
    return routed + shared


def hyper(w, p, xs, sublayer, cfg):
    """xs (S, n, D) -> (S, n, D): one sublayer under its residual path."""
    n, eps = cfg[N_STREAMS_KEY], cfg["hc_eps"]
    s = xs.shape[0]
    flat = xs.reshape(s, -1)
    flat = flat / jnp.sqrt(jnp.mean(jnp.square(flat), -1, keepdims=True) + eps)
    pre = w[p + "a_pre"] * jnp.matmul(flat, w[p + "p_pre"]) + w[p + "b_pre"]
    post = w[p + "a_post"] * jnp.matmul(flat, w[p + "p_post"]) + w[p + "b_post"]
    res = w[p + "a_res"] * jnp.matmul(flat, w[p + "p_res"]).reshape(s, n, n) \
        + w[p + "b_res"]
    h_pre, h_post = logistic(pre), 2.0 * logistic(post)
    m = jnp.exp(jnp.clip(res, cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"]))
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)            # rows
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)            # columns
    u = jnp.einsum("sn,snd->sd", h_pre, xs)
    y = sublayer(u)
    return jnp.einsum("snm,smd->snd", m, xs) + h_post[:, :, None] * y[:, None]


def layer(w, p, xs, cfg, mm, ffn):
    xs = hyper(w, p + "hc1_", xs, lambda u: attention(w, p, u, cfg, mm), cfg)
    return hyper(w, p + "hc2_", xs, lambda u: ffn(w, p, u, cfg, mm), cfg)


def _per_request(fn, x, cfg):
    """``fn`` over each request of x (B, S, n * D) in turn."""
    n = cfg[N_STREAMS_KEY]
    b, s, nd = x.shape
    out = jax.lax.map(lambda xr: fn(xr.reshape(s, n, nd // n)).reshape(s, nd),
                      x)
    return out


# -- what the harness calls -----------------------------------------------------

def embed(g, tokens, cfg):
    """Four copies of each token's embedding row, then the leading dense
    layer (``first_k_dense_replace`` = 1 here), streams flattened."""
    n = cfg[N_STREAMS_KEY]
    x0 = jnp.tile(g["wte"][tokens], (1, 1, n))                   # (B,S,n*D)
    return _per_request(
        lambda xs: layer(g, "d_", xs, cfg, jnp.matmul, dense_ffn), x0, cfg)


def block(w, x, cfg, mm=jnp.matmul):
    """One expert layer over x (B, S, n * D)."""
    return _per_request(lambda xs: layer(w, "", xs, cfg, mm, expert_ffn),
                        x, cfg)


def head(g, x, cfg, mm=jnp.matmul):
    n = cfg[N_STREAMS_KEY]
    xs = x.reshape(x.shape[:-1] + (n, x.shape[-1] // n))
    h = rms(jnp.sum(xs, -2), g["lnf_g"], cfg["rms_norm_eps"])
    return mm(h, g["w_head"])
