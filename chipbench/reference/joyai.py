"""JoyAI-LLM-Flash (jdopensource, ``config.json``: DeepSeek-V3's keys one
for one) as plain float32 ``jax.numpy``, for training: multi-head latent
attention (low-rank queries and keys/values, one rotary key part shared by
all heads, plain rotary frequencies at ``rope_theta``, interleaved pairs),
a leading dense SwiGLU layer, expert layers (sigmoid scores over all
``router_width`` experts, bias-corrected top-k, renormalised and scaled,
one shared expert), and one multi-token-prediction module (DeepSeek-V3
section 2.2) that shares the embedding and the head.

Written from the equations in ``ISSUE.md`` (PR 32), not from the program,
of which it imports nothing: no kernel, no sorting (every held expert runs
over every token and the router's choice is a mask), no grouped matmul,
one request at a time and one head at a time, so that the float32 scores
of 8192 positions fit (a head's are recomputed in the backward pass, which
changes no value). The chip holds ``n_routed_experts`` experts from
``experts_held_first`` on and computes the part of the sum they give; the
router and the load it reports are ``router_width`` wide.

How it meets ``chipbench/weights.py`` (one leaf spec a walked layer): the
walked layers are the ``n_layer`` expert layers; the leading dense layer's
leaves (prefix ``d_``) and the prediction module's (prefix ``m_``) live in
``globals`` beside the embedding, the final norm and the head.

``mm`` (the control's rounded product) is every product of the bfloat16
part of the deployment: attention, experts, the module's merge, the heads.
The router's scores are float32 in the deployment and stay plain float32
products here; the dense layer runs unrounded too, as in the Xing4
control."""

import math

import jax
import jax.numpy as jnp


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
            (pre + "w_o", (h * dv, d), "normal", std(h * dv)),
            (pre + "ln2_g", (d,), "gain", gs)]


def _expert_specs(cfg, pre):
    d, e, r = cfg["hidden_size"], cfg["n_routed_experts"], cfg["router_width"]
    fe = cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    std = lambda fan, gain=cfg["init_matrix_gain"]: gain / math.sqrt(fan)
    return _attn_specs(cfg, pre) + [
        (pre + "w_router", (d, r), "normal", std(d)),
        (pre + "b_router", (r,), "normal", cfg["init_router_bias_std"]),
        (pre + "we_gate", (e, d, fe), "normal", std(d)),
        (pre + "we_up", (e, d, fe), "normal", std(d)),
        (pre + "we_down", (e, fe, d), "normal",
         std(fe, cfg["init_expert_down_gain"])),
        (pre + "ws_gate", (d, fs), "normal", std(d)),
        (pre + "ws_up", (d, fs), "normal", std(d)),
        (pre + "ws_down", (fs, d), "normal",
         std(fs, cfg["init_shared_down_gain"]))]


def leaf_specs(cfg):
    """(name, shape, init, std) of every weight. ``init``: ``normal`` is
    N(0, std), ``gain`` is 1 + N(0, std). The scales are the Xing4
    configuration's (its file says why each)."""
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    gs, m = cfg["init_norm_gain_std"], cfg["init_matrix_gain"]
    std = lambda fan, gain=m: gain / math.sqrt(fan)
    dense = _attn_specs(cfg, "d_") + [
        ("d_w_gate", (d, f), "normal", std(d)),
        ("d_w_up", (d, f), "normal", std(d)),
        ("d_w_down", (f, d), "normal", std(f, cfg["init_down_gain"]))]
    module = [("m_ne_g", (d,), "gain", gs), ("m_nh_g", (d,), "gain", gs),
              ("m_w_eh", (2 * d, d), "normal", std(2 * d)),
              ("m_lnf_g", (d,), "gain", gs)] + _expert_specs(cfg, "m_")
    return {
        "globals": [("wte", (v, d), "normal", cfg["init_embed_std"]),
                    ("lnf_g", (d,), "gain", gs),
                    ("w_head", (d, v), "normal", std(d))] + dense + module,
        "layer": _expert_specs(cfg, ""),
    }


def used_by(part, name):
    """Whether the piece ``part`` of a step reads the global ``name``:
    ``head`` (the final norm and the head), ``mtp`` (the prediction
    module, with the embedding and the head it shares), ``embed`` (the
    embedding and the leading dense layer)."""
    return {"head": name in ("lnf_g", "w_head"),
            "mtp": name.startswith("m_") or name in ("wte", "w_head"),
            "embed": name.startswith("d_") or name == "wte"}[part]


def is_router_bias(name):
    """The leaves no gradient moves: a rule of their own updates them."""
    return name.endswith("b_router")


# -- the equations ------------------------------------------------------------

def rms(v, g, eps):
    return v / jnp.sqrt(jnp.mean(jnp.square(v), -1, keepdims=True) + eps) * g


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def logistic(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def rotate(x, cfg):
    """x (..., S, rope) by position along axis -2, interleaved pairs."""
    dr = cfg["qk_rope_head_dim"]
    f = cfg["rope_theta"] ** (-2.0 * jnp.arange(dr // 2,
                                                dtype=jnp.float32) / dr)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * f[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def attention(w, p, u, cfg, mm):
    """One request: u (S, D) -> (S, D). ``p`` prefixes the leaf names."""
    s = u.shape[0]
    h, kr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    un = rms(u, w[p + "ln1_g"], eps)
    c_q = rms(mm(un, w[p + "w_qa"]), w[p + "qa_g"], eps)
    q = mm(c_q, w[p + "w_qb"]).reshape(s, h, dn + dr).transpose(1, 0, 2)
    q_n, q_r = q[..., :dn], rotate(q[..., dn:], cfg)            # (H, S, .)
    ckr = mm(un, w[p + "w_kva"])
    c = rms(ckr[:, :kr], w[p + "kva_g"], eps)
    k_r = rotate(ckr[:, kr:], cfg)                               # (S, rope)
    kv = mm(c, w[p + "w_kvb"]).reshape(s, h, dn + dv).transpose(1, 0, 2)
    k_n, v = kv[..., :dn], kv[..., dn:]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def one_head(args):
        qn, qr, kn, vh = args
        scores = (mm(qn, kn.T) + mm(qr, k_r.T)) / math.sqrt(dn + dr)
        scores = jnp.where(causal, scores, -jnp.inf)
        scores = scores - jnp.max(scores, -1, keepdims=True)
        pr = jnp.exp(scores)
        return mm(pr / jnp.sum(pr, -1, keepdims=True), vh)

    o = jax.lax.map(jax.checkpoint(one_head), (q_n, q_r, k_n, v))  # (H,S,dv)
    return mm(o.transpose(1, 0, 2).reshape(s, h * dv), w[p + "w_o"])


def dense_ffn(w, p, u, cfg, mm):
    un = rms(u, w[p + "ln2_g"], cfg["rms_norm_eps"])
    return mm(silu(mm(un, w[p + "w_gate"])) * mm(un, w[p + "w_up"]),
              w[p + "w_down"])


def expert_ffn(w, p, u, cfg, mm):
    """The held experts over every token, the router's choice a mask:
    (the part of the layer's output computed here (S, D), the pairs the
    router sent to each of all ``router_width`` experts)."""
    k = cfg["num_experts_per_tok"]
    first, held = cfg["experts_held_first"], cfg["n_routed_experts"]
    un = rms(u, w[p + "ln2_g"], cfg["rms_norm_eps"])
    g = logistic(jnp.matmul(un, w[p + "w_router"]))              # (S, E)
    # the k largest of g + b, ties to the lower index
    order = jnp.argsort(-(g + w[p + "b_router"]), axis=-1, stable=True)
    chosen = jnp.zeros(g.shape, bool).at[
        jnp.arange(g.shape[0])[:, None], order[:, :k]].set(True)
    top = jnp.where(chosen, g, 0.0)
    weight = top / (jnp.sum(top, -1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]

    def one(acc, xs):
        wg, wu, wd, col = xs
        y = mm(silu(mm(un, wg)) * mm(un, wu), wd)
        return acc + col[:, None] * y, None

    # (an expert's projections are recomputed in the backward pass, which
    # changes no value: sixteen experts' would be 1.6 GB at 8192 tokens)
    routed, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(u),
        (w[p + "we_gate"], w[p + "we_up"], w[p + "we_down"],
         weight[:, first:first + held].T))
    shared = mm(silu(mm(un, w[p + "ws_gate"])) * mm(un, w[p + "ws_up"]),
                w[p + "ws_down"])
    return routed + shared, jnp.sum(chosen, 0).astype(jnp.int32)


def expert_layer(w, p, u, cfg, mm):
    """u (S, D) -> (the block's output, its router's load)."""
    u = u + attention(w, p, u, cfg, mm)
    y, load = expert_ffn(w, p, u, cfg, mm)
    return u + y, load


def _per_request(fn, x):
    """``fn`` (S, D) -> (S, D), load over each request of x (B, S, D) in
    turn; the loads added up."""
    y, load = jax.lax.map(fn, x)
    return y, jnp.sum(load, 0)


# -- what the follower calls ----------------------------------------------------

def embed(g, tokens, cfg):
    """tokens (B, S) -> (B, S, D): the embedding, then the leading dense
    layer (``first_k_dense_replace`` = 1), unrounded under the control."""
    def one(u):
        u = u + attention(g, "d_", u, cfg, jnp.matmul)
        return u + dense_ffn(g, "d_", u, cfg, jnp.matmul)
    return jax.lax.map(one, g["wte"][tokens])


def block(w, x, cfg, mm=jnp.matmul):
    """One expert layer over x (B, S, D) -> (x, load (router_width,))."""
    return _per_request(lambda u: expert_layer(w, "", u, cfg, mm), x)


def head(g, x, cfg, mm=jnp.matmul):
    """The final norm and the untied head: (..., D) -> (..., V)."""
    return mm(rms(x, g["lnf_g"], cfg["rms_norm_eps"]), g["w_head"])


def mtp_logits(g, h, tokens, cfg, mm=jnp.matmul):
    """The prediction module: h (B, S, D) the last block's output for
    tokens[:, :S], tokens (B, S + 1) -> (logits (B, S - 1, V) against
    tokens[:, 2:], the module's router's load)."""
    eps = cfg["rms_norm_eps"]

    def one(args):
        hr, tr = args
        merged = jnp.concatenate([rms(g["wte"][tr[1:-1]], g["m_ne_g"], eps),
                                  rms(hr[:-1], g["m_nh_g"], eps)], -1)
        return expert_layer(g, "m_", mm(merged, g["m_w_eh"]), cfg, mm)

    m, load = jax.lax.map(one, (h, tokens))
    return mm(rms(m, g["m_lnf_g"], eps), g["w_head"]), jnp.sum(load, 0)


def cross_entropy_sum(logits, labels):
    logz = jax.nn.logsumexp(logits, -1)
    hit = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(logz - hit)


def main_loss_sum(g, h, tokens, cfg, mm=jnp.matmul):
    """The main head's cross-entropy against tokens[:, 1:], summed over
    these rows' positions. h (B, S, D): the last block's output."""
    return cross_entropy_sum(head(g, h, cfg, mm), tokens[:, 1:])


def mtp_loss_sum(g, h, tokens, cfg, mm=jnp.matmul):
    """The prediction module's cross-entropy against tokens[:, 2:],
    summed, and the module's router's load."""
    logits, load = mtp_logits(g, h, tokens, cfg, mm)
    return cross_entropy_sum(logits, tokens[:, 2:]), load


def balance(bias, load, speed):
    """``noaux_tc``: b_e + speed * sign(mean(load) - load_e)."""
    load = load.astype(jnp.float32)
    return bias + speed * jnp.sign(jnp.mean(load) - load)
