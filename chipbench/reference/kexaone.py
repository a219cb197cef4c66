"""K-EXAONE-236B-A23B (LGAI-EXAONE, ``config.json``, ``model_type:
exaone_moe``) as plain float32 ``jax.numpy``: grouped-query attention, 64
query heads over 8 KV heads of 128, an RMSNorm over each head's values on
q and on k; three layers in four see only the last ``sliding_window``
keys and rotate q and k (RoPE, rotate-half), the fourth sees every earlier
key and rotates nothing; a leading dense gated-SiLU layer, then expert
layers (sigmoid scores over all 128 experts, the 8 largest of score + bias
chosen, the chosen scores renormalised and scaled, one shared expert), of
whose routed experts this chip holds ``[experts_held_first,
experts_held_first + num_experts)``.

Written from the equations in ``ISSUE.md`` (PR 43), not from the program:
no cache, no ring, no pages, no kernel, no sorting of tokens (every held
expert runs over every token and a mask keeps what the router chose), one
request at a time. It imports nothing of the program.

What the sizes force, and nothing else: a request of 33 280 tokens does
not fit as one array a step (the dense layer's hidden activations alone
would be 3 x 2.4 GB in float32, one head's scores over every key 4.4 GB),
so every per-token part runs over ``ROWS`` tokens at a time and attention
over ``ROWS`` queries at a time, each block the same arithmetic as the
whole; only the blocks that hold a real token run (``n``, the request's
length, is data), so a short request does not pay for the longest. A
window layer's block of queries reads the ``ROWS + sliding_window`` keys
it can see, a global layer's every key, both under the mask of the
equations.

How it meets the harness (``chipbench/weights.py``,
``reference/serve_logits_rows.py``):

- the harness walks ``cfg["n_layer"]`` layers of ONE leaf spec. Those are
  the expert layers, layer ``1 + i`` of the model; the leading dense
  layer's leaves live in ``globals`` under the prefix ``d_`` and run in
  ``dense_layer``. Which attention a layer has is ``layer_types[l]``;
- ``mm`` (the control's rounded product) is every product of the bfloat16
  part of the deployment: projections, scores, values, the dense layer,
  experts, head. The router's scores are float32 in the deployment and
  stay a plain float32 product here.

Assumed where ``config.json`` is silent (the configuration file lists
each with its reason): pre-norm placement, the q/k norms, no rotation on
global layers, no biases but the router's, rotate-half pairing."""

import math

import jax
import jax.numpy as jnp

#: tokens a block of the per-token parts, and queries a block of attention
ROWS = 128


def _attn_specs(cfg, pre):
    d, h, hkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    gs, m = cfg["init_norm_gain_std"], cfg["init_matrix_gain"]
    std = lambda fan: m / math.sqrt(fan)
    return [(pre + "ln1_g", (d,), "gain", gs),
            # [Wq | Wk | Wv] as one leaf: a leaf a key, so one matrix or
            # three is the same distribution, column for column
            (pre + "w_qkv", (d, (h + 2 * hkv) * dh), "normal", std(d)),
            (pre + "q_g", (dh,), "gain", gs),
            (pre + "k_g", (dh,), "gain", gs),
            (pre + "w_o", (h * dh, d), "normal", std(h * dh)),
            (pre + "ln2_g", (d,), "gain", gs)]


def leaf_specs(cfg):
    """(name, shape, init, std) of every weight. ``init``: ``normal`` is
    N(0, std), ``gain`` is 1 + N(0, std). A matrix's std is
    ``init_matrix_gain / sqrt(fan in)``, the down projections' smaller
    (``init_down_gain``, ``init_shared_down_gain``,
    ``init_expert_down_gain``), so that a sublayer adds a tenth to a third
    of the stream's RMS and one expert swapped for another at a near-tie
    of the router moves the stream by a few percent (PERF.md Findings,
    PR 28)."""
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    e, held, fe = (cfg["router_width"], cfg["num_experts"],
                   cfg["moe_intermediate_size"])
    fs = fe * cfg["num_shared_experts"]
    gs, m = cfg["init_norm_gain_std"], cfg["init_matrix_gain"]
    std = lambda fan, gain=m: gain / math.sqrt(fan)
    dense = _attn_specs(cfg, "d_") + [
        ("d_w_gate", (d, f), "normal", std(d)),
        ("d_w_up", (d, f), "normal", std(d)),
        ("d_w_down", (f, d), "normal", std(f, cfg["init_down_gain"]))]
    expert = _attn_specs(cfg, "") + [
        ("w_router", (d, e), "normal", std(d)),
        ("b_router", (e,), "normal", cfg["init_router_bias_std"]),
        ("we_gate", (held, d, fe), "normal", std(d)),
        ("we_up", (held, d, fe), "normal", std(d)),
        ("we_down", (held, fe, d), "normal",
         std(fe, cfg["init_expert_down_gain"])),
        ("ws_gate", (d, fs), "normal", std(d)),
        ("ws_up", (d, fs), "normal", std(d)),
        ("ws_down", (fs, d), "normal",
         std(fs, cfg["init_shared_down_gain"]))]
    return {"globals": [("wte", (v, d), "normal", cfg["init_embed_std"]),
                        ("lnf_g", (d,), "gain", gs),
                        ("w_head", (d, v), "normal", std(d))] + dense,
            "layer": expert}


# -- the equations ------------------------------------------------------------

def rms(v, g, eps):
    return v / jnp.sqrt(jnp.mean(jnp.square(v), -1, keepdims=True) + eps) * g


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def logistic(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def rotate(x, pos, cfg):
    """x (H, S, Dh) by ``pos`` (S,), rotate-half over all of Dh: value
    ``i`` pairs with ``i + Dh / 2``, frequency ``theta^(-2i / Dh)``."""
    half = x.shape[-1] // 2
    theta = cfg["rope_parameters"]["rope_theta"]
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def by_rows(fn, x, n, width):
    """``fn`` maps rows (ROWS, D) at positions ``pos`` (ROWS,) to rows
    (ROWS, ``width``); -> its value over the first ``n`` rows of x (S, D),
    a block of ROWS at a time, zeros past the last block that holds one."""
    s = x.shape[0]
    rows = min(ROWS, s)

    def body(i, out):
        # the last block of a length that is no multiple of ``rows``
        # starts early and computes some rows a second time
        lo = jnp.minimum(i * rows, s - rows)
        y = fn(jax.lax.dynamic_slice_in_dim(x, lo, rows), lo + jnp.arange(rows))
        return jax.lax.dynamic_update_slice_in_dim(out, y, lo, 0)

    return jax.lax.fori_loop(0, (n + rows - 1) // rows, body,
                             jnp.zeros((s, width), x.dtype))


def qkv(w, p, u, pos, cfg, mm, rotary):
    """u (R, D) at positions ``pos`` -> [q | k | v] (R, (H + 2 Hkv) Dh),
    q and k normed per head and, on a window layer, rotated."""
    r = u.shape[0]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    y = mm(rms(u, w[p + "ln1_g"], eps), w[p + "w_qkv"])
    heads = lambda t, k: t.reshape(r, k, dh).transpose(1, 0, 2)
    q = rms(heads(y[:, :h * dh], h), w[p + "q_g"], eps)
    k = rms(heads(y[:, h * dh:(h + hkv) * dh], hkv), w[p + "k_g"], eps)
    if rotary:
        q, k = rotate(q, pos, cfg), rotate(k, pos, cfg)
    flat = lambda t: t.transpose(1, 0, 2).reshape(r, -1)
    return jnp.concatenate([flat(q), flat(k), y[:, (h + hkv) * dh:]], -1)


def attend(q, k, v, pos_q, pos_k, window, cfg, mm):
    """q (R, H Dh) at ``pos_q`` over k, v (K, Hkv Dh) at ``pos_k``: query
    ``i`` sees keys ``j <= i``, and ``i - window < j`` on a window layer
    (``window`` None: every earlier key); each KV head serves H / Hkv
    query heads, one KV head's group after another (the scores of all 64
    heads over 33 280 keys would be 1.1 GB a block); softmax in float32.
    -> (R, H Dh)."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    r, g = q.shape[0], h // hkv
    seen = pos_k[None, :] <= pos_q[:, None]
    if window is not None:
        seen &= pos_q[:, None] - pos_k[None, :] < window

    def group(qkv_):
        qg, kh, vh = qkv_                   # (g, R, Dh), (K, Dh), (K, Dh)
        scores = mm(qg, kh.T[None]) / math.sqrt(dh)
        scores = jnp.where(seen[None], scores, -jnp.inf)
        scores = scores - jnp.max(scores, -1, keepdims=True)
        pr = jnp.exp(scores)
        pr = pr / jnp.sum(pr, -1, keepdims=True)
        return mm(pr, vh[None])                                # (g, R, Dv)

    heads = lambda t, n: t.reshape(t.shape[0], n, dh).transpose(1, 0, 2)
    o = jax.lax.map(group, (heads(q, h).reshape(hkv, g, r, dh),
                            heads(k, hkv), heads(v, hkv)))
    return o.reshape(h, r, dh).transpose(1, 0, 2).reshape(r, h * dh)


def attention(w, p, x, n, cfg, mm, window):
    """One request: x (S, D), its first ``n`` rows real -> (S, D)."""
    s = x.shape[0]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    both = by_rows(lambda u, pos: qkv(w, p, u, pos, cfg, mm,
                                      rotary=window is not None),
                   x, n, (h + 2 * hkv) * dh)
    q, k, v = (both[:, :h * dh], both[:, h * dh:(h + hkv) * dh],
               both[:, (h + hkv) * dh:])
    # the keys a block of queries can see: every key, or on a window layer
    # the block's own and the ``window`` before them
    span = s if window is None else min(s, min(ROWS, s) + window)

    def block(qb, pos):
        lo = 0 if window is None \
            else jnp.clip(pos[0] - window, 0, s - span)
        kb = jax.lax.dynamic_slice_in_dim(k, lo, span)
        vb = jax.lax.dynamic_slice_in_dim(v, lo, span)
        return attend(qb, kb, vb, pos, lo + jnp.arange(span), window, cfg, mm)

    o = by_rows(block, q, n, h * dh)
    return by_rows(lambda u, pos: mm(u, w[p + "w_o"]), o, n, x.shape[1])


def dense_ffn(w, u, cfg, mm):
    un = rms(u, w["d_ln2_g"], cfg["rms_norm_eps"])
    return mm(silu(mm(un, w["d_w_gate"])) * mm(un, w["d_w_up"]), w["d_w_down"])


def expert_ffn(w, u, cfg, mm):
    """The held experts over every token, the router's choice a mask, and
    the shared expert. What the experts held elsewhere would add is left
    out (the model-configs guide, section 4)."""
    k = cfg["num_experts_per_tok"]
    first, held = cfg["experts_held_first"], cfg["num_experts"]
    un = rms(u, w["ln2_g"], cfg["rms_norm_eps"])
    g = logistic(jnp.matmul(un, w["w_router"]))                  # (R, E)
    # the k largest of g + b, ties to the lower index; b only selects
    order = jnp.argsort(-(g + w["b_router"]), axis=-1, stable=True)
    chosen = jnp.zeros(g.shape, bool).at[
        jnp.arange(g.shape[0])[:, None], order[:, :k]].set(True)
    top = jnp.where(chosen, g, 0.0)
    weight = top / jnp.sum(top, -1, keepdims=True) \
        * cfg["routed_scaling_factor"]                 # norm_topk_prob

    def one(acc, xs):
        wg, wu, wd, col = xs
        y = mm(silu(mm(un, wg)) * mm(un, wu), wd)
        return acc + col[:, None] * y, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (w["we_gate"], w["we_up"], w["we_down"],
         weight[:, first:first + held].T))
    shared = mm(silu(mm(un, w["ws_gate"])) * mm(un, w["ws_up"]), w["ws_down"])
    return routed + shared


def window_of(cfg, layer):
    """Layer ``layer``'s window, None where it sees every earlier key."""
    kind = cfg["layer_types"][layer]
    if kind not in ("sliding_attention", "full_attention"):
        raise ValueError(f"unknown layer type {kind!r}")
    return cfg["sliding_window"] if kind == "sliding_attention" else None


# -- what the harness calls -----------------------------------------------------

def embed(g, tokens, cfg):
    return g["wte"][tokens]


def dense_layer(g, x, n, cfg, mm=jnp.matmul):
    """Layer 0 over one request: x (S, D), its first ``n`` rows real."""
    if cfg["mlp_layer_types"][0] != "dense":
        raise ValueError("the leading layer is the dense one")
    x = x + attention(g, "d_", x, n, cfg, mm, window_of(cfg, 0))
    return x + by_rows(lambda u, pos: dense_ffn(g, u, cfg, mm), x, n,
                       x.shape[1])


def expert_layer(w, x, n, layer, cfg, mm=jnp.matmul):
    """Layer ``layer`` >= 1 over one request."""
    if cfg["mlp_layer_types"][layer] != "sparse":
        raise ValueError("the walked layers are the sparse ones")
    x = x + attention(w, "", x, n, cfg, mm, window_of(cfg, layer))
    return x + by_rows(lambda u, pos: expert_ffn(w, u, cfg, mm), x, n,
                       x.shape[1])


def head(g, x, cfg, mm=jnp.matmul):
    return mm(rms(x, g["lnf_g"], cfg["rms_norm_eps"]), g["w_head"])
