"""SDAR-30B-A3B-Chat (JetLM, ``config.json``, ``model_type: sdar_moe``) as
plain float32 ``jax.numpy``: grouped-query attention with an RMSNorm over
each head's values on q and k before the rotation, rotate-half RoPE,
visibility causal over blocks of ``block_length`` positions and full
inside one, and in every layer 128 experts of which a softmax router
takes the 8 most probable, renormalised. It generates by diffusion over
blocks; what a pass of that reads is ``block(..., noisy=)`` below, and
``reference/serve_block_logits.py`` drives it.

Written from the equations in ``ISSUE.md`` (PR 37), not from the program:
no cache, no pages, no kernel, no sorting (every expert runs over every
token and a mask keeps what the router chose), one request at a time. It
imports nothing of the program.

Departures from the published description, each at its line:

- the three projections ``Wq``, ``Wk``, ``Wv`` are ONE leaf ``w_qkv``,
  their columns side by side (the benchmark makes weights from a seed, a
  leaf a key: one matrix or three is the same distribution, and the
  product is column for column the same);
- the q/k norms are assumed (``config.json`` has no key for them;
  ``sdar_moe`` follows Qwen3-MoE's attention, which has them);
- ``mm`` (the control's rounded product) is every product of the bfloat16
  part of the deployment: projections, scores, values, experts, head. The
  router's scores are float32 in the deployment and stay a plain float32
  product here.

A NOISY state is one (block, pass) of generation: ``L`` positions, some
holding the mask id, at absolute positions ``first .. first + L - 1``. It
attends to the CLEAN keys and values of every earlier block (positions
``< first``) and to its own ``L``: the family's training-time form of the
same mathematics (one clean copy and the noisy blocks beside it under one
mask), which is what the engine's incremental passes over a cache must
equal."""

import math

import jax
import jax.numpy as jnp


def leaf_specs(cfg):
    """(name, shape, init, std) of every weight. ``init``: ``normal`` is
    N(0, std), ``gain`` is 1 + N(0, std). A matrix's std is
    ``init_matrix_gain / sqrt(fan in)``; an expert's down projection uses
    ``init_expert_down_gain``, so that the eight chosen experts together
    add about a tenth of the stream's RMS and one expert swapped for
    another at a near-tie of the router moves the stream by two or three
    percent (independent random experts at gain 1 would move it by
    seven)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    gs, m = cfg["init_norm_gain_std"], cfg["init_matrix_gain"]
    std = lambda fan, gain=m: gain / math.sqrt(fan)
    return {
        "globals": [("wte", (v, d), "normal", cfg["init_embed_std"]),
                    ("lnf_g", (d,), "gain", gs),
                    ("w_head", (d, v), "normal", std(d))],
        "layer": [("ln1_g", (d,), "gain", gs),
                  # departure: [Wq | Wk | Wv] as one leaf
                  ("w_qkv", (d, (h + 2 * hkv) * dh), "normal", std(d)),
                  ("q_g", (dh,), "gain", gs),
                  ("k_g", (dh,), "gain", gs),
                  ("w_o", (h * dh, d), "normal", std(h * dh)),
                  ("ln2_g", (d,), "gain", gs),
                  ("w_router", (d, e), "normal", std(d)),
                  ("we_gate", (e, d, f), "normal", std(d)),
                  ("we_up", (e, d, f), "normal", std(d)),
                  ("we_down", (e, f, d), "normal",
                   std(f, cfg["init_expert_down_gain"]))],
    }


# -- the equations ------------------------------------------------------------

def rms(v, g, eps):
    return v / jnp.sqrt(jnp.mean(jnp.square(v), -1, keepdims=True) + eps) * g


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def rotate(x, pos, cfg):
    """x (H, S, Dh) by ``pos`` (S,), rotate-half over all of Dh: value
    ``i`` pairs with ``i + Dh / 2``, frequency ``theta^(-2i / Dh)``."""
    half = x.shape[-1] // 2
    freq = cfg["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def qkv(w, u, pos, cfg, mm):
    """u (S, D) at positions ``pos`` -> q (H, S, Dh), k, v (Hkv, S, Dh),
    q and k normed per head (assumed: see the module's note) and
    rotated."""
    s = u.shape[0]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    un = rms(u, w["ln1_g"], eps)
    p = mm(un, w["w_qkv"])
    heads = lambda t, n: t.reshape(s, n, dh).transpose(1, 0, 2)
    q = heads(p[:, :h * dh], h)
    k = heads(p[:, h * dh:(h + hkv) * dh], hkv)
    v = heads(p[:, (h + hkv) * dh:], hkv)
    q = rotate(rms(q, w["q_g"], eps), pos, cfg)
    k = rotate(rms(k, w["k_g"], eps), pos, cfg)
    return q, k, v


def attend(q, k, v, seen, cfg, mm):
    """q (H, Sq, Dh) over k, v (Hkv, Sk, Dh) where ``seen`` (Sq, Sk);
    each KV head serves H / Hkv query heads. -> (Sq, H * Dh)."""
    h, sq, dh = q.shape
    g = h // k.shape[0]
    k, v = jnp.repeat(k, g, axis=0), jnp.repeat(v, g, axis=0)
    scores = mm(q, k.transpose(0, 2, 1)) / math.sqrt(dh)
    scores = jnp.where(seen[None], scores, -jnp.inf)
    scores = scores - jnp.max(scores, -1, keepdims=True)
    pr = jnp.exp(scores)
    pr = pr / jnp.sum(pr, -1, keepdims=True)
    return mm(pr, v).transpose(1, 0, 2).reshape(sq, h * dh)


def experts(w, u, cfg, mm):
    """Every expert over every token; the router's choice is a mask."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    un = rms(u, w["ln2_g"], cfg["rms_norm_eps"])
    logits = jnp.matmul(un, w["w_router"])                       # (S, E)
    logits = logits - jnp.max(logits, -1, keepdims=True)
    p = jnp.exp(logits)
    p = p / jnp.sum(p, -1, keepdims=True)
    # the k largest: an expert is chosen where fewer than k are larger
    # (ties to the lower index, as a sort would have it)
    larger = (p[:, None, :] > p[:, :, None]) | (
        (p[:, None, :] == p[:, :, None])
        & (jnp.arange(e)[None, None, :] < jnp.arange(e)[None, :, None]))
    chosen = jnp.sum(larger, -1) < k                             # (S, E)
    top = jnp.where(chosen, p, 0.0)
    weight = top / jnp.sum(top, -1, keepdims=True)     # norm_topk_prob

    def one(acc, xs):
        wg, wu, wd, col = xs
        y = mm(silu(mm(un, wg)) * mm(un, wu), wd)
        return acc + col[:, None] * y, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (w["we_gate"], w["we_up"], w["we_down"], weight.T))
    return routed


def layer(w, x, cfg, mm, noisy=None):
    """One request: x (S, D) clean at positions 0 .. S - 1 under the
    block-causal visibility; ``noisy`` = (xn (M, L, D), first (M,)) the
    noisy states beside it. -> x, or (x, xn)."""
    s, blk = x.shape[0], cfg["block_length"]
    pos = jnp.arange(s)
    q, k, v = qkv(w, x, pos, cfg, mm)
    seen = (pos[None, :] // blk) <= (pos[:, None] // blk)
    x = x + mm(attend(q, k, v, seen, cfg, mm), w["w_o"])
    if noisy is None:
        return x + experts(w, x, cfg, mm)
    xn, first = noisy
    m, l, d = xn.shape
    npos = (first[:, None] + jnp.arange(l)[None, :]).reshape(-1)
    qn, kn, vn = qkv(w, xn.reshape(m * l, d), npos, cfg, mm)
    # a state sees the clean positions before its block, and itself
    state = jnp.repeat(jnp.arange(m), l)
    seen_clean = pos[None, :] < jnp.repeat(first, l)[:, None]   # (M*L, S)
    seen_self = state[None, :] == state[:, None]               # (M*L, M*L)
    o = attend(qn, jnp.concatenate([k, kn], 1), jnp.concatenate([v, vn], 1),
               jnp.concatenate([seen_clean, seen_self], 1), cfg, mm)
    xn = xn.reshape(m * l, d) + mm(o, w["w_o"])
    both = jnp.concatenate([x, xn], 0)
    both = both + experts(w, both, cfg, mm)
    return both[:s], both[s:].reshape(m, l, d)


# -- what the harness calls -----------------------------------------------------

def embed(g, tokens, cfg):
    return g["wte"][tokens]


def block(w, x, cfg, mm=jnp.matmul, noisy=None):
    """One layer over x (B, S, D), a request at a time; with ``noisy`` =
    (xn (B, M, L, D), first (B, M)) also over each request's noisy
    states, and then -> (x, xn)."""
    if noisy is None:
        return jax.lax.map(lambda xr: layer(w, xr, cfg, mm), x)
    return jax.lax.map(
        lambda a: layer(w, a[0], cfg, mm, noisy=(a[1], a[2])),
        (x, noisy[0], noisy[1]))


def head(g, x, cfg, mm=jnp.matmul):
    return mm(rms(x, g["lnf_g"], cfg["rms_norm_eps"]), g["w_head"])
