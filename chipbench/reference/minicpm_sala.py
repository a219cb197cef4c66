"""MiniCPM-SALA (OpenBMB, ``config.json``, ``model_type: minicpm_sala``) as
plain float32 ``jax.numpy``: a stack that mixes ``lightning-attn`` layers
(linear attention: one decaying state a head) with ``minicpm4`` layers
(InfLLM-V2: grouped-query softmax attention over blocks the query
chooses), a gated SiLU MLP after each, RMSNorm before every branch, and
MiniCPM's muP scalings.

Written from the equations in ``ISSUE.md`` (PR 45), not from the program:
no cache, no state store, no pages, no kernel, one request at a time. It
imports nothing of the program.

The equations (width D, heads H of d, eps ``rms_norm_eps``, no biases):

    h_0    = scale_emb * E[token]
    x     <- x + (scale_depth / sqrt(num_hidden_layers_published))
                 * branch(rms(x))          both branches of every layer
    logits = W_head (rms(h) / (hidden_size / dim_model_base))
    mlp(u) = W_down (silu(W_gate u) * (W_up u))

``lightning-attn`` (head h, position t; ``L = exp(-2^(-8 h / H))``,
h = 1 .. H):

    q_t = rope(rms_q(W_q u_t)),  k_t = rope(rms_k(W_k u_t)),  v_t = W_v u_t
    S_t = L S_{t-1} + k_t v_t^T          (d x d, S_{-1} = 0)
    o_t = d^(-1/2) q_t^T S_t
    y_t = W_o (sigmoid(W_g u_t) * rms_o(o_t))

:func:`recurrence` is that, position by position. The walk uses its chunk
form, ``ROWS`` positions a step. Unrolling the recurrence over a chunk's
positions i = 1 .. C from the state S before it gives ``S_i = L^i S +
sum_{j<=i} L^(i-j) k_j v_j^T``, so

    O  = d^(-1/2) [ ((Q K^T) * M) V + diag(L^1 .. L^C) Q S ],
                                       M_ij = L^(i-j) for j <= i, else 0
    S' = L^C S + sum_j L^(C-j) k_j v_j^T

(``(Q K^T)_ij = q_i . k_j`` times ``M_ij`` is the weight of ``v_j`` in
``o_i``; the second term is ``q_i^T L^i S``): the same sums in another
order. ``tests/test_linear_sparse_serving.py`` holds the two to each
other.

``minicpm4`` (32 query heads over 2 KV heads, groups of 16, NO rotation):
q, k normed per head, v plain. Compressed key j of KV head g:
``Kc_j = mean(k_{s j} .. k_{s j + w - 1})`` (window w, stride s), for the
windows that end at or before t. ``p_j = sum_{h in g} softmax_j(d^(-1/2)
q_{t,h} . Kc_j)``; block m (B positions) scores the largest ``p_j`` among
the windows that overlap it (0 where none has closed); the chosen set is
the first ``init_blocks`` blocks, the blocks that hold the last
``window_size`` positions, and the ``topk`` best-scoring of the rest (all
where fewer exist; a tie to the earlier block). ``o_{t,h} = softmax_i(
d^(-1/2) q_{t,h} . k_i) v_i`` over the positions ``i <= t`` of the chosen
blocks; ``y_t = W_o (sigmoid(W_g u_t) * o_t)``. A request whose PROMPT is
shorter than ``dense_len`` reads every ``i <= t`` for its whole life
(``dense``, which the walker reads from the prompt's length).

What the sizes force, and nothing else: a request of 66 048 tokens does
not fit as one array a step, so every per-token part runs over ``ROWS``
tokens at a time (:func:`by_rows`, as ``reference/kexaone.py``), a sparse
layer's queries ``ROWS`` at a time over EVERY key under the chosen set's
mask, one KV head's group after another, and a linear layer as one walk
over blocks of ``ROWS`` positions that carries the state; only the blocks
that hold a real token run (``n``, the request's length, is data).

How it meets the harness (``chipbench/weights.py``,
``reference/serve_logits_mixers.py``): the harness walks ``cfg["n_layer"]``
layers of ONE leaf spec; those are the ``lightning-attn`` layers, in
order. The ``minicpm4`` layers' leaves live in ``globals`` under the
prefixes ``s0_``, ``s1_``, ... in their order. ``mm`` (the control's
rounded product) is every matrix product of the bfloat16 part of the
deployment; the state's recurrence, the softmax and the norms stay
float32 in the control as in the deployment.

Assumed where ``config.json`` is silent (the configuration file lists
each with its reason): the family's ``sparse_config``, mean pooling and
max pooling, the dense switch read from the prompt, the decay slopes, the
gates as linear maps under a logistic, ``rms_o`` over each head with one
scale of width d, rotate-half pairing. A norm's learned scale is its leaf
times the configuration's ``init_*_qk_gain`` (the weights' maker knows
gains of mean 1 alone)."""

import math

import jax
import jax.numpy as jnp

#: tokens a block of the per-token parts, queries a block of a sparse
#: layer's attention, and positions a step of a linear layer's walk
ROWS = 128

LINEAR, SPARSE = "lightning-attn", "minicpm4"


def _mlp_specs(cfg, pre):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    std = lambda fan, gain: gain / math.sqrt(fan)
    m = cfg["init_matrix_gain"]
    return [(pre + "ln2_g", (d,), "gain", cfg["init_norm_gain_std"]),
            (pre + "w_gate", (d, f), "normal", std(d, m)),
            (pre + "w_up", (d, f), "normal", std(d, m)),
            (pre + "w_down", (f, d), "normal", std(f, cfg["init_down_gain"]))]


def _sparse_specs(cfg, pre):
    d, h, hkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    gs, m = cfg["init_norm_gain_std"], cfg["init_matrix_gain"]
    std = lambda fan, gain=m: gain / math.sqrt(fan)
    return [(pre + "ln1_g", (d,), "gain", gs),
            (pre + "w_qkv", (d, (h + 2 * hkv) * dh), "normal", std(d)),
            (pre + "q_g", (dh,), "gain", gs),
            (pre + "k_g", (dh,), "gain", gs),
            (pre + "w_g", (d, h * dh), "normal", std(d)),
            (pre + "w_o", (h * dh, d), "normal",
             std(h * dh, cfg["init_sparse_out_gain"]))] + _mlp_specs(cfg, pre)


def _linear_specs(cfg, pre):
    d, h, dh = (cfg["hidden_size"], cfg["lightning_nh"],
                cfg["lightning_head_dim"])
    gs, m = cfg["init_norm_gain_std"], cfg["init_matrix_gain"]
    std = lambda fan, gain=m: gain / math.sqrt(fan)
    return [(pre + "ln1_g", (d,), "gain", gs),
            (pre + "w_qkv", (d, 3 * h * dh), "normal", std(d)),
            (pre + "q_g", (dh,), "gain", gs),
            (pre + "k_g", (dh,), "gain", gs),
            (pre + "o_g", (dh,), "gain", gs),
            (pre + "w_g", (d, h * dh), "normal", std(d)),
            (pre + "w_o", (h * dh, d), "normal",
             std(h * dh, cfg["init_linear_out_gain"]))] + _mlp_specs(cfg, pre)


def mixers(cfg):
    """The mixer of each layer that is run: the first
    ``num_hidden_layers`` entries of ``mixer_types``."""
    kinds = cfg["mixer_types"][:cfg["num_hidden_layers"]]
    if set(kinds) - {LINEAR, SPARSE}:
        raise ValueError(f"unknown mixer among {kinds}")
    if (kinds.count(LINEAR), kinds.count(SPARSE)) != (
            cfg["n_layer"], cfg["n_sparse_layer"]):
        raise ValueError("n_layer counts the lightning-attn layers that "
                         "are run and n_sparse_layer the minicpm4 layers")
    return kinds


def leaf_specs(cfg):
    """(name, shape, init, std) of every weight. ``init``: ``normal`` is
    N(0, std), ``gain`` is 1 + N(0, std). A matrix's std is
    ``init_matrix_gain / sqrt(fan in)``; the projections back into the
    stream have gains of their own (``init_down_gain``,
    ``init_sparse_out_gain``, ``init_linear_out_gain``), and the head's
    (``init_head_gain``) makes the logits' std 1 after the division by
    ``hidden_size / dim_model_base``."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    # the maker hands this function the configuration's plain numbers and
    # strings alone: the two counts, not ``mixer_types``
    sparse = [s for i in range(cfg["n_sparse_layer"])
              for s in _sparse_specs(cfg, f"s{i}_")]
    return {"globals": [("wte", (v, d), "normal", cfg["init_embed_std"]),
                        ("lnf_g", (d,), "gain", cfg["init_norm_gain_std"]),
                        ("w_head", (d, v), "normal",
                         cfg["init_head_gain"] / math.sqrt(d))] + sparse,
            "layer": _linear_specs(cfg, "")}


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
    freq = cfg["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def branch_scale(cfg):
    return cfg["scale_depth"] / math.sqrt(cfg["num_hidden_layers_published"])


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


def mlp(w, p, u, cfg, mm):
    un = rms(u, w[p + "ln2_g"], cfg["rms_norm_eps"])
    return mm(silu(mm(un, w[p + "w_gate"])) * mm(un, w[p + "w_up"]),
              w[p + "w_down"])


# -- lightning-attn -----------------------------------------------------------

def decays(cfg):
    """``L_h`` (H,): ``exp(-2^(-8 h / H))``, h = 1 .. H."""
    h = cfg["lightning_nh"]
    return jnp.exp(-(2.0 ** (-8.0 * jnp.arange(1, h + 1, dtype=jnp.float32)
                             / h)))


def recurrence(q, k, v, lam, state=None):
    """The equations as written, a position a step: q, k, v (H, S, d) ->
    (o (H, S, d), the state after the last position)."""
    d = q.shape[-1]
    if state is None:
        state = jnp.zeros((q.shape[0], d, v.shape[-1]), jnp.float32)

    def step(s, qkv):
        qt, kt, vt = qkv
        s = lam[:, None, None] * s + kt[:, :, None] * vt[:, None, :]
        return s, jnp.einsum("hd,hde->he", qt, s) / math.sqrt(d)

    state, o = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v)))
    return jnp.moveaxis(o, 0, 1), state


def chunk(q, k, v, lam, state, real=None):
    """The chunk form (module docstring): q, k, v (H, C, d) from ``state``
    (H, d, d) -> (O (H, C, d), S'). ``real`` (default C): the rows that
    count for S', the first ``real`` of the chunk; S' is then the state
    after them (``C`` in the formula is ``real``, a later row's weight 0)."""
    c, d = q.shape[1], q.shape[2]
    real = c if real is None else real
    i = jnp.arange(c)
    ll = jnp.log(lam)[:, None, None]
    back = i[:, None] - i[None, :]
    m = jnp.where(back >= 0, jnp.exp(ll * jnp.maximum(back, 0)), 0.0)
    o = jnp.matmul(jnp.matmul(q, jnp.swapaxes(k, 1, 2)) * m, v) \
        + jnp.exp(ll * (i + 1)[None, :, None]) * jnp.matmul(q, state)
    weight = jnp.where(i < real, jnp.exp(ll * jnp.maximum(real - 1 - i, 0)),
                       0.0)                                    # (H, 1, C)
    new = jnp.exp(ll * real) * state + jnp.matmul(
        jnp.swapaxes(k, 1, 2) * weight, v)
    return o / math.sqrt(d), new


def linear_layer(w, x, n, cfg, mm=jnp.matmul):
    """One ``lightning-attn`` layer over one request: x (S, D), its first
    ``n`` rows real -> ((S, D), the state (H, d, d) after row ``n - 1``).
    One walk over blocks of ROWS positions that carries the state; a
    block's rows go through attention and the MLP before the next block
    starts."""
    s, d = x.shape
    h, dh, eps = (cfg["lightning_nh"], cfg["lightning_head_dim"],
                  cfg["rms_norm_eps"])
    if cfg["lightning_nkv"] != h:
        raise ValueError("the reference has as many key/value heads as "
                         "query heads in a lightning-attn layer")
    rows = min(ROWS, s)
    pad = -s % rows
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    lam, scale = decays(cfg), branch_scale(cfg)
    gq = w["q_g"] * cfg["init_linear_qk_gain"]
    gk = w["k_g"] * cfg["init_linear_qk_gain"]

    def body(i, carry):
        out, state = carry
        lo = i * rows
        xb = jax.lax.dynamic_slice_in_dim(xp, lo, rows)
        pos = lo + jnp.arange(rows)
        u = rms(xb, w["ln1_g"], eps)
        heads = lambda t: t.reshape(rows, h, dh).transpose(1, 0, 2)
        q, k, v = (heads(t) for t in jnp.split(mm(u, w["w_qkv"]), 3, -1))
        q, k = rms(q, gq, eps), rms(k, gk, eps)
        if cfg["lightning_use_rope"]:
            q, k = rotate(q, pos, cfg), rotate(k, pos, cfg)
        o, state = chunk(q, k, v, lam, state, jnp.clip(n - lo, 0, rows))
        o = rms(o, w["o_g"], eps).transpose(1, 0, 2).reshape(rows, h * dh)
        xb = xb + scale * mm(logistic(mm(u, w["w_g"])) * o, w["w_o"])
        xb = xb + scale * mlp(w, "", xb, cfg, mm)
        return jax.lax.dynamic_update_slice_in_dim(out, xb, lo, 0), state

    out, state = jax.lax.fori_loop(
        0, (n + rows - 1) // rows, body,
        (jnp.zeros_like(xp), jnp.zeros((h, dh, dh), jnp.float32)))
    return out[:s], state


# -- minicpm4 -----------------------------------------------------------------

def compressed_keys(k, cfg):
    """k (S, Hkv, d) -> (W, Hkv, d): the mean of every window of
    ``kernel_size`` keys, ``kernel_stride`` apart, that lies inside S."""
    sc = cfg["sparse_config"]
    n = max((k.shape[0] - sc["kernel_size"]) // sc["kernel_stride"] + 1, 0)
    at = sc["kernel_stride"] * jnp.arange(n)[:, None] \
        + jnp.arange(sc["kernel_size"])[None, :]
    return jnp.mean(k[at], axis=1)


def chosen_blocks(p, t, n_blocks, cfg):
    """p (R, W) the windows' worth to the queries at ``t`` (R,), zeros at
    windows that have not closed -> (R, n_blocks) bool, the blocks each
    query reads."""
    sc = cfg["sparse_config"]
    s, w, b = sc["kernel_stride"], sc["kernel_size"], sc["block_size"]
    j = jnp.arange(p.shape[1])
    # a window lies across its first and its last position's blocks
    score = jnp.zeros((p.shape[0], n_blocks)) \
        .at[:, (s * j) // b].max(p, mode="drop") \
        .at[:, (s * j + w - 1) // b].max(p, mode="drop")
    m = jnp.arange(n_blocks)[None, :]
    tt = t[:, None]
    exists = m <= tt // b
    forced = exists & ((m < sc["init_blocks"])
                       | (m >= (tt - sc["window_size"] + 1) // b))
    rest = exists & ~forced
    order = jnp.argsort(jnp.where(rest, -score, jnp.inf), axis=-1,
                        stable=True)[:, :sc["topk"]]
    picked = jnp.zeros(rest.shape, bool).at[
        jnp.arange(p.shape[0])[:, None], order].set(True)
    return forced | (picked & rest)


def sparse_attend(q, k, v, kc, pos_q, dense, cfg, mm):
    """q (R, H d) at ``pos_q`` over k, v (S, Hkv d) at positions 0 .. S-1
    with the compressed keys kc (W, Hkv, d); ``dense`` (a bool, traced):
    no selection. One KV head's group after another. -> (R, H d)."""
    sc = cfg["sparse_config"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    r, g, s = q.shape[0], h // hkv, k.shape[0]
    n_blocks = -(-s // sc["block_size"])
    pos_k = jnp.arange(s)
    causal = pos_k[None, :] <= pos_q[:, None]
    closed = (sc["kernel_stride"] * jnp.arange(kc.shape[0])[None, :]
              + sc["kernel_size"] - 1) <= pos_q[:, None]

    def softmax(scores, seen):
        scores = jnp.where(seen, scores, -jnp.inf)
        top = jnp.max(scores, -1, keepdims=True)
        e = jnp.where(seen, jnp.exp(scores - jnp.where(
            jnp.isfinite(top), top, 0.0)), 0.0)
        total = jnp.sum(e, -1, keepdims=True)
        return e / jnp.where(total == 0.0, 1.0, total)

    def group(args):
        qg, kh, vh, kch = args          # (g, R, d), (S, d), (S, d), (W, d)
        p = jnp.sum(softmax(mm(qg, kch.T[None]) / math.sqrt(dh),
                            closed[None]), axis=0)               # (R, W)
        chosen = chosen_blocks(p, pos_q, n_blocks, cfg)
        seen = causal & (dense | chosen[:, pos_k // sc["block_size"]])
        return mm(softmax(mm(qg, kh.T[None]) / math.sqrt(dh), seen[None]),
                  vh[None])

    heads = lambda t, n: t.reshape(t.shape[0], n, dh).transpose(1, 0, 2)
    o = jax.lax.map(group, (heads(q, h).reshape(hkv, g, r, dh),
                            heads(k, hkv), heads(v, hkv),
                            kc.transpose(1, 0, 2)))
    return o.reshape(h, r, dh).transpose(1, 0, 2).reshape(r, h * dh)


def sparse_layer(w, p, x, n, dense, cfg, mm=jnp.matmul):
    """One ``minicpm4`` layer over one request: x (S, D), its first ``n``
    rows real, its leaves under the prefix ``p`` -> (S, D)."""
    h, hkv, dh, eps = (cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"],
                       cfg["rms_norm_eps"])
    if cfg["attn_use_rope"]:
        raise ValueError("the reference rotates nothing in a minicpm4 layer")
    gq = w[p + "q_g"] * cfg["init_sparse_qk_gain"]
    gk = w[p + "k_g"] * cfg["init_sparse_qk_gain"]

    def project(xb, pos):
        r = xb.shape[0]
        u = rms(xb, w[p + "ln1_g"], eps)
        y = mm(u, w[p + "w_qkv"])
        heads = lambda t, k: t.reshape(r, k, dh)
        q = rms(heads(y[:, :h * dh], h), gq, eps).reshape(r, -1)
        k = rms(heads(y[:, h * dh:(h + hkv) * dh], hkv), gk, eps) \
            .reshape(r, -1)
        return jnp.concatenate([q, k, y[:, (h + hkv) * dh:],
                                logistic(mm(u, w[p + "w_g"]))], -1)

    both = by_rows(project, x, n, (2 * h + 2 * hkv) * dh)
    q, k, v, gate = jnp.split(
        both, [h * dh, (h + hkv) * dh, (h + 2 * hkv) * dh], axis=-1)
    kc = compressed_keys(k.reshape(-1, hkv, dh), cfg)
    o = by_rows(lambda qb, pos: sparse_attend(qb, k, v, kc, pos, dense, cfg,
                                              mm), q, n, h * dh)
    scale = branch_scale(cfg)
    x = x + scale * by_rows(lambda ob, pos: mm(ob, w[p + "w_o"]), gate * o, n,
                            x.shape[1])
    return x + scale * by_rows(lambda xb, pos: mlp(w, p, xb, cfg, mm), x, n,
                               x.shape[1])


# -- what the harness calls -----------------------------------------------------

def embed(g, tokens, cfg):
    return cfg["scale_emb"] * g["wte"][tokens]


def head(g, x, cfg, mm=jnp.matmul):
    return mm(rms(x, g["lnf_g"], cfg["rms_norm_eps"])
              / (cfg["hidden_size"] / cfg["dim_model_base"]), g["w_head"])


def is_dense(cfg, prompt_len):
    """Whether a request of this prompt selects nothing, for its whole
    life."""
    return prompt_len < cfg["sparse_config"]["dense_len"]
