"""The plain float32 reading of what a model that generates by blocks
should have filled: the family's TRAINING-time form of the same
mathematics. One clean pass over each sampled request's prompt and served
tokens under the block-causal mask gives every layer's keys and values;
beside it runs every (block, pass) state the engine went through, rebuilt
from the served tokens and the pass that filled each (``fill_pass``), as
``L`` positions that attend to the clean keys of all earlier blocks and to
each other (``reference/sdar.py`` ``block(..., noisy=)``). ``steps x N``
noisy positions beside ``P + N`` clean ones a request; no cache object,
no incremental state. The model is walked a layer at a time, each layer's
weights made again from the seed in the served type and widened.

Two numbers a state (a pass of a block):

- the LOGIT gap: at every position the pass filled, the reference's best
  logit there less its logit of the served token;
- the CONFIDENCE gap: the reference's largest log-confidence
  (``max log softmax``) over the positions still masked in the state,
  less its log-confidence at the position the engine filled; where a
  pass fills ``n`` positions, the least log-confidence among the ``n``
  the reference would have filled less the least among the engine's
  ``n`` (0 where they are the same positions). A pick of the wrong
  position, a stale mask, a block read before its commit show here and
  not in the first.

With ``control_mm`` the same walk is made a second time in lower
precision (keys of the clean pass included), and the gaps read are those
of what the lower precision would have filled: the token it puts first at
the filled positions, the positions it is most confident of.

Departure: a request's LAST block is left out where the engine dropped
part of it (tokens past ``max_new_tokens`` are not streamed, so its
states cannot be rebuilt from what the client saw): at most ``L - 1``
served tokens a request go unread."""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W


def states_of(prompt, tokens, fill_pass, block, mask_id):
    """The (block, pass) states of one request as the engine ran them:
    ``(ids (M, L), first (M,), filled (M, L) bool, masked (M, L) bool)``.
    ``filled[m]`` are the positions state ``m``'s pass filled, ``masked``
    those that were masked when it ran. Only blocks streamed whole."""
    p = len(prompt)
    start = p - p % block
    seq = np.concatenate([prompt, tokens])
    given = np.concatenate([np.full(p, -1), fill_pass])
    ids, first, filled, masked = [], [], [], []
    for b0 in range(start, len(seq) - block + 1, block):
        tok, at = seq[b0:b0 + block], given[b0:b0 + block]
        for s in range(int(at.max()) + 1):
            ids.append(np.where(at < s, tok, mask_id))
            first.append(b0)
            filled.append(at == s)
            masked.append(at >= s)
    return (np.asarray(ids, np.int32).reshape(-1, block),
            np.asarray(first, np.int32),
            np.asarray(filled, bool).reshape(-1, block),
            np.asarray(masked, bool).reshape(-1, block))


def served_gaps(cfg, seed, samples, served_dtype, width, max_new,
                control_mm=None):
    """``samples``: list of (prompt, tokens, fill_pass) int arrays, each
    no longer than ``width`` together and ``max_new`` served tokens; the
    shapes are fixed by the mix, so every run compiles the same programs.
    Returns ``{"logit": [per request: a gap a filled position],
    "confidence": [per request: a gap a pass], "control_logit",
    "control_confidence": the same of the control, or None}``."""
    with jax.default_matmul_precision("highest"):
        return _gaps(W.family(cfg), cfg, seed, samples, served_dtype,
                     width, max_new, control_mm)


def _gaps(fam, cfg, seed, samples, served_dtype, width, max_new, control_mm):
    blk, mask_id = cfg["block_length"], cfg["mask_token_id"]
    # a block more than max_new / L: the first block may open mid-prompt
    n_states = (max_new // blk + 1) * blk
    n = len(samples)
    ids = np.zeros((n, width), np.int32)
    sid = np.full((n, n_states, blk), mask_id, np.int32)
    first = np.zeros((n, n_states), np.int32)
    filled = np.zeros((n, n_states, blk), bool)
    masked = np.zeros((n, n_states, blk), bool)
    served = np.zeros((n, n_states, blk), np.int32)
    for i, (p, t, fp) in enumerate(samples):
        ids[i, :len(p)] = p
        ids[i, len(p):len(p) + len(t)] = t
        s_ids, s_first, s_filled, s_masked = states_of(
            np.asarray(p), np.asarray(t), np.asarray(fp), blk, mask_id)
        m = len(s_ids)
        sid[i, :m], first[i, :m] = s_ids, s_first
        filled[i, :m], masked[i, :m] = s_filled, s_masked
        served[i, :m] = ids[i][s_first[:, None] + np.arange(blk)[None, :]]
    ids, sid, first = jnp.asarray(ids), jnp.asarray(sid), jnp.asarray(first)
    f32 = lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), t)
    g = f32(W.make_globals(seed, cfg, served_dtype))
    mms = [jnp.matmul] + ([control_mm] if control_mm else [])
    blocks = [jax.jit(lambda wl, x, xn, mm=mm: fam.block(
        wl, x, cfg, mm, noisy=(xn, first))) for mm in mms]
    embed = jax.jit(lambda g, t: fam.embed(g, t, cfg))
    xs = [(embed(g, ids), embed(g, sid)) for _ in mms]
    for i in range(W.n_layers(cfg)):
        wl = f32(W.make_layer(seed, cfg, i, served_dtype))
        xs = [b(wl, x, xn) for b, (x, xn) in zip(blocks, xs)]

    def surest(logc, masked_r, n_fill):
        """The ``n_fill`` masked positions of highest ``logc`` a state,
        ties to the lowest position: what a pass fills."""
        c = jnp.where(masked_r, logc, -jnp.inf)
        i = jnp.arange(blk)
        ahead = (c[..., None, :] > c[..., :, None]) | (
            (c[..., None, :] == c[..., :, None]) & (i[None, :] < i[:, None]))
        return masked_r & (jnp.sum(ahead, -1) < n_fill[..., None])

    def one(g, args):
        """One request's states: the float32 logits of (n_states, L,
        vocab) are read here and go no further."""
        xn, xn_low, served_r, filled_r, masked_r = args
        n_fill = jnp.sum(filled_r, -1)
        lg = fam.head(g, xn, cfg)
        best = jnp.max(lg, -1)
        logc = best - jax.nn.logsumexp(lg, -1)
        least = lambda at: jnp.min(jnp.where(at, logc, jnp.inf), -1)
        ours = least(surest(logc, masked_r, n_fill))
        out = [best - jnp.take_along_axis(lg, served_r[..., None],
                                          -1)[..., 0],
               ours - least(filled_r)]
        if control_mm is not None:
            low = fam.head(g, xn_low, cfg, control_mm)
            put_first = jnp.argmax(low, -1)
            picks = surest(jnp.max(low, -1) - jax.nn.logsumexp(low, -1),
                           masked_r, n_fill)
            out += [best - jnp.take_along_axis(lg, put_first[..., None],
                                               -1)[..., 0],
                    ours - least(picks)]
        return out

    read = jax.jit(lambda g, xn, xn_low: jax.lax.map(
        lambda args: one(g, args),
        (xn, xn_low, jnp.asarray(served), jnp.asarray(filled),
         jnp.asarray(masked))))
    out = [np.asarray(a) for a in read(g, xs[0][1], xs[-1][1])]
    ran = filled.any(-1)                     # the states that exist
    at = lambda a: [a[i][filled[i]] for i in range(n)]
    per_state = lambda a: [a[i][ran[i]] for i in range(n)]
    res = {"logit": at(out[0]), "confidence": per_state(out[1]),
           "control_logit": None, "control_confidence": None}
    if control_mm:
        res["control_logit"] = at(out[2])
        res["control_confidence"] = per_state(out[3])
    return res
