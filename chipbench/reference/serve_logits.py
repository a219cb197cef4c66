"""The plain float32 reading of what a served model should have said:
one teacher-forced forward over each sampled request's prompt and served
tokens, walking the model a layer at a time (each layer's weights are
made again from the seed in the served type and widened to float32, so
the reference never holds a float32 copy of a model that would not fit).

Returns, for every served token, how far its reference logit lies below
the reference's best at that position. With ``control_mm`` the same walk
is made a second time in lower precision, and the gap read is that of
the token the lower precision puts first (the control does not decode)."""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W


def served_gaps(cfg, seed, samples, served_dtype, width, max_new,
                control_mm=None):
    """``samples``: list of (prompt, tokens) int arrays, each no longer
    than ``width`` together and ``max_new`` served tokens; the shapes are
    fixed by the mix, so every run compiles the same programs. Returns
    ``{"served": [gaps per request], "control": [...] or None}``."""
    with jax.default_matmul_precision("highest"):
        return _gaps(W.family(cfg), cfg, seed, samples, served_dtype,
                     width, max_new, control_mm)


def _gaps(fam, cfg, seed, samples, served_dtype, width, max_new, control_mm):
    ids = np.zeros((len(samples), width), np.int32)
    at = np.zeros((len(samples), max_new), np.int32)
    served = np.zeros((len(samples), max_new), np.int32)
    for i, (p, t) in enumerate(samples):
        ids[i, :len(p)] = p
        ids[i, len(p):len(p) + len(t)] = t
        # position len(p) - 1 + j predicts served token j
        at[i] = np.minimum(len(p) - 1 + np.arange(max_new), width - 1)
        served[i, :len(t)] = t
    ids, at, served = jnp.asarray(ids), jnp.asarray(at), jnp.asarray(served)
    f32 = lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), t)
    g = f32(W.make_globals(seed, cfg, served_dtype))
    mms = [jnp.matmul] + ([control_mm] if control_mm else [])
    blocks = [jax.jit(lambda wl, x, mm=mm: fam.block(wl, x, cfg, mm))
              for mm in mms]
    embed = jax.jit(lambda g: fam.embed(g, ids, cfg))
    xs = [embed(g) for _ in mms]
    for i in range(W.n_layers(cfg)):
        wl = f32(W.make_layer(seed, cfg, i, served_dtype))
        xs = [blk(wl, x) for blk, x in zip(blocks, xs)]

    @jax.jit
    def read(g, x, x_low):
        rows = jnp.take_along_axis(x, at[..., None], 1)
        ref = fam.head(g, rows, cfg)
        best = jnp.max(ref, -1)
        gap = best - jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
        if x_low is None:
            return gap, None
        low = fam.head(g, jnp.take_along_axis(x_low, at[..., None], 1),
                       cfg, control_mm)
        first = jnp.argmax(low, -1)
        return gap, best - jnp.take_along_axis(ref, first[..., None],
                                               -1)[..., 0]

    gap, low_gap = read(g, xs[0], xs[1] if control_mm else None)
    gap = np.asarray(gap)
    cut = lambda a: [a[i, :len(t)] for i, (_, t) in enumerate(samples)]
    return {"served": cut(gap),
            "control": cut(np.asarray(low_gap)) if control_mm else None}
