"""``serve_logits.served_gaps`` for a family whose requests are too long
to stand side by side: the same contract (one teacher-forced float32
forward over each sampled request's prompt and served tokens; for every
served token, how far its reference logit lies below the reference's best
there; with ``control_mm`` the gap of the token a lower precision puts
first), walked ONE REQUEST AT A TIME. Eight requests of 33 280 tokens at
width 6144 would be 6.5 GB of float32 activations going into a layer and
as much coming out, beside the layer's float32 weights.

Every request is padded to the mix's ``width``, so a run compiles one
program a layer kind, and the family's layers run only the blocks of rows
that hold a real token (``reference/kexaone.py`` ``by_rows``: the length
is data), so a request of 1 k tokens costs a thirtieth of the longest.
Each layer's weights are made again from the seed for every request, in
the served type, and widened to float32: the reference never holds a
float32 copy of the model.

What it asks of the family beside ``embed`` and ``head``:
``dense_layer(g, x, n, cfg, mm)`` (layer 0, its leaves among the globals)
and ``expert_layer(w, x, n, layer, cfg, mm)`` for the walked layers."""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W


def served_gaps(cfg, seed, samples, served_dtype, width, max_new,
                control_mm=None):
    """``samples``: list of (prompt, tokens) int arrays, each no longer
    than ``width`` together and ``max_new`` served tokens. Returns
    ``{"served": [gaps per request], "control": [...] or None}``."""
    with jax.default_matmul_precision("highest"):
        return _gaps(W.family(cfg), cfg, seed, samples, served_dtype,
                     width, max_new, control_mm)


def _gaps(fam, cfg, seed, samples, served_dtype, width, max_new, control_mm):
    f32 = lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), t)
    g = f32(W.make_globals(seed, cfg, served_dtype))
    mms = [jnp.matmul] + ([control_mm] if control_mm else [])
    embed = jax.jit(lambda g, ids: fam.embed(g, ids, cfg))
    dense = [jax.jit(lambda g, x, n, mm=mm: fam.dense_layer(g, x, n, cfg, mm))
             for mm in mms]
    # one program a kind of layer: the layer's index decides its attention
    sparse = [jax.jit(lambda w, x, n, layer, mm=mm:
                      fam.expert_layer(w, x, n, layer, cfg, mm),
                      static_argnums=3) for mm in mms]

    @jax.jit
    def gaps(g, x, at, tokens):
        """How far the reference's logit of ``tokens`` lies below its
        best, at rows ``at`` of x."""
        ref = fam.head(g, x[at], cfg)
        return jnp.max(ref, -1) \
            - jnp.take_along_axis(ref, tokens[:, None], -1)[:, 0]

    @jax.jit
    def first(g, x_low, at):
        """The tokens a lower precision puts first at rows ``at``."""
        return jnp.argmax(fam.head(g, x_low[at], cfg, control_mm), -1)

    out = {"served": [], "control": [] if control_mm else None}
    for p, t in samples:
        n = len(p) + len(t)
        ids = np.zeros((width,), np.int32)
        ids[:len(p)], ids[len(p):n] = p, t
        # position len(p) - 1 + j predicts served token j
        at = np.minimum(len(p) - 1 + np.arange(max_new), width - 1)
        served = np.zeros((max_new,), np.int32)
        served[:len(t)] = t
        x0 = embed(g, jnp.asarray(ids))
        xs = [d(g, x0, n) for d in dense]
        for i in range(W.n_layers(cfg)):
            wl = f32(W.make_layer(seed, cfg, i, served_dtype))
            xs = [blk(wl, x, n, 1 + i) for blk, x in zip(sparse, xs)]
        at, served = jnp.asarray(at), jnp.asarray(served)
        out["served"].append(np.asarray(gaps(g, xs[0], at, served))[:len(t)])
        if control_mm:
            out["control"].append(np.asarray(
                gaps(g, xs[0], at, first(g, xs[1], at)))[:len(t)])
    return out
