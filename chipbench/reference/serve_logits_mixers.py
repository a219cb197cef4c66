"""``serve_logits_rows.served_gaps`` for a family whose layers are mixers
of two kinds (``reference/minicpm_sala.py``): the same contract (one
teacher-forced float32 forward over each sampled request's prompt and
served tokens, ONE REQUEST AT A TIME, padded to the mix's ``width`` so that
a run compiles one program a layer kind; for every served token, how far
its reference logit lies below the reference's best there; with
``control_mm`` the gap of the token a lower precision puts first), and
beside it :func:`served_states`: what a linear layer's state should be
after a request, which no logit shows closely (a state rounded to
bfloat16 moves a served token's logit by less than the rounding of the
activations does, PERF.md Findings, PR 45).

What it asks of the family beside ``embed`` and ``head``: ``mixers(cfg)``
(the kind of each layer), ``linear_layer(w, x, n, cfg, mm)`` for the
walked layers (``weights.make_layer``, in their order; -> the rows and the
layer's state after row ``n - 1``), ``sparse_layer(g, prefix, x, n, dense,
cfg, mm)`` for the layers whose leaves live among the globals under
``s<i>_``, and ``is_dense(cfg, prompt_len)``: a request's prompt decides,
for its whole life, whether its sparse layers select.

The globals are widened to float32 a part at a time as a layer needs
them (embedding, head and both sparse layers together would be 4.4 GB
beside a layer's 8 GB of rows at the longest request: the embedding's
rows are widened after the look-up, the head inside the program that
reads the served rows), and a request is padded to the least of a
quarter, a half and the whole of the mix's ``width`` that holds it: a
sparse layer's block of queries reads every key of the padded width, so
a request of 9 k tokens at the width of 66 k would pay seven times its
keys."""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W


class Walk:
    """One model's reference, a program a layer kind (and one more each
    for the control's products)."""

    def __init__(self, cfg, seed, served_dtype, control_mm=None):
        self.fam, self.cfg, self.seed = W.family(cfg), cfg, seed
        self.dtype, self.control_mm = served_dtype, control_mm
        fam = self.fam
        self.f32 = lambda t: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), t)
        self.g = W.make_globals(seed, cfg, served_dtype)
        mms = [jnp.matmul] + ([control_mm] if control_mm else [])
        self.embed = jax.jit(lambda g, ids: fam.embed(
            {"wte": g["wte"][ids].astype(jnp.float32)},
            jnp.arange(ids.shape[0]), cfg))
        self.head = lambda g, x, mm=jnp.matmul: fam.head(
            self.f32({k: g[k] for k in ("lnf_g", "w_head")}), x, cfg, mm)
        self.linear = [jax.jit(lambda w, x, n, mm=mm:
                               fam.linear_layer(w, x, n, cfg, mm))
                       for mm in mms]
        # one program for every sparse layer: its prefix taken off the names
        self.sparse = [jax.jit(lambda w, x, n, dense, mm=mm:
                               fam.sparse_layer(w, "", x, n, dense, cfg, mm))
                       for mm in mms]

    def rows(self, prompt, tokens, width, n=None):
        """The hidden rows before the head, one array a product (the
        plain one, then the control's), and each linear layer's state
        after row ``n - 1`` (default: the whole request), likewise."""
        fam, cfg = self.fam, self.cfg
        n = len(prompt) + len(tokens) if n is None else n
        width = next(w for w in (width // 4, width // 2, width)
                     if w >= len(prompt) + len(tokens))
        ids = np.zeros((width,), np.int32)
        ids[:len(prompt)] = prompt
        ids[len(prompt):len(prompt) + len(tokens)] = tokens
        dense = jnp.asarray(bool(fam.is_dense(cfg, len(prompt))))
        xs = [self.embed(self.g, jnp.asarray(ids))] * len(self.linear)
        states, n_linear, n_sparse = [], 0, 0
        for mixer in fam.mixers(cfg):
            if mixer == fam.SPARSE:
                pre = f"s{n_sparse}_"
                wl = self.f32({k[len(pre):]: v for k, v in self.g.items()
                               if k.startswith(pre)})
                xs = [blk(wl, x, n, dense) for blk, x in zip(self.sparse, xs)]
                n_sparse += 1
            else:
                wl = self.f32(W.make_layer(self.seed, cfg, n_linear,
                                           self.dtype))
                both = [blk(wl, x, n) for blk, x in zip(self.linear, xs)]
                xs = [x for x, _ in both]
                states.append([st for _, st in both])
                n_linear += 1
            del wl
        return xs, states


class Reference:
    """``served_gaps`` and ``served_states`` over ONE walk: the globals are
    made once and a layer kind's program is traced once for both (the
    kind puts an instance in ``kinds/serve.py``'s ``serve_logits`` place
    and asks it for the states afterwards)."""

    def __init__(self):
        self._walk = None

    def walk(self, cfg, seed, served_dtype, control_mm):
        if self._walk is None:
            self._walk = Walk(cfg, seed, served_dtype, control_mm)
        return self._walk

    def served_gaps(self, cfg, seed, samples, served_dtype, width, max_new,
                    control_mm=None):
        """``samples``: list of (prompt, tokens) int arrays, each no longer
        than ``width`` together and ``max_new`` served tokens. Returns
        ``{"served": [gaps per request], "control": [...] or None}``."""
        with jax.default_matmul_precision("highest"):
            return _gaps(self.walk(cfg, seed, served_dtype, control_mm),
                         samples, width, max_new)

    def served_states(self, cfg, seed, prompt, tokens, served_dtype, width,
                      control_mm=None):
        """Each linear layer's state once the request (prompt, tokens) has
        been served: after its prompt and all its tokens but the last,
        whose entry no step wrote (the engine runs no step for a token
        that is the request's last). Returns ``{"reference": [(H, d, d) a
        layer], "control": [...] or None}``, numpy float32."""
        with jax.default_matmul_precision("highest"):
            _, states = self.walk(cfg, seed, served_dtype, control_mm).rows(
                prompt, tokens, width, n=len(prompt) + len(tokens) - 1)
        return {"reference": [np.asarray(st[0]) for st in states],
                "control": [np.asarray(st[1]) for st in states]
                if control_mm else None}


def served_gaps(*args, **kw):
    return Reference().served_gaps(*args, **kw)


def served_states(*args, **kw):
    return Reference().served_states(*args, **kw)


def _gaps(walk, samples, width, max_new):
    control_mm = walk.control_mm

    @jax.jit
    def gaps(g, x, at, tokens):
        """How far the reference's logit of ``tokens`` lies below its
        best, at rows ``at`` of x."""
        ref = walk.head(g, x[at])
        return jnp.max(ref, -1) \
            - jnp.take_along_axis(ref, tokens[:, None], -1)[:, 0]

    @jax.jit
    def first(g, x_low, at):
        """The tokens a lower precision puts first at rows ``at``."""
        return jnp.argmax(walk.head(g, x_low[at], control_mm), -1)

    out = {"served": [], "control": [] if control_mm else None}
    for p, t in samples:
        # position len(p) - 1 + j predicts served token j
        at = np.minimum(len(p) - 1 + np.arange(max_new),
                        len(p) + len(t) - 1)
        served = np.zeros((max_new,), np.int32)
        served[:len(t)] = t
        xs, _ = walk.rows(p, t, width)
        at, served = jnp.asarray(at), jnp.asarray(served)
        out["served"].append(
            np.asarray(gaps(walk.g, xs[0], at, served))[:len(t)])
        if control_mm:
            out["control"].append(np.asarray(gaps(
                walk.g, xs[0], at, first(walk.g, xs[1], at)))[:len(t)])
    return out
