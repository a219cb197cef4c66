"""The plain float32 follower of a training job whose model brings its own
loss and a state outside the optimizer (kind ``train_mtp``): forward, the
two-head loss, gradients, AdamW on every leaf but the router biases, and
the biases' own rule, written out layer by layer as
``reference/train_steps.py`` is (one layer's activations live at a time,
in blocks of rows).

Imports nothing of the program. The family's reference
(``reference/<family>.py``) gives ``embed``, ``block`` (which also returns
its router's load), ``main_loss_sum`` and ``mtp_loss_sum`` (each head's
summed cross-entropy, the second with the prediction module's load),
``balance`` and ``is_router_bias``. The learning rate is the job's law of
the step, written here on its own (``rate``): constant, or a linear
warm-up to ``lr`` where the job names ``warmup_steps``. ``mm`` is
the matrix product; the control passes a lower-precision one. Returns what
``correct`` compares: each step's loss (and each head's), the norm of
every leaf of the first gradient (the biases have none), and the norm of
every leaf's change after the last step (the biases' too)."""

import jax
import jax.numpy as jnp

from chipbench import weights as W
from chipbench.reference.train_steps import _adamw, _norms


def follow(cfg, seed, batches, job, mm=jnp.matmul, devices=None):
    """Follow ``len(batches)`` steps from the seed's weights. ``batches``
    are host arrays (rows, seq + 1); ``job`` is the traffic file
    (``optimizer``, ``mtp_weight``, ``bias_update_speed``,
    ``reference_row_block``). Runs under ``highest`` matmul precision: on
    a TPU a float32 product is otherwise computed in bfloat16 passes."""
    with jax.default_matmul_precision("highest"):
        return _follow(W.family(cfg), cfg, seed, batches, job, mm,
                       list(devices or jax.devices()[:1])[0])


def rate(opt, t):
    """The learning rate of update ``t`` (counted from 1): ``lr``, or
    under a warm-up of ``warmup_steps`` the ramp ``lr * t / warmup_steps``
    until it reaches ``lr`` (the update's own count, so step ``k`` counted
    from 0 runs at ``lr * min(1, (k + 1) / warmup_steps)``)."""
    if "warmup_steps" not in opt:
        return opt["lr"]
    return opt["lr"] * jnp.minimum(1.0, t / opt["warmup_steps"])


def programs(fam, cfg, job, mm, n_main, n_mtp):
    """The follower's compiled pieces, each over one block of rows:
    ``embed``, ``fwd`` and ``bwd`` of one expert layer, the two heads
    (``top_main``: the final norm and the head; ``top_mtp``: the
    prediction module and the head again, apart so that neither holds the
    other's temporaries), ``bottom`` (the embedding and the dense layer's
    backward), each with the gradient of the globals it reads
    (``fam.used_by``), ``update``, ``add``, ``norms``. ``n_main`` / ``n_mtp``: the
    positions each head's mean is over."""
    tmap = jax.tree_util.tree_map
    opt, lam, speed = (job["optimizer"], job["mtp_weight"],
                       job["bias_update_speed"])

    def bwd(wl, x, dy):
        _, pull, _ = jax.vjp(lambda wl, x: fam.block(wl, x, cfg, mm), wl, x,
                             has_aux=True)
        return pull(dy)

    def mine(part, g):
        """The globals ``part`` reads, and the rest: a gradient is formed
        for the first alone (the others' would be a gigabyte of zeros)."""
        used = {n: x for n, x in g.items() if fam.used_by(part, n)}
        return used, {n: x for n, x in g.items() if n not in used}

    def top(part, loss_of):
        def run(g, h, tokens):
            used, rest = mine(part, g)
            loss, pull, aux = jax.vjp(
                lambda u, h: loss_of({**u, **rest}, h, tokens), used, h,
                has_aux=True)
            return (loss, aux) + pull(jnp.ones((), jnp.float32))
        return run

    def main_loss(g, h, tokens):
        return fam.main_loss_sum(g, h, tokens, cfg, mm) / n_main, None

    def mtp_loss(g, h, tokens):
        total, load = fam.mtp_loss_sum(g, h, tokens, cfg, mm)
        return lam * total / n_mtp, load

    def bottom(g, tokens, dx):
        used, rest = mine("embed", g)
        _, pull = jax.vjp(lambda u: fam.embed({**u, **rest}, tokens, cfg),
                          used)
        return pull(dx)[0]

    def update(p, g, m, v, t, loads):
        """AdamW on every leaf but the router biases, which take their
        own rule from ``loads`` ({leaf name: load})."""
        out = {}
        for name in p:
            if fam.is_router_bias(name):
                out[name] = (fam.balance(p[name], loads[name], speed),
                             m[name], v[name])
            else:
                out[name] = _adamw(p[name], g[name], m[name], v[name], t,
                                   {**opt, "lr": rate(opt, t)})
        return tuple({n: o[i] for n, o in out.items()} for i in range(3))

    return {
        "embed": jax.jit(lambda g, tokens: fam.embed(g, tokens, cfg)),
        "fwd": jax.jit(lambda wl, x: fam.block(wl, x, cfg, mm)),
        "bwd": jax.jit(bwd), "top_main": jax.jit(top("head", main_loss)),
        "top_mtp": jax.jit(top("mtp", mtp_loss)), "bottom": jax.jit(bottom),
        "update": jax.jit(update, donate_argnums=(0, 2, 3)),
        "add": jax.jit(lambda a, b: tmap(jnp.add, a, b), donate_argnums=0),
        "norms": jax.jit(lambda t: _norms(
            {n: x for n, x in t.items() if not fam.is_router_bias(n)}))}


def _follow(fam, cfg, seed, batches, job, mm, device):
    tmap = jax.tree_util.tree_map
    lam, row_block = job["mtp_weight"], job["reference_row_block"]
    on = lambda x: jax.device_put(x, device)
    g_w = on(W.make_globals(seed, cfg, jnp.float32))
    layers = [on(W.make_layer(seed, cfg, i, jnp.float32))
              for i in range(W.n_layers(cfg))]
    # AdamW's moments wait on the host between a group's updates: with
    # them resident (twice the parameters) the prediction module's
    # backward did not fit beside them on the chip
    moments = {}

    def step_group(key, w, grads, t, loads):
        m, v = moments.pop(key, (None, None))
        if m is None:
            m, v = tmap(jnp.zeros_like, w), tmap(jnp.zeros_like, w)
        w, m, v = update(w, grads, on(m), on(v), float(t), loads)
        if t < len(batches):
            moments[key] = jax.device_get((m, v))
        return w

    rows_all, width = batches[0].shape
    p = programs(fam, cfg, job, mm, rows_all * (width - 1),
                 rows_all * (width - 2))
    embed, fwd, bwd, bottom, update, add, norms = (
        p[k] for k in ("embed", "fwd", "bwd", "bottom", "update", "add",
                       "norms"))

    out = {"losses": [], "losses_main": [], "losses_mtp": [],
           "grad_norms": None, "delta_norms": None, "loads": []}
    for t, batch in enumerate(batches, start=1):
        rows = [on(batch[r:r + row_block])
                for r in range(0, batch.shape[0], row_block)]
        xs, loads = [[embed(g_w, r[:, :-1]) for r in rows]], []
        for wl in layers:
            ys = [fwd(wl, x) for x in xs[-1]]
            xs.append([y for y, _ in ys])
            loads.append(sum(load for _, load in ys))
        main = mtp = 0.0
        dg, load_m, dxs = {}, 0, []

        def gather(part):
            """Add a piece's gradient of the globals it reads to ``dg``."""
            for n, x in part.items():
                dg[n] = dg[n] + x if n in dg else x
            jax.block_until_ready(dg)

        for r, h in zip(rows, xs.pop()):
            l_main, _, dg_r, dx = p["top_main"](g_w, h, r)
            gather(dg_r)
            l_mtp, load, dg_r, dx_m = p["top_mtp"](g_w, h, r)
            gather(dg_r)
            main, mtp = main + l_main, mtp + l_mtp / lam
            load_m = load_m + load
            dxs.append(dx + dx_m)
        loss = main + lam * mtp
        grad_norms = {"layers": [None] * len(layers)}
        for i in reversed(range(len(layers))):
            dwl, xin = None, xs.pop()
            for j, x in enumerate(xin):
                dwl_r, dxs[j] = bwd(layers[i], x, dxs[j])
                dwl = dwl_r if dwl is None else add(dwl, dwl_r)
            if t == 1:
                grad_norms["layers"][i] = norms(dwl)
            layers[i] = step_group(i, layers[i], dwl, t,
                                   {"b_router": loads[i]})
            # buffers are taken when a call is queued, not when it runs
            # (reference/train_steps.py): keep the host a layer behind
            jax.block_until_ready(layers[i])
        for r, dx in zip(rows, dxs):
            gather(bottom(g_w, r[:, :-1], dx))
        if t == 1:
            grad_norms["globals"] = norms(dg)
            out["grad_norms"] = jax.device_get(grad_norms)
        g_w = step_group("globals", g_w, dg, t, {"m_b_router": load_m})
        out["losses"].append(float(loss))
        out["losses_main"].append(float(main))
        out["losses_mtp"].append(float(mtp))
        out["loads"].append([[int(c) for c in l] for l in loads + [load_m]])
    sub = jax.jit(lambda a, b: _norms(tmap(jnp.subtract, a, b)))
    out["delta_norms"] = jax.device_get({
        "globals": sub(g_w, on(W.make_globals(seed, cfg, jnp.float32))),
        "layers": [sub(layers[i], on(W.make_layer(seed, cfg, i,
                                                  jnp.float32)))
                   for i in range(len(layers))]})
    return out
