"""The plain float32 follower of a training job's first steps: forward,
loss, gradients and AdamW, written out layer by layer so that a model
whose float32 state fills most of a chip still fits (one layer's
activations and attention scores are live at a time, in blocks of rows).

Imports nothing of the program. ``mm`` is the matrix product; the control
passes a lower-precision one. Returns the numbers ``correct`` compares:
each step's loss, the norm of every leaf of the first gradient, and the
norm of every leaf's change after the last step."""

import collections

import jax
import jax.numpy as jnp

from chipbench import weights as W


def _adamw(p, g, m, v, t, o):
    m = o["b1"] * m + (1 - o["b1"]) * g
    v = o["b2"] * v + (1 - o["b2"]) * jnp.square(g)
    mhat = m / (1 - o["b1"] ** t)
    vhat = v / (1 - o["b2"] ** t)
    p = p * (1 - o["lr"] * o["weight_decay"]) \
        - o["lr"] * mhat / (jnp.sqrt(vhat) + o["eps"])
    return p, m, v


def _norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def follow(cfg, seed, batches, opt, row_block, mm=jnp.matmul, devices=None):
    """Follow ``len(batches)`` steps from the seed's weights. ``batches``
    are host arrays (rows, seq + 1). The whole follower runs under
    ``highest`` matmul precision: on a TPU a float32 product is otherwise
    computed in bfloat16 passes. With several ``devices`` layer ``i`` and
    its state live on device ``i mod n`` and its work runs there (the
    float32 state of a model trained across chips does not fit one)."""
    fam = W.family(cfg)
    devices = list(devices or jax.devices()[:1])
    with jax.default_matmul_precision("highest"):
        return _follow(fam, cfg, seed, batches, opt, row_block, mm, devices)


def _follow(fam, cfg, seed, batches, opt, row_block, mm, devices):
    tmap = jax.tree_util.tree_map
    home = lambda i: devices[i % len(devices)]
    on = jax.device_put
    g_w = on(W.make_globals(seed, cfg, jnp.float32), devices[0])
    layers = [on(W.make_layer(seed, cfg, i, jnp.float32), home(i))
              for i in range(W.n_layers(cfg))]
    zeros = lambda t: tmap(jnp.zeros_like, t)
    g_m, g_v = zeros(g_w), zeros(g_w)
    l_m, l_v = [zeros(l) for l in layers], [zeros(l) for l in layers]

    n_tokens = batches[0].shape[0] * (batches[0].shape[1] - 1)

    fwd = jax.jit(lambda wl, x: fam.block(wl, x, cfg, mm))

    @jax.jit
    def bwd(wl, x, dy):
        _, pull = jax.vjp(lambda wl, x: fam.block(wl, x, cfg, mm), wl, x)
        return pull(dy)

    @jax.jit
    def top(g, x, labels):
        def loss_sum(g, x):
            logits = fam.head(g, x, cfg, mm)
            logz = jax.nn.logsumexp(logits, -1)
            hit = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
            return jnp.sum(logz - hit) / n_tokens
        loss, pull = jax.vjp(loss_sum, g, x)
        dg, dx = pull(jnp.ones((), jnp.float32))
        return loss, dg, dx

    @jax.jit
    def bottom(g, tokens, dx):
        _, pull = jax.vjp(lambda g: fam.embed(g, tokens, cfg), g)
        return pull(dx)[0]

    embed = jax.jit(lambda g, tokens: fam.embed(g, tokens, cfg))
    update = jax.jit(lambda p, g, m, v, t: jax.tree_util.tree_transpose(
        jax.tree_util.tree_structure(p), jax.tree_util.tree_structure((0, 0, 0)),
        tmap(lambda p, g, m, v: _adamw(p, g, m, v, t, opt), p, g, m, v)))
    add = jax.jit(lambda a, b: tmap(jnp.add, a, b))
    norms = jax.jit(_norms)

    out = {"losses": [], "grad_norms": None, "delta_norms": None}
    for t, batch in enumerate(batches, start=1):
        rows = [on(batch[r:r + row_block], devices[0])
                for r in range(0, batch.shape[0], row_block)]
        xs = [[on(embed(g_w, r[:, :-1]), home(0)) for r in rows]]
        for i, wl in enumerate(layers):
            xs[-1] = [on(x, home(i)) for x in xs[-1]]
            xs.append([fwd(wl, x) for x in xs[-1]])
        loss, dg, dxs = 0.0, None, []
        for r, x in zip(rows, xs.pop()):
            l, dg_r, dx = top(g_w, on(x, devices[0]), r[:, 1:])
            loss = loss + l
            dg = dg_r if dg is None else add(dg, dg_r)
            dxs.append(dx)
            jax.block_until_ready(dg)     # see the note on in_flight below
        grad_norms = {"layers": [None] * len(layers)}
        in_flight = collections.deque()
        for i in reversed(range(len(layers))):
            dwl, xin = None, xs.pop()
            for j, x in enumerate(xin):
                dwl_r, dxs[j] = bwd(layers[i], x, on(dxs[j], home(i)))
                dwl = dwl_r if dwl is None else add(dwl, dwl_r)
            if t == 1:
                grad_norms["layers"][i] = norms(dwl)
            layers[i], l_m[i], l_v[i] = update(layers[i], dwl, l_m[i],
                                               l_v[i], float(t))
            # buffers are taken when a call is queued, not when it runs:
            # keep the host no more than a layer for each further device
            # ahead, or every layer's gradients are held at once and the
            # peak memory read is the reference's, not the program's
            in_flight.append(l_v[i])
            if len(in_flight) >= len(devices):
                jax.block_until_ready(in_flight.popleft())
        for r, dx in zip(rows, dxs):
            dg = add(dg, bottom(g_w, r[:, :-1], on(dx, devices[0])))
        if t == 1:
            grad_norms["globals"] = norms(dg)
            out["grad_norms"] = jax.device_get(grad_norms)
        g_w, g_m, g_v = update(g_w, dg, g_m, g_v, float(t))
        out["losses"].append(float(loss))
    sub = jax.jit(lambda a, b: _norms(jax.tree_util.tree_map(jnp.subtract,
                                                             a, b)))
    out["delta_norms"] = jax.device_get({
        "globals": sub(g_w, on(W.make_globals(seed, cfg, jnp.float32),
                               devices[0])),
        "layers": [sub(layers[i], on(W.make_layer(seed, cfg, i, jnp.float32),
                                     home(i)))
                   for i in range(len(layers))]})
    return out
