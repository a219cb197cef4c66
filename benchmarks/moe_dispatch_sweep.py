"""Sweep of the expert layer's dispatch and combine at the cells' shapes:
the form that gathers every routed pair and gathers it back
(``DroplessMoE._every_pair``: what a layer that holds every expert runs,
and what every layer ran before PR 44) beside the form that works on the
blocks of sorted pairs that hold a pair of this chip's experts
(``DroplessMoE._pairs_here``), over the block's size
(``parallel/moe.py::_BLOCK_ROWS``), forward and with the gradient.

A shape is ``T`` tokens of width ``D`` routed ``k`` ways over experts of
width ``F``, ``held`` of ``n_routed`` experts here, and the share of the
``T * k`` pairs that came to them:

- ``joyai``: the training step's layer (8192, 2048, top-8, 768; 16 of
  256 held) at 6, 10 and 16 % of the pairs here, forward and gradient;
- ``kexaone``: the K-EXAONE cell's ``prefill_b1024`` chunk (1024, 6144,
  top-8, 2048; 16 of 128) at 12.5 %;
- the layers that hold every expert, where the question is whether one
  form could serve all: Xing4's chunk (1024 tokens, 3584, top-4, 1024,
  64 experts) and decode pass (64 tokens), SDAR's chunk (1024, 2048,
  top-8, 768, 128 experts) and block step (256 tokens).

What is timed is ``DroplessMoE.routed`` with the routing GIVEN (the
router is the same work in every arm, so a subclass hands the chosen
experts and weights in as data): the sort, the dispatch, the three
grouped matmuls, the elementwise and the combine. An arm is timed as
``grouped_matmul_sweep._time_scan`` times a kernel: serial calls inside
one jitted loop whose carry perturbs the activations by an opaque zero. Forward arms on a TPU take the Mosaic grouped matmul as a serving
program does, gradient arms ``ragged_dot`` as a training step does.

Each row is printed as it is measured (``# {json}``) and the rows are
left in ``chiprun_out/moe_dispatch_sweep.json``; the table comes last.
PERF.md Findings PR 44 holds the chip's table and the constant read off
it.

Usage: python benchmarks/moe_dispatch_sweep.py [--shape NAME] [--blocks 512,2048]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from distributed_pytorch_tpu.parallel import moe

#: name -> (T, D, k, F, held, n_routed, shares of the pairs here, gradient too)
SHAPES = {
    "joyai": (8192, 2048, 8, 768, 16, 256, (0.06, 0.10, 0.16), True),
    "kexaone": (1024, 6144, 8, 2048, 16, 128, (0.125,), False),
    "xing4-chunk": (1024, 3584, 4, 1024, 64, 64, (1.0,), False),
    "xing4-pass": (64, 3584, 4, 1024, 64, 64, (1.0,), False),
    "sdar-chunk": (1024, 2048, 8, 768, 128, 128, (1.0,), False),
    "sdar-pass": (256, 2048, 8, 768, 128, 128, (1.0,), False),
}
BLOCKS = (512, 1024, 2048, 4096)


class Given(moe.DroplessMoE):
    """The layer with its routing handed in: ``params["chosen"]`` (T, k)
    int32 and ``params["weights"]`` (T, k) float32."""

    def route(self, params, xt):
        return params["chosen"], params["weights"], None


class GatherBack(Given):
    """The same, every routed pair gathered and gathered back whatever
    share of the experts the layer holds."""

    _pairs_here = moe.DroplessMoE._every_pair


def draw_routing(t, k, held, n_routed, share, seed=0):
    """(T, k) distinct experts a token, ``share`` of the pairs (in the
    mean) among the first ``held``, and weights that sum to 1 a token."""
    rng = np.random.default_rng(seed)
    if held == n_routed:
        scores = rng.random((t, n_routed))
    else:
        n_here = rng.binomial(k, share, size=t)
        scores = rng.random((t, n_routed))
        # the n_here best of the held experts, then the best of the rest
        rank_here = np.argsort(np.argsort(-scores[:, :held], axis=1), axis=1)
        scores[:, :held] = np.where(rank_here < n_here[:, None],
                                    2.0 + scores[:, :held], -1.0)
    chosen = np.argsort(-scores, axis=1)[:, :k].astype(np.int32)
    weights = rng.random((t, k)).astype(np.float32) + 0.1
    return chosen, weights / weights.sum(1, keepdims=True)


def make_params(layer, chosen, weights, seed=0):
    c, d, f = layer.count, layer.dim, layer.width
    kg, ku, kd = jax.random.split(jax.random.PRNGKey(seed), 3)
    mk = lambda key, shape, fan: (jax.random.normal(key, shape, jnp.float32)
                                  / np.sqrt(fan)).astype(jnp.bfloat16)
    return {"experts": {"gate": mk(kg, (c, d, f), d), "up": mk(ku, (c, d, f), d),
                        "down": mk(kd, (c, f, d), f)},
            "chosen": jnp.asarray(chosen), "weights": jnp.asarray(weights)}


def _time_scan(layer, params, xt, grad: bool, budget_s: float = 0.3):
    """Seconds a call of ``layer.routed`` (``grad``: with the gradient
    of a scalar of its result for the activations, the experts and the
    pairs' weights)."""
    def once(c, params, xt):
        x = xt + c.astype(xt.dtype)
        if not grad:
            return layer.routed(params, x)[0][0, 0] * 1e-30

        def scalar(x, experts, weights):
            y = layer.routed({**params, "experts": experts,
                              "weights": weights}, x)[0]
            return jnp.sum(y * y)

        gx, ge, gw = jax.grad(scalar, argnums=(0, 1, 2))(
            x, params["experts"], params["weights"])
        return (gx[0, 0].astype(jnp.float32) + ge["down"][0, 0, 0].astype(
            jnp.float32) + gw[0, 0]) * 1e-30

    @jax.jit
    def run(c0, reps, params, xt):
        return lax.fori_loop(0, reps, lambda _, c: once(c, params, xt), c0)

    zero = jnp.zeros((), jnp.float32)
    run(zero, 2, params, xt).block_until_ready()
    t0 = time.perf_counter()
    run(zero, 2, params, xt).block_until_ready()
    reps = int(min(100, max(4, budget_s / ((time.perf_counter() - t0) / 2))))
    c = run(zero, reps, params, xt)
    c.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(2):
        c = run(c, reps, params, xt)
    c.block_until_ready()
    return (time.perf_counter() - t0) / (2 * reps)


def sweep(names, blocks):
    rows = []
    for name in names:
        t, d, k, f, held, n_routed, shares, with_grad = SHAPES[name]
        xt = (jax.random.normal(jax.random.PRNGKey(1), (t, d), jnp.float32)
              ).astype(jnp.bfloat16)
        for share in shares:
            chosen, weights = draw_routing(t, k, held, n_routed, share)
            here = int(np.sum(chosen < held))
            for grad in (False, True) if with_grad else (False,):
                one_block = None
                for arm in ("gather-back",) + tuple(blocks):
                    cls = GatherBack if arm == "gather-back" else Given
                    if arm != "gather-back":
                        moe._BLOCK_ROWS = arm
                        if arm >= t * k and one_block is not None:
                            # one block of all the pairs again: the same
                            # program as the arm before
                            rows.append({**one_block, "arm": str(arm)})
                            continue
                    # an all-held layer takes the blocked form only as a
                    # share of one more expert that gets no pair
                    extra = 1 if held == n_routed and cls is Given else 0
                    layer = cls(d, n_routed + extra, f, top_k=k, n_shared=0,
                                held=(0, held), dtype=jnp.bfloat16)
                    params = make_params(layer, chosen, weights)
                    secs = _time_scan(layer, params, xt, grad)
                    row = {"shape": name, "pairs": t * k, "pairs_here": here,
                           "pass": "fwd+grad" if grad else "fwd",
                           "arm": str(arm), "ms": round(secs * 1e3, 4)}
                    print("# " + json.dumps(row), flush=True)
                    rows.append(row)
                    if arm != "gather-back" and arm >= t * k:
                        one_block = row
    return rows


def table(rows):
    arms = []
    for r in rows:
        if r["arm"] not in arms:
            arms.append(r["arm"])
    print("| shape | pairs (here) | pass | " + " | ".join(arms) + " |")
    print("|---|---|---|" + "---|" * len(arms))
    seen = []
    for r in rows:
        key = (r["shape"], r["pairs"], r["pairs_here"], r["pass"])
        if key in seen:
            continue
        seen.append(key)
        cells = {x["arm"]: x["ms"] for x in rows if (
            x["shape"], x["pairs"], x["pairs_here"], x["pass"]) == key}
        print(f"| {key[0]} | {key[1]} ({key[2]}) | {key[3]} | " + " | ".join(
            f"{cells[a]:.3f}" if a in cells else "-" for a in arms) + " |")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", action="append", choices=sorted(SHAPES))
    ap.add_argument("--blocks", default=",".join(map(str, BLOCKS)))
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    print(f"# device {dev.platform} {dev.device_kind}", flush=True)
    constant = moe._BLOCK_ROWS
    try:
        rows = sweep(args.shape or list(SHAPES),
                     [int(b) for b in args.blocks.split(",")])
    finally:
        moe._BLOCK_ROWS = constant
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "moe_dispatch_sweep.json"), "w") as fh:
        json.dump({"device": dev.device_kind, "constant": constant,
                   "rows": rows}, fh, indent=1)
    table(rows)


if __name__ == "__main__":
    main()
