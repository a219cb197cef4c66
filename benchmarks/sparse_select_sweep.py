"""Sweep of a prompt chunk's selection at the MiniCPM-SALA cell's shapes:
2 KV heads of 16 query heads, 1024 queries of width 128, a slot of 1032
blocks of 64 positions = 4128 compressed keys (kernel 32, stride 16), the
64 best blocks beside the first and those of the last 2048 positions.

The plain form (every window of the slot scored, softmaxed and pooled for
128 queries at a time: ``choose_blocks(window_probs(...))`` under
``lax.map``, what a chunk ran before PR 46 and what the decode step and
the whole-sequence mask still run) beside the walk over the windows that
the prompt so far has closed (``chunk_block_scores`` then
``choose_scored``: what ``SelectedPages.attend_tail`` runs), over the
walk's windows a trip (``nn/sparse_attention.py::WINDOW_BLOCK``) and the
chunk's offset; ``choice`` is ``choose_scored`` alone on given scores,
the part both forms share. Every arm is one jitted program of the
selection alone (the ``select`` scope of a sparse layer of
``prefill_b1024``), the offset a traced scalar as in the served program,
timed over repeated calls that end in ``block_until_ready``. The walk's
sets are compared with the plain form's at every offset (``same``: the
share of (KV head, query, block) that agree).

Each row is printed as it is measured (``# {json}``) and the rows are
left in ``chiprun_out/sparse_select_sweep.json``; the table comes last.
PERF.md Findings PR 46 holds the chip's table and the constant read off
it.

Usage: python benchmarks/sparse_select_sweep.py [--offsets 0,16384] [--blocks 256,512]
"""

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_pytorch_tpu.nn.sparse_attention import (
    Selection, choose_blocks, choose_scored, chunk_block_scores, window_probs)

SEL = Selection(kernel=32, stride=16, block=64, topk=64, init_blocks=1,
                window=2048, dense_len=8192)
HKV, G, S, DH, N_BLOCKS = 2, 16, 1024, 128, 1032
OFFSETS = (0, 16384, 32768, 65024)       # the last: the slot's last chunk
BLOCKS = (256, 512, 1024)
SCALE = 1.0 / math.sqrt(DH)


def plain(q, ck, offset):
    """(Hkv, S, n_blocks) bool, as a chunk chose before PR 46."""
    qb = 128
    positions = offset + jnp.arange(S)

    def choose(args):
        qq, tt = args                              # (Hkv, g, qb, Dh), (qb,)
        tt = jnp.broadcast_to(tt[None, :], (HKV, qb))
        p = window_probs(jnp.moveaxis(qq, 1, 2), ck[:, None], tt, SEL, SCALE)
        return choose_blocks(p, tt, SEL, N_BLOCKS)[0]

    chosen = jax.lax.map(choose, (
        jnp.moveaxis(q.reshape(HKV, G, S // qb, qb, DH), 2, 0),
        positions.reshape(-1, qb)))
    return jnp.moveaxis(chosen, 0, 1).reshape(HKV, S, N_BLOCKS)


def walk(window_block):
    def run(q, ck, offset):
        positions = offset + jnp.arange(S)
        score = chunk_block_scores(q, ck, positions, SEL.closed(offset + S),
                                   SEL, SCALE, N_BLOCKS, window_block)
        return choose_scored(score, positions, SEL)[0]
    return run


def choice(q, ck, offset):
    # scores that depend on the inputs, so that nothing folds away
    score = jnp.abs(ck[:, :N_BLOCKS, 0].astype(jnp.float32))[:, None, :] \
        + jnp.abs(q[:, 0, :, :1].astype(jnp.float32))
    return choose_scored(score, offset + jnp.arange(S), SEL)[0]


def time_ms(fn, *args, budget_s=1.0):
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    fn(*args).block_until_ready()
    reps = int(min(50, max(3, budget_s / (time.perf_counter() - t0))))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e3


def sweep(offsets, blocks):
    kq, kk = jax.random.split(jax.random.PRNGKey(46))
    # unit-norm heads times the cell's q/k gain: attention logits of std 1.44
    q = (1.2 * jax.random.normal(kq, (HKV, G, S, DH), jnp.float32)
         ).astype(jnp.bfloat16)
    ck = (1.2 * jax.random.normal(
        kk, (HKV, N_BLOCKS * (SEL.block // SEL.stride), DH), jnp.float32)
        ).astype(jnp.bfloat16)
    arms = [("plain", jax.jit(plain)), ("choice", jax.jit(choice))] + [
        (f"walk-{b}", jax.jit(walk(b))) for b in blocks]
    rows = []
    for offset in offsets:
        at = jnp.asarray(offset, jnp.int32)
        want = None
        for name, fn in arms:
            row = {"offset": offset, "arm": name,
                   "ms": round(time_ms(fn, q, ck, at), 4)}
            if name == "plain":
                want = np.asarray(fn(q, ck, at))
            elif name != "choice":
                row["same"] = float(np.mean(np.asarray(fn(q, ck, at))
                                            == want))
            print("# " + json.dumps(row), flush=True)
            rows.append(row)
    return rows


def table(rows):
    arms = list(dict.fromkeys(r["arm"] for r in rows))
    print("| offset | " + " | ".join(arms) + " |")
    print("|---|" + "---|" * len(arms))
    for offset in dict.fromkeys(r["offset"] for r in rows):
        cells = {r["arm"]: r for r in rows if r["offset"] == offset}
        print(f"| {offset} | " + " | ".join(
            f"{cells[a]['ms']:.3f}" + (f" ({cells[a]['same']:.6f})"
                                       if "same" in cells[a] else "")
            for a in arms) + " |")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--offsets", default=",".join(map(str, OFFSETS)))
    ap.add_argument("--blocks", default=",".join(map(str, BLOCKS)))
    args = ap.parse_args()
    d = jax.devices()[0]
    print(f"# device {d.platform} {d.device_kind} x{jax.device_count()}")
    rows = sweep([int(x) for x in args.offsets.split(",")],
                 [int(x) for x in args.blocks.split(",")])
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/sparse_select_sweep.json", "w") as f:
        json.dump({"device": d.device_kind, "rows": rows}, f, indent=1)
    table(rows)


if __name__ == "__main__":
    main()
