"""Sweep of the grouped expert matmul at the serving programs' shapes:
``jax.lax.ragged_dot`` (what XLA runs), megablox's ``gmm`` (the installed
JAX's Pallas kernel, tiles as a parameter) and this repo's kernel
(``ops/grouped_matmul_kernel.py``), each beside the least time the
touched groups' weight bytes allow at the chip's HBM rate.

A shape is ``G,R,K,N``: ``R`` rows sorted into ``G`` groups, each group
through its own ``(K, N)`` weights, bf16 in and float32 out. The default
list is the four serving shapes and their down-projection transposes:
SDAR's block step (128,2048,2048,768) and prefill chunk
(128,8192,2048,768), Xing4's decode step (64,256,3584,1024) and chunk
(64,4096,3584,1024). Group sizes are drawn three ways, and are DATA of one
compiled arm:

- ``uniform``: ``R // G`` rows each;
- ``real``: eight groups share two fifths of the rows (about a hundred
  each at R 2048), the others hold one to four, a twelfth of them none,
  and what is left is the idle tail past the last group: what SDAR's
  block step sees while half a pass's positions hold the mask embedding;
- ``one``: all rows in one group.

An arm is timed as ``flash_block_sweep._time_scan`` times a kernel: R
serial calls inside one jitted ``lax.scan`` whose carry perturbs the
group sizes by an opaque zero (``where(c == 12345, 1, 0)``: a result's
unwritten rows may hold a NaN), so the body cannot be hoisted and nothing
but the call is in the loop.

Each row is printed as it is measured (``# {json}``), the table last.
PERF.md Findings PR 38 holds the chip's table and what was read off it
(the row tile of 128, and no rule on the rows a group:
``parallel/moe.py::_kernel_interpret``).

Usage: python benchmarks/grouped_matmul_sweep.py
           [--shape G,R,K,N] [--tiles]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox import gmm

from benchmarks.flash_block_sweep import _peaks
from distributed_pytorch_tpu.ops import grouped_matmul_kernel as gk

SHAPES = ((128, 2048, 2048, 768), (128, 2048, 768, 2048),
          (128, 8192, 2048, 768), (128, 8192, 768, 2048),
          (64, 256, 3584, 1024), (64, 256, 1024, 3584),
          (64, 4096, 3584, 1024), (64, 4096, 1024, 3584))
DRAWS = ("uniform", "real", "one")


def draw_sizes(kind: str, g: int, r: int, seed: int = 0) -> np.ndarray:
    """Group sizes (G,) int32 of one draw; their sum is at most ``r``."""
    rng = np.random.default_rng(seed)
    if kind == "uniform":
        return np.full((g,), r // g, np.int32)
    if kind == "one":
        sizes = np.zeros((g,), np.int32)
        sizes[g // 2] = r
        return sizes
    cold = min(4, max(1, r // (2 * g)))
    sizes = rng.integers(1, cold + 1, size=g).astype(np.int32)
    sizes[rng.choice(g, g // 12, replace=False)] = 0
    hot = rng.choice(g, 8, replace=False)
    sizes[hot] = (2 * r // 5) // 8 + rng.integers(-4, 5, size=8)
    assert sizes.sum() <= r, (kind, g, r, sizes.sum())
    return sizes


def _time_scan(call, args, sizes, least_s):
    """Seconds a call of ``call(*args, sizes) -> array``: see the
    module's docstring. The carry reads two elements of the result."""
    reps = int(min(200, max(8, 0.2 / (3 * least_s))))

    def repeated(c0, sizes, *args):
        def body(c, _):
            out = call(*args, sizes + jnp.where(c == 12345.0, 1, 0))
            return (out[0, 0] + out[-1, -1]).astype(jnp.float32) * 1e-30, \
                None
        return lax.scan(body, c0, None, length=reps)[0]

    f = jax.jit(repeated)
    times = {}
    for name, s in sizes.items():
        c = f(jnp.zeros((), jnp.float32), s, *args)
        c.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(2):
            c = f(c, s, *args)
        c.block_until_ready()
        times[name] = (time.perf_counter() - t0) / (2 * reps)
    return times


def _ragged(xs, w, sizes):
    return lax.ragged_dot(xs, w, sizes, preferred_element_type=jnp.float32)


def arms(k: int, n: int, tiles: bool):
    """(name, call) of every arm of one shape."""
    kern = lambda tm: lambda xs, w, s: gk.grouped_matmul(xs, w, s, tm=tm)
    mega = lambda t: lambda xs, w, s: gmm(
        xs, w, s, preferred_element_type=jnp.float32, tiling=t)
    out = [("ragged_dot", _ragged),
           ("gmm 128,128,128", mega((128, 128, 128))),
           ("gmm 128,K,N", mega((128, k, n))),
           ("kernel tm=128", kern(128))]
    if tiles:
        out += [("gmm 512,1024,1024", mega((512, 1024, 1024))),
                ("gmm 256,K,N", mega((256, k, n))),
                ("gmm 128,K/2,N", mega((128, k // 2, n))),
                ("gmm 128,K,N/2", mega((128, k, n // 2))),
                ("kernel tm=64", kern(64)),
                ("kernel tm=256", kern(256)),
                ("kernel tm=512", kern(512))]
    return out


def sweep_shape(g, r, k, n, tiles=False) -> list:
    """Every arm of one shape under the three draws."""
    peaks = _peaks()
    kx, kw = jax.random.split(jax.random.PRNGKey(g + r + k))
    xs = jax.random.normal(kx, (r, k), jnp.bfloat16)
    w = jax.random.normal(kw, (g, k, n), jnp.bfloat16) * k ** -0.5
    sizes = {d: jnp.asarray(draw_sizes(d, g, r)) for d in DRAWS}
    least = {d: int(jnp.sum(s > 0)) * k * n * 2 / peaks["hbm_bytes_per_s"]
             for d, s in sizes.items()}
    want = {d: _ragged(xs, w, s) for d, s in sizes.items()}
    rows = []
    for name, call in arms(k, n, tiles):
        row = {"shape": [g, r, k, n], "arm": name}
        try:
            times = _time_scan(call, (xs, w), sizes, least["uniform"])
            got = {d: jax.jit(call)(xs, w, s) for d, s in sizes.items()}
        except Exception as e:  # noqa: BLE001 — an arm that VMEM refuses
            row["error"] = f"{type(e).__name__}: {str(e)[:120]}"
            print(f"# {json.dumps(row)}", flush=True)
            rows.append(row)
            continue
        for d in DRAWS:
            rows_in = int(jnp.sum(sizes[d]))
            gap = float(jnp.max(jnp.abs(
                got[d][:rows_in].astype(jnp.float32)
                - want[d][:rows_in].astype(jnp.float32)))) if rows_in else 0.0
            row[d] = {"us": round(times[d] * 1e6, 1),
                      "least_us": round(least[d] * 1e6, 1),
                      "bytes_rate_pct": round(100 * least[d] / times[d], 1),
                      "max_gap": round(gap, 5)}
        print(f"# {json.dumps(row)}", flush=True)
        rows.append(row)
    return rows


def table(rows) -> str:
    head = "| G,R,K,N | arm | " + " | ".join(
        f"{d} us (% of bytes' rate)" for d in DRAWS) + " |"
    lines = [head, "|---|---|" + "---|" * len(DRAWS)]
    for r in rows:
        cells = [r.get("error", "")] * len(DRAWS) if "error" in r else [
            f"{r[d]['us']} ({r[d]['bytes_rate_pct']})" for d in DRAWS]
        lines.append(f"| {','.join(map(str, r['shape']))} | {r['arm']} | "
                     + " | ".join(cells) + " |")
    return "\n".join(lines)


def main(argv):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": "no TPU available", "device": str(dev)}))
        return 1
    shapes = SHAPES
    if "--shape" in argv:
        shapes = (tuple(int(x) for x in
                        argv[argv.index("--shape") + 1].split(",")),)
    rows = []
    for shape in shapes:
        rows += sweep_shape(*shape, tiles="--tiles" in argv)
    print(table(rows))
    print(json.dumps({"device": dev.device_kind, "rows": rows}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
