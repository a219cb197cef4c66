"""Block-size sweep for the blockwise attention kernels — train AND
decode shapes from one driver.

The flash kernel's cost at moderate sequence lengths is dominated by
grid-step count (per-step fixed overhead + per-tile mask/stat VPU
work), not MXU time, so (block_q, block_k) is the first-order tuning
knob. This sweeps tilings per sequence length, timed with the amortized
scan-repeat method (see flash_attention_tpu._time_kernel) and prints
the best per seq — those become the kernel's dispatch-table defaults.

``--shape b,h,s,d,dv`` sweeps ONE call shape kernel by kernel (forward,
dK/dV, dQ, each timed alone) and prints for every (kernel, tile) row,
beside its time and share of the roofline, what the schedule does,
counted from the index maps the ``pallas_call`` is built with:
``grid_steps``, ``steps_visible`` (those ``_frontier_ok`` lets compute),
``inner_mb_copied`` (megabytes of the operands that move along the inner
grid axis — K and V in forward and dQ; q, dO and the row statistics in
dK/dV — that the pipeline copies in: a step whose block index equals the
step before's copies nothing) and ``vmem_limit_mb``. Then the default
tiles' forward and forward + backward time through the public op.
``_block_sizes``' table for head widths over 128 was read off these rows
at ``1,32,<s>,192,128`` for s 1024, 2048, 4096, 8192 (PERF.md, Findings,
PR 36); ``8,25,1024,64,64`` is GPT-2 XL's call.

``--decode`` sweeps the DECODE page-scan instead
(ops/decode_attention.py): block length vs resident length over a long
slot pool, so the same table that picks the training tiles also picks
the serving page/block size (the decode kernel is shared by
serve/cache.py, serve/pages/ and both engines — docs/compute.md).

Usage: python benchmarks/flash_block_sweep.py
           [--fwdbwd | --decode | --shape b,h,s,d,dv]
"""

import importlib
import itertools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import numpy as np
from jax import lax

from benchmarks.flash_attention_tpu import _qkv, _time_kernel
from distributed_pytorch_tpu.ops import flash_attention
from distributed_pytorch_tpu.ops.decode_attention import (
    blockwise_decode_attention, dense_decode_attention)


def sweep_decode(pool_len: int = 4096, n_slots: int = 8, h: int = 8,
                 h_kv: int = 4, d: int = 64) -> dict:
    """Decode page-scan point: ms/step per (block_len, resident_len)
    over a (n_slots, Hkv, pool_len, Dh) pool, plus the dense full-pool
    baseline per resident length. The right block length balances
    per-block loop overhead against wasted tail width — exactly the
    grid-step-vs-tile tradeoff of the training sweep, at decode shapes.
    """
    dtype = jnp.bfloat16
    scale = 1.0 / math.sqrt(d)
    key = jax.random.PRNGKey(3)
    q, k, v = _qkv(key, n_slots, h, 1, pool_len, d, dtype)
    k = k[:, :h_kv]
    v = v[:, :h_kv]
    table = {}
    for resident in (64, 512, pool_len):
        lengths = jnp.full((n_slots,), resident - 1, jnp.int32)
        rows = []
        for blk in (64, 128, 256, 512):

            def fn(q, k, v, _b=blk):
                return jnp.sum(blockwise_decode_attention(
                    q, k, v, lengths, scale=scale,
                    block_len=_b).astype(jnp.float32))

            try:
                t = _time_kernel(fn, q, k, v)
            except Exception as e:  # noqa: BLE001
                print(f"# decode res={resident} blk={blk}: "
                      f"{type(e).__name__}", file=sys.stderr, flush=True)
                continue
            rows.append({"block_len": blk, "ms": round(t * 1e3, 3)})
            print(f"# decode res={resident} blk={blk}: {t*1e3:.3f}ms",
                  file=sys.stderr, flush=True)

        def dense_fn(q, k, v):
            mask = jnp.arange(pool_len)[None, :] <= lengths[:, None]
            return jnp.sum(dense_decode_attention(
                q, k, v, mask, scale=scale).astype(jnp.float32))

        try:
            t = _time_kernel(dense_fn, q, k, v)
            dense_ms = round(t * 1e3, 3)
        except Exception as e:  # noqa: BLE001
            dense_ms = f"{type(e).__name__}"
        rows.sort(key=lambda r: r["ms"])
        table[resident] = {"dense_full_pool_ms": dense_ms, "arms": rows}
        print(f"# decode res={resident} best: "
              f"{json.dumps(rows[0]) if rows else 'ALL FAILED'} "
              f"(dense {dense_ms}ms)", flush=True)
    return {"mode": "decode", "pool_len": pool_len, "n_slots": n_slots,
            "best": {r: t["arms"][0] for r, t in table.items()
                     if t["arms"]},
            "all": table}


_fa = importlib.import_module("distributed_pytorch_tpu.ops.flash_attention")
_KERNELS = ("fwd", "dkv", "dq")


def _peaks():
    """The chip's peaks, from the benchmark's one table."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "peaks.json")) as f:
        return json.load(f)[jax.devices()[0].device_kind]


def _least_seconds(kernel, b, h, s, d, dv, peaks, causal=True):
    """The least time one kernel of one call can take: the larger of its
    matmul FLOPs over the bf16 peak and its operands' bytes over the HBM
    peak. Forward: QK^T at d and PV at dv; dK/dV: QK^T, dK = dS^T Q at d
    and dV = P^T dO, dP = dO V^T at dv; dQ: QK^T, dQ = dS K at d and dP
    at dv. Causal halves the FLOPs; every operand and result moves once."""
    widths = {"fwd": d + dv, "dkv": 2 * d + 2 * dv, "dq": 2 * d + dv}[kernel]
    flops = b * h * 2 * s * s * widths * (0.5 if causal else 1.0)
    qk, vo = b * h * s * d, b * h * s * dv
    els = {"fwd": 2 * qk + 2 * vo, "dkv": 3 * qk + 3 * vo + vo,
           "dq": 3 * qk + 3 * vo}[kernel]
    return max(flops / peaks["bf16_flops_per_s"],
               (els * 2 + b * h * s * 4) / peaks["hbm_bytes_per_s"])


def _schedule(call, kernel, bh):
    """What the ``pallas_call`` recorded in ``call`` does, counted from
    its own grid and index maps on the CPU."""
    grid, in_specs, ok = call
    _, n_out, n_in = grid
    outer, inner = np.meshgrid(np.arange(n_out), np.arange(n_in),
                               indexing="ij")
    outer, inner = outer.ravel(), inner.ravel()
    with jax.default_device(jax.devices("cpu")[0]):
        iq, ik = (inner, outer) if kernel == "dkv" else (outer, inner)
        visible = int(np.sum(np.asarray(ok(iq, ik)))) if ok else outer.size
        copied = 0
        # the operands that move along the inner axis: K and V (forward,
        # dQ); q, dO and the two row statistics (dK/dV)
        for i in (0, 3, 4, 5) if kernel == "dkv" else (1, 2):
            spec, width = in_specs[i]
            idx = np.stack([np.broadcast_to(np.asarray(x), outer.shape)
                            for x in spec.index_map(np.zeros_like(outer),
                                                    outer, inner)], 1)
            moved = 1 + int(np.sum(np.any(idx[1:] != idx[:-1], axis=1)))
            copied += moved * spec.block_shape[1] * width
    return {"grid_steps": bh * outer.size, "steps_visible": bh * visible,
            "inner_mb_copied": round(bh * copied / 1e6, 1)}


def _record_calls(fn, *args):
    """The ``pallas_call``s that tracing ``fn(*args)`` builds: (grid,
    [(in_spec, bytes a row)], the kernel's ``_frontier_ok`` or None)."""
    calls, real = [], _fa.pl.pallas_call

    def recording(kernel, **kw):
        def call(*ops):
            kws = kernel.keywords
            geom = {k: kws[k] for k in ("block_q", "block_k", "q_len",
                                        "k_len", "window", "diag_offset")}
            ok = (lambda iq, ik: _fa._frontier_ok(iq, ik, **geom)) \
                if kws["causal"] else None
            calls.append((kw["grid"], [
                (spec, op.shape[-1] * op.dtype.itemsize)
                for spec, op in zip(kw["in_specs"], ops)], ok))
            return real(kernel, **kw)(*ops)
        return call

    _fa.pl.pallas_call = recording
    try:
        jax.clear_caches()
        jax.eval_shape(fn, *args)
    finally:
        _fa.pl.pallas_call = real
        jax.clear_caches()
    return calls


def _time_scan(scalar_fn, args, least_s):
    """Seconds a call of ``scalar_fn(*args) -> scalar``: R serial calls
    inside one jitted ``lax.scan`` whose carry perturbs the first operand
    (so the body cannot be hoisted; see ``_time_kernel``), R sized from
    the call's least time so that an arm takes a fraction of a second."""
    r = int(min(100, max(4, 0.25 / (3 * least_s))))

    def repeated(c0, *args):
        def body(c, _):
            out = scalar_fn(args[0] + c.astype(args[0].dtype), *args[1:])
            return out.astype(jnp.float32) * 1e-30, None
        return lax.scan(body, c0, None, length=r)[0]

    f = jax.jit(repeated)
    c = f(jnp.zeros((), jnp.float32), *args)
    c.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(2):
        c = f(c, *args)
    c.block_until_ready()
    return (time.perf_counter() - t0) / (2 * r)


def sweep_shape(b, h, s, d, dv, causal=True) -> dict:
    """One call shape, kernel by kernel and tile by tile."""
    dtype = jnp.bfloat16
    peaks = _peaks()
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(kq, (b, h, s, d), dtype)
    k = jax.random.normal(kk, (b, h, s, d), dtype)
    v = jax.random.normal(kv, (b, h, s, dv), dtype)
    g = jax.random.normal(kg, (b, h, s, dv), dtype)
    scale = d ** -0.5
    o, lse = jax.jit(lambda q, k, v: _fa.flash_attention_with_lse(
        q, k, v, causal=causal, scale=scale, interpret=False))(q, k, v)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), -1)
    shape = (b, h, h, s, s, d, dv)
    rest = (causal, scale, False, None, 0, 0)

    def arm(kernel, bq, bk):
        if kernel == "fwd":
            def fn(q, k, v):
                return jnp.sum(_fa.flash_attention(
                    q, k, v, causal=causal, scale=scale, block_q=bq,
                    block_k=bk, interpret=False).astype(jnp.float32))
            return fn, (q, k, v)
        call = _fa._bwd_dkv if kernel == "dkv" else _fa._bwd_dq

        def fn(q, k, v, g, lse, delta):
            ops = _fa._bwd_operands(q, k, v, g, lse, delta, bq, bk)
            outs = call(ops, shape, bq, bk, *rest)
            return sum(jnp.sum(x.astype(jnp.float32))
                       for x in jax.tree.leaves(outs))
        return fn, (q, k, v, g, lse, delta)

    sizes = [t for t in (256, 512, 1024) if t <= s]
    tiles = list(itertools.product(sizes, sizes))
    if s >= 4096:
        tiles += [(2048, 1024), (1024, 2048)]
    rows, failed = [], []
    for kernel in _KERNELS:
        least = _least_seconds(kernel, b, h, s, d, dv, peaks, causal)
        for bq, bk in tiles:
            fn, args = arm(kernel, bq, bk)
            row = {"kernel": kernel, "bq": bq, "bk": bk}
            try:
                t = _time_scan(fn, args, least)
            except Exception as e:  # noqa: BLE001 — VMEM overflow arms
                failed.append({**row, "error": type(e).__name__})
                print(f"# {json.dumps(failed[-1])}", flush=True)
                continue
            call, = _record_calls(fn, *args)
            row.update(ms=round(t * 1e3, 3),
                       roofline_pct=round(100 * least / t, 1),
                       **_schedule(call, kernel, b * h),
                       vmem_limit_mb=round(_fa._vmem_limit(
                           kernel, bq, bk, d, dv, 2) / 2 ** 20, 1))
            rows.append(row)
            # stdout on purpose: completed rows survive a mid-sweep kill
            print(f"# {json.dumps(row)}", flush=True)

    def attn(q, k, v):
        return jnp.sum(_fa.flash_attention(
            q, k, v, causal=causal, scale=scale,
            interpret=False).astype(jnp.float32))

    grad = jax.grad(attn, argnums=(0, 1, 2))
    least_all = sum(_least_seconds(kn, b, h, s, d, dv, peaks, causal)
                    for kn in _KERNELS)
    defaults = {
        "tiles": {kn: _fa._block_sizes(s, s, None, None, d=d,
                                       bwd=kn != "fwd") for kn in _KERNELS},
        "fwd_ms": round(_time_scan(attn, (q, k, v), least_all / 4) * 1e3, 3),
        "fwd_bwd_ms": round(_time_scan(
            lambda q, k, v: sum(jnp.sum(x.astype(jnp.float32))
                                for x in grad(q, k, v)),
            (q, k, v), least_all) * 1e3, 3)}
    print(f"# defaults {json.dumps(defaults)}", flush=True)
    best = {kn: min((r for r in rows if r["kernel"] == kn),
                    key=lambda r: r["ms"]) for kn in _KERNELS
            if any(r["kernel"] == kn for r in rows)}
    return {"mode": "shape", "shape": [b, h, s, d, dv], "best": best,
            "defaults": defaults, "failed": failed, "all": rows}


def main(argv):
    if "--decode" in argv:
        print(json.dumps(sweep_decode()))
        return 0
    if "--shape" in argv:
        dims = argv[argv.index("--shape") + 1].split(",")
        print(json.dumps(sweep_shape(*(int(x) for x in dims))))
        return 0
    grad_mode = "--fwdbwd" in argv
    b, h, d = 4, 8, 64
    dtype = jnp.bfloat16
    blocks = [128, 256, 512, 1024]
    table = {}
    for s in (512, 1024, 2048, 4096):
        q, k, v = _qkv(jax.random.PRNGKey(2), b, h, s, s, d, dtype)
        results = []
        for bq, bk in itertools.product(blocks, blocks):
            if bq > s or bk > s:
                continue

            def fwd(q, k, v, _bq=bq, _bk=bk):
                return jnp.sum(flash_attention(
                    q, k, v, causal=True, block_q=_bq, block_k=_bk,
                    interpret=False).astype(jnp.float32))

            if grad_mode:
                g = jax.grad(fwd, argnums=(0, 1, 2))
                fn = lambda q, k, v, _g=g: sum(
                    jnp.sum(x.astype(jnp.float32)) for x in _g(q, k, v))
            else:
                fn = fwd
            try:
                t = _time_kernel(fn, q, k, v)
            except Exception as e:  # noqa: BLE001 — VMEM overflow arms
                print(f"# s={s} bq={bq} bk={bk}: "
                      f"{type(e).__name__}", file=sys.stderr, flush=True)
                continue
            results.append({"bq": bq, "bk": bk, "ms": round(t * 1e3, 3)})
            print(f"# s={s} bq={bq} bk={bk}: {t*1e3:.3f}ms",
                  file=sys.stderr, flush=True)
        results.sort(key=lambda r: r["ms"])
        table[s] = results
        # stdout on purpose: the collector's timeout handler keeps the
        # stdout tail, so completed seq rows survive a mid-sweep SIGKILL
        print(f"# s={s} best: "
              f"{json.dumps(results[0]) if results else 'ALL FAILED'}",
              flush=True)
    print(json.dumps({"mode": "fwdbwd" if grad_mode else "fwd",
                      "best": {s: r[0] for s, r in table.items() if r},
                      "all": table}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
