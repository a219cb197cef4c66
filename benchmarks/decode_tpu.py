"""Single-chip autoregressive decode benchmark: tokens/sec with the
compiled KV-cache path (models/generate.py).

The reference has no inference path at all; this measures ours where it
matters — per-token decode latency/throughput on the flagship-class model.
Decode is bandwidth-bound (each step streams the params + KV cache once),
so the companion number to MFU here is achieved HBM bandwidth:

    bytes/step ~= param_bytes + kv_cache_bytes(current length)
    achieved GB/s = bytes/step * tokens/step / step_time

Usage: python benchmarks/decode_tpu.py [--small] [--gqa]
(``--gqa`` adds a grouped-query arm — group 4 at full scale — and the
decode speedup the shrunken cache buys.) Prints one JSON line.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

# Public spec-sheet HBM bandwidth per chip (bytes/s).
HBM_BW = {
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v6e": 1640e9,
}


def run(dim=768, n_layers=12, n_heads=12, vocab=32000,
        prompt_len=128, max_new=256, batch=8, n_kv_heads=None,
        int8_weights=False, pin_weight_stream=False, window=None,
        dtype=jnp.bfloat16) -> dict:
    from benchmarks.mfu_transformer import count_params
    from distributed_pytorch_tpu import models
    from distributed_pytorch_tpu.models import make_generate_fn
    from distributed_pytorch_tpu.models.generate import prefill
    from distributed_pytorch_tpu.ops.flash_attention import \
        make_flash_attn_fn
    from distributed_pytorch_tpu.ops.quant import (quantize_tree,
                                                   quantized_bytes)
    from distributed_pytorch_tpu.utils.profiler import (fetch_fence,
                                                        time_steps_amortized)

    max_seq = prompt_len + max_new
    # a sliding window switches generate to the rolling O(window) cache
    # (models/generate.py): each decode step streams min(window, total)
    # cache slots instead of max_seq — the bandwidth lever this arm
    # measures
    attn_fn = make_flash_attn_fn(window=window) if window else None
    model = models.TransformerLM(vocab=vocab, dim=dim, n_layers=n_layers,
                                 n_heads=n_heads, n_kv_heads=n_kv_heads,
                                 max_seq=max_seq, dtype=dtype,
                                 attn_fn=attn_fn)
    params = model.init(jax.random.PRNGKey(0))
    n_params = count_params(params)
    if int8_weights:
        params = quantize_tree(params)
    param_bytes = quantized_bytes(params)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt_len),
                                0, vocab, dtype=jnp.int32)

    gen = jax.jit(make_generate_fn(
        model, max_new, pin_weight_stream=pin_weight_stream))
    rng = jax.random.PRNGKey(2)

    # Amortized timing with host-fetch fencing: successive gen calls are
    # chained through an rng folded with the previous output, so one
    # final fetch waits for all of them and the per-call dispatch
    # amortizes over n calls.
    toks = gen(params, prompt, rng)
    fetch_fence(toks[:, -1])                  # compile + drain

    def gen_step(state):
        r, _ = state
        t = gen(params, prompt, r)
        return (jax.random.fold_in(r, t[:, -1].sum()), t)

    n_gen = 5
    t_total, _ = time_steps_amortized(gen_step, (rng, toks), n_gen,
                                      lambda s: s[1][:, -1])

    # prefill timed separately so the decode metrics are decode-only:
    # gen() = one prefill (which also yields the FIRST new token's logits)
    # + (max_new - 1) scanned decode steps. Chained by perturbing the
    # prompt with a zero derived from the previous output.
    cache_len = min(window, max_seq) if window else max_seq
    pf = jax.jit(lambda p, toks: prefill(model, p, toks, max_seq,
                                         window=(cache_len if window
                                                 else None)))
    out0 = pf(params, prompt)
    fetch_fence(jax.tree_util.tree_leaves(out0)[0].ravel()[0])

    def pf_step(state):
        pr, prev = state
        dep = jax.tree_util.tree_leaves(prev)[0].ravel()[0]
        pr = pr + (dep * 0).astype(pr.dtype)
        return (pr, pf(params, pr))

    t_prefill, _ = time_steps_amortized(
        pf_step, (prompt, out0), 5,
        lambda s: jax.tree_util.tree_leaves(s[1])[0].ravel()[0])
    decode_steps = max_new - 1
    t_decode = max(t_total - t_prefill, 1e-9)

    tok_s_e2e = batch * max_new / t_total
    tok_s_decode = batch * decode_steps / t_decode
    bpe = jnp.dtype(dtype).itemsize
    # each decode step streams the params (int8 bytes when quantized —
    # an ASSUMPTION the est_achieved_hbm numbers inherit: if XLA hoists
    # the dequant out of the decode scan, actual traffic is the bf16
    # bytes; the int8-vs-bf16 tok/s comparison in run_gqa_compare is the
    # empirical check) plus the FULL preallocated cache (decode attends
    # over max_len under a position mask — static shapes); GQA shrinks
    # the cache rows to n_kv_heads * head_dim
    kv_dim = (n_kv_heads or n_heads) * (dim // n_heads)
    kv_bytes = n_layers * 2 * batch * kv_dim * cache_len * bpe
    bytes_per_step = param_bytes + kv_bytes
    achieved_bw = bytes_per_step * decode_steps / t_decode

    dev = jax.devices()[0]
    peak_bw = HBM_BW.get(dev.device_kind)
    return {
        "device": dev.device_kind,
        "config": {"dim": dim, "n_layers": n_layers, "n_heads": n_heads,
                   "n_kv_heads": n_kv_heads or n_heads,
                   "vocab": vocab, "prompt_len": prompt_len,
                   "max_new": max_new, "batch": batch,
                   "int8_weights": bool(int8_weights),
                   "pin_weight_stream": bool(pin_weight_stream),
                   "window": window, "cache_len": cache_len,
                   "dtype": str(jnp.dtype(dtype).name)},
        "n_params": n_params,
        "param_bytes": int(param_bytes),
        "wall_s_median": round(t_total, 4),
        "prefill_ms": round(t_prefill * 1e3, 3),
        "e2e_tokens_per_sec": round(tok_s_e2e, 1),
        "decode_tokens_per_sec": round(tok_s_decode, 1),
        "decode_per_token_latency_ms": round(1e3 * t_decode / decode_steps,
                                             3),
        "est_achieved_hbm_gbps": round(achieved_bw / 1e9, 1),
        "peak_hbm_gbps": round(peak_bw / 1e9, 1) if peak_bw else None,
        "est_hbm_utilization": round(achieved_bw / peak_bw, 3)
        if peak_bw else None,
    }


def run_gqa_compare(small: bool = False) -> dict:
    """MHA vs grouped-query decode vs int8 weights, at equal model class.
    Decode is bandwidth-bound (params + KV cache stream once per token),
    so the speedups quantify what the group-factor-smaller cache (GQA)
    and the halved weight bytes (int8) buy — untrained weights, identical
    compute graph shape. One schema for the small and full arms."""
    kw = dict(dim=128, n_layers=2, n_heads=4, vocab=512, prompt_len=16,
              max_new=32, batch=2) if small else {}
    n_kv = 1 if small else 3                         # group 4

    import bench

    def arm(msg, fn, *a, **k):
        # bench.arm contract: a hang mid-arm leaves WHICH arm hung in
        # the collector's kept stdout tail
        return bench.arm(f"decode arm: {msg}", lambda: fn(*a, **k))

    mha = arm("mha", run, **kw)
    gqa = arm("gqa", run, n_kv_heads=n_kv, **kw)
    gqa_int8 = arm("gqa_int8", run, n_kv_heads=n_kv, int8_weights=True,
                   **kw)
    # pinned arm: weight stream tied into the scan so int8 dequant can't
    # be hoisted (generate.py:pin_weight_stream). int8 vs int8_pinned is
    # the empirical answer to "did XLA hoist the dequant": if pinned is
    # faster, the plain arm was streaming bf16.
    gqa_int8_pin = arm("gqa_int8_pinned", run, n_kv_heads=n_kv,
                       int8_weights=True, pin_weight_stream=True, **kw)
    # rolling-cache arm: sliding window = 1/3 of the total length, so
    # the cache the decode step streams shrinks 3x (models/generate.py
    # rolling buffer) — stacks with GQA's group-factor shrink
    win = 16 if small else 128
    gqa_window = arm("gqa_window", run, n_kv_heads=n_kv, window=win,
                     **kw)
    bench.progress("decode arms done")
    base = mha["decode_tokens_per_sec"]
    return {"mha": mha, "gqa": gqa, "gqa_int8": gqa_int8,
            "gqa_int8_pinned": gqa_int8_pin,
            "gqa_window": gqa_window,
            "gqa_decode_speedup": round(
                gqa["decode_tokens_per_sec"] / base, 2),
            "gqa_int8_decode_speedup": round(
                gqa_int8["decode_tokens_per_sec"] / base, 2),
            "gqa_int8_pinned_decode_speedup": round(
                gqa_int8_pin["decode_tokens_per_sec"] / base, 2),
            "gqa_window_decode_speedup": round(
                gqa_window["decode_tokens_per_sec"] / base, 2)}


def main(argv):
    small = "--small" in argv
    if "--gqa" in argv:
        rec = run_gqa_compare(small=small)
    elif small:
        rec = run(dim=128, n_layers=2, n_heads=4, vocab=512,
                  prompt_len=16, max_new=32, batch=2)
    else:
        rec = run()
    # one compact line: collectors parse the last stdout line as JSON
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
