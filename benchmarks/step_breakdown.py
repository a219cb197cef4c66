"""Where does the flagship step's time go? (the MFU bottleneck map)

Ablation-based attribution: time the full train step and a ladder of
variants with the honest amortized fetch-fenced method, then read the
components off the differences:

- ``full``        forward + backward + AdamW update  (the flagship step)
- ``no_opt``      forward + backward only            -> optimizer cost
- ``fwd``         forward (loss) only                -> backward cost
- ``attn_stub``   full, attention replaced by identity(v)
                                                     -> attention cost
- ``no_head``     full, vocab projection + CE replaced by a mean over
                  hidden                             -> head+CE cost
- ``dense_attn``  full, dense-einsum attention core  (flash vs dense at
                                                       the flagship seq)

Each variant is a REAL compiled step — XLA fusion effects stay in — and
the breakdown is differences of amortized step times.

Also answers "why doesn't batch 16-64 beat batch 8": run with --batch 8
and --batch 32 and compare which component fails to scale sublinearly.

Usage: python benchmarks/step_breakdown.py [--batch N] [--seq N] [--steps N]
       python benchmarks/step_breakdown.py --compute   (remat x mp ladder:
           step time + compiled activation-memory per policy, docs/compute.md)
       python benchmarks/step_breakdown.py --comm      (grad-reduce arms)
Prints one JSON line; appends nothing (bench.py owns the log).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks.mfu_transformer import (FLAGSHIP, PEAK_BF16,
                                        model_flops_per_token)


def _flag(argv, name, default, cast=int):
    if name in argv:
        i = argv.index(name)
        if i + 1 < len(argv):
            return cast(argv[i + 1])
    return default


def _time_step(step, params, opt_state, tokens, steps):
    """Amortized chained timing, one host fetch at the end."""
    from distributed_pytorch_tpu.utils.profiler import (fetch_fence,
                                                        time_steps_amortized)
    out = step(params, opt_state, tokens)
    fetch_fence(out.loss)
    out = step(out.params, out.opt_state, tokens)
    fetch_fence(out.loss)
    step_s, _ = time_steps_amortized(
        lambda o: step(o.params, o.opt_state, tokens), out, steps,
        lambda o: o.loss)
    return step_s


def _time_fwd(loss_fn, params, tokens, steps):
    """Forward-only chain: the loss feeds back through a zero-sum trick
    so each call depends on the previous (no dead-code elimination)."""
    from distributed_pytorch_tpu.utils.profiler import (fetch_fence,
                                                        time_steps_amortized)

    @jax.jit
    def fwd(carry, params, toks):
        loss, _ = loss_fn(params, toks)
        return carry + loss

    c = fwd(jnp.float32(0.0), params, tokens)
    fetch_fence(c)
    c = fwd(c, params, tokens)
    fetch_fence(c)
    step_s, _ = time_steps_amortized(
        lambda c: fwd(c, params, tokens), c, steps, lambda c: c)
    return step_s


def run(dim=FLAGSHIP["dim"], n_layers=FLAGSHIP["n_layers"],
        n_heads=FLAGSHIP["n_heads"], vocab=FLAGSHIP["vocab"],
        seq=FLAGSHIP["seq"], batch=FLAGSHIP["batch"], steps=20,
        dtype=jnp.bfloat16) -> dict:
    from distributed_pytorch_tpu import models, optim
    from distributed_pytorch_tpu.ops import make_flash_attn_fn
    from distributed_pytorch_tpu.ops.losses import cross_entropy
    from distributed_pytorch_tpu.parallel import make_train_step

    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1),
                                0, vocab, dtype=jnp.int32)
    opt = optim.adamw(3e-4)

    def build(attn_fn):
        model = models.TransformerLM(vocab=vocab, dim=dim,
                                     n_layers=n_layers, n_heads=n_heads,
                                     max_seq=seq, attn_fn=attn_fn,
                                     dtype=dtype)
        params = model.init(jax.random.PRNGKey(0))
        return model, params

    def ce_loss(model):
        def loss_fn(p, toks):
            logits = model.apply(p, toks[:, :-1]).astype(jnp.float32)
            return cross_entropy(logits, toks[:, 1:]), {}
        return loss_fn

    def headless_loss(model):
        def loss_fn(p, toks):
            hid = model.apply(p, toks[:, :-1], return_hidden=True)
            return jnp.mean(hid.astype(jnp.float32) ** 2), {}
        return loss_fn

    flash = make_flash_attn_fn()

    def attn_identity(q, k, v, *, causal=False, scale=None):
        # keep a q/k dependence so neither projection is dead code, at
        # negligible FLOPs vs the real attention matmuls
        return v + 0.0 * (q + k.repeat(q.shape[-3] // k.shape[-3], -3))

    import bench

    def arm(name, thunk):
        # bench.arm: banner BEFORE any of the arm's work (setup deferred
        # into the thunk), so a hang during build/opt.init is
        # attributed to the right arm in the collector's stdout tail
        rows[name] = bench.arm(f"breakdown arm: {name}", thunk)

    rows = {}
    bench.progress("breakdown: building flagship model (first device "
                   "allocation)")
    model, params = build(flash)
    st = opt.init(params)

    arm("full", lambda: _time_step(
        make_train_step(ce_loss(model), opt, donate=False),
        params, st, tokens, steps))

    @jax.jit
    def fwd_bwd(params, opt_state, toks):
        (loss, _), grads = jax.value_and_grad(ce_loss(model),
                                              has_aux=True)(params, toks)
        # fold grads into the carried loss so the whole backward is live.
        # The scale is derived from runtime DATA (not a literal 0.0), so
        # no simplifier/fast-math pass can prove the term away and
        # dead-code-eliminate the backward; numerically it is ~1e-30 *
        # mean|g| — far below f32 resolution next to the loss.
        eps = (toks[0, 0].astype(jnp.float32) + 1.0) * 1e-30
        gsum = sum(jnp.mean(jnp.abs(g).astype(jnp.float32))
                   for g in jax.tree_util.tree_leaves(grads))
        from distributed_pytorch_tpu.parallel.spmd import SpmdStepOutput
        return SpmdStepOutput(params, opt_state, loss + eps * gsum, {})

    arm("no_opt", lambda: _time_step(fwd_bwd, params, st, tokens, steps))
    arm("fwd", lambda: _time_fwd(ce_loss(model), params, tokens, steps))

    def attn_stub_arm():
        m2, p2 = build(attn_identity)
        return _time_step(make_train_step(ce_loss(m2), opt,
                                          donate=False),
                          p2, opt.init(p2), tokens, steps)
    arm("attn_stub", attn_stub_arm)

    arm("no_head", lambda: _time_step(
        make_train_step(headless_loss(model), opt, donate=False),
        params, st, tokens, steps))

    def dense_arm():
        m3, p3 = build(None)  # dense einsum core
        return _time_step(make_train_step(ce_loss(m3), opt,
                                          donate=False),
                          p3, opt.init(p3), tokens, steps)
    arm("dense_attn", dense_arm)
    bench.progress("breakdown arms done")

    full = rows["full"]
    ms = {k: round(v * 1e3, 3) for k, v in rows.items()}
    attribution = {
        "attention_ms": round((full - rows["attn_stub"]) * 1e3, 3),
        "head_ce_ms": round((full - rows["no_head"]) * 1e3, 3),
        "optimizer_ms": round((full - rows["no_opt"]) * 1e3, 3),
        "backward_ms": round((rows["no_opt"] - rows["fwd"]) * 1e3, 3),
        "flash_vs_dense_ms": round((rows["dense_attn"] - full) * 1e3, 3),
    }
    dev = jax.devices()[0]
    peak = PEAK_BF16.get(dev.device_kind)
    tok = batch * seq
    fl = 3 * model_flops_per_token(dim, n_layers, vocab, seq) * tok
    return {"device": dev.device_kind,
            "config": {"dim": dim, "n_layers": n_layers, "vocab": vocab,
                       "seq": seq, "batch": batch,
                       "dtype": str(jnp.dtype(dtype).name)},
            "steps_timed": steps,
            "step_ms": ms,
            "attribution_ms": attribution,
            "mfu_full": round(fl / rows["full"] / peak, 4) if peak else None}


def run_compute(dim=FLAGSHIP["dim"], n_layers=FLAGSHIP["n_layers"],
                n_heads=FLAGSHIP["n_heads"], vocab=FLAGSHIP["vocab"],
                seq=FLAGSHIP["seq"], batch=FLAGSHIP["batch"], steps=20,
                dtype=jnp.float32) -> dict:
    """The compute-path ladder (docs/compute.md): remat policies x
    mixed precision, each a REAL compiled train step measured with the
    amortized fetch-fenced method plus XLA's compiled memory analysis —
    the activation-memory/step-time tradeoff as data, not prose.

    Arms: remat none/full/dots_saveable at mp=off, plus the composed
    recipe (dots_saveable + bf16 mixed precision). Per arm: step_ms,
    temp (activation high-water) bytes, argument bytes. The model is
    f32-NATIVE on purpose — the mp arm measures the master-weights
    recipe (f32 master, bf16 compute cast) against the f32 baseline;
    the bf16-native flagship is mfu_transformer's own measurement.
    Run with ``--compute``."""
    from distributed_pytorch_tpu import models, optim
    from distributed_pytorch_tpu.models.transformer import REMAT_POLICIES
    from distributed_pytorch_tpu.ops import make_flash_attn_fn
    from distributed_pytorch_tpu.ops.losses import cross_entropy
    from distributed_pytorch_tpu.parallel import make_train_step
    from distributed_pytorch_tpu.utils.profiler import compiled_memory

    import bench

    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1),
                                0, vocab, dtype=jnp.int32)
    opt = optim.adamw(3e-4)
    arms = [(pol, "off") for pol in REMAT_POLICIES] \
        + [("dots_saveable", "bf16")]
    rows = {}
    for pol, mp in arms:
        label = f"remat={pol},mp={mp}"

        def arm_thunk(pol=pol, mp=mp):
            model = models.TransformerLM(
                vocab=vocab, dim=dim, n_layers=n_layers, n_heads=n_heads,
                max_seq=seq, attn_fn=make_flash_attn_fn(), remat=pol,
                dtype=dtype)
            params = model.init(jax.random.PRNGKey(0))

            def loss_fn(p, toks):
                logits = model.apply(p, toks[:, :-1]).astype(jnp.float32)
                return cross_entropy(logits, toks[:, 1:]), {}

            step = make_train_step(loss_fn, opt, donate=False,
                                   mixed_precision=mp)
            st = opt.init(params)
            t = _time_step(step, params, st, tokens, steps)
            mem = compiled_memory(
                lambda p, o, b: step(p, o, b), params, st, tokens)
            return {"step_ms": round(t * 1e3, 3),
                    "temp_bytes": mem.get("temp_size_bytes"),
                    "argument_bytes": mem.get("argument_size_bytes")}

        rows[label] = bench.arm(f"compute arm: {label}", arm_thunk)
    base = rows.get("remat=none,mp=off", {})
    dev = jax.devices()[0]
    return {"device": dev.device_kind,
            "config": {"dim": dim, "n_layers": n_layers, "vocab": vocab,
                       "seq": seq, "batch": batch,
                       "dtype": str(jnp.dtype(dtype).name)},
            "steps_timed": steps,
            "arms": rows,
            # the tradeoff, joined: bytes saved vs ms paid per policy
            "vs_none": {k: {"step_ms_delta": round(
                                v["step_ms"] - base.get("step_ms", 0), 3),
                            "temp_bytes_saved":
                                (base.get("temp_bytes") - v["temp_bytes"])
                                if (base.get("temp_bytes") is not None
                                    and v.get("temp_bytes") is not None)
                                else None}
                        for k, v in rows.items()
                        if k != "remat=none,mp=off" and "step_ms" in v}}


def run_comm(world=8, hidden=1024, in_dim=256, batch_per_rank=8,
             steps=30) -> dict:
    """Gradient-reduce comm breakdown on the virtual CPU mesh: the same
    DP step with ``grad_reduce="mean"`` (exact f32 pmean) vs ``"quant"``
    (block-int8 bucket), plus per-step wire-byte accounting from
    ``comm/primitives``. The quantized-vs-f32 comm cost of the tentpole
    quantized collective layer, measured as REAL compiled steps (XLA
    fusion effects stay in). Per-step comm seconds = step-time delta vs
    a world-1 compute-only step on the same per-rank batch.

    Run with ``--comm`` (forces JAX_PLATFORMS=cpu + an 8-device virtual
    mesh, so it works on any host); invoke in a fresh process — the
    platform switch must precede backend init.
    """
    import numpy as np

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", world)
    from distributed_pytorch_tpu.runtime import env as _envreg
    if _envreg.raw("DPX_CPU_DEVICES") is None:
        _envreg.set("DPX_CPU_DEVICES", world)

    import distributed_pytorch_tpu as dist
    from distributed_pytorch_tpu import models, optim
    from distributed_pytorch_tpu.comm import primitives as prim
    from distributed_pytorch_tpu.ops.losses import cross_entropy
    from distributed_pytorch_tpu.parallel import make_train_step

    model = models.DummyModel(in_dim=in_dim, hidden_dim=hidden,
                              n_classes=16)
    opt = optim.adamw(1e-4)

    def loss_fn(p, batch):
        x, y = batch
        return cross_entropy(model.apply(p, x), y), {}

    hists = {}

    def arm(world_size, grad_reduce):
        dist.cleanup()
        dist.init_process_group(rank=0, world_size=world_size)
        params = model.init(jax.random.PRNGKey(0))
        gb = batch_per_rank * world_size
        x = dist.shard_batch(np.random.default_rng(0).standard_normal(
            (gb, in_dim)).astype(np.float32))
        y = dist.shard_batch((np.arange(gb) % 16).astype(np.int32))
        step = make_train_step(loss_fn, opt, donate=False,
                               grad_reduce=grad_reduce)
        t = _time_step(step, params, opt.init(params), (x, y), steps)
        chooser = getattr(step, "width_chooser", None)
        if chooser is not None:
            # the adaptive-width histogram: which wire the chooser
            # actually picked, step by step (hysteresis included)
            hists[grad_reduce] = {str(k): v for k, v
                                  in chooser.histogram().items()}
        return t

    n_grad = sum(x.size for x in jax.tree_util.tree_leaves(
        model.init(jax.random.PRNGKey(0))))
    base_s = arm(1, "mean")          # compute-only floor (no dp axis)
    mean_s = arm(world, "mean")
    quant_s = arm(world, "quant")
    q4_s = arm(world, "q4")
    adaptive_s = arm(world, "adaptive")
    dist.cleanup()
    f32_bytes = prim.ring_allreduce_wire_bytes(n_grad, world)
    return {
        "world": world,
        "grad_elems": n_grad,
        "step_ms": {"world1": round(base_s * 1e3, 3),
                    "mean": round(mean_s * 1e3, 3),
                    "quant": round(quant_s * 1e3, 3),
                    "q4": round(q4_s * 1e3, 3),
                    "adaptive": round(adaptive_s * 1e3, 3)},
        "comm_ms": {"mean": round((mean_s - base_s) * 1e3, 3),
                    "quant": round((quant_s - base_s) * 1e3, 3),
                    "q4": round((q4_s - base_s) * 1e3, 3),
                    # the adaptive arm pays a per-step scalar fetch for
                    # the chooser statistic — part of its honest cost
                    "adaptive": round((adaptive_s - base_s) * 1e3, 3)},
        "adaptive_width_hist": hists.get("adaptive"),
        "wire_bytes_per_step": {
            "mean_f32": f32_bytes,
            "quant": prim.quantized_pmean_wire_bytes(n_grad, world)},
    }


def main(argv):
    if "--comm" in argv:
        print(json.dumps(run_comm(steps=_flag(argv, "--steps", 30))))
        return 0
    if "--compute" in argv:
        print(json.dumps(run_compute(
            batch=_flag(argv, "--batch", FLAGSHIP["batch"]),
            seq=_flag(argv, "--seq", FLAGSHIP["seq"]),
            steps=_flag(argv, "--steps", 20))))
        return 0
    rec = run(batch=_flag(argv, "--batch", FLAGSHIP["batch"]),
              seq=_flag(argv, "--seq", FLAGSHIP["seq"]),
              steps=_flag(argv, "--steps", 20))
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
