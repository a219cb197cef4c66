"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two normal paths once, in ONE process (one process can drive
every chip of a host; a second one would find the chip taken), through
the entry points a user calls, at the full width of the pinned 135M
``FLAGSHIP`` (benchmarks/mfu_transformer.py), weights random from a seed:

1. the reference workload: ``dist.launch(min_ddp.main_worker)`` on one chip;
2. the trainer: the flash kernels against the dense reference, then the
   flagship train step through the front door for a few steps;
3. the server: ``InferenceEngine`` answering streamed requests,
   greedy streams compared with standalone ``generate()`` (equal up to the
   first bf16 tie — docs/serving.md, "On the chip");
4. with four or more chips: ``min_ddp`` at world 4, the flagship step at
   dp=4 and at the ZeRO-3 spec point, first losses against the one-chip
   losses on the same global batch. With fewer chips it says so.

It checks what comes out and catches nothing: the first failed check
raises and the exit code is nonzero. It fails before building anything
when JAX finds no TPU. It prints one line per phase, no rate, no time per
step, no utilization, and as its last line one JSON object naming the
device as JAX reports it.

Run it through the chip tool from the root of a checkout:
``python chip_smoke.py``.
"""

import contextlib
import io
import json
import os
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "examples"))

#: max-norm error of the bf16 kernel against the f32 dense reference,
#: relative to the reference's largest magnitude: bf16 keeps 8 mantissa
#: bits (2^-8 = 0.4%) and the kernel rounds p and ds to it once each.
FLASH_BF16_TOL = 2e-2
#: two bf16 logits this close are a tie: 2 ulps of bf16 (2^-7 each)
#: relative to the larger one. Greedy decoding through two differently
#: shaped programs may break a tie either way.
TIE_RTOL = 2.0 ** -6
#: a dp/ZeRO first loss against the one-chip loss on the same rows: the
#: same bf16 forward partitioned differently, averaged over 8192 tokens.
LOSS_RTOL = 1e-3
#: the flash kernel at every shape class the models use:
#: (batch, q heads, kv heads, seq, head_dim, window)
FLASH_SHAPES = (
    (8, 12, 12, 1024, 64, None),     # the flagship's own attention
    (2, 12, 12, 4096, 64, None),     # long sequence
    (4, 8, 8, 2048, 128, None),      # head_dim 128
    (4, 8, 2, 2048, 128, None),      # grouped-query
    (2, 12, 12, 4096, 64, 512),      # sliding window
)


def say(phase: str, msg: str) -> None:
    print(f"chip_smoke: {phase}: {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    """A check that survives ``python -O`` (``assert`` does not)."""
    if not ok:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# phase 1 / 4a: the reference workload through launch
# ---------------------------------------------------------------------------


def run_min_ddp(world: int) -> None:
    """``dist.launch(min_ddp.main_worker)`` on ``world`` chips, checked
    on the per-rank blocks it prints."""
    import distributed_pytorch_tpu as dist
    import min_ddp
    from distributed_pytorch_tpu.runtime import env

    env.set("DPX_VISIBLE_DEVICES", ",".join(str(i) for i in range(world)))
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            dist.launch(min_ddp.main_worker,
                        ["--epochs", "1", "--batch-size", "1"])
    finally:
        env.unset("DPX_VISIBLE_DEVICES")
    lines = buf.getvalue().splitlines()
    devices = [l.split("Device:")[1].strip() for l in lines
               if l.startswith("Device:")]
    inputs = [l.split("Input:")[1].strip() for l in lines
              if "Input:" in l]
    finishes = [l for l in lines if l.startswith("Finish iteration")]
    check(finishes and "nan" not in " ".join(finishes).lower(),
          f"min_ddp printed no finite Finish line: {lines[-3:]}")
    if world == 1:
        check(devices and all("TPU" in d.upper() for d in devices),
              f"min_ddp Device lines do not name the TPU: {devices[:2]}")
    else:
        first = devices[:world]
        check(first == [f"mesh[{r}]" for r in range(world)],
              f"expected {world} mesh[r] blocks, got {first}")
        check(inputs[:world] == [f"[{r}]" for r in range(world)],
              f"rank r's first input should be r, got {inputs[:world]}")
    say(f"min_ddp world={world}",
        f"PASS Device: {devices[0]} | {finishes[-1]}")


# ---------------------------------------------------------------------------
# phase 2: kernels, then the trainer
# ---------------------------------------------------------------------------


def check_flash(b, h, h_kv, s, d, window):
    """Flash forward and gradients (compiled Mosaic, never interpreted)
    against ``dense_attention`` in f32 at one shape."""
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_tpu.nn.attention import dense_attention
    from distributed_pytorch_tpu.ops import flash_attention

    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(s + d + h_kv), 4)
    q = jax.random.normal(kq, (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, h_kv, s, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, h_kv, s, d), jnp.bfloat16)
    g = jax.random.normal(kg, (b, h, s, d), jnp.float32)

    def flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, window=window)
        return jnp.sum(o.astype(jnp.float32) * g), o

    def dense(q, k, v):
        o = dense_attention(q, k, v, causal=True, window=window)
        return jnp.sum(o * g), o

    fn = jax.jit(jax.value_and_grad(flash, argnums=(0, 1, 2), has_aux=True))
    n_calls = fn.lower(q, k, v).as_text().count("tpu_custom_call")
    check(n_calls == 3, f"flash fwd+bwd should lower to three Mosaic "
                        f"calls (fwd, dK/dV, dQ), found {n_calls}")
    (_, o), grads = fn(q, k, v)
    with jax.default_matmul_precision("highest"):
        (_, o_ref), grads_ref = jax.jit(jax.value_and_grad(
            dense, argnums=(0, 1, 2), has_aux=True))(
            *(x.astype(jnp.float32) for x in (q, k, v)))
    errs = {}
    for name, got, ref in zip(("o", "dq", "dk", "dv"),
                              (o,) + tuple(grads),
                              (o_ref,) + tuple(grads_ref)):
        got = got.astype(jnp.float32)
        check(bool(jnp.all(jnp.isfinite(got))), f"flash {name} not finite")
        errs[name] = float(jnp.max(jnp.abs(got - ref))
                           / jnp.max(jnp.abs(ref)))
    shape = f"b{b} h{h}/{h_kv} s{s} d{d} window={window}"
    check(max(errs.values()) <= FLASH_BF16_TOL,
          f"flash vs dense at {shape}: {errs} exceeds {FLASH_BF16_TOL}")
    say("flash", f"PASS {shape} compiled (3 tpu_custom_call) max-norm "
                 f"rel err " + " ".join(f"{k}={v:.1e}"
                                        for k, v in errs.items()))


def flagship_model(cfg):
    import jax.numpy as jnp

    from distributed_pytorch_tpu import models
    from distributed_pytorch_tpu.ops import make_flash_attn_fn

    return models.TransformerLM(
        vocab=cfg["vocab"], dim=cfg["dim"], n_layers=cfg["n_layers"],
        n_heads=cfg["n_heads"], max_seq=cfg["seq"],
        attn_fn=make_flash_attn_fn(), dtype=jnp.bfloat16)


def make_loss_fn(model):
    import jax.numpy as jnp

    from distributed_pytorch_tpu.ops.losses import cross_entropy

    def loss_fn(p, tokens):
        logits = model.apply(p, tokens[:, :-1]).astype(jnp.float32)
        return cross_entropy(logits, tokens[:, 1:]), {}
    return loss_fn


def global_tokens(cfg, world: int = 4):
    """The one fixed seeded batch: ``world`` x the flagship batch, so
    the one-chip run takes the first rows and dp=4 takes all of it."""
    import jax
    import jax.numpy as jnp

    return jax.random.randint(
        jax.random.PRNGKey(1), (world * cfg["batch"], cfg["seq"] + 1),
        0, cfg["vocab"], dtype=jnp.int32)


def run_steps(step, params, opt_state, batch, n_steps, n_layers, label):
    """``n_steps`` steps on one batch; returns (losses, last output)."""
    import numpy as np

    losses, out = [], None
    for _ in range(n_steps):
        out = step(params, opt_state, batch)
        params, opt_state = out.params, out.opt_state
        losses.append(np.asarray(out.loss, np.float64).ravel())
    flat = np.stack(losses)
    check(bool(np.all(np.isfinite(flat))), f"{label}: loss not finite")
    check(flat[-1].mean() < flat[0].mean(),
          f"{label}: loss did not fall ({flat[0]} -> {flat[-1]})")
    # which attention ran: the flagship sits exactly on the flash/dense
    # hand-off (DPX_FLASH_MIN_SEQ keys), so read it off the program
    n_calls = step.lower(params, opt_state, batch).as_text().count(
        "tpu_custom_call")
    check(n_calls == 3 * n_layers,
          f"{label}: step holds {n_calls} Mosaic calls, want "
          f"{3 * n_layers} (fwd, dK/dV, dQ per layer)")
    check(step.compiles == 1,
          f"{label}: compiled {step.trace_counts}, want exactly one")
    say(label, f"PASS {n_steps} steps compiles=1 mosaic_calls={n_calls} "
               f"losses={[float(l.mean()) for l in losses]}")
    return flat, out


def phase_trainer(cfg, n_steps: int = 5):
    import jax

    from distributed_pytorch_tpu import optim
    from distributed_pytorch_tpu.parallel import make_train_step

    model = flagship_model(cfg)
    loss_fn = make_loss_fn(model)
    opt = optim.adamw(3e-4)
    params = model.init(jax.random.PRNGKey(0))
    step = make_train_step(loss_fn, opt, donate=True)
    batch = global_tokens(cfg)[:cfg["batch"]]
    losses, _ = run_steps(step, params, opt.init(params), batch, n_steps,
                          cfg["n_layers"],
                          f"trainer 1 chip dim={cfg['dim']} "
                          f"L={cfg['n_layers']} seq={cfg['seq']} "
                          f"batch={cfg['batch']} bf16 flash adamw donate")
    return model, loss_fn, opt, losses


# ---------------------------------------------------------------------------
# phase 3: the server
# ---------------------------------------------------------------------------


def phase_server(model, cfg, prompt_lens=(12, 17, 600, 12), max_new=24):
    """The paged engine on the same model: streamed requests of mixed
    prompt length (short, one token past a page, >= 512), each greedy
    stream compared with standalone ``generate()``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu.models.generate import make_generate_fn
    from distributed_pytorch_tpu.serve import (EngineConfig,
                                               InferenceEngine,
                                               SamplingParams)

    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg["vocab"], n).astype(np.int32)
               for n in prompt_lens]
    eng = InferenceEngine(model, params, EngineConfig(
        n_slots=4, max_len=cfg["seq"]))
    check(eng.pool.page_len + 1 in prompt_lens,
          f"no prompt one past a page boundary (page_len "
          f"{eng.pool.page_len})")
    streamed = [[] for _ in prompts]
    eng.start()
    try:
        handles = [
            eng.submit(p, SamplingParams(max_new_tokens=max_new),
                       on_token=lambda tok, i, s=s: s.append(int(tok)))
            for p, s in zip(prompts, streamed)]
        results = [np.asarray(h.result(timeout=900)) for h in handles]
    finally:
        eng.shutdown()
    stats = eng.stats()
    buckets = {min(b for b in eng.buckets if b >= n) for n in prompt_lens}
    check(stats["decode_compiles"] == 1,
          f"decode compiled {stats['decode_compiles']} times")
    check(stats["prefill_compiles"] == {b: 1 for b in buckets},
          f"prefill compiles {stats['prefill_compiles']} != one per "
          f"bucket used {sorted(buckets)}")
    gen = jax.jit(make_generate_fn(model, max_new, max_len=cfg["seq"]))
    ties = []
    for i, (p, got, cb) in enumerate(zip(prompts, results, streamed)):
        check(got.shape == (max_new,) and cb == got.tolist(),
              f"request {i}: asked {max_new} tokens, result "
              f"{got.shape}, streamed {len(cb)}")
        ref = np.asarray(gen(params, jnp.asarray(p[None]),
                             jax.random.PRNGKey(i)))[0]
        if np.array_equal(got, ref):
            continue
        # the streams part: that is only right at a tie. Judge it on the
        # reference's own logits for the two candidates at that position
        at = int(np.argmax(got != ref))
        ctx = np.concatenate([p, ref[:at]])[None]
        logits = np.asarray(model.apply(params, jnp.asarray(ctx))
                            [0, -1].astype(jnp.float32))
        a, b = float(logits[got[at]]), float(logits[ref[at]])
        where = (f"request {i} (prompt {len(p)}) position {at}: engine "
                 f"{got[at]} (logit {a:.6f}) vs generate() {ref[at]} "
                 f"(logit {b:.6f}), gap {abs(a - b):.3e}")
        check(abs(a - b) <= TIE_RTOL * max(abs(a), abs(b)),
              f"engine stream leaves generate() where there is no tie: "
              f"{where}")
        ties.append(where)
    say("server", f"PASS paged engine {len(prompts)} requests prompts="
                  f"{list(prompt_lens)} x {max_new} new tokens streamed; "
                  f"{len(prompts) - len(ties)} greedy streams == "
                  f"generate(), {len(ties)} part at a bf16 tie; "
                  f"decode_compiles=1 "
                  f"prefill_compiles={stats['prefill_compiles']}")
    for where in ties:
        say("server", f"tie: {where}")


# ---------------------------------------------------------------------------
# phase 4: four chips
# ---------------------------------------------------------------------------


def phase_four_chips(model, loss_fn, opt, cfg, one_chip_first, n_steps=3):
    import jax
    import numpy as np

    import distributed_pytorch_tpu as dist
    from distributed_pytorch_tpu.parallel import (StepSpecs, make_step,
                                                  shard_layouts)

    world = 4
    run_min_ddp(world)

    tokens = global_tokens(cfg, world)
    # the one-chip losses on the same global batch: forward only, shard
    # by shard, the rows each rank will see
    fwd = jax.jit(lambda p, t: loss_fn(p, t)[0])
    p0 = model.init(jax.random.PRNGKey(0))
    rows = cfg["batch"]
    want = np.array([float(fwd(p0, tokens[r * rows:(r + 1) * rows]))
                     for r in range(world)])
    check(abs(want[0] - one_chip_first) <= LOSS_RTOL * want[0],
          f"forward loss {want[0]} != trainer's first loss "
          f"{one_chip_first}")

    dist.init_process_group(0, world)
    try:
        mesh = dist.get_mesh()

        def check_placement(out, label):
            for leaf in jax.tree_util.tree_leaves(out.params):
                check(len(leaf.sharding.device_set) == world,
                      f"{label}: a parameter leaf lives on "
                      f"{len(leaf.sharding.device_set)} devices")

        batch = dist.shard_batch(tokens)
        shards = {(s.device.id, s.data.shape)
                  for s in batch.addressable_shards}
        check(len(shards) == world and
              all(shape[0] == rows for _, shape in shards),
              f"batch not split {world} ways: {sorted(shards)}")

        label = f"trainer dp={world} global batch={world * rows}"
        step = make_step(loss_fn, opt, donate=True)
        params = model.init(jax.random.PRNGKey(0))
        losses, out = run_steps(step, params, opt.init(params), batch,
                                n_steps, cfg["n_layers"], label)
        check_placement(out, label)
        np.testing.assert_allclose(losses[0], want, rtol=LOSS_RTOL)
        say(label, f"PASS per-rank first losses {losses[0].tolist()} == "
                   f"one-chip {want.tolist()} (rtol {LOSS_RTOL})")

        label = f"trainer ZeRO-3 specs over dp={world}"
        params = model.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        p_specs, _, axes = shard_layouts(params, opt_state, n_shards=world)
        check(axes == {"dp": world}, f"shard_layouts axes {axes}")
        step = make_step(loss_fn, opt, mesh=mesh,
                         specs=StepSpecs(params=p_specs), donate=True)
        losses, out = run_steps(step, params, opt_state, batch, n_steps,
                                cfg["n_layers"], label)
        check_placement(out, label)
        n_split = sum(
            not leaf.sharding.is_fully_replicated
            for leaf in jax.tree_util.tree_leaves(out.params))
        check(n_split > 0, f"{label}: no parameter leaf is sharded")
        np.testing.assert_allclose(losses[0], want.mean(), rtol=LOSS_RTOL)
        say(label, f"PASS {n_split} leaves sharded, first loss "
                   f"{float(losses[0][0])} == one-chip mean "
                   f"{float(want.mean())} (rtol {LOSS_RTOL})")
    finally:
        dist.cleanup()


# ---------------------------------------------------------------------------


def main() -> int:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: FAIL JAX found no TPU (backend {backend!r}); "
              f"nothing was built or run", file=sys.stderr)
        return 1

    from benchmarks.mfu_transformer import FLAGSHIP
    from distributed_pytorch_tpu.runtime import compile_cache

    cache_dir = compile_cache.enable()
    cache = {"hits": 0, "misses": 0}

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1
    jax.monitoring.register_event_listener(on_event)

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say("start", f"jax {jax.__version__} backend=tpu device_kind="
                 f"{device['kind']!r} devices={device['count']} "
                 f"compile_cache={cache_dir}")

    run_min_ddp(1)
    for shape in FLASH_SHAPES:
        check_flash(*shape)
    model, loss_fn, opt, losses = phase_trainer(FLAGSHIP)
    phase_server(model, FLAGSHIP)
    if len(devs) >= 4:
        phase_four_chips(model, loss_fn, opt, FLAGSHIP,
                         float(losses[0].mean()))
    else:
        say("four chips", f"NOT RUN: JAX reports {len(devs)} device(s); "
                          f"the world-4 min_ddp, dp=4 and ZeRO-3 checks "
                          f"need 4")
    say("compile cache", f"dir={cache_dir} hits={cache['hits']} "
                         f"misses={cache['misses']}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
