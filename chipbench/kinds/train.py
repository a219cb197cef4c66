"""A training job: closed loop, one step after another.

Set-up builds ONE object, the compiled step with its state, drives it
from the seed through the job's first ``check_steps`` steps by the
window's own call and feed, and hands that same object to the window. The
plain float32 reference follows those same steps first, in a process of
its own that has ended before this one touches the chip
(chipbench/reference_proc.py), so that the peak memory read is the
program's."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic_gen
from chipbench import weights as W
from chipbench.reference import train_steps

# The limits of ``correct`` are the cell's (``limits/<cell>.json``):
#   loss_rel_gap            |loss - reference| / reference, each first step
#   grad_norm_worst_leaf    worst leaf of | ||g|| - ||g_ref|| | over the larger
#                           of ||g_ref|| and the median leaf's
#   param_change_worst_leaf the same for each leaf's change after the steps;
#                           held against a step that returns its state
#                           unchanged, which reads 1


class StepRunner:
    """The window's call and feed: step ``i`` runs on batch ``i``, and
    batch ``i + 1`` is made on the host and uploaded while it runs."""

    def __init__(self, step, params, opt_state, feed, place):
        self.step, self.params, self.opt_state = step, params, opt_state
        self.feed, self.place = feed, place
        self.i = 0
        self._next = self._upload(0)

    def _upload(self, i):
        with jax.profiler.TraceAnnotation("make_batch"):
            return self.place(self.feed.batch(i))

    def __call__(self):
        batch = self._next
        with jax.profiler.TraceAnnotation("step_call"):
            out = self.step(self.params, self.opt_state, batch)
        self.params, self.opt_state = out.params, out.opt_state
        self.i += 1
        self._next = self._upload(self.i)
        return out.loss


def worst_leaf_gap(got, ref):
    """Both are dicts of per-leaf norms under the reference's names."""
    g = np.asarray(jax.tree_util.tree_leaves(got), np.float64)
    r = np.asarray(jax.tree_util.tree_leaves(ref), np.float64)
    return float(np.max(np.abs(g - r) / np.maximum(r, np.median(r))))


def build(cell, devices, seed):
    """The program: model, optimizer and compiled step through the
    public doors, with weights the benchmark makes from the seed."""
    import importlib

    import distributed_pytorch_tpu as dist
    from distributed_pytorch_tpu import models, optim
    from distributed_pytorch_tpu.ops import make_flash_attn_fn
    from distributed_pytorch_tpu.parallel import (StepSpecs, make_step,
                                                  make_train_step,
                                                  shard_layouts)

    cfg, job = cell.config, cell.traffic
    adapter = importlib.import_module(f"chipbench.adapters.{cfg['family']}")
    attn = {"flash": make_flash_attn_fn, "dense": lambda: None}[
        job["attention"]]()
    model = models.TransformerLM(**adapter.model_kwargs(cfg), attn_fn=attn,
                                 remat=job["remat"], dtype=jnp.bfloat16)

    def loss_fn(p, tokens):
        logits = model.apply(p, tokens[:, :-1]).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, -1)
        hit = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
        return jnp.mean(logz - hit), {}

    o = job["optimizer"]
    opt = optim.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                      weight_decay=o["weight_decay"])
    params = adapter.to_program(W.make(seed, cfg, jnp.float32))
    common = dict(mixed_precision=job["mixed_precision"],
                  donate=job["donate"])
    if len(devices) == 1:
        opt_state = opt.init(params)
        step = make_train_step(loss_fn, opt, **common)
        place = jnp.asarray
    else:
        if job["sharding"] != "zero3_over_dp":
            raise ValueError(f"unknown sharding {job['sharding']!r}")
        from jax.sharding import NamedSharding, PartitionSpec

        dist.init_process_group(0, len(devices))
        mesh = dist.get_mesh()
        named = lambda specs: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda s: isinstance(s, PartitionSpec))
        # state is made sharded: whole, its moments alone would overfill
        # the first chip
        p_specs, o_specs, _ = shard_layouts(
            params, jax.eval_shape(opt.init, params), n_shards=len(devices))
        params = jax.device_put(params, named(p_specs))
        opt_state = jax.jit(opt.init, out_shardings=named(o_specs))(params)
        step = make_step(loss_fn, opt, mesh=mesh,
                         specs=StepSpecs(params=p_specs), **common)
        place = dist.shard_batch
    return adapter, step, params, opt_state, place


def control(cell, devices):
    """The control's readings (chipbench/control.py): the reference in
    the program's place, computed in fp8, against the float32 reference,
    on the first step's loss and gradient."""
    from chipbench import lowprec

    cfg, job = cell.config, cell.traffic
    feed = traffic_gen.TrainFeed(job, cell.seed, cfg["vocab_size"],
                                 len(devices))
    batches = [feed.batch(0)]
    ref = train_steps.follow(cfg, cell.seed, batches, job["optimizer"],
                             job["reference_row_block"], devices=devices)
    low = train_steps.follow(cfg, cell.seed, batches, job["optimizer"],
                             job["reference_row_block"], mm=lowprec.mm_fp8,
                             devices=devices)
    return {"loss_rel_gap": abs(low["losses"][0] - ref["losses"][0])
            / abs(ref["losses"][0]),
            "grad_norm_worst_leaf": worst_leaf_gap(low["grad_norms"],
                                                   ref["grad_norms"])}


def before_devices(cell, require_chip):
    """Called by ``run.py`` before it looks for the chip: the reference
    follows the job's first steps in its own process and leaves its
    numbers in the run's output directory."""
    out = os.path.join(cell.out_dir, "reference.json")
    if os.path.exists(out):
        os.remove(out)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # started from the checkout, so that ``-m`` finds ``chipbench`` there
    cmd = [sys.executable, "-m", "chipbench.reference_proc", "--root",
           os.path.abspath(cell.root), "--workload", cell.name, "--seed",
           str(cell.seed), "--out", os.path.abspath(out)] \
        + ([] if require_chip else ["--cpu"])
    t0 = time.perf_counter()
    subprocess.run(cmd, check=True, cwd=repo, timeout=1100)
    with open(out) as f:
        cell.reference = json.load(f)
    cell.reference["process_seconds"] = time.perf_counter() - t0


def run(cell, devices, tracer, t_start, broken=None):
    """``broken`` is the tests' fault: a function that wraps the step."""
    cfg, job = cell.config, cell.traffic
    feed = traffic_gen.TrainFeed(job, cell.seed, cfg["vocab_size"],
                                 len(devices))
    n_check = job["check_steps"]

    ref, t_ref = cell.reference, cell.reference["process_seconds"]
    print(f"chipbench: reference followed {n_check} steps in "
          f"{ref['seconds']:.1f} s of a process of {t_ref:.1f} s (not "
          f"counted in setup_s), its peak {ref['peak_bytes']} bytes",
          flush=True)

    adapter, step, params, opt_state, place = build(cell, devices, cell.seed)
    cell.phases.end("build")
    try:
        return _drive(cell, devices, tracer, t_start, broken, adapter, step,
                      params, opt_state, place, feed, ref, t_ref)
    finally:
        if len(devices) > 1:
            import distributed_pytorch_tpu as dist
            dist.cleanup()


def _drive(cell, devices, tracer, t_start, broken, adapter, step, params,
           opt_state, place, feed, ref, t_ref):
    cfg, job = cell.config, cell.traffic
    n_check = job["check_steps"]
    if broken is not None:
        step = broken(step)
    runner = StepRunner(step, params, opt_state, feed, place)
    del params, opt_state

    norms = jax.jit(lambda tree: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        tree))
    delta = jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))
    losses, grad_norms = [], None
    for i in range(n_check):
        loss = runner()
        if i == 0:
            # the first gradient as the optimizer got it: after one step
            # AdamW's first moment is (1 - b1) g
            mu = norms(runner.opt_state.mu)
            grad_norms = jax.tree_util.tree_map(
                lambda x: float(x) / (1.0 - job["optimizer"]["b1"]),
                jax.device_get(adapter.from_program(mu)))
        losses.append(float(np.mean(np.asarray(loss, np.float64))))
    # made again from the seed, one layer at a time: the step donated the
    # first copy
    p_now = adapter.from_program(runner.params)
    like = lambda new, old: jax.device_put(new, jax.tree_util.tree_map(
        lambda x: x.sharding, old))
    change = jax.device_get({
        "globals": delta(p_now["globals"], like(
            W.make_globals(cell.seed, cfg, jnp.float32), p_now["globals"])),
        "layers": [delta(layer, like(
            W.make_layer(cell.seed, cfg, i, jnp.float32), layer))
            for i, layer in enumerate(p_now["layers"])]})
    del p_now
    checks = [{"name": f"loss_rel_gap.step{i + 1}",
               "value": abs(l - r) / abs(r), "limit": cell.limits["loss_rel_gap"]}
              for i, (l, r) in enumerate(zip(losses, ref["losses"]))]
    checks += [
        {"name": "grad_norm_worst_leaf",
         "value": worst_leaf_gap(grad_norms, ref["grad_norms"]),
         "limit": cell.limits["grad_norm_worst_leaf"]},
        {"name": "param_change_worst_leaf",
         "value": worst_leaf_gap(change, ref["delta_norms"]),
         "limit": cell.limits["param_change_worst_leaf"]}]
    print(f"chipbench: first losses program {losses} reference "
          f"{ref['losses']}", flush=True)

    cell.phases.end("first_steps")
    compiles_before = getattr(step, "compiles", 0)
    tokens_per_step = feed.rows * job["seq"]
    all_losses = []
    if cell.trace:
        tracer.start()
    jax.block_until_ready(runner.params)
    t_w0 = time.perf_counter()
    setup_s = t_w0 - t_start - t_ref
    n, prev, traced_steps, traced_tps, stamps = 0, None, None, None, []
    with jax.profiler.TraceAnnotation("window"):
        while True:
            loss = runner()
            if prev is not None:
                # one step stays in flight: wait for the one before
                all_losses.append(np.asarray(prev))
                stamps.append(time.perf_counter())
            prev = loss
            n += 1
            now = time.perf_counter()
            if tracer.on and now - t_w0 >= job["trace_seconds"]:
                jax.block_until_ready(loss)
                traced_tps = n * tokens_per_step / (time.perf_counter() - t_w0)
                tracer.stop()
                traced_steps = n
            if now - t_w0 >= cell.seconds:
                break
        all_losses.append(np.asarray(prev))
        jax.block_until_ready(runner.params)
    t_w1 = time.perf_counter()
    cell.phases.end("trace_start", at=t_w0)
    cell.phases.end("window", at=t_w1)
    if tracer.on:
        traced_tps = n * tokens_per_step / (t_w1 - t_w0)
        tracer.stop()
        traced_steps = n
    if tracer.stop_span:
        print(f"chipbench: traced part held {traced_steps} steps; writing "
              f"it out took {tracer.stop_span[1] - tracer.stop_span[0]:.1f}"
              f" s of the window", flush=True)
    flat = np.array([float(np.mean(l)) for l in all_losses])
    k = min(5, max(1, n // 2))
    fell = float(flat[-k:].mean() - flat[:k].mean())
    checks.append({"name": "window_losses_not_finite",
                   "value": float(np.sum(~np.isfinite(flat))), "limit": 0.0})
    checks.append({"name": "window_loss_last_minus_first", "value": fell,
                   "limit": 0.0})
    tps = n * tokens_per_step / (t_w1 - t_w0)
    pace = np.diff(stamps) * 1e3
    print(f"chipbench: step ms first {np.round(pace[:4], 2).tolist()} median "
          f"{np.median(pace):.3f} max {pace.max():.3f} (at step "
          f"{int(pace.argmax()) + 2})", flush=True)
    print(f"chipbench: window {n} steps in {t_w1 - t_w0:.3f} s, "
          f"{tokens_per_step} tokens a step, loss {flat[0]:.4f} -> "
          f"{flat[-1]:.4f}", flush=True)
    return {
        "checks": checks, "attempted": n, "failed": 0,
        "end_to_end": {"train_tokens_per_s": tps, "setup_s": setup_s},
        "counters": {
            "compiles_in_window":
                getattr(step, "compiles", 0) - compiles_before,
            "tokens_per_s": tps, "traced_tokens_per_s": traced_tps, "steps": n, "traced_steps": traced_steps,
            "tokens_per_step": tokens_per_step, "seq": job["seq"],
            "rows_per_chip": job["rows_per_chip"]}}
