"""A training job whose model brings its own loss and a state outside the
optimizer: the closed loop of ``kinds/train.py`` (its ``StepRunner``,
``_drive`` and ``worst_leaf_gap`` unchanged, so the window, the clock and
the checks' names are the training cell's own, and the result's keys but
for the rate's, which the job names (``rate_metric``); one check is added
to them, the routers' choice against the reference's,
``router_pairs_elsewhere_share``), with the program built through the model's doors: ``TransformerLM(mtp=1)``,
``ops.losses.lm_mtp_loss``, and ``make_train_step(buffers=...)`` for the
router biases. The plain float32 reference follows the same steps first,
in a process of its own (chipbench/reference_proc_mtp.py)."""

import importlib
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
from chipbench.kinds.train import StepRunner, _drive, worst_leaf_gap  # noqa: F401
from chipbench.reference import train_steps_mtp
from distributed_pytorch_tpu.optim.schedules import ScheduledState

#: window means of the step's own metrics, put among the run's counters
STEP_COUNTERS = ("loss_main", "loss_mtp", "moe_pairs_here", "moe_load_max",
                 "moe_load_mean", "moe_bias_abs_max")


def build(cell, devices, seed):
    """The program: model, loss, optimizer, buffers and compiled step
    through the public doors, with weights the benchmark makes from the
    seed."""
    from distributed_pytorch_tpu import models
    from distributed_pytorch_tpu.ops import make_flash_attn_fn
    from distributed_pytorch_tpu.ops.losses import lm_mtp_loss
    from distributed_pytorch_tpu.parallel import Buffers, make_train_step

    cfg, job = cell.config, cell.traffic
    if len(devices) != 1:
        raise ValueError("kind train_mtp drives one chip")
    adapter = importlib.import_module(f"chipbench.adapters.{cfg['family']}")
    attn = {"flash": make_flash_attn_fn, "dense": lambda: None}[
        job["attention"]]()
    model = models.TransformerLM(**adapter.model_kwargs(cfg), attn_fn=attn,
                                 remat=job["remat"], dtype=jnp.bfloat16)

    def loss_fn(p, tokens):
        return lm_mtp_loss(model, p, tokens, weight=job["mtp_weight"])

    opt = optimizer(job["optimizer"])
    buffers = Buffers(
        mask=model.router_bias_mask,
        update=lambda p, aux: model.balance_router_bias(
            p, aux["moe_load"], job["bias_update_speed"]))
    params = adapter.to_program(W.make(seed, cfg, jnp.float32))
    opt_state = moments_shown(opt.init(buffers.trainable(params)))
    step = make_train_step(loss_fn, opt, mixed_precision=job["mixed_precision"],
                           donate=job["donate"], buffers=buffers)
    return adapter, step, params, opt_state, jnp.asarray


def optimizer(o):
    """AdamW as the job's ``optimizer`` states it, through what the
    library gives a user: at the constant ``lr``, or, where the job names
    ``warmup_steps``, under ``with_schedule`` on a linear ramp from 0 to
    ``lr`` (the peak) over that many steps and constant after. The
    schedule counts the optimizer's own steps, so the check steps and the
    window run on one ramp."""
    from distributed_pytorch_tpu import optim

    adamw = lambda lr: optim.adamw(lr, b1=o["b1"], b2=o["b2"], eps=o["eps"],
                                   weight_decay=o["weight_decay"])
    if "warmup_steps" not in o:
        return adamw(o["lr"])
    return optim.with_schedule(adamw, optim.linear_warmup(
        optim.constant(o["lr"]), o["warmup_steps"]))


class _MomentsShown(ScheduledState):
    """A scheduled optimizer's state, ``(step, inner)``, showing AdamW's
    first moment where ``_drive`` reads the first gradient from it:
    ``opt_state.mu``. A named tuple's subclass is the same pytree under
    another type, so the step is traced once as long as every call is
    handed this type: ``_Metered`` wraps what comes back."""

    __slots__ = ()
    mu = property(lambda self: self.inner.mu)


def moments_shown(state):
    return _MomentsShown(*state) if isinstance(state, ScheduledState) \
        else state


def control(cell, devices):
    """The control's readings (chipbench/control.py): the reference in
    the program's place, computed in fp8, against the float32 reference,
    on the first step's loss, gradient and routers' choice."""
    from chipbench import lowprec

    cfg, job = cell.config, cell.traffic
    feed = traffic_gen.TrainFeed(job, cell.seed, cfg["vocab_size"],
                                 len(devices))
    batches = [feed.batch(0)]
    ref = train_steps_mtp.follow(cfg, cell.seed, batches, job,
                                 devices=devices)
    low = train_steps_mtp.follow(cfg, cell.seed, batches, job,
                                 mm=lowprec.mm_fp8, devices=devices)
    return {"loss_rel_gap": abs(low["losses"][0] - ref["losses"][0])
            / abs(ref["losses"][0]),
            "grad_norm_worst_leaf": worst_leaf_gap(low["grad_norms"],
                                                   ref["grad_norms"]),
            "router_pairs_elsewhere_share": pairs_elsewhere_share(
                low["loads"][0], ref["loads"][0], cfg, job, feed.rows)}


def pairs_elsewhere_share(loads, ref_loads, cfg, job, rows):
    """The largest share, over the expert layers, of a step's (token,
    expert) pairs that one router sent to another expert than the other
    router did, as far as the loads show (half the summed difference of
    the counts over the pairs a layer routes). Float32 against bfloat16
    it counts the near-ties that rounding decides the other way."""
    moved = [np.abs(np.asarray(a, np.int64) - np.asarray(b, np.int64)).sum()
             // 2 for a, b in zip(loads, ref_loads)]
    return float(max(moved)) / (rows * job["seq"]
                                * cfg["num_experts_per_tok"])


def before_devices(cell, require_chip):
    """Called by ``run.py`` before it looks for the chip: the reference
    follows the job's first steps in its own process and leaves its
    numbers in the run's output directory."""
    # a program without the doors this kind drives (the parent of the PR
    # that added them) fails here, at once, not after the reference's
    # minutes; the import touches no device
    from distributed_pytorch_tpu.parallel import Buffers  # noqa: F401
    out = os.path.join(cell.out_dir, "reference.json")
    if os.path.exists(out):
        os.remove(out)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    cmd = [sys.executable, "-m", "chipbench.reference_proc_mtp", "--root",
           os.path.abspath(cell.root), "--workload", cell.name, "--seed",
           str(cell.seed), "--out", os.path.abspath(out)] \
        + ([] if require_chip else ["--cpu"])
    t0 = time.perf_counter()
    subprocess.run(cmd, check=True, cwd=repo, timeout=1100)
    with open(out) as f:
        cell.reference = json.load(f)
    cell.reference["process_seconds"] = time.perf_counter() - t0


class _Metered:
    """The step, keeping each call's metrics (device scalars, fetched
    after the window): ``_drive`` hands on the loss alone. Of the first
    call it also keeps the routers' loads, which are compared with the
    reference's."""

    def __init__(self, step):
        self.step, self.metrics, self.first_load = step, [], None

    def __call__(self, params, opt_state, batch):
        out = self.step(params, opt_state, batch)
        out = out._replace(opt_state=moments_shown(out.opt_state))
        self.metrics.append({k: out.metrics[k] for k in STEP_COUNTERS})
        if self.first_load is None:
            self.first_load = out.metrics["moe_load"]
        return out

    @property
    def compiles(self):
        return self.step.compiles


def run(cell, devices, tracer, t_start, broken=None):
    """``broken`` is the tests' fault: a function that wraps the step."""
    cfg, job = cell.config, cell.traffic
    feed = traffic_gen.TrainFeed(job, cell.seed, cfg["vocab_size"],
                                 len(devices))
    ref, t_ref = cell.reference, cell.reference["process_seconds"]
    print(f"chipbench: reference followed {job['check_steps']} steps in "
          f"{ref['seconds']:.1f} s of a process of {t_ref:.1f} s (not "
          f"counted in setup_s), its peak {ref['peak_bytes']} bytes; heads "
          f"main {ref['losses_main']} mtp {ref['losses_mtp']}", flush=True)
    adapter, step, params, opt_state, place = build(cell, devices, cell.seed)
    cell.phases.end("build")
    step = _Metered(step)
    out = _drive(cell, devices, tracer, t_start, broken, adapter, step,
                 params, opt_state, place, feed, ref, t_ref)
    # the window's rate under the name the job gives it: the same number,
    # an end-to-end metric of its own where the job's pace follows its
    # seed's routers and cannot stand under the dense cells' bound
    out["end_to_end"][job["rate_metric"]] = \
        out["end_to_end"].pop("train_tokens_per_s")
    fetched = jax.device_get(step.metrics)      # one read, after the window
    load = jax.device_get(step.first_load)
    # one comparison of the kind's own beside ``_drive``'s: the routers'
    # choice, step 1, as exactly as the loads allow
    out["checks"].append({
        "name": "router_pairs_elsewhere_share",
        "value": pairs_elsewhere_share(load, ref["loads"][0], cfg, job,
                                       feed.rows),
        "limit": cell.limits["router_pairs_elsewhere_share"]})
    first, window = (fetched[:job["check_steps"]],
                     fetched[job["check_steps"]:])
    print("chipbench: first steps' heads main "
          f"{[float(m['loss_main']) for m in first]} mtp "
          f"{[float(m['loss_mtp']) for m in first]}", flush=True)
    for k in STEP_COUNTERS:
        out["counters"][k] = float(np.mean([m[k] for m in window]))
    out["counters"]["moe_pairs_routed"] = feed.rows * job["seq"] \
        * cfg["num_experts_per_tok"]
    mine = {k: out["counters"][k]
            for k in STEP_COUNTERS + ("moe_pairs_routed",)}
    print("chipbench: window means of the step's metrics "
          + json.dumps(mine), flush=True)
    # for chipbench/scope_dump_train.py, by hand after a traced run
    with open(os.path.join(cell.out_dir, "step_counters.json"), "w") as f:
        json.dump(mine, f)
    return out
