"""A served model that GENERATES BY BLOCKS under the open loop of
``kinds/serve.py``: the same schedule, clocks, window, lead-in, drain and
traced part (``Load`` with its ``Client``, ``warm_up``, ``trace_window_end``
with its ``trace_lead`` and ``compiles`` are that module's, imported), and another reading of ``correct``. A step
of such a model fills positions of a block and does not yield a token a
row, a block's tokens reach the client together, and what has to be
checked is not only WHICH token was served but WHERE the engine filled
next.

What decides ``correct`` (``limits/<cell>.json``), over ``check_requests``
finished requests drawn from the seed, the longest among them,
teacher-forced along the engine's own trajectory
(``reference/serve_block_logits.py``):

  served_logit_gap_max        for every filled position, in the state of
                              the pass that filled it, the reference's
                              best logit there less its logit of the
                              served token
  served_confidence_gap_mean  for every pass, the reference's largest
                              log-confidence over the positions still
                              masked less its log-confidence at the
                              position the engine filled (a pass of n
                              positions: the least among the reference's
                              n surest less the least among the
                              engine's); the mean over every pass read

The LARGEST confidence gap of a run (``served_confidence_gap_max``, which
ISSUE 37 named as the check) is printed and kept among the counters, and
decides nothing: all of a block's masked positions hold the same mask
embedding, so with weights random from a seed their confidences lie
within 0.2 of each other and no gap can pass that. A sound run's largest
(near-ties that bfloat16 decides the other way, 0.06-0.12), the fp8
control's (0.14-0.15) and a wrong fill order's (0.18-0.19) crowd under
that ceiling, where no limit has room on both sides; their means lie a
factor of 4.6 and of 15 apart (PERF.md section 2).

Repeated from ``kinds/serve.py`` because they cannot be imported as they
are: ``build`` (that one hands the model a flash ``attn_fn``, which cannot
take the block-causal mask, and makes the weights before the model; here
the MODEL is built first, so that a program without the mechanism fails in
seconds, before 10 GB of weights) and ``run`` (its tail: the sample, the
reference and the checks; its head only because it calls ``build``). ``run`` takes ``broken=`` and
``control_mm=`` as that module's does."""

import contextlib
import dataclasses
import gc
import importlib
import time
from concurrent.futures import ThreadPoolExecutor

import jax.numpy as jnp
import numpy as np

from chipbench import stats, traffic_gen
from chipbench import weights as W
from chipbench.kinds.serve import (Load, compiles, trace_window_end,
                                   warm_up)
from chipbench.reference import serve_block_logits


def build(cell, seed):
    from distributed_pytorch_tpu import models
    from distributed_pytorch_tpu.serve import EngineConfig, InferenceEngine

    cfg, mix = cell.config, cell.traffic
    e = dict(mix["engine"])
    e["buckets"] = tuple(e["buckets"])
    adapter = importlib.import_module(f"chipbench.adapters.{cfg['family']}")
    # the model before a single weight: a program that lacks a keyword
    # (the parent of the PR that brought the mechanism) dies here
    model = models.TransformerLM(
        **adapter.model_kwargs(cfg, max_len=e["max_len"]),
        dtype=jnp.bfloat16)
    if model.gen_block != mix["block_length"]:
        raise ValueError("the mix's block_length is not the configuration's")
    params = adapter.to_program(W.make(seed, cfg, jnp.bfloat16))
    return InferenceEngine(model, params, EngineConfig(**e))


class WithSteps:
    """The engine as ``Load`` drives it, every request given the mix's
    ``denoise_steps``."""

    def __init__(self, eng, steps):
        self.eng, self.steps = eng, steps

    def submit(self, prompt, sp, on_token=None):
        return self.eng.submit(
            prompt, dataclasses.replace(sp, denoise_steps=self.steps),
            on_token=on_token)

    def stats(self):
        return self.eng.stats()


# -- the two seeded faults (tests; chipbench/faults_blocks.py on the chip) ----

def fault_token(on_token):
    """A served token altered where it is produced: every 7th token of a
    stream becomes its neighbour in the vocabulary."""
    return lambda tok, i: on_token(tok + 1 if i % 7 == 3 else tok, i)


@contextlib.contextmanager
def fault_fill_order():
    """A fill order altered: the pick takes the LEAST confident of the
    masked positions (the program's ``fill_block`` with the order of the
    confidences turned round), every token still its position's argmax."""
    from distributed_pytorch_tpu.serve import sampling
    from distributed_pytorch_tpu.serve.pages import cache

    def least_confident(logits, tokens, masked, n_fill):
        x0, conf = sampling.block_confidence(logits)
        return sampling.fill_surest(x0, 1.0 - conf, tokens, masked, n_fill)

    sound = cache.fill_block
    assert sound is sampling.fill_block
    cache.fill_block = least_confident
    try:
        yield
    finally:
        cache.fill_block = sound


def run(cell, devices, tracer, t_start, broken=None, control_mm=None):
    """``broken`` is the tests' fault: a function applied to every token
    where it is produced (it wraps the client's ``on_token``).
    ``control_mm`` (chipbench/control.py) also reads the control: both
    gaps of what a lower-precision reference would have filled, at the
    same states."""
    cfg, mix = cell.config, cell.traffic
    # ids are drawn below the mask id, which no prompt may hold
    schedule = traffic_gen.serve_requests(mix, cell.seed, cell.seconds,
                                          cfg["mask_token_id"])
    eng = build(cell, cell.seed)
    eng.start()
    cell.phases.end("build")
    traced = {}
    try:
        warm_up(eng, mix, cfg["mask_token_id"])
        cell.phases.end("warm_up")
        load = Load(WithSteps(eng, mix["denoise_steps"]), mix, schedule,
                    cell.seconds, broken)
        if cell.trace:
            with ThreadPoolExecutor(
                    1, thread_name_prefix="chipbench-load") as pool:
                offered = pool.submit(load.run)
                traced = trace_window_end(load, tracer)
                offered.result()
        else:
            load.run()
        t_w0, t_w1 = load.t_w0, load.t_w1
        cell.phases.end("lead_in", at=t_w0)
        cell.phases.end("window", at=t_w1)
        cell.phases.end("drain", at=load.t_done)
        if cell.trace:
            cell.phases.end("trace_stop")
            wrote = tracer.stop_span[1] - tracer.stop_span[0]
            print(f"chipbench: traced part started "
                  f"{traced['trace_lead_s']:.2f} s before the window's end "
                  f"(set by {traced['trace_lead_by']}) and held "
                  f"{traced['traced_iterations']} iterations and "
                  f"{traced['traced_admissions']} admissions in "
                  f"{traced['traced_seconds']:.2f} s; writing it out took "
                  f"{wrote:.1f} s beside a drain of "
                  f"{load.t_done - t_w1:.1f} s", flush=True)
    finally:
        eng.shutdown()
    clients, sample, lateness = load.clients, load.sample, load.lateness
    s0, s1, s2 = load.s0, load.s1, load.s2
    setup_s = t_w0 - t_start

    ok = [c for c in sample
          if c.error is None and len(c.tokens) == c.req["max_new"]]
    failed = len(sample) - len(ok)
    ttft = [(c.times[0] - c.due_t) * 1e3 for c in ok]
    tpot = [(c.times[-1] - c.times[0]) / (len(c.times) - 1) * 1e3
            for c in ok if len(c.times) > 1]
    gaps = [(b - a) * 1e3 for c in ok
            for a, b in zip(c.times[:-1], c.times[1:])]
    lasted = [c.times[-1] - c.due_t for c in ok]
    print(f"chipbench: load {len(clients)} requests sent, {len(sample)} due "
          f"in the window, {len(ok)} finished whole, {failed} failed; "
          f"generator late by mean "
          f"{np.mean([l for l, _ in lateness]) * 1e3:.2f} ms, max "
          f"{max(lateness)[0] * 1e3:.2f} ms; the longest request lasted "
          f"{max(lasted):.1f} s (lead-in {mix['lead_in_s']} s)", flush=True)
    block_gaps = [g for g in gaps if g > 1.0]
    print(f"chipbench: samples ttft {len(ttft)} tpot {len(tpot)} itl gaps "
          f"{len(gaps)}, of them {len(block_gaps)} between blocks (p50 "
          f"{stats.median(block_gaps):.1f} ms); engine window iterations "
          f"{s1['iterations'] - s0['iterations']} tokens "
          f"{s1['tokens_emitted'] - s0['tokens_emitted']} row-passes "
          f"{s1['block_passes'] - s0['block_passes']}", flush=True)
    print("chipbench: ttft ms p50/p80/p95 "
          + "/".join(f"{stats.percentile(ttft, q):.2f}" for q in (50, 80, 95))
          + "; tpot ms p50/p95 "
          + "/".join(f"{stats.percentile(tpot, q):.3f}" for q in (50, 95))
          + "; itl ms p50/p95/p99 "
          + "/".join(f"{stats.percentile(gaps, q):.3f}" for q in (50, 95, 99))
          + f"; setup_s {setup_s:.2f}", flush=True)
    end_to_end = {"tpot_p50_ms": stats.median(tpot),
                  "itl_p95_ms": stats.percentile(gaps, 95),
                  "setup_s": setup_s}

    # free the engine and its weights, then the reference walks the model
    picked = _pick(ok, np.random.default_rng([cell.seed, 4]),
                   mix["check_requests"])
    del eng, load
    gc.collect()
    cell.phases.end("shutdown")
    t0 = time.perf_counter()
    ref = serve_block_logits.served_gaps(
        cfg, cell.seed, picked, jnp.bfloat16,
        width=mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"],
        max_new=mix["answer_tokens"]["max"], control_mm=control_mm)
    worst = lambda key: float(max(g.max() for g in ref[key] if len(g)))
    mean = lambda key: float(np.mean(np.concatenate(ref[key])))
    if control_mm is not None:
        print(f"chipbench: control served_logit_gap_max "
              f"{worst('control_logit'):.6g} served_confidence_gap_max "
              f"{worst('control_confidence'):.6g} "
              f"served_confidence_gap_mean "
              f"{mean('control_confidence'):.6g}", flush=True)
    print(f"chipbench: reference read "
          f"{sum(len(g) for g in ref['logit'])} filled positions in "
          f"{sum(len(g) for g in ref['confidence'])} passes of "
          f"{len(picked)} requests in {time.perf_counter() - t0:.1f} s; "
          f"served_confidence_gap_max {worst('confidence'):.6g}",
          flush=True)
    cell.phases.end("reference")
    passes = s1["block_passes"] - s0["block_passes"]
    tokens = s1["tokens_emitted"] - s0["tokens_emitted"]
    return {
        "checks": [{"name": name, "value": value,
                    "limit": cell.limits[name]}
                   for name, value in (
                       ("served_logit_gap_max", worst("logit")),
                       ("served_confidence_gap_mean", mean("confidence")))],
        "attempted": len(sample), "failed": failed,
        "end_to_end": end_to_end,
        "counters": {
            **traced,
            "compiles_in_window": compiles(s2) - compiles(s0),
            "iterations": s1["iterations"] - s0["iterations"],
            "tokens_emitted": tokens,
            "block_passes": passes,
            "block_commits": s1["block_commits"] - s0["block_commits"],
            "block_fills": s1["block_fills"] - s0["block_fills"],
            "blocks_emitted": s1["blocks_emitted"] - s0["blocks_emitted"],
            "block_passes_per_token": passes / max(tokens, 1),
            "served_confidence_gap_max": worst("confidence"),
            "queue_depth": (s0["queue_depth"], s1["queue_depth"]),
            "active_slots": (s0["active_slots"], s1["active_slots"]),
            "window_tokens_per_s": tokens / cell.seconds,
            "ttft_p50_ms": stats.median(ttft),
            "ttft_p95_ms": stats.percentile(ttft, 95)}}


def _pick(ok, rng, n):
    """``n`` finished requests drawn from the seed, the longest among
    them, as (prompt, tokens, fill_pass)."""
    longest = max(ok, key=lambda c: len(c.req["prompt"]) + len(c.tokens))
    rest = [c for c in ok if c is not longest]
    picks = [longest] + [rest[i] for i in rng.permutation(len(rest))[:n - 1]]
    return [(c.req["prompt"], np.asarray(c.tokens, np.int32),
             np.asarray(c.handle.fill_pass, np.int32)) for c in picks]
