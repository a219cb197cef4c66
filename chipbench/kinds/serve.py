"""A served model under an open loop: requests are due on a schedule
fixed from the seed before the run, whether or not earlier ones have
finished. Times are the benchmark's own: a request's clock starts when
it was DUE, and each token is stamped in its ``on_token`` callback.

Load starts ``lead_in_s`` before the window so that the window opens on
a full engine, and goes on after it until every request due inside the
window has finished (or ``drain_limit_s`` has passed: what is unfinished
then has failed). The sample is the requests due inside the window."""

import gc
import importlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import stats, traffic_gen
from chipbench import weights as W
from chipbench.reference import serve_logits

# The limit of ``correct`` is the cell's (``limits/<cell>.json``):
#   served_logit_gap_max  the widest gap, over a seeded sample of finished
#                         requests, by which a served token's float32
#                         reference logit lies below the reference's best


class Client:
    """One request as the user sees it."""

    __slots__ = ("req", "due_t", "times", "tokens", "handle", "error")

    def __init__(self, req, due_t):
        self.req, self.due_t = req, due_t
        self.times, self.tokens = [], []
        self.handle = self.error = None

    def on_token(self, tok, i):
        self.times.append(time.perf_counter())
        self.tokens.append(int(tok))

    def done(self):
        return (self.error is not None
                or len(self.tokens) >= self.req["max_new"]
                or self.handle.state == "failed")


def build(cell, seed):
    from distributed_pytorch_tpu import models
    from distributed_pytorch_tpu.ops import make_flash_attn_fn
    from distributed_pytorch_tpu.serve import EngineConfig, InferenceEngine

    cfg, mix = cell.config, cell.traffic
    e = dict(mix["engine"])
    e["buckets"] = tuple(e["buckets"])
    adapter = importlib.import_module(f"chipbench.adapters.{cfg['family']}")
    model = models.TransformerLM(
        **adapter.model_kwargs(cfg, max_len=e["max_len"]),
        attn_fn=make_flash_attn_fn(), dtype=jnp.bfloat16)
    params = adapter.to_program(W.make(seed, cfg, jnp.bfloat16))
    return InferenceEngine(model, params, EngineConfig(**e))


def warm_up(eng, mix, vocab):
    """Every program the mix's traffic will use and no other: one prefill
    per bucket, the decode program, the greedy sampler, and the per-length
    key split that ``submit`` makes for each distinct answer length."""
    from distributed_pytorch_tpu.serve import SamplingParams

    rng = np.random.default_rng(0)
    grid = traffic_gen.answer_grid(mix)
    handles = [eng.submit(rng.integers(0, vocab, b).astype(np.int32),
                          SamplingParams(max_new_tokens=grid[0]))
               for b in mix["engine"]["buckets"]]
    for h in handles:
        h.result(timeout=1200)
    for n in grid:
        np.asarray(jax.random.split(jax.random.PRNGKey(0), n))


def compiles(stats_):
    return (stats_["decode_compiles"] + stats_["sample_compiles"]
            + sum(stats_["prefill_compiles"].values()))


def run(cell, devices, tracer, t_start, broken=None, control_mm=None):
    """``broken`` is the tests' fault: a function applied to every token
    where it is produced (it wraps the client's ``on_token``).
    ``control_mm`` (chipbench/control.py) also reads the control: the gap
    of the token a lower-precision reference puts first, at the same
    positions of the same prompts and tokens."""
    from distributed_pytorch_tpu.serve import SamplingParams

    cfg, mix = cell.config, cell.traffic
    schedule = traffic_gen.serve_requests(mix, cell.seed, cell.seconds,
                                          cfg["vocab_size"])
    eng = build(cell, cell.seed)
    eng.start()
    try:
        warm_up(eng, mix, cfg["vocab_size"])
        clients = []
        lateness, submit_took = [], []
        stopper = None
        t_load = time.perf_counter()
        t_w0 = t_load + mix["lead_in_s"]
        t_w1 = t_w0 + cell.seconds
        s0 = s1 = None
        for req in schedule:
            due_t = t_load + req["due_s"]
            while True:
                now = time.perf_counter()
                if s0 is None and now >= t_w0:
                    s0 = eng.stats()
                # the traced part is the window's last seconds: writing a
                # trace out takes half a minute and slows the engine's
                # thread meanwhile, which then falls after the window
                if (cell.trace and not tracer.on and stopper is None
                        and now >= t_w1 - mix["trace_seconds"]):
                    tracer.start()
                if tracer.on and now >= t_w1:
                    # off this thread: the schedule must not wait for it
                    tracer.on = False
                    stopper = threading.Thread(target=tracer.stop,
                                               name="chipbench-trace-stop")
                    stopper.start()
                if s1 is None and now >= t_w1:
                    s1 = eng.stats()
                if now >= due_t:
                    break
                with jax.profiler.TraceAnnotation("wait_for_due"):
                    time.sleep(min(due_t - now, 0.05))
            if not req["in_window"] and now >= t_w1 and all(
                    c.done() for c in clients if c.req["in_window"]):
                break                      # lead-out: the sample is in
            c = Client(req, due_t)
            lateness.append((now - due_t, due_t - t_w0))
            on_token = c.on_token if broken is None else broken(c.on_token)
            try:
                with jax.profiler.TraceAnnotation("submit"):
                    c.handle = eng.submit(
                        req["prompt"],
                        SamplingParams(max_new_tokens=req["max_new"]),
                        on_token=on_token)
            except Exception as e:  # noqa: BLE001 - a refusal is a failure
                c.error = e
            submit_took.append((time.perf_counter() - now, due_t - t_w0))
            clients.append(c)
        if tracer.on:
            tracer.stop()
        if s1 is None:
            s1 = eng.stats()
        sample = [c for c in clients if c.req["in_window"]]
        limit = time.perf_counter() + mix["drain_limit_s"]
        with jax.profiler.TraceAnnotation("drain"):
            for c in sample:
                if c.error is None:
                    try:
                        c.handle.result(
                            timeout=max(0.0, limit - time.perf_counter()))
                    except Exception as e:  # noqa: BLE001
                        c.error = e
        s2 = eng.stats()
        if stopper is not None:
            stopper.join()
    finally:
        eng.shutdown()
    setup_s = t_w0 - t_start

    ok = [c for c in sample
          if c.error is None and len(c.tokens) == c.req["max_new"]]
    failed = len(sample) - len(ok)
    ttft = [(c.times[0] - c.due_t) * 1e3 for c in ok]
    tpot = [(c.times[-1] - c.times[0]) / (len(c.times) - 1) * 1e3
            for c in ok if len(c.times) > 1]
    gaps = [(b - a) * 1e3 for c in ok
            for a, b in zip(c.times[:-1], c.times[1:])]
    print(f"chipbench: load {len(clients)} requests sent, {len(sample)} due "
          f"in the window, {len(ok)} finished whole, {failed} failed; "
          f"generator late by mean "
          f"{np.mean([l for l, _ in lateness]) * 1e3:.2f} ms, max "
          f"{max(lateness)[0] * 1e3:.2f} ms (due {max(lateness)[1]:.1f} s "
          f"into the window); longest submit call "
          f"{max(submit_took)[0] * 1e3:.2f} ms (due {max(submit_took)[1]:.1f}"
          f" s into the window)", flush=True)
    stalls = sorted(((b - a, a - t_w0) for c in ok
                     for a, b in zip(c.times[:-1], c.times[1:])),
                    reverse=True)[:3]
    print("chipbench: longest gaps between tokens "
          + ", ".join(f"{g * 1e3:.0f} ms at {at:.1f} s" for g, at in stalls),
          flush=True)
    print(f"chipbench: samples ttft {len(ttft)} tpot {len(tpot)} "
          f"itl gaps {len(gaps)}; ttft p50 "
          f"{stats.median(ttft):.1f} ms; engine window iterations "
          f"{s1['iterations'] - s0['iterations']} tokens "
          f"{s1['tokens_emitted'] - s0['tokens_emitted']}", flush=True)
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
    del eng
    gc.collect()
    rng = np.random.default_rng([cell.seed, 4])
    longest = max(ok, key=lambda c: len(c.req["prompt"]) + len(c.tokens))
    rest = [c for c in ok if c is not longest]
    picks = [longest] + [rest[i] for i in rng.permutation(len(rest))[
        :mix["check_requests"] - 1]]
    t0 = time.perf_counter()
    gaps_ref = serve_logits.served_gaps(
        cfg, cell.seed, [(c.req["prompt"], np.asarray(c.tokens, np.int32))
                         for c in picks], jnp.bfloat16,
        width=mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"],
        max_new=mix["answer_tokens"]["max"], control_mm=control_mm)
    if control_mm is not None:
        print(f"chipbench: control served_logit_gap_max "
              f"{max(float(g.max()) for g in gaps_ref['control']):.6g}",
              flush=True)
    worst = float(max(g.max() for g in gaps_ref["served"]))
    n_tok = sum(len(g) for g in gaps_ref["served"])
    print(f"chipbench: reference read {n_tok} served tokens of "
          f"{len(picks)} requests in {time.perf_counter() - t0:.1f} s",
          flush=True)
    return {
        "checks": [{"name": "served_logit_gap_max", "value": worst,
                    "limit": cell.limits["served_logit_gap_max"]}],
        "attempted": len(sample), "failed": failed,
        "end_to_end": end_to_end,
        "counters": {
            "compiles_in_window": compiles(s2) - compiles(s0),
            "iterations": s1["iterations"] - s0["iterations"],
            "tokens_emitted": s1["tokens_emitted"] - s0["tokens_emitted"],
            "queue_depth": (s0["queue_depth"], s1["queue_depth"]),
            "active_slots": (s0["active_slots"], s1["active_slots"]),
            "window_tokens_per_s":
                (s1["tokens_emitted"] - s0["tokens_emitted"]) / cell.seconds,
            "ttft_p50_ms": stats.median(ttft),
            "ttft_p95_ms": stats.percentile(ttft, 95)}}
