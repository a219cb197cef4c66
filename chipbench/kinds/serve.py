"""A served model under an open loop: requests are due on a schedule
fixed from the seed before the run, whether or not earlier ones have
finished. Times are the benchmark's own: a request's clock starts when
it was DUE, and each token is stamped in its ``on_token`` callback.

Load starts ``lead_in_s`` before the window so that the window opens on
a full engine, and goes on after it until every request due inside the
window has finished (or ``drain_limit_s`` has passed: what is unfinished
then has failed). The sample is the requests due inside the window.

A traced run traces the window's last ``trace_iterations`` passes of the
engine loop, by the pace of the window so far, and never more than its
last ``trace_seconds``: a trace's size, and the time it takes to write
out and to read, follow the work it holds (17 k device operations a
decode program), so a faster engine must not be given a larger one. It
also has to hold the window's last ``trace_admissions`` arrivals, which
the benchmark knows from its own schedule: where the iterations' part is
too short for them (an engine of 12 ms an iteration whose last arrivals
lie seconds apart) the traced part starts 0.1 s before the earliest of
them, still inside ``trace_seconds``; else the readers of an admission
and of a prefill find nothing to read.
Writing it out (``jax.profiler.stop_trace``: 30 s for 35 iterations on the
v5e host, during which the engine's thread runs a fifth slower) starts
when the window closes, on the main thread, while the load goes on from a
thread of its own; reading it takes 3-4 s, the readers as long again."""

import gc
import importlib
import time
from concurrent.futures import ThreadPoolExecutor

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


class Load:
    """The open loop of one run: every request of the schedule submitted
    when it is due, from ``lead_in_s`` before the window until the sample
    (the requests due inside the window) has finished. ``s0``, ``s1`` and
    ``s2`` are the engine's ``stats()`` at the window's start, at its end
    and after the drain."""

    def __init__(self, eng, mix, schedule, seconds, broken):
        self.eng, self.mix, self.schedule, self.broken = \
            eng, mix, schedule, broken
        self.clients, self.lateness, self.submit_took = [], [], []
        self.t_load = time.perf_counter()
        self.t_w0 = self.t_load + mix["lead_in_s"]
        self.t_w1 = self.t_w0 + seconds
        self.s0 = self.s1 = self.s2 = self.t_done = None
        self.sample = []

    def run(self):
        from distributed_pytorch_tpu.serve import SamplingParams

        eng, clients, t_w0, t_w1 = self.eng, self.clients, self.t_w0, self.t_w1
        for req in self.schedule:
            due_t = self.t_load + req["due_s"]
            while True:
                now = time.perf_counter()
                if self.s0 is None and now >= t_w0:
                    self.s0 = eng.stats()
                if self.s1 is None and now >= t_w1:
                    self.s1 = eng.stats()
                if now >= due_t:
                    break
                with jax.profiler.TraceAnnotation("wait_for_due"):
                    time.sleep(min(due_t - now, 0.05))
            if not req["in_window"] and now >= t_w1 and all(
                    c.done() for c in clients if c.req["in_window"]):
                break                      # lead-out: the sample is in
            c = Client(req, due_t)
            self.lateness.append((now - due_t, due_t - t_w0))
            on_token = c.on_token if self.broken is None \
                else self.broken(c.on_token)
            try:
                with jax.profiler.TraceAnnotation("submit"):
                    c.handle = eng.submit(
                        req["prompt"],
                        SamplingParams(max_new_tokens=req["max_new"]),
                        on_token=on_token)
            except Exception as e:  # noqa: BLE001 - a refusal is a failure
                c.error = e
            self.submit_took.append((time.perf_counter() - now,
                                     due_t - t_w0))
            clients.append(c)
        if self.s1 is None:
            self.s1 = eng.stats()
        self.sample = [c for c in clients if c.req["in_window"]]
        limit = time.perf_counter() + self.mix["drain_limit_s"]
        with jax.profiler.TraceAnnotation("drain"):
            for c in self.sample:
                if c.error is None:
                    try:
                        c.handle.result(
                            timeout=max(0.0, limit - time.perf_counter()))
                    except Exception as e:  # noqa: BLE001
                        c.error = e
        self.s2 = eng.stats()
        self.t_done = time.perf_counter()


#: seconds before an arrival at which a traced part that has to hold it
#: starts: the profiler's own start (tens of milliseconds) falls in them
ARRIVAL_LEAD_S = 0.1


def arrivals_before_end(load):
    """Seconds before the window's end at which each request of the
    window is due, the last first: the benchmark made the schedule."""
    end = load.t_w1 - load.t_load
    return sorted(end - r["due_s"] for r in load.schedule if r["in_window"])


def trace_lead(mix, stats_, s0, into_window_s, before_end=()):
    """(seconds before the window's end at which the traced part starts,
    what set them): the longer of ``iterations``, ``trace_iterations``
    passes of the engine loop at the mean pace of the window so far, and
    ``arrivals``, ``ARRIVAL_LEAD_S`` before the ``trace_admissions``-th
    last arrival of the window (``before_end``: ``arrivals_before_end``;
    nothing where the window holds fewer), and at most
    ``trace_seconds``."""
    done = stats_["iterations"] - s0["iterations"]
    hold = mix["trace_admissions"]
    leads = {"iterations": mix["trace_seconds"] if done <= 0
             else mix["trace_iterations"] * into_window_s / done,
             "arrivals": before_end[hold - 1] + ARRIVAL_LEAD_S
             if hold <= len(before_end) else 0.0}
    by = max(leads, key=leads.get)
    if leads[by] > mix["trace_seconds"]:
        return mix["trace_seconds"], "trace_seconds"
    return leads[by], by


def trace_window_end(load, tracer):
    """Trace the last of the window, from the process's main thread while
    ``load`` runs on another: ``jax.profiler.stop_trace`` takes three
    times as long from a thread that is not the main one (97-102 s
    against 29-31 s for the same 32 iterations, PERF.md Findings PR 26).
    The traced part ends with the window, so that writing the trace out,
    which slows the engine's thread, falls after it. Returns what the
    traced part held, as counters."""
    eng, mix, t_w1 = load.eng, load.mix, load.t_w1
    before_end = arrivals_before_end(load)
    while True:
        now = time.perf_counter()
        wake = t_w1 - mix["trace_seconds"]
        if now >= wake and load.s0 is not None:
            lead, by = trace_lead(mix, eng.stats(), load.s0,
                                  now - load.t_w0, before_end)
            wake = t_w1 - lead
            if now >= wake:
                break
        if now >= t_w1:
            raise RuntimeError("the window closed before it opened: the "
                               "load has stopped")
        time.sleep(min(0.05, max(0.001, wake - now)))
    tracer.start()
    s_on, t_on = eng.stats(), time.perf_counter()
    time.sleep(max(0.0, t_w1 - t_on))
    s_off, t_off = eng.stats(), time.perf_counter()
    tracer.stop()
    return {"trace_lead_s": t_w1 - now, "trace_lead_by": by,
            "traced_seconds": t_off - t_on,
            "traced_iterations": s_off["iterations"] - s_on["iterations"],
            "traced_admissions": s_off["admitted"] - s_on["admitted"]}


def run(cell, devices, tracer, t_start, broken=None, control_mm=None):
    """``broken`` is the tests' fault: a function applied to every token
    where it is produced (it wraps the client's ``on_token``).
    ``control_mm`` (chipbench/control.py) also reads the control: the gap
    of the token a lower-precision reference puts first, at the same
    positions of the same prompts and tokens."""
    cfg, mix = cell.config, cell.traffic
    schedule = traffic_gen.serve_requests(mix, cell.seed, cell.seconds,
                                          cfg["vocab_size"])
    eng = build(cell, cell.seed)
    eng.start()
    cell.phases.end("build")
    traced = {}
    try:
        warm_up(eng, mix, cfg["vocab_size"])
        cell.phases.end("warm_up")
        load = Load(eng, mix, schedule, cell.seconds, broken)
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
                  f"(set by {traced['trace_lead_by']}: at most "
                  f"{mix['trace_seconds']} s, the longer of "
                  f"{mix['trace_iterations']} iterations at the window's "
                  f"pace and the last {mix['trace_admissions']} "
                  f"arrivals) and held {traced['traced_iterations']} "
                  f"iterations "
                  f"and {traced['traced_admissions']} admissions in "
                  f"{traced['traced_seconds']:.2f} s; writing it out took "
                  f"{wrote:.1f} s beside a drain of "
                  f"{load.t_done - t_w1:.1f} s", flush=True)
    finally:
        eng.shutdown()
    clients, sample, lateness, submit_took = \
        load.clients, load.sample, load.lateness, load.submit_took
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
    del eng, load
    gc.collect()
    cell.phases.end("shutdown")
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
    cell.phases.end("reference")
    return {
        "checks": [{"name": "served_logit_gap_max", "value": worst,
                    "limit": cell.limits["served_logit_gap_max"]}],
        "attempted": len(sample), "failed": failed,
        "end_to_end": end_to_end,
        "counters": {
            **traced,
            "compiles_in_window": compiles(s2) - compiles(s0),
            "iterations": s1["iterations"] - s0["iterations"],
            "tokens_emitted": s1["tokens_emitted"] - s0["tokens_emitted"],
            "queue_depth": (s0["queue_depth"], s1["queue_depth"]),
            "active_slots": (s0["active_slots"], s1["active_slots"]),
            "window_tokens_per_s":
                (s1["tokens_emitted"] - s0["tokens_emitted"]) / cell.seconds,
            "ttft_p50_ms": stats.median(ttft),
            "ttft_p95_ms": stats.percentile(ttft, 95)}}
