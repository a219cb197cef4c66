"""Serving benchmark: continuous batching vs static batching.

Two load shapes over the same request population:

- **closed loop**: every request submitted at t=0 (the floodgates
  case) — measures peak engine throughput and the TTFT spread induced
  by queueing behind the slot pool;
- **open loop**: Poisson arrivals at ``--rate`` req/s (seeded, so a
  run is reproducible) — the serving-paper methodology (the TTFT/TPOT
  numbers that matter under load are open-loop ones; arxiv 2605.25645
  makes the same point for TPU serving).

The baseline arm is **static batching**: the same requests grouped
FCFS into fixed batches of ``n_slots``, each batch served by ONE
compiled ``generate()`` call (everyone in the batch waits for the
whole batch's decode — the pre-Orca serving shape). Uniform prompt
length/max-new in that arm, since ``generate`` has no per-row
lengths; the engine arms use the mixed population.

Per-arm output: tokens/s, p50/p99 TTFT and TPOT (serve.metrics
definitions). Throughput numbers go through the perfbench statistical
policy (docs/benchmarking.md): each closed-loop arm runs
warmup-discarded repeated trials, tokens/s is the median with IQR and
the hard spread gate attached, and the engine-vs-static throughput
ratio is structurally withheld (with the gate's reason) when either
side comes back untrusted. The printed line is a schema-valid
``dpx.bench.record`` (perfbench/record.py).

The **shared-prefix arm** (serve/pages/, docs/serving.md) runs the same
seeded Poisson open loop over K "system prompts" round-robined across
N requests, paged+prefix-shared vs the unshared engine: TTFT p50/p99 as
gated medians, ``prefill_tokens_saved``, pool occupancy and hit rate,
and a ``vs_unshared_ttft_p50_x`` ratio withheld-or-printed per the
spread-gate policy; non-smoke runs append the record to
``benchmarks/tpu_results.jsonl`` (stage ``serve_shared``).

The **disaggregated arm** (serve/disagg/, docs/serving.md) runs the
same seeded Poisson open loop through the split engine (PrefillEngine +
DecodeEngine over the KV-page handoff) vs the monolithic paged engine
on the SAME population/arrivals: TTFT and TPOT p50/p99 as gated
medians, per-request handoff bytes, and a ``vs_monolithic_tpot_p99_x``
ratio printed-or-withheld per the spread gate; a second record (stage
``serve_disagg``) lands in ``benchmarks/tpu_results.jsonl`` on
non-smoke runs. A one-shot q8 run pins the handoff byte claim:
CommStats-booked bytes equal the ``wire.handoff_page_wire_bytes``
formula, at >= 3.5x under the f32 frame.

The **quantized resident pool arm** (``kv_dtype``, serve/pages/,
docs/serving.md "Quantized resident pool") reruns the shared-prefix
population with q8 block-quantized resident pages vs the exact f32
pool: the headline is the deterministic bytes-per-resident-token
capacity ratio (~3.9x at q8, ~7.5x at q4 — reported as pure storage
math), with TTFT p50/p99 gated medians, occupancy/hit-rate/evictions,
and the token-divergence fraction vs the exact pool; non-smoke runs
append stage ``serve_kvq``.

The **speculative decoding arm** (serve/spec/, docs/serving.md
"Speculative decoding") runs the mixed greedy population closed-loop
through the paged engine with a draft model proposing ``--draft-len``
tokens per iteration vs the SAME engine non-spec: acceptance rate and
tokens/iteration are the speculation headline, TPOT p50/p99 ride as
gated medians, and ``vs_nonspec_tpot_p50_x`` is printed-or-withheld
per the spread gate. Smoke self-drafts (draft == target) so the gate
set — accepted streams bit-exact vs ``generate()``, acceptance > 0,
verify compiles == {draft_len+1: 1}, ``tools/dpxmon.py replay`` rc 0
over the spec engine's metrics log — is deterministic; non-smoke runs
use a thin 1-layer draft and append stage ``serve_spec``.

The **fleet arm** (serve/fleet/, docs/serving.md "Multi-replica
fleet") runs the shared-prefix population through the prefix-affine
FleetRouter at R=1, 2, 4 replicas on the SAME seeded Poisson arrivals:
tokens/s and TTFT p50/p99 as gated medians per R, and
``vs_single_replica_r{2,4}_x`` throughput ratios printed-or-withheld
per the spread gate (on one CPU host the replicas share cores, so a
withheld-or-flat ratio is the honest outcome — the record is the
methodology rail for a real multi-host run). Non-smoke runs append
stage ``serve_fleet``. The separate ``--fleet-smoke`` mode is the CI
gate (tier1.yml ``fleet-smoke``): an R=2 fleet serves the
shared-prefix mix BIT-IDENTICAL to standalone ``generate()`` (and to
the R=1 fleet — routing never changes tokens) with affinity hit rate
> 0; one replica killed mid-run fails ONLY its in-flight requests as
typed replica-attributed ``ReplicaFailed`` while a co-resident stream
finishes bit-exact; and ``tools/dpxmon.py replay`` exits 0 over the
fleet's emitted metrics log.

``--smoke`` shrinks everything to a seconds-scale CPU run AND asserts
engine streams equal standalone ``generate()`` (all three engines —
continuous, paged+shared, disaggregated), that the shared arm's hit
rate is > 0 with ``prefill_tokens_saved`` exactly the analytic count
for the synthetic population, that the paged AND disagg engines kept
ONE decode program (zero on the prefill side of the split), and the
q8 handoff byte gates above — the CI job that keeps the engine loops
from rotting (tier1.yml).

Usage: python benchmarks/serve_bench.py [--smoke | --fleet-smoke]
           [--requests N] [--rate R] [--max-new N] [--seed S]
           [--slots N] [--trials N] [--warmup N] [--prefixes K]
           [--prefix-len N] [--draft-len K]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def build_model(smoke: bool):
    import jax
    from distributed_pytorch_tpu import models
    if smoke:
        model = models.TransformerLM(vocab=61, dim=32, n_layers=2,
                                     n_heads=4, n_kv_heads=2, pos="rope",
                                     max_seq=256)
    else:
        model = models.TransformerLM(vocab=512, dim=256, n_layers=4,
                                     n_heads=8, n_kv_heads=4, pos="rope",
                                     max_seq=1024)
    return model, model.init(jax.random.PRNGKey(0))


def make_requests(n, vocab, max_new, seed, uniform=False):
    """(prompt, SamplingParams, key) population; ``uniform`` pins one
    shape for the static-batching arm."""
    import jax
    from distributed_pytorch_tpu.serve import SamplingParams
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        s = 16 if uniform else int(rng.integers(4, 24))
        mn = max_new if uniform else int(rng.integers(max(2, max_new // 2),
                                                      max_new + 1))
        prompt = rng.integers(0, vocab, (s,)).astype(np.int32)
        out.append((prompt, SamplingParams(max_new_tokens=mn),
                    jax.random.PRNGKey(1000 + i)))
    return out


def make_shared_requests(n, vocab, max_new, seed, k_prefixes, prefix_len,
                         tail_max):
    """The shared-prefix serving population: ``k_prefixes`` "system
    prompts" of ``prefix_len`` tokens round-robined over ``n`` requests,
    each with a private random tail — the consumer-traffic shape the
    paged prefix cache exists for (the first occurrence of each prefix
    is cold, every later one shares its full pages)."""
    import jax
    from distributed_pytorch_tpu.serve import SamplingParams
    rng = np.random.default_rng(seed + 7)
    prefixes = [rng.integers(0, vocab, (prefix_len,)).astype(np.int32)
                for _ in range(k_prefixes)]
    out = []
    for i in range(n):
        t = int(rng.integers(1, tail_max + 1))
        prompt = np.concatenate(
            [prefixes[i % k_prefixes],
             rng.integers(0, vocab, (t,))]).astype(np.int32)
        out.append((prompt, SamplingParams(max_new_tokens=max_new),
                    jax.random.PRNGKey(2000 + i)))
    return out


def run_engine(model, params, reqs, n_slots, max_len, rate=None, seed=0,
               page_len=None, prefix_share=True,
               kv_dtype=None, draft_model=None, draft_params=None,
               draft_len=None, metrics=None, log_every=16):
    """Submit ``reqs`` (closed loop, or Poisson open loop at ``rate``)
    and aggregate per-request SLO records. A non-None ``draft_model``
    turns on speculative decoding (serve/spec/) and attaches the
    engine's speculation accounting as ``rep["spec"]``."""
    from distributed_pytorch_tpu.serve import (EngineConfig,
                                               InferenceEngine, aggregate)
    eng = InferenceEngine(model, params,
                          EngineConfig(n_slots=n_slots, max_len=max_len,
                                       page_len=page_len,
                                       prefix_share=prefix_share,
                                       kv_dtype=kv_dtype,
                                       spec_decode=draft_model is not None,
                                       draft_model=draft_model,
                                       draft_params=draft_params,
                                       draft_len=draft_len,
                                       metrics=metrics,
                                       log_every=log_every))
    rng = np.random.default_rng(seed)
    handles = []
    t0 = time.monotonic()
    with eng:
        for prompt, sp, key in reqs:
            if rate is not None:
                time.sleep(rng.exponential(1.0 / rate))
            handles.append(eng.submit(prompt, sp, rng=key))
        outs = [h.result(timeout=600) for h in handles]
    wall = time.monotonic() - t0
    rep = aggregate([h.metrics for h in handles], wall_s=wall)
    st = eng.stats()
    rep["stats"] = {k: v for k, v in st.items()
                    if k in ("iterations", "decode_compiles",
                             "prefill_compiles", "sample_compiles")}
    rep["pages"] = st["pages"]
    if draft_model is not None:
        rep["spec"] = st["spec"]
    return rep, outs


def run_disagg(model, params, reqs, n_slots, max_len, rate=None, seed=0,
               page_len=None, width="f32"):
    """Submit ``reqs`` through the disaggregated split (closed loop, or
    Poisson open loop at ``rate``) and aggregate per-request records —
    which now carry the TTFT decomposition and handoff bytes."""
    from distributed_pytorch_tpu.serve import (DisaggConfig, DisaggEngine,
                                               aggregate)
    eng = DisaggEngine(model, params,
                       DisaggConfig(n_slots=n_slots, max_len=max_len,
                                    page_len=page_len,
                                    handoff_width=width))
    rng = np.random.default_rng(seed)
    handles = []
    t0 = time.monotonic()
    with eng:
        for prompt, sp, key in reqs:
            if rate is not None:
                time.sleep(rng.exponential(1.0 / rate))
            handles.append(eng.submit(prompt, sp, rng=key))
        outs = [h.result(timeout=600) for h in handles]
    wall = time.monotonic() - t0
    rep = aggregate([h.metrics for h in handles], wall_s=wall)
    st = eng.stats()
    rep["stats"] = {
        "decode_compiles": st["decode"]["decode_compiles"],
        "prefill_side_decode_compiles": st["prefill"]["decode_compiles"],
        "prefill_compiles": st["prefill"]["prefill_compiles"],
    }
    rep["handoff"] = st["handoff"]
    return rep, outs


def run_fleet(model, params, reqs, n_replicas, n_slots, max_len,
              rate=None, seed=0, page_len=None, metrics=None):
    """Submit ``reqs`` through an R-replica prefix-affine fleet
    (closed loop, or Poisson open loop at ``rate``) and aggregate the
    per-request SLO records, with the fleet routing counters
    attached."""
    from distributed_pytorch_tpu.serve import EngineConfig, aggregate
    from distributed_pytorch_tpu.serve.fleet import (FleetConfig,
                                                     FleetRouter)
    fleet = FleetRouter(
        model, params,
        FleetConfig(n_replicas=n_replicas,
                    engine=EngineConfig(n_slots=n_slots, max_len=max_len,
                                        page_len=page_len),
                    metrics=metrics))
    rng = np.random.default_rng(seed)
    handles = []
    t0 = time.monotonic()
    with fleet:
        for prompt, sp, key in reqs:
            if rate is not None:
                time.sleep(rng.exponential(1.0 / rate))
            handles.append(fleet.submit(prompt, sp, rng=key))
        outs = [h.result(timeout=600) for h in handles]
    wall = time.monotonic() - t0
    rep = aggregate([h.metrics for h in handles], wall_s=wall)
    fst = fleet.stats()
    rep["fleet"] = {"replicas": n_replicas, "routes": fst["routes"],
                    "spills": fst["spills"],
                    "route_affinity_hit_rate":
                        fst["route_affinity_hit_rate"]}
    return rep, outs


def run_static(model, params, reqs, n_slots, max_len):
    """Static batching: FCFS groups of ``n_slots`` through one compiled
    generate() each; every request's TTFT is its group's full wall time
    (tokens only exist when the whole batch finishes)."""
    import jax
    import jax.numpy as jnp
    from distributed_pytorch_tpu.models.generate import make_generate_fn
    from distributed_pytorch_tpu.serve import aggregate
    sp = reqs[0][1]
    fn = jax.jit(make_generate_fn(model, sp.max_new_tokens,
                                  max_len=max_len))
    # compile lands inside the wall, same as the engine arm (both pay
    # their first-call compiles in the measured region)
    records, t0 = [], time.monotonic()
    for g0 in range(0, len(reqs), n_slots):
        group = reqs[g0:g0 + n_slots]
        prompts = jnp.asarray(np.stack([p for p, _, _ in group]))
        gt0 = time.monotonic()
        toks = fn(params, prompts, group[0][2])
        jax.block_until_ready(toks)
        gt1 = time.monotonic()
        for i in range(len(group)):
            n = sp.max_new_tokens
            records.append({
                "request_id": g0 + i, "outcome": "ok",
                "prompt_len": int(prompts.shape[1]), "n_tokens": n,
                # all tokens arrive at batch completion: TTFT is the
                # group wall from t=0 (closed loop), TPOT the amortized
                # per-token group time
                "ttft_ms": (gt1 - t0) * 1e3,
                "tpot_ms": (gt1 - gt0) * 1e3 / n,
                "queue_ms": (gt0 - t0) * 1e3,
            })
    return aggregate(records, wall_s=time.monotonic() - t0)


def measured_stats(run_once, keys, *, warmup, trials,
                   absent_as_zero=("prefill_tokens_saved",)):
    """``measured_arm`` generalized to several scalar keys — the
    shared-prefix latency arms gate TTFT p50/p99 medians (and the
    deterministic prefill-savings count), not tokens/s.

    A key missing from a trial rep is a HARD error (KeyError), never a
    silent 0 — for a lower-is-better latency a fabricated 0 would be a
    perfect trusted median, exactly the null-laundering the perfbench
    schema forbids.  The one exception is ``absent_as_zero``:
    ``aggregate()`` legitimately omits ``prefill_tokens_saved`` when
    nothing was saved, and 0 is its honest (direction=higher,
    pessimistic) value."""
    from distributed_pytorch_tpu.perfbench import stats as pbstats
    reps = [run_once() for _ in range(warmup + trials)]
    sts = {}
    for k in keys:
        vals = []
        for i, r in enumerate(reps):
            v = r.get(k)
            if v is None:
                if k in absent_as_zero:
                    v = 0
                else:
                    raise KeyError(
                        f"metric {k!r} absent from trial {i}'s aggregate "
                        f"— refusing to launder a missing measurement "
                        f"into a 0")
            vals.append(v)
        sts[k] = pbstats.summarize(vals, warmup=warmup)
    rep = dict(reps[-1])
    for k, st in sts.items():
        rep[k] = round(st.median, 2)
        rep[k + "_trials"] = st.to_dict(nd=2)
    return rep, sts


def measured_arm(run_once, *, warmup, trials):
    """Repeated-trial wrapper for one throughput arm: runs ``run_once``
    (returning an aggregate rep with ``tokens_per_sec``) ``warmup +
    trials`` times under the perfbench policy.  The first trial pays the
    arm's jit compiles — exactly the cold-start artifact the warmup
    discard exists for.  Returns ``(last rep + trials detail, stats)``."""
    rep, sts = measured_stats(run_once, ("tokens_per_sec",),
                              warmup=warmup, trials=trials,
                              absent_as_zero=())
    return rep, sts["tokens_per_sec"]


def fleet_smoke(argv):
    """The CI fleet gate (tier1.yml ``fleet-smoke``): an R=2
    prefix-affine fleet serves the shared-prefix mix BIT-IDENTICAL to
    both standalone ``generate()`` and an R=1 fleet (routing never
    changes tokens) with affinity hit rate > 0; one replica killed
    mid-run fails ONLY its in-flight request as typed
    replica-attributed ``ReplicaFailed`` while a co-resident stream on
    the survivor finishes bit-exact and a same-id revive serves again;
    and ``tools/dpxmon.py replay`` exits 0 over the fleet's emitted
    metrics log (strict snapshot validation + the replica-failure
    health stream recovering)."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from benchmarks.soak import _run_cli
    from distributed_pytorch_tpu.models.generate import make_generate_fn
    from distributed_pytorch_tpu.serve import EngineConfig, SamplingParams
    from distributed_pytorch_tpu.serve.fleet import (FleetConfig,
                                                     FleetRouter,
                                                     ReplicaFailed)
    from distributed_pytorch_tpu.utils.logging import MetricsLogger

    model, params = build_model(True)
    max_len, page_len = 64, 8
    n_req, k_prefixes, prefix_len, max_new = 10, 2, 8, 8
    reqs = make_shared_requests(n_req, model.vocab, max_new, 0,
                                k_prefixes, prefix_len, tail_max=7)
    problems = []
    workdir = tempfile.mkdtemp(prefix="dpx_fleet_smoke_")
    log = os.path.join(workdir, "fleet_metrics.jsonl")

    # R=2 vs R=1 vs standalone: the determinism gate
    rep2, outs2 = run_fleet(model, params, reqs, 2, 2, max_len,
                            rate=50.0, seed=3, page_len=page_len,
                            metrics=MetricsLogger(log))
    _, outs1 = run_fleet(model, params, reqs, 1, 2, max_len,
                         rate=50.0, seed=3, page_len=page_len)
    for i, (a, b) in enumerate(zip(outs1, outs2)):
        if not np.array_equal(a, b):
            problems.append(f"request {i}: R=2 stream != R=1 stream")
    for i in (0, n_req // 2, n_req - 1):
        prompt, sp, key = reqs[i]
        ref = np.asarray(jax.jit(make_generate_fn(
            model, sp.max_new_tokens, max_len=max_len))(
            params, jnp.asarray(prompt[None]), key))[0]
        if not np.array_equal(outs2[i], ref):
            problems.append(f"request {i} diverged from standalone "
                            f"generate()")
    hit = rep2["fleet"]["route_affinity_hit_rate"] or 0.0
    if not hit > 0:
        problems.append(f"affinity hit rate {hit} not > 0")

    # kill one replica mid-run: victim-only typed failure, co-resident
    # bit-exact, same-id revive serves again
    fleet = FleetRouter(
        model, params,
        FleetConfig(n_replicas=2,
                    engine=EngineConfig(n_slots=2, max_len=max_len,
                                        page_len=page_len),
                    metrics=MetricsLogger(log), log_every=4))
    rng = np.random.default_rng(5)
    with fleet:
        fleet.submit(reqs[0][0][:6],
                     SamplingParams(max_new_tokens=2)).result(timeout=120)
        pa = reqs[0][0]
        victim = fleet.home_of(pa)
        # everything the kill window doesn't need happens BEFORE the
        # victim stream starts: the off-victim prompt scan and the key
        # constructions would otherwise eat the in-flight runway
        q = None
        for _ in range(64):        # a prompt homed OFF the victim
            cand = rng.integers(0, model.vocab, (10,)).astype(np.int32)
            if fleet.home_of(cand) != victim:
                q = cand
                break
        ka, kb = jax.random.PRNGKey(7), jax.random.PRNGKey(8)
        kc = jax.random.PRNGKey(9)
        spb, spc = (SamplingParams(max_new_tokens=6),
                    SamplingParams(max_new_tokens=40))
        if q is None:
            problems.append("no off-victim prompt found in 64 draws")
        else:
            # the co-resident stream starts FIRST (on the survivor),
            # then the victim stream; the kill lands the moment the
            # victim stream has a token in flight — nothing else sits
            # in that window (this model decodes a token every few ms,
            # so any work between first-token and kill loses the race)
            hc = fleet.submit(q, spc, rng=kc)
            ha = fleet.submit(pa, SamplingParams(max_new_tokens=45),
                              rng=ka)
            while not ha.tokens:   # in flight on its home replica
                time.sleep(0.005)
            fleet.kill_replica(victim, reason="fleet_smoke_kill")
            try:
                ha.result(timeout=120)
                problems.append("in-flight request on the killed "
                                "replica did not fail")
            except ReplicaFailed as e:
                if e.replica != victim or e.request_id != ha.request_id:
                    problems.append(
                        f"ReplicaFailed misattributed: replica="
                        f"{e.replica} request={e.request_id} (wanted "
                        f"{victim}/{ha.request_id})")
            except Exception as e:  # noqa: BLE001 — the gate reports it
                problems.append(f"in-flight failure not typed "
                                f"ReplicaFailed: {type(e).__name__}")
            out_c = hc.result(timeout=120)
            ref_c = np.asarray(jax.jit(make_generate_fn(
                model, spc.max_new_tokens, max_len=max_len))(
                params, jnp.asarray(q[None]), kc))[0]
            if not np.array_equal(out_c, ref_c):
                problems.append("co-resident stream diverged after "
                                "the kill")
            # the dead replica's shard re-homes: a post-kill submit of
            # the SAME prompt must land on the survivor, bit-exact
            if fleet.home_of(pa) == victim:
                problems.append("prefix shard did not re-home off the "
                                "killed replica")
            hb = fleet.submit(pa, spb, rng=kb)
            if hb.replica == victim:
                problems.append("post-kill submit routed to the dead "
                                "replica")
            out_b = hb.result(timeout=120)
            ref_b = np.asarray(jax.jit(make_generate_fn(
                model, spb.max_new_tokens, max_len=max_len))(
                params, jnp.asarray(pa[None]), kb))[0]
            if not np.array_equal(out_b, ref_b):
                problems.append("re-homed stream diverged from "
                                "standalone generate()")
            fleet.revive_replica(victim)
            out_d = fleet.submit(
                pa, SamplingParams(max_new_tokens=4)).result(timeout=120)
            if not len(out_d) > 0:
                problems.append("revived replica served nothing")
        fleet.emit_snapshot()
        fleet.emit_snapshot()

    # replay the fleet's own log: strict validation + the
    # replica-failure stream must degrade AND recover (rc 0); the rule
    # spec is the fleet SLO (queue ceiling) — process-growth rules
    # don't apply to a log whose snapshots straddle jit compiles
    rc, out = _run_cli("tools.dpxmon",
                       ["replay", log, "--rules",
                        "fleet.max_queue_depth<=64"])
    if rc != 0:
        problems.append(f"dpxmon replay over the fleet log exited "
                        f"{rc}: {out.strip()[-200:]}")

    if problems:
        print(json.dumps({"bench": "serve_fleet",
                          "error": "; ".join(problems)}))
        return 1
    shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"bench": "serve_fleet", "fleet_smoke_gates": {
        "engine_matches_generate": True,
        "matches_single_replica": True,
        "route_affinity_hit_rate": round(hit, 4),
        "spills": rep2["fleet"]["spills"],
        "kill_typed_attributed": True,
        "coresident_bit_exact": True,
        "dpxmon_replay_rc": rc}}))
    return 0


def main(argv):
    if "--fleet-smoke" in argv:
        return fleet_smoke(argv)
    smoke = "--smoke" in argv

    def flag(name, default):
        if name in argv:
            return type(default)(argv[argv.index(name) + 1])
        return default

    n_slots = flag("--slots", 4)
    n_req = flag("--requests", 12 if smoke else 64)
    max_new = flag("--max-new", 8 if smoke else 64)
    rate = flag("--rate", 0.0) or (50.0 if smoke else 8.0)
    seed = flag("--seed", 0)
    max_len = 64 if smoke else 512
    from distributed_pytorch_tpu.perfbench import record as pbrecord
    from distributed_pytorch_tpu.perfbench import stats as pbstats
    from distributed_pytorch_tpu.runtime import env as dpxenv
    warmup = flag("--warmup", 1 if smoke else
                  int(dpxenv.get("DPX_BENCH_WARMUP")))
    trials = flag("--trials", 3 if smoke else
                  int(dpxenv.get("DPX_BENCH_TRIALS")))

    model, params = build_model(smoke)
    rec = pbrecord.make_record("serve_engine_closed_tokens_per_sec",
                               "tokens_per_sec", device="cpu-loopback")
    rec.update({"bench": "serve", "smoke": smoke,
                "config": {"n_slots": n_slots, "n_requests": n_req,
                           "max_new": max_new, "rate_rps": rate,
                           "max_len": max_len, "vocab": model.vocab,
                           "dim": model.dim, "n_layers": model.n_layers,
                           "warmup": warmup, "trials": trials},
                "arms": {}})

    # closed loop (mixed population) — the headline arm. outs (for the
    # smoke correctness gate) come from the FIRST run: identical
    # submissions, and divergence would invalidate every trial equally.
    mixed = make_requests(n_req, model.vocab, max_new, seed)
    first = {}

    def closed_once():
        rep, outs = run_engine(model, params, mixed, n_slots, max_len)
        first.setdefault("outs", outs)
        return rep

    closed, closed_st = measured_arm(closed_once, warmup=warmup,
                                     trials=trials)
    outs = first["outs"]
    rec["arms"]["engine_closed"] = closed
    rec["value"] = round(closed_st.median, 2)
    rec["provenance"] = "measured"
    rec["trusted"] = closed_st.trusted
    if closed_st.trusted:
        rec.pop("untrusted_reason", None)
    else:
        rec["untrusted_reason"] = closed_st.untrusted_reason
    rec["metrics"]["serve_engine_closed_tokens_per_sec"] = \
        pbrecord.make_metric(None, "tokens_per_sec", stats=closed_st)

    if smoke:
        # correctness gate: engine streams == standalone generate()
        import jax
        import jax.numpy as jnp
        from distributed_pytorch_tpu.models.generate import make_generate_fn
        for i in (0, n_req // 2, n_req - 1):
            prompt, sp, key = mixed[i]
            ref = np.asarray(jax.jit(make_generate_fn(
                model, sp.max_new_tokens, max_len=max_len))(
                params, jnp.asarray(prompt[None]), key))[0]
            if not np.array_equal(outs[i], ref):
                print(json.dumps({"bench": "serve", "error":
                                  f"request {i} diverged from "
                                  f"standalone generate()"}))
                return 1
        rec["engine_matches_generate"] = True

    # open loop (Poisson arrivals, mixed population)
    open_rep, _ = run_engine(model, params, mixed, n_slots, max_len,
                             rate=rate, seed=seed + 1)
    rec["arms"]["engine_open_poisson"] = open_rep

    # static-batching baseline (uniform shapes; generate has no per-row
    # lengths) — same trial policy on BOTH sides of the ratio
    uni = make_requests(n_req, model.vocab, max_new, seed, uniform=True)
    static, static_st = measured_arm(
        lambda: run_static(model, params, uni, n_slots, max_len),
        warmup=warmup, trials=trials)
    rec["arms"]["static_batch"] = static
    rec["metrics"]["serve_static_batch_tokens_per_sec"] = \
        pbrecord.make_metric(None, "tokens_per_sec", stats=static_st)
    eng_uni, eng_uni_st = measured_arm(
        lambda: run_engine(model, params, uni, n_slots, max_len)[0],
        warmup=warmup, trials=trials)
    rec["arms"]["engine_closed_uniform"] = eng_uni
    rec["metrics"]["serve_engine_uniform_tokens_per_sec"] = \
        pbrecord.make_metric(None, "tokens_per_sec", stats=eng_uni_st)

    # continuous-vs-static throughput: printed only when both sides pass
    # the spread gate, withheld with the gate's reason otherwise
    ratio, why = pbstats.gated_ratio(eng_uni_st, static_st)
    if ratio is not None:
        rec["engine_vs_static_tokens_x"] = round(ratio, 2)
    else:
        rec["engine_vs_static_tokens_x_withheld"] = why
    st, en = static, eng_uni
    if st.get("ttft_ms_p50") and en.get("ttft_ms_p50"):
        # last-trial latency detail (a distribution, not a gated median)
        rec["engine_vs_static_ttft_p50_x"] = round(
            st["ttft_ms_p50"] / en["ttft_ms_p50"], 2)

    # ---- shared-prefix paged arm (serve/pages/, ROADMAP item 4) ----
    # K "system prompts" round-robined over N requests, seeded Poisson
    # open loop: the paged+prefix-shared engine vs the unshared engine
    # on the SAME population/arrivals. TTFT p50/p99 go through the
    # spread-gate policy; vs_unshared is withheld with the gate's
    # reason when either side comes back untrusted.
    k_prefixes = flag("--prefixes", 3 if smoke else 8)
    prefix_len = flag("--prefix-len", 16 if smoke else 128)
    page_len = 8 if smoke else 16
    tail_max = 7 if smoke else 32
    shared_reqs = make_shared_requests(n_req, model.vocab, max_new, seed,
                                       k_prefixes, prefix_len, tail_max)
    rec["config"].update({"k_prefixes": k_prefixes,
                          "prefix_len": prefix_len,
                          "page_len": page_len, "tail_max": tail_max})
    first_shared = {}

    def shared_once():
        rep, outs = run_engine(model, params, shared_reqs, n_slots,
                               max_len, rate=rate, seed=seed + 2,
                               page_len=page_len)
        first_shared.setdefault("outs", outs)
        first_shared.setdefault("rep", rep)
        return rep

    shared_rep, shared_st = measured_stats(
        shared_once,
        ("ttft_ms_p50", "ttft_ms_p99", "prefill_tokens_saved"),
        warmup=warmup, trials=trials)
    rec["arms"]["engine_paged_shared"] = shared_rep
    unshared_rep, unshared_st = measured_stats(
        lambda: run_engine(model, params, shared_reqs, n_slots, max_len,
                           rate=rate, seed=seed + 2, page_len=page_len,
                           prefix_share=False)[0],
        ("ttft_ms_p50", "ttft_ms_p99"), warmup=warmup, trials=trials)
    rec["arms"]["engine_unshared_open"] = unshared_rep
    for name, stx in (
            ("serve_shared_ttft_ms_p50", shared_st["ttft_ms_p50"]),
            ("serve_shared_ttft_ms_p99", shared_st["ttft_ms_p99"]),
            ("serve_unshared_ttft_ms_p50", unshared_st["ttft_ms_p50"]),
            ("serve_unshared_ttft_ms_p99", unshared_st["ttft_ms_p99"]),
            ("serve_prefill_tokens_saved",
             shared_st["prefill_tokens_saved"])):
        rec["metrics"][name] = pbrecord.make_metric(
            None, "ms" if "ttft" in name else "tokens", stats=stx,
            direction="lower" if "ttft" in name else "higher")
    pages = first_shared["rep"]["pages"]
    rec["metrics"]["serve_paged_pool_occupancy"] = pbrecord.make_metric(
        round(pages["pool_occupancy"], 4), "frac")
    rec["metrics"]["serve_paged_prefix_hit_rate"] = pbrecord.make_metric(
        round(pages["prefix_hit_rate"] or 0.0, 4), "frac")
    # TTFT is lower-better, so the speedup ratio is unshared/shared
    vs, why = pbstats.gated_ratio(unshared_st["ttft_ms_p50"],
                                  shared_st["ttft_ms_p50"])
    if vs is not None:
        rec["vs_unshared_ttft_p50_x"] = round(vs, 2)
    else:
        rec["vs_unshared_ttft_p50_withheld"] = why

    if smoke:
        # the shared-prefix CI gates (tier1.yml): sharing must actually
        # happen, save EXACTLY the analytic token count for this
        # synthetic population ((n-k) repeats x prefix_len — smoke
        # tails are < one page so nothing else can be indexed), keep
        # the one-decode-program discipline, and stay bit-exact
        problems = []
        hit_rate = pages["prefix_hit_rate"] or 0.0
        if not hit_rate > 0:
            problems.append(f"prefix hit rate {hit_rate} not > 0")
        analytic = (n_req - k_prefixes) * prefix_len
        got_saved = first_shared["rep"].get("prefill_tokens_saved", 0)
        if got_saved != analytic:
            problems.append(f"prefill_tokens_saved {got_saved} != "
                            f"analytic {analytic}")
        if first_shared["rep"]["stats"]["decode_compiles"] != 1:
            problems.append(
                f"paged decode_compiles "
                f"{first_shared['rep']['stats']['decode_compiles']} != 1")
        import jax
        import jax.numpy as jnp
        from distributed_pytorch_tpu.models.generate import make_generate_fn
        for i in (0, k_prefixes, n_req - 1):   # cold + shared samples
            prompt, sp, key = shared_reqs[i]
            ref = np.asarray(jax.jit(make_generate_fn(
                model, sp.max_new_tokens, max_len=max_len))(
                params, jnp.asarray(prompt[None]), key))[0]
            if not np.array_equal(first_shared["outs"][i], ref):
                problems.append(f"shared request {i} diverged from "
                                f"standalone generate()")
        if problems:
            print(json.dumps({"bench": "serve", "error":
                              "; ".join(problems)}))
            return 1
        rec["shared_prefix_gates"] = {
            "prefix_hit_rate": round(hit_rate, 4),
            "prefill_tokens_saved": got_saved, "analytic": analytic,
            "engine_matches_generate": True}

    # ---- disaggregated prefill/decode arm (serve/disagg/) ----
    # the SAME mixed population and Poisson arrivals through the split
    # engine vs the monolithic paged engine; TTFT/TPOT p50/p99 as gated
    # medians, vs_monolithic withheld-or-printed per the spread gate,
    # and the q8 handoff byte claim pinned against the wire formula.
    from distributed_pytorch_tpu.serve.disagg import kv_wire_bytes
    rec_d = pbrecord.make_record("serve_disagg_tpot_ms_p99", "ms",
                                 device="cpu-loopback")
    rec_d.update({"bench": "serve_disagg", "smoke": smoke,
                  "config": dict(rec["config"], page_len=page_len,
                                 handoff_width="f32"),
                  "arms": {}})
    lat_keys = ("ttft_ms_p50", "ttft_ms_p99", "tpot_ms_p50",
                "tpot_ms_p99")
    first_disagg = {}

    def disagg_once():
        rep, outs = run_disagg(model, params, mixed, n_slots, max_len,
                               rate=rate, seed=seed + 3,
                               page_len=page_len)
        first_disagg.setdefault("outs", outs)
        first_disagg.setdefault("rep", rep)
        return rep

    disagg_rep, disagg_st = measured_stats(
        disagg_once, lat_keys, warmup=warmup, trials=trials,
        absent_as_zero=())
    rec_d["arms"]["engine_disagg_open"] = disagg_rep
    mono_rep, mono_st = measured_stats(
        lambda: run_engine(model, params, mixed, n_slots, max_len,
                           rate=rate, seed=seed + 3, page_len=page_len)[0],
        lat_keys, warmup=warmup, trials=trials, absent_as_zero=())
    rec_d["arms"]["engine_monolithic_open"] = mono_rep
    for k in lat_keys:
        rec_d["metrics"][f"serve_disagg_{k}"] = pbrecord.make_metric(
            None, "ms", stats=disagg_st[k], direction="lower")
        rec_d["metrics"][f"serve_monolithic_{k}"] = pbrecord.make_metric(
            None, "ms", stats=mono_st[k], direction="lower")
    rec_d["value"] = round(disagg_st["tpot_ms_p99"].median, 2)
    rec_d["provenance"] = "measured"
    rec_d["trusted"] = disagg_st["tpot_ms_p99"].trusted
    if rec_d["trusted"]:
        rec_d.pop("untrusted_reason", None)
    else:
        rec_d["untrusted_reason"] = \
            disagg_st["tpot_ms_p99"].untrusted_reason
    # TPOT is lower-better: >1 means the split decodes at a faster
    # cadence than the prefill-interleaved monolithic loop
    vs, why = pbstats.gated_ratio(mono_st["tpot_ms_p99"],
                                  disagg_st["tpot_ms_p99"])
    if vs is not None:
        rec_d["vs_monolithic_tpot_p99_x"] = round(vs, 2)
    else:
        rec_d["vs_monolithic_tpot_p99_withheld"] = why
    # handoff byte claim: one q8 closed-loop pass over the population;
    # booked bytes must EQUAL the wire formula on both widths and the
    # q8 frame must be >= 3.5x under f32
    q8_rep, _ = run_disagg(model, params, mixed, n_slots, max_len,
                           page_len=page_len, width="q8")
    pe = (getattr(model, "n_kv_heads", model.n_heads) * page_len
          * (model.dim // model.n_heads))
    f32_formula = sum(
        kv_wire_bytes(model.n_layers, -(-len(p) // page_len), pe, None)
        for p, _, _ in mixed)
    q8_formula = sum(
        kv_wire_bytes(model.n_layers, -(-len(p) // page_len), pe, 8)
        for p, _, _ in mixed)
    f32_bytes = first_disagg["rep"]["handoff"]["bytes_sent"]
    q8_bytes = q8_rep["handoff"]["bytes_sent"]
    rec_d["handoff"] = {
        "f32_bytes": f32_bytes, "q8_bytes": q8_bytes,
        "f32_formula": f32_formula, "q8_formula": q8_formula,
        "q8_vs_f32_bytes_x": round(f32_bytes / q8_bytes, 2),
        "page_elems": pe,
        "handoff_ms_p50": first_disagg["rep"].get("handoff_ms_p50"),
    }
    rec_d["metrics"]["serve_disagg_q8_vs_f32_bytes_x"] = \
        pbrecord.make_metric(round(f32_bytes / q8_bytes, 2), "x")

    if smoke:
        # the disagg CI gates (tier1.yml): exact-handoff streams must
        # equal standalone generate(), the q8 handoff must book >= 3.5x
        # fewer bytes than f32 with CommStats EXACTLY the wire formula,
        # and the split must keep ONE decode program (zero on the
        # prefill side)
        import jax
        import jax.numpy as jnp
        from distributed_pytorch_tpu.models.generate import make_generate_fn
        problems = []
        for i in (0, n_req // 2, n_req - 1):
            prompt, sp, key = mixed[i]
            ref = np.asarray(jax.jit(make_generate_fn(
                model, sp.max_new_tokens, max_len=max_len))(
                params, jnp.asarray(prompt[None]), key))[0]
            if not np.array_equal(first_disagg["outs"][i], ref):
                problems.append(f"disagg request {i} diverged from "
                                f"standalone generate()")
        if f32_bytes != f32_formula:
            problems.append(f"f32 handoff bytes {f32_bytes} != wire "
                            f"formula {f32_formula}")
        if q8_bytes != q8_formula:
            problems.append(f"q8 handoff bytes {q8_bytes} != wire "
                            f"formula {q8_formula}")
        if not f32_bytes / q8_bytes >= 3.5:
            problems.append(f"q8 handoff byte cut "
                            f"{f32_bytes / q8_bytes:.2f}x < 3.5x")
        st_d = first_disagg["rep"]["stats"]
        if st_d["decode_compiles"] != 1:
            problems.append(f"disagg decode_compiles "
                            f"{st_d['decode_compiles']} != 1")
        if st_d["prefill_side_decode_compiles"] != 0:
            problems.append(
                f"prefill-side decode_compiles "
                f"{st_d['prefill_side_decode_compiles']} != 0")
        if problems:
            print(json.dumps({"bench": "serve_disagg",
                              "error": "; ".join(problems)}))
            return 1
        rec_d["disagg_gates"] = {
            "engine_matches_generate": True,
            "q8_vs_f32_bytes_x": round(f32_bytes / q8_bytes, 2),
            "commstats_equals_formula": True,
            "decode_compiles": 1}

    # ---- quantized resident pool arm (serve/pages/ kv_dtype) ----
    # the shared-prefix population through the paged engine at q8
    # resident storage vs the exact f32 pool: capacity per byte is the
    # headline (a deterministic storage-layout ratio), TTFT p50/p99
    # ride as gated medians, and the smoke asserts the quality bound —
    # cold first tokens EXACT (in-register prefill, zero quant error at
    # admission), bounded token divergence on the mixed cold/shared
    # population, and the one-decode-program discipline intact.
    from distributed_pytorch_tpu.serve.pages import PagedSlotPool
    rec_q = pbrecord.make_record("serve_kvq_capacity_x", "x",
                                 device="cpu-loopback")
    rec_q.update({"bench": "serve_kvq", "smoke": smoke,
                  "config": dict(rec["config"], page_len=page_len,
                                 kv_dtype="q8"),
                  "arms": {}})
    first_kvq = {}

    def kvq_once():
        # closed loop on purpose: identical admission order on every
        # trial makes the q8-vs-f32 token comparison deterministic
        rep, outs = run_engine(model, params, shared_reqs, n_slots,
                               max_len, page_len=page_len,
                               kv_dtype="q8")
        first_kvq.setdefault("outs", outs)
        first_kvq.setdefault("rep", rep)
        return rep

    kvq_rep, kvq_st = measured_stats(
        kvq_once, ("ttft_ms_p50", "ttft_ms_p99"), warmup=warmup,
        trials=trials, absent_as_zero=())
    rec_q["arms"]["engine_paged_q8"] = kvq_rep
    f32_rep, f32_outs = run_engine(model, params, shared_reqs, n_slots,
                                   max_len, page_len=page_len)
    rec_q["arms"]["engine_paged_f32"] = f32_rep
    for k in ("ttft_ms_p50", "ttft_ms_p99"):
        rec_q["metrics"][f"serve_kvq_{k}"] = pbrecord.make_metric(
            None, "ms", stats=kvq_st[k], direction="lower")
    pq = first_kvq["rep"]["pages"]
    pf = f32_rep["pages"]
    # q4 rides along as pure storage math — same constructor, no run
    q4_bpt = PagedSlotPool(
        model, n_slots, max_len, page_len=page_len,
        n_pages=n_slots * (-(-max_len // page_len)),
        kv_dtype="q4").bytes_per_resident_token()
    capacity_x = (pf["bytes_per_resident_token"]
                  / pq["bytes_per_resident_token"])
    div = float(np.mean([a != b
                         for x, y in zip(f32_outs, first_kvq["outs"])
                         for a, b in zip(x, y)]))
    rec_q["metrics"]["serve_kvq_bytes_per_token_f32"] = \
        pbrecord.make_metric(round(pf["bytes_per_resident_token"], 2),
                             "bytes", direction="lower")
    rec_q["metrics"]["serve_kvq_bytes_per_token_q8"] = \
        pbrecord.make_metric(round(pq["bytes_per_resident_token"], 2),
                             "bytes", direction="lower")
    rec_q["metrics"]["serve_kvq_bytes_per_token_q4"] = \
        pbrecord.make_metric(round(q4_bpt, 2), "bytes",
                             direction="lower")
    rec_q["metrics"]["serve_kvq_pool_occupancy"] = pbrecord.make_metric(
        round(pq["pool_occupancy"], 4), "frac")
    rec_q["metrics"]["serve_kvq_prefix_hit_rate"] = pbrecord.make_metric(
        round(pq["prefix_hit_rate"] or 0.0, 4), "frac")
    rec_q["metrics"]["serve_kvq_page_evictions"] = pbrecord.make_metric(
        pq["evictions"], "count")
    rec_q["metrics"]["serve_kvq_token_divergence"] = \
        pbrecord.make_metric(round(div, 4), "frac", direction="lower")
    # the headline is a deterministic storage-layout ratio, not a
    # timing sample — no spread gate applies
    rec_q["value"] = round(capacity_x, 2)
    rec_q["provenance"] = "measured"
    rec_q["trusted"] = True
    rec_q.pop("untrusted_reason", None)
    rec_q["kv_pool_bytes"] = {"f32": pf["kv_pool_bytes"],
                              "q8": pq["kv_pool_bytes"]}

    if smoke:
        # the quantized-pool CI gates (tier1.yml): ~4x resident pages
        # per byte at q8, cold first tokens bit-exact (their prefill
        # attends in-register f32 — quantization cannot touch token 0
        # of a cold prompt), bounded divergence on the mixed
        # cold/shared stream, ONE decode program
        problems = []
        if not capacity_x >= 3.5:
            problems.append(f"q8 capacity {capacity_x:.2f}x < 3.5x "
                            f"resident pages per byte")
        if first_kvq["rep"]["stats"]["decode_compiles"] != 1:
            problems.append(
                f"q8 decode_compiles "
                f"{first_kvq['rep']['stats']['decode_compiles']} != 1")
        for i in range(k_prefixes):   # the cold (first-occurrence) reqs
            if f32_outs[i][0] != first_kvq["outs"][i][0]:
                problems.append(f"cold request {i} first token "
                                f"{first_kvq['outs'][i][0]} != exact "
                                f"{f32_outs[i][0]}")
        if not div <= 0.25:
            problems.append(f"q8 token divergence {div:.3f} > 0.25 on "
                            f"the shared-prefix population")
        if problems:
            print(json.dumps({"bench": "serve_kvq",
                              "error": "; ".join(problems)}))
            return 1
        rec_q["kvq_gates"] = {
            "capacity_x": round(capacity_x, 2),
            "cold_first_tokens_exact": True,
            "token_divergence": round(div, 4),
            "decode_compiles": 1}

    # ---- speculative decoding arm (serve/spec/) ----
    # the mixed greedy population through the paged engine with a
    # draft proposing k tokens per iteration vs the SAME engine
    # non-spec on the SAME closed-loop population: acceptance rate and
    # tokens/iteration are the speculation headline, TPOT p50/p99 ride
    # as gated medians, and the TPOT speedup is printed-or-withheld
    # per the spread gate. Smoke self-drafts (draft == target) so the
    # wiring/accounting gates are deterministic (acceptance 1.0 by
    # construction); real runs use a thin 1-layer draft so acceptance
    # is a measurement, not a tautology.
    draft_len = flag("--draft-len", 3)
    if smoke:
        draft_model, draft_params = model, params
    else:
        import jax
        from distributed_pytorch_tpu import models
        draft_model = models.TransformerLM(
            vocab=model.vocab, dim=max(16, model.dim // 4), n_layers=1,
            n_heads=2, n_kv_heads=1, pos="rope", max_seq=model.max_seq)
        draft_params = draft_model.init(jax.random.PRNGKey(11))
    rec_s = pbrecord.make_record("serve_spec_tpot_ms_p50", "ms",
                                 device="cpu-loopback")
    rec_s.update({"bench": "serve_spec", "smoke": smoke,
                  "config": dict(rec["config"], page_len=page_len,
                                 draft_len=draft_len,
                                 draft="self" if smoke else "thin-1l"),
                  "arms": {}})
    spec_keys = ("tpot_ms_p50", "tpot_ms_p99")
    first_spec = {}

    def spec_once():
        rep, souts = run_engine(model, params, mixed, n_slots, max_len,
                                page_len=page_len,
                                draft_model=draft_model,
                                draft_params=draft_params,
                                draft_len=draft_len)
        first_spec.setdefault("outs", souts)
        first_spec.setdefault("rep", rep)
        return rep

    spec_rep, spec_sts = measured_stats(spec_once, spec_keys,
                                        warmup=warmup, trials=trials,
                                        absent_as_zero=())
    rec_s["arms"]["engine_spec_closed"] = spec_rep
    nonspec_rep, nonspec_sts = measured_stats(
        lambda: run_engine(model, params, mixed, n_slots, max_len,
                           page_len=page_len)[0],
        spec_keys, warmup=warmup, trials=trials, absent_as_zero=())
    rec_s["arms"]["engine_nonspec_closed"] = nonspec_rep
    for k in spec_keys:
        rec_s["metrics"][f"serve_spec_{k}"] = pbrecord.make_metric(
            None, "ms", stats=spec_sts[k], direction="lower")
        rec_s["metrics"][f"serve_nonspec_{k}"] = pbrecord.make_metric(
            None, "ms", stats=nonspec_sts[k], direction="lower")
    sp_st = first_spec["rep"]["spec"]
    rec_s["acceptance_rate"] = round(sp_st["acceptance_rate"] or 0.0, 4)
    rec_s["tokens_per_iteration"] = round(
        sp_st["tokens_per_iteration"] or 0.0, 4)
    rec_s["metrics"]["serve_spec_acceptance_rate"] = \
        pbrecord.make_metric(rec_s["acceptance_rate"], "frac")
    rec_s["metrics"]["serve_spec_tokens_per_iteration"] = \
        pbrecord.make_metric(rec_s["tokens_per_iteration"], "tokens")
    rec_s["value"] = round(spec_sts["tpot_ms_p50"].median, 2)
    rec_s["provenance"] = "measured"
    rec_s["trusted"] = spec_sts["tpot_ms_p50"].trusted
    if rec_s["trusted"]:
        rec_s.pop("untrusted_reason", None)
    else:
        rec_s["untrusted_reason"] = \
            spec_sts["tpot_ms_p50"].untrusted_reason
    # TPOT is lower-better: > 1 means speculation beats plain decode
    # on wall-clock cadence, not just on tokens/iteration
    vs, why = pbstats.gated_ratio(nonspec_sts["tpot_ms_p50"],
                                  spec_sts["tpot_ms_p50"])
    if vs is not None:
        rec_s["vs_nonspec_tpot_p50_x"] = round(vs, 2)
    else:
        rec_s["vs_nonspec_tpot_p50_withheld"] = why

    if smoke:
        # the spec CI gates (tier1.yml): speculation must be invisible
        # (accepted greedy streams == standalone generate() bit-exact),
        # must actually accept on this self-draft workload, must keep
        # the one-verify-program-per-bucket discipline, and the spec
        # engine's own metrics log (snapshots carrying the serve.spec_*
        # gauges) must replay clean through tools/dpxmon.py
        import shutil
        import tempfile

        import jax
        import jax.numpy as jnp

        from benchmarks.soak import _run_cli
        from distributed_pytorch_tpu.models.generate import make_generate_fn
        from distributed_pytorch_tpu.utils.logging import MetricsLogger
        problems = []
        for i in (0, n_req // 2, n_req - 1):
            prompt, sp_i, key = mixed[i]
            ref = np.asarray(jax.jit(make_generate_fn(
                model, sp_i.max_new_tokens, max_len=max_len))(
                params, jnp.asarray(prompt[None]), key))[0]
            if not np.array_equal(first_spec["outs"][i], ref):
                problems.append(f"spec request {i} diverged from "
                                f"standalone generate()")
        if not (sp_st["acceptance_rate"] or 0.0) > 0:
            problems.append(f"acceptance rate "
                            f"{sp_st['acceptance_rate']} not > 0 "
                            f"under the self-draft")
        if sp_st["verify_compiles"] != {draft_len + 1: 1}:
            problems.append(f"verify compiles "
                            f"{sp_st['verify_compiles']} != "
                            f"{{{draft_len + 1}: 1}}")
        # record-schema gate: the full-size record must land on real
        # hardware with the speculation fields present and the speedup
        # ratio either printed or withheld-with-reason — never absent
        for field in ("acceptance_rate", "tokens_per_iteration"):
            if field not in rec_s:
                problems.append(f"spec record missing {field}")
        if (("vs_nonspec_tpot_p50_x" in rec_s)
                == ("vs_nonspec_tpot_p50_withheld" in rec_s)):
            problems.append(
                "spec record must carry exactly one of "
                "vs_nonspec_tpot_p50_x / vs_nonspec_tpot_p50_withheld")
        workdir = tempfile.mkdtemp(prefix="dpx_spec_smoke_")
        log = os.path.join(workdir, "spec_metrics.jsonl")
        run_engine(model, params, mixed, n_slots, max_len,
                   page_len=page_len, draft_model=draft_model,
                   draft_params=draft_params, draft_len=draft_len,
                   metrics=MetricsLogger(log), log_every=2)
        rc, out_cli = _run_cli("tools.dpxmon", ["replay", log])
        if rc != 0:
            problems.append(f"dpxmon replay over the spec log exited "
                            f"{rc}: {out_cli.strip()[-200:]}")
        shutil.rmtree(workdir, ignore_errors=True)
        if problems:
            print(json.dumps({"bench": "serve_spec",
                              "error": "; ".join(problems)}))
            return 1
        rec_s["spec_gates"] = {
            "engine_matches_generate": True,
            "acceptance_rate": rec_s["acceptance_rate"],
            "tokens_per_iteration": rec_s["tokens_per_iteration"],
            "verify_compiles": {str(k): v for k, v
                                in sp_st["verify_compiles"].items()},
            "dpxmon_replay_rc": rc}

    # ---- multi-replica fleet arm (serve/fleet/) ----
    # the shared-prefix population through the prefix-affine fleet at
    # R=1, 2, 4 replicas on the SAME seeded Poisson arrivals: tokens/s
    # and TTFT p50/p99 as gated medians per R, the scaling ratios
    # printed-or-withheld per the spread gate. On one CPU host the
    # replicas contend for the same cores, so a flat/withheld ratio is
    # the honest outcome; the record is the methodology rail for a
    # real multi-host run. Smoke runs skip this arm — the dedicated
    # --fleet-smoke CI step owns the fleet correctness gates.
    rec_f = None
    if not smoke:
        fleet_rs = (1, 2, 4)
        rec_f = pbrecord.make_record("serve_fleet_tokens_per_sec",
                                     "tokens_per_sec",
                                     device="cpu-loopback")
        rec_f.update({"bench": "serve_fleet", "smoke": smoke,
                      "config": dict(rec["config"], page_len=page_len,
                                     fleet_replicas=list(fleet_rs)),
                      "arms": {}})
        fleet_sts = {}
        fkeys = ("tokens_per_sec", "ttft_ms_p50", "ttft_ms_p99")
        for r in fleet_rs:
            rep_r, sts_r = measured_stats(
                lambda r=r: run_fleet(model, params, shared_reqs, r,
                                      n_slots, max_len, rate=rate,
                                      seed=seed + 4,
                                      page_len=page_len)[0],
                fkeys, warmup=warmup, trials=trials, absent_as_zero=())
            rec_f["arms"][f"fleet_r{r}_open"] = rep_r
            fleet_sts[r] = sts_r
            for k in fkeys:
                rec_f["metrics"][f"serve_fleet_r{r}_{k}"] = \
                    pbrecord.make_metric(
                        None,
                        "tokens_per_sec" if k == "tokens_per_sec"
                        else "ms", stats=sts_r[k],
                        direction="higher" if k == "tokens_per_sec"
                        else "lower")
        top = fleet_sts[fleet_rs[-1]]["tokens_per_sec"]
        rec_f["value"] = round(top.median, 2)
        rec_f["provenance"] = "measured"
        rec_f["trusted"] = top.trusted
        if top.trusted:
            rec_f.pop("untrusted_reason", None)
        else:
            rec_f["untrusted_reason"] = top.untrusted_reason
        for r in fleet_rs[1:]:
            vs, why = pbstats.gated_ratio(
                fleet_sts[r]["tokens_per_sec"],
                fleet_sts[1]["tokens_per_sec"])
            if vs is not None:
                rec_f[f"vs_single_replica_r{r}_x"] = round(vs, 2)
            else:
                rec_f[f"vs_single_replica_r{r}_x_withheld"] = why

    issues = pbrecord.validate_record(rec, strict=False)
    if issues:
        rec["schema_issues"] = issues
        print(f"# WARNING: serve record failed schema self-validation: "
              f"{'; '.join(issues[:3])}", file=sys.stderr)
    print(json.dumps(rec))
    issues = pbrecord.validate_record(rec_d, strict=False)
    if issues:
        rec_d["schema_issues"] = issues
        print(f"# WARNING: disagg record failed schema self-validation: "
              f"{'; '.join(issues[:3])}", file=sys.stderr)
    print(json.dumps(rec_d))
    issues = pbrecord.validate_record(rec_q, strict=False)
    if issues:
        rec_q["schema_issues"] = issues
        print(f"# WARNING: kvq record failed schema self-validation: "
              f"{'; '.join(issues[:3])}", file=sys.stderr)
    print(json.dumps(rec_q))
    issues = pbrecord.validate_record(rec_s, strict=False)
    if issues:
        rec_s["schema_issues"] = issues
        print(f"# WARNING: spec record failed schema self-validation: "
              f"{'; '.join(issues[:3])}", file=sys.stderr)
    print(json.dumps(rec_s))
    if rec_f is not None:
        issues = pbrecord.validate_record(rec_f, strict=False)
        if issues:
            rec_f["schema_issues"] = issues
            print(f"# WARNING: fleet record failed schema "
                  f"self-validation: {'; '.join(issues[:3])}",
                  file=sys.stderr)
        print(json.dumps(rec_f))
    if not smoke and dpxenv.get("DPX_BENCH_SELFLOG"):
        # real (non-CI) runs land in the trajectory store so the
        # shared-prefix TTFT numbers join the BENCH record trail
        store = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tpu_results.jsonl")
        pbrecord.append_row(store, "serve_shared", rec)
        pbrecord.append_row(store, "serve_disagg", rec_d)
        pbrecord.append_row(store, "serve_kvq", rec_q)
        pbrecord.append_row(store, "serve_spec", rec_s)
        if rec_f is not None:
            pbrecord.append_row(store, "serve_fleet", rec_f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
