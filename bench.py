"""Headline benchmark — a thin shim over ``distributed_pytorch_tpu.perfbench``.

Prints ONE schema-validated JSON line (``perfbench/record.py``,
``docs/benchmarking.md``):

  {"schema": "dpx.bench.record", "metric": ..., "value": N, ...}

Three measurements, most important first:

1. **Flagship MFU** (the headline ``value``): TransformerLM, ~135M params,
   bf16, flash attention, seq 1024, trained single-chip. ``value`` is the
   MFU fraction = achieved model FLOP/s / chip peak bf16 FLOP/s
   (benchmarks/mfu_transformer.py). The reference cannot run this model at
   all; ``vs_baseline`` is our tokens/s over eager-torch-CPU tokens/s on
   the same model — the only measurable torch baseline in this
   environment (torch has no TPU backend here).
2. **min_ddp metric** (``min_ddp`` field): the reference's implicit
   benchmark (MLP 1->32->4, batch 8, reference min_DDP.py:44-48).
3. **world-8 DP step** (``dp8`` field): the same min_ddp train step on an
   8-device virtual CPU mesh (subprocess), so collective overhead is
   measured at all. steps/s on 8 CPU devices, global batch 64.

The statistical policy is perfbench's, end to end: warmup-discarded
repeated trials, median + IQR, the hard spread gate (``DPX_BENCH_MAX_
SPREAD``) that structurally withholds ``vs_baseline``, and the roofline
plausibility gate. When the backend probe finds no TPU the record
carries the newest verified on-chip number as an explicit ``last_good``
carry-forward with provenance — a metric is never null
(perfbench/trajectory.py); before falling back to a
carry-forward, a no-TPU container measures the pinned HOST flagship
arm against a calibrated host peak (``mfu_host`` stage,
docs/compute.md) so the headline stays a fresh gated measurement.
``--smoke`` runs the CPU-gated perfbench smoke (CI: the bench-smoke
job); ``--headline`` measures and lands ONLY the flagship headline.

One process per chip: this parent never initializes a JAX backend. The
backend probe and every measurement stage run in children, one at a
time (perfbench/runner.py), each taking the chip and releasing it on
exit. On a stage failure the script still prints a parseable JSON record
with an ``error`` field and whatever measurements did succeed (rc stays
0 so the record is recorded).
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

try:
    from distributed_pytorch_tpu.perfbench import (record as _record,
                                                   roofline_gate,
                                                   runner as _runner,
                                                   stats as _stats,
                                                   trajectory as _trajectory)
    from distributed_pytorch_tpu.runtime import env as _env
except Exception as e:  # noqa: BLE001 — the record contract survives even this
    # the parseable-record exit is for DIRECT invocation only (incl.
    # --stage children): a library importer (mfu_transformer,
    # step_breakdown, decode_tpu) must see the real ImportError, not
    # have its process killed rc-0 behind a flagship-metric error line
    if __name__ != "__main__":
        raise
    print(json.dumps({"metric": "transformer_lm_mfu_single_chip",
                      "unit": "mfu_fraction",
                      "error": f"perfbench import failed: "
                               f"{type(e).__name__}: {e}"}))
    # rc 0 keeps the record-emission contract for the collector — but
    # --smoke is a CI GATE, and a gate that never ran must not pass
    raise SystemExit(1 if "--smoke" in sys.argv[1:] else 0)

BATCH = 8
HIDDEN = 32
N_CLASSES = 4
DATA_SIZE = 32

HEADLINE_METRIC = _trajectory.FLAGSHIP_METRIC

# compat re-exports: the plumbing's canonical home is perfbench.runner
probe_backend = _runner.probe_backend
progress = _runner.progress
arm = _runner.arm
run_json_subprocess = _runner.run_json_subprocess

RESULTS_LOG = os.path.join(REPO, "benchmarks", "tpu_results.jsonl")


def append_result(stage: str, result: dict, *, ok: bool = None,
                  wall_s: float = None) -> None:
    """Append one raw benchmark record to the trajectory store as a
    {stage, ok, wall_s, result, ts} row, through perfbench's thread-safe
    append path. Every run leaves a raw-JSON trace: numbers that live
    only in prose cannot be checked."""
    if not _record.append_row(RESULTS_LOG, stage, result, ok=ok,
                              wall_s=wall_s):
        print(f"# could not append to {RESULTS_LOG}", file=sys.stderr)


def last_good_record() -> dict:
    """Newest non-retracted, actually-measured flagship record from the
    trajectory store (perfbench/trajectory.py) — the carry-forward
    source for a run that finds no TPU."""
    return _trajectory.last_good_flagship(RESULTS_LOG)


def attach_roofline(rec: dict) -> None:
    """The analytic roofline travels WITH the headline (perfbench/
    roofline_gate.py): floors, the overlap/no-overlap MFU ceilings,
    achieved/ceiling, and the plausibility gate."""
    roofline_gate.attach_flagship(rec)


def _run_stage(stage: str, timeout_s: int) -> dict:
    """Re-invoke this script for one measurement stage in a subprocess
    with a hard timeout — the child owns the chip while it runs, and
    the parseable-JSON-on-failure contract must survive a hang."""
    return run_json_subprocess(
        [sys.executable, os.path.abspath(__file__), "--stage", stage],
        timeout_s, label=f"stage {stage}")


# ---------------------------------------------------------------------------
# measurement 2: the reference's implicit benchmark (min_ddp MLP)
# ---------------------------------------------------------------------------


def _batches(n_steps: int, seed: int = 0):
    import numpy as np
    from distributed_pytorch_tpu.data import DummyDataset
    ds = DummyDataset(DATA_SIZE, N_CLASSES, seed=seed)
    xs, ys = [], []
    for t in range(n_steps):
        idx = np.arange(t * BATCH, (t + 1) * BATCH) % DATA_SIZE
        xs.append(ds.data[idx])
        ys.append(ds.labels[idx])
    return np.stack(xs), np.stack(ys)


def bench_min_ddp(n_steps: int = 2000, fused_chunk: int = 100) -> dict:
    import jax
    import jax.numpy as jnp
    from distributed_pytorch_tpu import models, optim
    from distributed_pytorch_tpu.ops.losses import cross_entropy
    from distributed_pytorch_tpu.parallel import (make_scan_train_steps,
                                                  make_train_step)

    model = models.DummyModel(in_dim=1, hidden_dim=HIDDEN,
                              n_classes=N_CLASSES)
    params = model.init(jax.random.PRNGKey(0))
    opt = optim.adamw(1e-4)
    opt_state = opt.init(params)

    def loss_fn(p, batch):
        x, y = batch
        return cross_entropy(model.apply(p, x), y), {}

    xs, ys = _batches(fused_chunk)
    xs, ys = jnp.asarray(xs), jnp.asarray(ys)

    # All fences below are HOST MATERIALIZATIONS (np.asarray of a scalar):
    # a fetch cannot complete before the value exists, and chaining steps
    # through params makes the final fetch wait for the whole run.
    from distributed_pytorch_tpu.utils.profiler import (fetch_fence,
                                                        time_steps_amortized)

    # per-step path FIRST (the honest number for the reference's per-step
    # semantics): one jitted call per step, chained; one fetch at the end.
    step = make_train_step(loss_fn, opt, donate=False)
    b0 = (xs[0], ys[0])
    out = step(params, opt_state, b0)
    fetch_fence(out.loss)
    m = min(n_steps, 500)
    s_per_step, out = time_steps_amortized(
        lambda o: step(o.params, o.opt_state, b0), out, m,
        lambda o: o.loss)
    per_step_sps = 1.0 / s_per_step

    # per-step latency with the loss materialized on the host EVERY step
    # (the reference's literal eager semantics, min_DDP.py:110-130) — one
    # device-to-host round trip per step; reported separately.
    t0 = time.perf_counter()
    for _ in range(20):
        out = step(out.params, out.opt_state, b0)
        fetch_fence(out.loss)
    eager_sps = 20 / (time.perf_counter() - t0)

    # scan-fused fast path (different semantics: no per-step host visibility)
    run = make_scan_train_steps(loss_fn, opt, n_steps=fused_chunk)
    p2, o2, losses = run(params, opt_state, (xs, ys))
    fetch_fence(losses)
    n_calls = max(n_steps // fused_chunk, 1)
    t0 = time.perf_counter()
    p, o = p2, o2
    for _ in range(n_calls):
        p, o, losses = run(p, o, (xs, ys))
    fetch_fence(losses)
    fused_sps = n_calls * fused_chunk / (time.perf_counter() - t0)

    return {"steps_per_sec": round(per_step_sps, 1),
            "per_step_host_loss_steps_per_sec": round(eager_sps, 1),
            "fused_steps_per_sec": round(fused_sps, 1),
            "timing_method": "chained dispatch, host-fetch fence"}


def _baseline_detail(st: "_stats.TrialStats", key: str) -> dict:
    """Legacy-shaped baseline detail (median under ``key``, runs under
    ``runs_<key>``).  No ``trials`` dict here: the full perfbench blob
    for the same stats lands exactly once, under ``metrics`` — two
    copies in one appended line double store growth and can silently
    diverge."""
    return {key: round(st.median, 1),
            f"runs_{key}": [round(r, 1) for r in st.runs],
            "spread_frac": round(st.spread_frac, 3),
            "range_frac": round(st.range_frac, 3),
            "trusted": st.trusted,
            **({"untrusted_reason": st.untrusted_reason}
               if st.untrusted_reason else {})}


def bench_torch_cpu_mlp(n_steps: int = 500) -> "_stats.TrialStats":
    """Measured baseline: the reference's workload in eager torch on this
    host's CPU (the reference's world<=1 branch runs exactly this,
    reference distributed.py:54-58). Thread-pinned; trials/warmup/gate
    from the perfbench policy — consumers withhold ratios when the
    stats come back untrusted."""
    import torch
    import torch.nn as nn
    from distributed_pytorch_tpu.data import DummyDataset

    _stats.pin_torch_threads(torch)
    torch.manual_seed(0)
    model = nn.Sequential(nn.Linear(1, HIDDEN), nn.Linear(HIDDEN, N_CLASSES))
    opt = torch.optim.AdamW(model.parameters(), 1e-4)
    crit = nn.CrossEntropyLoss()
    ds = DummyDataset(DATA_SIZE, N_CLASSES)
    x = torch.tensor(ds.data[:BATCH])
    y = torch.tensor(ds.labels[:BATCH]).long()
    for _ in range(20):
        opt.zero_grad(); crit(model(x), y).backward(); opt.step()

    def one_run():
        t0 = time.perf_counter()
        for _ in range(n_steps):
            opt.zero_grad()
            loss = crit(model(x), y)
            loss.backward()
            opt.step()
        return n_steps / (time.perf_counter() - t0)

    return _stats.measure(one_run)


def bench_torch_cpu_lm(batch=2, n_steps=2) -> "_stats.TrialStats":
    """tokens/s for the flagship LM config in eager torch CPU — the
    vs_baseline denominator for the MFU headline. The model config comes
    from benchmarks.mfu_transformer.FLAGSHIP (single source of truth);
    only batch is reduced — CPU throughput is ~flat in batch and a full
    flagship batch takes minutes per step here. Thread-pinned;
    trials/warmup/gate from the perfbench policy (round-3 runs varied
    +/-46% under host contention; r05's 70% spread forced the harness
    to withhold vs_baseline — the gate now does that structurally)."""
    import torch
    import torch.nn as nn

    from benchmarks.mfu_transformer import FLAGSHIP
    _stats.pin_torch_threads(torch)
    dim, n_layers, n_heads = (FLAGSHIP["dim"], FLAGSHIP["n_layers"],
                              FLAGSHIP["n_heads"])
    vocab, seq = FLAGSHIP["vocab"], FLAGSHIP["seq"]
    torch.manual_seed(0)
    layer = nn.TransformerEncoderLayer(
        dim, n_heads, 4 * dim, batch_first=True, norm_first=True,
        activation="gelu")
    enc = nn.TransformerEncoder(layer, n_layers)
    emb = nn.Embedding(vocab, dim)
    head = nn.Linear(dim, vocab, bias=False)
    params = (list(enc.parameters()) + list(emb.parameters())
              + list(head.parameters()))
    opt = torch.optim.AdamW(params, 3e-4)
    crit = nn.CrossEntropyLoss()
    mask = nn.Transformer.generate_square_subsequent_mask(seq)
    tokens = torch.randint(0, vocab, (batch, seq + 1))

    def one_step():
        opt.zero_grad()
        h = emb(tokens[:, :-1])
        h = enc(h, mask=mask, is_causal=True)
        loss = crit(head(h).reshape(-1, vocab),
                    tokens[:, 1:].reshape(-1))
        loss.backward()
        opt.step()

    def one_run():
        t0 = time.perf_counter()
        for _ in range(n_steps):
            one_step()
        return n_steps * batch * seq / (time.perf_counter() - t0)

    return _stats.measure(one_run)


# ---------------------------------------------------------------------------
# measurement 3: world-8 DP step on the virtual CPU mesh (subprocess —
# platform selection must happen before backend init)
# ---------------------------------------------------------------------------

def _dp8_code(n_steps: int = 15, min_trial_s: float = 1.0,
              budget_s: float = None) -> str:
    """The dp8 child program. Statistical policy comes from perfbench:
    process affinity pinned (r05 variance source: thread migration),
    warmup discard (r05: 621.6 cold vs ~900 warm steps/s), median + IQR
    + the spread gate.  Two further defenses against THIS container's
    noise structure (2 visible cores, /proc/stat fully masked, available
    CPU swinging 2x over tens of seconds as invisible neighbors come and
    go):

    * each trial's sample is the PEAK ``n_steps``-chunk rate inside a
      >= ``min_trial_s`` window (the min-timing technique, as in
      timeit): external preemption only ever subtracts throughput, so
      the best ~25 ms chunk estimates the uncontended rate and is the
      run-to-run comparable number — the mean rate of the same windows
      measured 18-49%% spread here, the peak-chunk rate 5%%;
    * aggregation is ``stats.measure_until``: a sliding window over
      trials that returns the first gate-passing stationary window
      within ``budget_s``, so a neighbor-load mode switch mid-run ages
      out of the window instead of poisoning the whole estimate.

    The sustained (mean) rate of the final window is reported alongside
    as ``sustained_steps_per_sec`` — on a quiet host the two agree; a
    large gap is a contention fingerprint, not a speedup."""
    if budget_s is None:
        # resolved HERE so the documented env knob actually governs the
        # generated child (the child inherits the parent's environment)
        budget_s = float(_env.get("DPX_BENCH_BUDGET_S"))
    return r"""
import json, time
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
import jax.numpy as jnp
import numpy as np
import distributed_pytorch_tpu as dist
from distributed_pytorch_tpu import models, optim
from distributed_pytorch_tpu.ops.losses import cross_entropy
from distributed_pytorch_tpu.parallel import make_train_step
from distributed_pytorch_tpu.perfbench import record as pbrecord
from distributed_pytorch_tpu.perfbench import stats as pbstats

# one CPU per virtual device, deterministic placement across runs
# (count from DPX_BENCH_AFFINITY — 0 disables pinning)
pbstats.pin_process()

dist.init_process_group(rank=0, world_size=8)
model = models.DummyModel(in_dim=1, hidden_dim=32, n_classes=4)
params = model.init(jax.random.PRNGKey(0))
opt = optim.adamw(1e-4)
opt_state = opt.init(params)

def loss_fn(p, batch):
    x, y = batch
    return cross_entropy(model.apply(p, x), y), {}

step = make_train_step(loss_fn, opt, donate=False)
x = dist.shard_batch(np.arange(64, dtype=np.float32)[:, None])
y = dist.shard_batch(np.zeros(64, dtype=np.int32))
out = step(params, opt_state, (x, y))
jax.block_until_ready(out.loss)
# fence every step: on a small host the 8-way rendezvous aborts if many
# async steps pile up (and the reference's workload materializes loss
# per step anyway, so the fenced number is the semantically right one).
n = %(n_steps)d
min_s = %(min_trial_s)f
state = {"out": out, "sustained": 0.0}

def one_trial():
    o = state["out"]
    best = 0.0
    steps = 0
    t0 = time.perf_counter()
    while True:
        c0 = time.perf_counter()
        for _ in range(n):
            o = step(o.params, o.opt_state, (x, y))
            jax.block_until_ready(o.loss)
        c1 = time.perf_counter()
        best = max(best, n / (c1 - c0))
        steps += n
        if c1 - t0 >= min_s:
            break
    state["out"] = o
    state["sustained"] = steps / (time.perf_counter() - t0)
    return best

st = pbstats.measure_until(one_trial, budget_s=%(budget_s)f)
blob = pbrecord.make_metric(None, "steps_per_sec", stats=st)
print(json.dumps({"steps_per_sec": round(st.median, 1),
                  "sustained_steps_per_sec": round(state["sustained"], 1),
                  "runs_steps_per_sec": [round(r, 1) for r in st.runs],
                  "spread_frac": round(st.spread_frac, 3),
                  "trusted": st.trusted,
                  "timing_method": "peak %(n_steps)d-step-chunk rate "
                                   "per >=%(min_trial_s).0fs window, "
                                   "stationary-window aggregation",
                  "metric_blob": blob,
                  "world": 8, "global_batch": 64}))
""" % {"n_steps": n_steps, "min_trial_s": min_trial_s,
       "budget_s": budget_s}


# 32 MiB f32 gradient bucket: big enough that the ring is bandwidth-
# bound even on loopback (real DDP buckets are tens of MB — ResNet-50's
# full gradient is ~98 MB), which is the regime the quantized wire is
# for; at a few MiB the 8-process mesh is scheduling-latency-bound and
# wire width barely matters.
COMM_BUCKET_ELEMS = 1 << 23
COMM_WORLD = 8
COMM_REPS = 6


def _dp8_comm_worker(rank, world, q, n_elems, reps, runs):
    """Host-ring comm microbench worker: the same flat gradient bucket
    allreduced over the native TCP ring, f32 wire vs quantized (block
    int8) wire. Barrier-fenced so every timed window measures all
    ranks' slowest path; rank 0 reports."""
    import numpy as np

    import distributed_pytorch_tpu as dist
    from distributed_pytorch_tpu.runtime import context

    dist.init_process_group(rank, world)
    comm = context.get_host_comm()
    try:
        rng = np.random.default_rng(rank)
        x = rng.standard_normal(n_elems).astype(np.float32)

        def timed(op):
            samples = []
            for _ in range(runs):
                comm.barrier()
                t0 = time.perf_counter()
                for _ in range(reps):
                    op(x.copy())
                comm.barrier()
                samples.append(reps / (time.perf_counter() - t0))
            samples.sort()
            return samples[len(samples) // 2], samples

        # one untimed warm rep each (socket buffers, allocator)
        comm.allreduce(x.copy())
        comm.allreduce_q8(x.copy())
        f32_sps, f32_runs = timed(comm.allreduce)
        q_sps, q_runs = timed(comm.allreduce_q8)
        if rank == 0:
            from distributed_pytorch_tpu.comm import wire
            q.put({
                "comm_world": world,
                "comm_bucket_mb": round(n_elems * 4 / (1 << 20), 2),
                # per-rank wire payload of ONE allreduce of the bucket
                "comm_bytes": wire.quant_ring_allreduce_wire_bytes(
                    n_elems, world) // world,
                "comm_f32_bytes": wire.ring_allreduce_wire_bytes(
                    n_elems, world) // world,
                "comm_quant_steps_per_sec": round(q_sps, 2),
                "comm_f32_steps_per_sec": round(f32_sps, 2),
                "comm_runs": {"f32": [round(r, 2) for r in f32_runs],
                              "quant": [round(r, 2) for r in q_runs]},
            })
    finally:
        dist.cleanup()


def bench_dp8_comm() -> dict:
    """8-process native-ring gradient-bucket allreduce: f32 vs quantized
    wire, reported into the dp8 record (comm_bytes /
    comm_quant_steps_per_sec acceptance fields)."""
    import multiprocessing as mp

    from distributed_pytorch_tpu.runtime.multiprocess import (
        launch_multiprocess)

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    launch_multiprocess(_dp8_comm_worker, COMM_WORLD, q,
                        COMM_BUCKET_ELEMS, COMM_REPS, 5)
    return q.get(timeout=60)


# sharded-update flagship arm: big enough that the update compute and
# optimizer state are meaningful (16 MiB f32 bucket ~ a real DDP
# bucket), small enough that the 8-process loopback arm stays
# seconds-scale in the CI smoke
SHARDED_BUCKET_ELEMS = 1 << 22

# hierarchical/adaptive flagship arm: same sizing logic (16 MiB f32
# bucket); the topology is 4 "hosts" x 2 ranks on loopback — the slow
# hop is emulated, so the honest headline is the BYTE accounting (q4 >=
# 6.5x vs f32, slow-hop bytes 1/local_world of flat) plus the measured
# exposed_ms drop; steps/s vs_q8 is reported gated like every ratio
HIER_BUCKET_ELEMS = 1 << 22
HIER_LOCAL_WORLD = 2


def _dp8_sharded_worker(rank, world, q, n_elems, reps, runs):
    """dp8_sharded_adam flagship arm worker: the SAME flat gradient
    bucket driven through (a) the replicated update — quantized ring
    allreduce + full-bucket AdamW on every rank — and (b) the ZeRO-1
    sharded update (optim/sharded/): EF + reduce_scatter_q8 + AdamW on
    the owned 1/world slice + allgather_q8. Each trial's sample is the
    PEAK barrier-fenced ``reps``-step chunk rate over a FIXED number of
    chunks (the dp8 min-timing defense against this container's
    neighbor noise — preemption only ever subtracts throughput; the
    chunk count is fixed, not wall-clock-driven, so every rank runs the
    identical collective schedule and the ring cannot deadlock on a
    diverging loop exit); rank 0 reports the median of trials, measured
    wire bytes (CommStats vs the wire.py accounting), blocking comm ms,
    and per-rank optimizer-state bytes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import distributed_pytorch_tpu as dist
    from distributed_pytorch_tpu import optim
    from distributed_pytorch_tpu.comm import wire
    from distributed_pytorch_tpu.ops.quant import ErrorFeedback
    from distributed_pytorch_tpu.optim.sharded import (build_layout,
                                                       shard_optimizer)
    from distributed_pytorch_tpu.runtime import context

    dist.init_process_group(rank, world)
    comm = context.get_host_comm()
    try:
        rng = np.random.default_rng(rank)
        params = np.zeros(n_elems, np.float32)
        g = (rng.standard_normal(n_elems) * 1e-2).astype(np.float32)
        opt = optim.adamw(1e-3)
        layout = build_layout(params, world)
        sharded = shard_optimizer(opt, layout)
        n = layout.n_padded
        lo, hi = layout.span(layout.ring_segment(rank))

        # BOTH arms run on the padded bucket (n may exceed n_elems when
        # the knob isn't a world*block multiple) — the replicated arm
        # must update the same element count it allreduces
        rep = {"params": jnp.asarray(layout.flatten_np(params)),
               "state": opt.init(jnp.asarray(layout.flatten_np(params)))}
        upd_full = jax.jit(opt.update)
        sh = {"state": sharded.init_slice(params, rank)}
        upd_slice = jax.jit(sharded.update_flat)
        # one EF residual per arm: the production replicated quant path
        # (parallel/data_parallel._make_host_train_step) compensates its
        # bucket too, so both arms pay the same codec-side work and the
        # ratio compares the update strategies, not EF-vs-no-EF
        ef = ErrorFeedback()
        rep_ef = ErrorFeedback()
        gbuf = layout.flatten_np(g)

        def rep_step():
            flat = rep_ef.compensate(gbuf)
            comm.allreduce_q8(flat)
            new_p, rep["state"] = upd_full(jnp.asarray(flat / world),
                                           rep["state"], rep["params"])
            rep["params"] = jax.block_until_ready(new_p)

        def sh_step():
            flat = ef.compensate(gbuf)
            comm.reduce_scatter_q8(flat)
            new_master, sh["state"] = upd_slice(
                jnp.asarray(flat[lo:hi] / world), sh["state"])
            flat[lo:hi] = np.asarray(jax.block_until_ready(new_master))
            comm.allgather_q8(flat)

        CHUNKS = 3

        def timed(fn):
            samples = []
            for _ in range(runs):
                best = 0.0
                for _ in range(CHUNKS):
                    comm.barrier()
                    t0 = time.perf_counter()
                    for _ in range(reps):
                        fn()
                    comm.barrier()
                    best = max(best, reps / (time.perf_counter() - t0))
                samples.append(best)
            samples.sort()
            return samples[len(samples) // 2], samples

        rep_step()
        sh_step()  # warm: compile, sockets, allocator
        comm.stats.reset()
        rep_sps, rep_runs = timed(rep_step)
        rep_stats = comm.stats.summary()
        comm.stats.reset()
        sh_sps, sh_runs = timed(sh_step)
        sh_stats = comm.stats.summary()

        if rank == 0:
            nsteps = runs * CHUNKS * reps
            leg = wire.quant_leg_wire_bytes(n, world) // world
            blocking = lambda s: sum(d["seconds"] for d in s.values())
            # per-rank optimizer bytes: replicated holds 2 full f32
            # moments; sharded holds 2 moments + the exact master on
            # 1/world of the bucket
            rep_opt_bytes = 2 * 4 * n
            sh_opt_bytes = 3 * 4 * layout.seg
            q.put({
                "sharded_world": world,
                "sharded_bucket_mb": round(n * 4 / (1 << 20), 2),
                "sharded_steps_per_sec": round(sh_sps, 2),
                "replicated_steps_per_sec": round(rep_sps, 2),
                "sharded_runs": {
                    "sharded": [round(r, 2) for r in sh_runs],
                    "replicated": [round(r, 2) for r in rep_runs]},
                # per-rank wire payload of ONE step: what CommStats
                # accounted across the run vs the per-step expectation.
                # This pins the runtime's per-op accounting (op counts,
                # n, world, block) against the wire.py formula — NOT a
                # socket-level byte count; that the formula describes
                # the actual framed bytes is pinned separately by the
                # native-vs-numpy-spec bit-parity tests
                "sharded_wire_bytes": (sh_stats["reduce_scatter"]["bytes"]
                                       + sh_stats["allgather"]["bytes"])
                // nsteps,
                "sharded_wire_bytes_expected": 2 * leg,
                "replicated_wire_bytes":
                    rep_stats["allreduce_q8"]["bytes"] // nsteps,
                "replicated_f32_wire_bytes":
                    wire.ring_allreduce_wire_bytes(n, world) // world,
                "sharded_blocking_ms_per_step": round(
                    1000 * blocking(sh_stats) / nsteps, 3),
                "replicated_blocking_ms_per_step": round(
                    1000 * blocking(rep_stats) / nsteps, 3),
                "sharded_opt_state_bytes_per_rank": sh_opt_bytes,
                "replicated_opt_state_bytes_per_rank": rep_opt_bytes,
                "opt_state_shrink": round(rep_opt_bytes / sh_opt_bytes,
                                          2),
            })
    finally:
        dist.cleanup()


def _dp8_hier_worker(rank, world, q, n_elems, reps, runs):
    """dp8_hier_adaptive flagship arm worker. Three measurements on the
    SAME gradient bucket over the 8-process native group:

    (a) paired A/B: flat q8 ring vs the two-level ring with the
        adaptive width chooser (4 hosts x 2 ranks emulated on
        loopback), peak barrier-fenced chunk rates like the sharded
        arm — rank 0 reports both run lists so vs_q8 goes through the
        perfbench spread gate;
    (b) byte accounting: a flat q4 allreduce's CommStats bytes vs the
        wire.py formula vs the f32 ring formula (the >= 6.5x smoke
        assert), and the hier arm's slow-hop bytes vs its formula given
        the widths the chooser actually picked;
    (c) overlap: the real host train step (small MLP) with the bucketed
        overlap OFF then ON — CommStats exposed_ms/overlapped_ms per
        step both ways (the measured hidden fraction)."""
    import jax
    import numpy as np

    import distributed_pytorch_tpu as dist
    from distributed_pytorch_tpu import models, optim
    from distributed_pytorch_tpu.comm import wire
    from distributed_pytorch_tpu.comm.hier import hier_ring
    from distributed_pytorch_tpu.ops.losses import cross_entropy
    from distributed_pytorch_tpu.ops.quant import ErrorFeedback
    from distributed_pytorch_tpu.parallel import make_train_step
    from distributed_pytorch_tpu.runtime import context

    dist.init_process_group(rank, world)
    comm = context.get_host_comm()
    try:
        local_world = HIER_LOCAL_WORLD
        ring = hier_ring(comm, local_world)
        nh = world // local_world
        rng = np.random.default_rng(rank)
        g = (rng.standard_normal(n_elems) * 1e-2).astype(np.float32)

        ef_q8, ef_q4, ef_hier = (ErrorFeedback(), ErrorFeedback(),
                                 ErrorFeedback())
        chooser = wire.WidthChooser()

        def q8_step():
            comm.allreduce_q8(ef_q8.compensate(g))

        def q4_step():
            comm.allreduce_q4(ef_q4.compensate(g, bits=4))

        def hier_step():
            bits = chooser.width
            flat = ef_hier.compensate(g, bits=bits)
            ring.allreduce(flat, bits=bits)
            chooser.observe(flat)

        CHUNKS = 3

        def timed(fn):
            samples = []
            for _ in range(runs):
                best = 0.0
                for _ in range(CHUNKS):
                    comm.barrier()
                    t0 = time.perf_counter()
                    for _ in range(reps):
                        fn()
                    comm.barrier()
                    best = max(best, reps / (time.perf_counter() - t0))
                samples.append(best)
            samples.sort()
            return samples[len(samples) // 2], samples

        # warm (sockets, EF residuals, chooser ramps past hysteresis)
        for _ in range(3):
            q8_step(); q4_step(); hier_step()

        comm.stats.reset()
        q8_sps, q8_runs = timed(q8_step)
        q8_stats = comm.stats.summary()
        comm.stats.reset()
        q4_sps, q4_runs = timed(q4_step)
        q4_stats = comm.stats.summary()
        comm.stats.reset()
        w0 = len(chooser.widths)
        hier_sps, hier_runs = timed(hier_step)
        hier_stats = comm.stats.summary()
        hier_widths = chooser.widths[w0:]

        # (c) overlap: the actual host train step, bucketed, on an MLP
        # sized so each bucket's REPLICATED AdamW update is real device
        # work (~2M params -> ~4ms/bucket) — that update, dispatched
        # async, is what the next bucket's ring traffic hides behind
        # (one fused backward delivers all grads atomically, so there
        # is no later-layer backward to overlap; the is_ready-measured
        # accounting in parallel/data_parallel.py would book ZERO
        # overlap for a too-small model, honestly)
        model = models.DummyModel(in_dim=1024, hidden_dim=2048,
                                  n_classes=16)
        params = model.init(jax.random.PRNGKey(0))
        opt = optim.adamw(1e-3)

        def loss_fn(p, batch):
            x, y = batch
            return cross_entropy(model.apply(p, x), y), {}

        xb = rng.standard_normal((8, 1024)).astype(np.float32)
        yb = (np.arange(8) % 16).astype(np.int32)

        def run_overlap(on):
            step = make_train_step(loss_fn, opt, donate=False,
                                   grad_reduce="quant", overlap=on,
                                   comm_buckets=4)
            st = (step.init_opt_state(params)
                  if hasattr(step, "init_opt_state")
                  else opt.init(params))
            out = step(params, st, (xb, yb))     # warm/compile
            jax.block_until_ready(out.params)
            comm.barrier()
            comm.stats.reset()
            nsteps = 5
            t0 = time.perf_counter()
            for _ in range(nsteps):
                out = step(out.params, out.opt_state, (xb, yb))
            jax.block_until_ready(out.params)
            wall = time.perf_counter() - t0
            snap = comm.stats.snapshot()
            comm.barrier()
            return {"exposed_ms": round(1e3 * snap["exposed_s"]
                                        / nsteps, 3),
                    "overlapped_ms": round(1e3 * snap["overlapped_s"]
                                           / nsteps, 3),
                    # wall time travels with the record so an "overlap"
                    # that relabels without hiding is visible
                    "step_ms": round(1e3 * wall / nsteps, 3)}

        no_ov = run_overlap(False)
        ov = run_overlap(True)

        if rank == 0:
            nsteps = runs * CHUNKS * reps
            blocking = lambda s: sum(d["seconds"] for d in s.values())
            # expected hier slow-hop bytes per leader step given the
            # widths the chooser ACTUALLY used in the timed window —
            # //nh INSIDE the per-leg term, exactly as HierRing
            # accounts each leg (the outer-division form differs by a
            # rounding byte whenever leg_bytes % nh >= nh/2)
            hier_expected = sum(
                2 * (wire.quant_leg_wire_bytes(n_elems, nh, bits=b)
                     // nh)
                for b in hier_widths)
            hier_measured = (hier_stats["hier_reduce"]["bytes"]
                             + hier_stats["hier_gather"]["bytes"])
            hist = {}
            for b in hier_widths:
                hist[str(b)] = hist.get(str(b), 0) + 1
            q.put({
                "hier_world": world,
                "hier_local_world": local_world,
                "hier_bucket_mb": round(n_elems * 4 / (1 << 20), 2),
                "q8_steps_per_sec": round(q8_sps, 2),
                "q4_steps_per_sec": round(q4_sps, 2),
                "hier_steps_per_sec": round(hier_sps, 2),
                "hier_runs": {"q8": [round(r, 2) for r in q8_runs],
                              "q4": [round(r, 2) for r in q4_runs],
                              "hier": [round(r, 2) for r in hier_runs]},
                # per-rank wire payload accounting vs the wire.py
                # formulas (CommStats accounting parity — actual framed
                # bytes are pinned by the native bit-parity tests)
                "f32_wire_bytes": wire.ring_allreduce_wire_bytes(
                    n_elems, world) // world,
                "q8_wire_bytes":
                    q8_stats["allreduce_q8"]["bytes"] // nsteps,
                "q4_wire_bytes":
                    q4_stats["allreduce_q4"]["bytes"] // nsteps,
                "q4_wire_bytes_expected":
                    wire.quant_ring_allreduce_wire_bytes(
                        n_elems, world, bits=4) // world,
                # slow-hop (leader-ring) bytes of the two-level arm:
                # measured on THIS leader vs formula-from-used-widths
                # (the CommStats accounting parity pin), plus the
                # all-leaders total vs the flat ring's all-ranks total
                # — on a flat host ring EVERY byte of EVERY rank rides
                # the slow transport, so the total is the ~local_world
                # reduction headline
                "hier_slow_hop_bytes": hier_measured,
                "hier_slow_hop_bytes_expected": hier_expected,
                # the PER-STEP figure the report renders next to the
                # per-step flat-arm columns (the window total above is
                # the exact-equality accounting pin)
                "hier_slow_hop_bytes_per_step": hier_measured // nsteps,
                "hier_slow_hop_bytes_total": sum(
                    2 * wire.quant_leg_wire_bytes(n_elems, nh, bits=b)
                    for b in hier_widths),
                "flat_slow_hop_bytes_q8":
                    nsteps * wire.quant_ring_allreduce_wire_bytes(
                        n_elems, world),
                # the flat all-ranks ring AT THE SAME WIDTHS the
                # adaptive hier arm actually used: dividing by this
                # isolates the TOPOLOGY cut (~(W-1)/(nh-1)) from the
                # q4 width cut the separate q4 gate already claims
                "flat_slow_hop_bytes_matched_width": sum(
                    wire.quant_ring_allreduce_wire_bytes(
                        n_elems, world, bits=b)
                    for b in hier_widths),
                "hier_width_hist": hist,
                "hier_blocking_ms_per_step": round(
                    1000 * blocking(hier_stats) / nsteps, 3),
                "q8_blocking_ms_per_step": round(
                    1000 * blocking(q8_stats) / nsteps, 3),
                "overlap": {"off": no_ov, "on": ov},
            })
    finally:
        dist.cleanup()


def bench_dp8_hier(n_elems: int = None, reps: int = 2,
                   runs: int = 5, world: int = COMM_WORLD) -> dict:
    """The ``dp8_hier_adaptive`` flagship arm: adaptive-width two-level
    ring vs the flat q8 ring on the same bucket, plus the measured
    overlap exposed_ms drop."""
    import multiprocessing as mp

    from distributed_pytorch_tpu.runtime.multiprocess import (
        launch_multiprocess)

    if n_elems is None:
        n_elems = int(_env.get("DPX_BENCH_HIER_ELEMS")) \
            or HIER_BUCKET_ELEMS
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    launch_multiprocess(_dp8_hier_worker, world, q, n_elems, reps, runs)
    return q.get(timeout=180)


def _dp8_hier_metric_blobs(rec: dict) -> dict:
    """Gated metric blobs + the vs_q8 gated_ratio for the
    dp8_hier_adaptive arm (the flagship claim is a RATIO, so both sides
    run through the spread gate — never a bare division)."""
    blobs = {}
    runs = rec.get("hier_runs") or {}
    stats = {}
    for name, key in (("dp8_hier_adaptive_steps_per_sec", "hier"),
                      ("dp8_hier_q8_steps_per_sec", "q8"),
                      ("dp8_hier_q4_steps_per_sec", "q4")):
        if runs.get(key):
            stats[key] = _stats.summarize(runs[key], warmup=0)
            blobs[name] = _record.make_metric(None, "steps_per_sec",
                                              stats=stats[key])
    if "hier" in stats and "q8" in stats:
        ratio, why = _stats.gated_ratio(stats["hier"], stats["q8"])
        if ratio is not None:
            rec["vs_q8"] = round(ratio, 2)
        else:
            rec["vs_q8_withheld"] = why
    return blobs


def bench_dp8_sharded(n_elems: int = None, reps: int = 2,
                      runs: int = 5, world: int = COMM_WORLD) -> dict:
    """The ``dp8_sharded_adam`` flagship arm: ZeRO-1 sharded AdamW vs
    the replicated update on the 8-process native quantized ring."""
    import multiprocessing as mp

    from distributed_pytorch_tpu.runtime.multiprocess import (
        launch_multiprocess)

    if n_elems is None:
        # smoke sizing knob (registry-typed): 0 means the full-size arm
        n_elems = int(_env.get("DPX_BENCH_SHARDED_ELEMS")) \
            or SHARDED_BUCKET_ELEMS
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    launch_multiprocess(_dp8_sharded_worker, world, q, n_elems, reps,
                        runs)
    return q.get(timeout=120)


def _dp8_sharded_metric_blobs(rec: dict) -> dict:
    """Gated metric blobs + the vs_replicated gated_ratio for the
    dp8_sharded_adam arm (the flagship claim is a RATIO, so both sides
    run through the spread gate — never a bare division)."""
    blobs = {}
    runs = rec.get("sharded_runs") or {}
    stats = {}
    for name, key in (("dp8_sharded_adam_steps_per_sec", "sharded"),
                      ("dp8_sharded_replicated_steps_per_sec",
                       "replicated")):
        if runs.get(key):
            stats[key] = _stats.summarize(runs[key], warmup=0)
            blobs[name] = _record.make_metric(None, "steps_per_sec",
                                              stats=stats[key])
    if "sharded" in stats and "replicated" in stats:
        # TrialStats numerator: gated_ratio gates BOTH sides itself
        ratio, why = _stats.gated_ratio(stats["sharded"],
                                        stats["replicated"])
        if ratio is not None:
            rec["vs_replicated"] = round(ratio, 2)
        else:
            rec["vs_replicated_withheld"] = why
    return blobs


def bench_dp8(n_steps: int = 15) -> dict:
    rec = run_json_subprocess(
        [sys.executable, "-c", _dp8_code(n_steps)], 600,
        label="dp8 bench",
        env={"JAX_PLATFORMS": "cpu", "DPX_CPU_DEVICES": "8"})
    comm = run_json_subprocess(
        [sys.executable, os.path.abspath(__file__), "--stage", "dp8_comm"],
        600, label="dp8 comm bench", env={"JAX_PLATFORMS": "cpu"})
    if "error" in comm:
        rec["comm_error"] = comm["error"]
    rec.update({k: v for k, v in comm.items() if k.startswith("comm_")})
    return rec


def _dp8_metric_blobs(dp8: dict) -> dict:
    """Gated metric blobs from the dp8 record — the entries benchdiff
    anchors regression verdicts on. The comm medians re-run through
    summarize() (already-warmed samples: warmup=0)."""
    blobs = {}
    if isinstance(dp8.get("metric_blob"), dict):
        # move, don't copy: the record stores each trials blob ONCE,
        # under metrics — the append-only store grows per byte
        blobs["dp8_steps_per_sec"] = dp8.pop("metric_blob")
    for name, key in (("dp8_comm_quant_steps_per_sec", "quant"),
                      ("dp8_comm_f32_steps_per_sec", "f32")):
        runs = (dp8.get("comm_runs") or {}).get(key)
        if runs:
            st = _stats.summarize(runs, warmup=0)
            blobs[name] = _record.make_metric(None, "steps_per_sec",
                                              stats=st)
    return blobs


# ---------------------------------------------------------------------------
# dp8_donate arm: whole-step buffer donation A/B on the pjit front door
# (docs/front_door.md) — the same spec point built donate=ON (the
# default: params + opt state donated, out == in shardings) and
# donate=OFF, paired steps/s through the perfbench policy plus XLA's
# OWN memory accounting (memory_analysis): the donated build must
# alias its state buffers (alias bytes > 0) and its peak bytes must be
# STRICTLY below the copy build's — the HBM the roofline says the
# compute-bound flagship needs back. Compile counters assert one
# program per arm (the front-door discipline, not trusted).
# ---------------------------------------------------------------------------

DONATE_HIDDEN = 2048
DONATE_IN_DIM = 512


def bench_dp8_donate(steps: int = 20) -> dict:
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    import numpy as np

    import distributed_pytorch_tpu as dist
    from distributed_pytorch_tpu import models, optim
    from distributed_pytorch_tpu.ops.losses import cross_entropy
    from distributed_pytorch_tpu.parallel import front_door, make_step

    _stats.pin_process()
    dist.init_process_group(rank=0, world_size=8)
    model = models.DummyModel(in_dim=DONATE_IN_DIM,
                              hidden_dim=DONATE_HIDDEN, n_classes=16)
    params0 = model.init(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(np.shape(l)))
                   for l in jax.tree_util.tree_leaves(params0))
    opt = optim.adamw(1e-3)

    def loss_fn(p, batch):
        x, y = batch
        return cross_entropy(model.apply(p, x), y), {}

    rng = np.random.default_rng(0)
    x = dist.shard_batch(
        rng.standard_normal((64, DONATE_IN_DIM)).astype(np.float32))
    y = dist.shard_batch((np.arange(64) % 16).astype(np.int32))
    batch = (x, y)

    front_door.cache_clear()
    arms = {}
    for name, donate in (("donated", True), ("copy", False)):
        step = make_step(loss_fn, opt, donate=donate)
        p = model.init(jax.random.PRNGKey(0))
        st = opt.init(p)
        out = step(p, st, batch)          # compile + warm (counted)
        jax.block_until_ready(out.loss)
        # memory_analysis AFTER the counted first call: lower() shares
        # the jit trace cache, so the other order would satisfy the
        # first call from the uncounted analysis trace
        arms[name] = {"step": step,
                      "mem": step.memory_analysis(out.params,
                                                  out.opt_state, batch)}
        state = {"out": out}

        def one_run(step=step, state=state):
            o = state["out"]
            t0 = time.perf_counter()
            for _ in range(steps):
                o = step(o.params, o.opt_state, batch)
            jax.block_until_ready(o.loss)
            state["out"] = o
            return steps / (time.perf_counter() - t0)

        arms[name]["stats"] = _stats.measure(one_run)

    don, cop = arms["donated"], arms["copy"]
    rec = {
        "donate_world": 8,
        "model_params": n_params,
        "global_batch": 64,
        "donated_steps_per_sec": round(don["stats"].median, 2),
        "copy_steps_per_sec": round(cop["stats"].median, 2),
        "donate_runs": {
            "donated": [round(r, 2) for r in don["stats"].runs],
            "copy": [round(r, 2) for r in cop["stats"].runs]},
        # XLA's compiled accounting, not a narrative: peak = args +
        # outputs + temps - aliased; donation aliases params+opt state
        "donated_peak_bytes": don["mem"]["peak_bytes"],
        "copy_peak_bytes": cop["mem"]["peak_bytes"],
        "donated_alias_bytes": don["mem"]["alias"],
        "copy_alias_bytes": cop["mem"]["alias"],
        "peak_saved_bytes": (cop["mem"]["peak_bytes"]
                             - don["mem"]["peak_bytes"]),
        "peak_saved_frac": round(
            1 - don["mem"]["peak_bytes"]
            / max(cop["mem"]["peak_bytes"], 1), 4),
        # the front-door compile discipline, asserted by the smoke
        "donated_compiles": don["step"].compiles,
        "copy_compiles": cop["step"].compiles,
        "timing_method": f"{steps}-step chained windows, fetch-fenced, "
                         "perfbench trials",
    }
    dist.cleanup()
    return rec


def _dp8_donate_metric_blobs(rec: dict) -> dict:
    """Gated metric blobs + the vs_copy gated_ratio for the dp8_donate
    arm (the flagship claim is a RATIO, so both sides run through the
    spread gate — never a bare division)."""
    blobs = {}
    runs = rec.get("donate_runs") or {}
    stats = {}
    for name, key in (("dp8_donate_steps_per_sec", "donated"),
                      ("dp8_donate_copy_steps_per_sec", "copy")):
        if runs.get(key):
            stats[key] = _stats.summarize(runs[key], warmup=0)
            blobs[name] = _record.make_metric(None, "steps_per_sec",
                                              stats=stats[key])
    if "donated" in stats and "copy" in stats:
        ratio, why = _stats.gated_ratio(stats["donated"], stats["copy"])
        if ratio is not None:
            rec["vs_copy"] = round(ratio, 2)
        else:
            rec["vs_copy_withheld"] = why
    return blobs


# ---------------------------------------------------------------------------
# decode-attention arm: the page-blockwise decode kernel vs the dense
# full-pool baseline (docs/compute.md) — the CI smoke gates (i) token
# streams bit-identical to generate() on a LONG pool serving short
# requests and (ii) measured short-resident decode step time <= the
# dense-full-width softmax it replaced
# ---------------------------------------------------------------------------

DECODE_ATTN_POOL = 2048     # pool width (positions) — the "capacity"
DECODE_ATTN_RESIDENT = 12   # resident length — the "occupancy"


def bench_decode_attention(max_len: int = DECODE_ATTN_POOL,
                           n_slots: int = 4,
                           resident: int = DECODE_ATTN_RESIDENT,
                           steps: int = 30) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu import models
    from distributed_pytorch_tpu.models.generate import (
        decode_step_slots_paged, make_generate_fn)
    from distributed_pytorch_tpu.nn.paged import ExactSide, KVPages
    from distributed_pytorch_tpu.ops.decode_attention import (
        DECODE_BLOCK, resident_blocks)
    from distributed_pytorch_tpu.serve import (EngineConfig,
                                               InferenceEngine,
                                               SamplingParams)
    from distributed_pytorch_tpu.utils.profiler import fetch_fence

    model = models.TransformerLM(vocab=128, dim=64, n_layers=2,
                                 n_heads=4, n_kv_heads=2, pos="rope",
                                 max_seq=max_len)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    # (i) token contract on the long pool: engine streams == generate()
    progress("decode-attn arm: token contract (long pool, short "
             "requests)")
    prompts = [rng.integers(0, 128, (s,)).astype(np.int32)
               for s in (5, resident, 7, 9)]
    sp = SamplingParams(max_new_tokens=6)
    keys = [jax.random.PRNGKey(40 + i) for i in range(len(prompts))]
    eng = InferenceEngine(model, params,
                          EngineConfig(n_slots=n_slots, max_len=max_len))
    with eng:
        outs = [eng.submit(p, sp, rng=k).result(timeout=300)
                for p, k in zip(prompts, keys)]
    decode_compiles = eng.pool.compiles.decode
    tokens_equal = True
    for p, k, out in zip(prompts, keys, outs):
        fn = make_generate_fn(model, sp.max_new_tokens, max_len=max_len)
        ref = np.asarray(jax.jit(fn)(params, jnp.asarray(p[None]), k))[0]
        tokens_equal = tokens_equal and bool(np.array_equal(out, ref))

    # (ii) decode step time at short resident length, blockwise vs the
    # dense full-pool softmax — same jitted step, same donation, only
    # the kernel differs (a page is one DECODE_BLOCK of positions)
    per_row = -(-max_len // DECODE_BLOCK)
    tables = jnp.arange(n_slots * per_row, dtype=jnp.int32).reshape(
        n_slots, per_row)
    active = jnp.ones((n_slots,), bool)

    def make_step(blockwise):
        def f(p, state, lengths, tokens):
            return decode_step_slots_paged(
                model, p, state, tables, lengths, tokens, active,
                page_len=DECODE_BLOCK, blockwise=blockwise)
        return jax.jit(f, donate_argnums=(1,))

    dh = model.dim // model.n_heads
    lengths = jnp.asarray(
        rng.integers(1, resident, (n_slots,)).astype(np.int32))
    tokens = jnp.asarray(rng.integers(0, 128, (n_slots,)), jnp.int32)

    def one_run(step_fn):
        side = lambda: ExactSide(jnp.asarray(rng.standard_normal(
            (n_slots * per_row, 2, DECODE_BLOCK, dh)), jnp.float32))
        state = [KVPages(side(), side()) for _ in range(model.n_layers)]
        lo, state = step_fn(params, state, lengths, tokens)  # compile
        fetch_fence(lo)
        t0 = time.perf_counter()
        for _ in range(steps):
            lo, state = step_fn(params, state, lengths, tokens)
        fetch_fence(lo)
        return steps / (time.perf_counter() - t0)   # steps/s

    rows = {}
    for name, blockwise in (("blockwise", True), ("dense", False)):
        progress(f"decode-attn arm: timing {name} decode "
                 f"(pool {max_len}, resident <= {resident})")
        fn = make_step(blockwise)
        # steps/s through the perfbench policy (trials, warmup discard,
        # spread gate) — the ms medians below are its reciprocal view
        rows[name] = _stats.measure(lambda fn=fn: one_run(fn))
    blk_ms = 1e3 / rows["blockwise"].median
    dense_ms = 1e3 / rows["dense"].median
    visited = int(resident_blocks(lengths, DECODE_BLOCK,
                                  -(-max_len // DECODE_BLOCK)))
    return {"pool_len": max_len,
            "resident_len_max": int(np.asarray(lengths).max()),
            "block_len": DECODE_BLOCK,
            "blocks_total": -(-max_len // DECODE_BLOCK),
            "blocks_visited": visited,
            "tokens_equal_generate": tokens_equal,
            "decode_compiles": decode_compiles,
            "blockwise_step_ms": round(blk_ms, 3),
            "dense_step_ms": round(dense_ms, 3),
            "speedup_x": round(dense_ms / blk_ms, 2) if blk_ms else None,
            "blockwise_trusted": rows["blockwise"].trusted,
            "dense_trusted": rows["dense"].trusted,
            "runs_blockwise_ms": [round(1e3 / r, 3)
                                  for r in rows["blockwise"].runs],
            "runs_dense_ms": [round(1e3 / r, 3)
                              for r in rows["dense"].runs]}


def bench_obs_overhead(n: int = 20000) -> dict:
    """dpxtrace span-API overhead (docs/observability.md): ns/span with
    tracing OFF (must be unmeasurable — one global read + one ``if``),
    ON with the ring only, and ON with the line-JSON sink. The smoke
    gate turns the ON cost into a fraction of the measured dp8 step
    (spans/step x ns/span) and asserts it stays small; the perfbench
    policy (trials, warmup discard, spread gate) governs every number."""
    import tempfile

    from distributed_pytorch_tpu.obs import trace as dpxtrace

    def ns_per_span():
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with dpxtrace.span("bench.op", b=1):
                pass
        return (time.perf_counter_ns() - t0) / n

    rows = {}
    log_path = os.path.join(tempfile.mkdtemp(prefix="dpxtrace_bench_"),
                            "spans.jsonl")
    for name, kw in (
            ("off", dict(enabled=False)),
            # ring only: the flight-recorder-armed production shape
            ("on_ring", dict(enabled=True, ring=256, log_path=None)),
            # full sink: every span to the line-JSON log
            ("on_log", dict(enabled=True, ring=256,
                            log_path=log_path))):
        dpxtrace.reset()
        dpxtrace.configure(**kw)
        rows[name] = _stats.measure(ns_per_span)
    dpxtrace.reset()
    try:
        sz = os.path.getsize(log_path)
    except OSError:
        sz = 0

    # dpxmon counter hot path (obs/metrics.py): metrics-off must be the
    # same one-global-read shape as the disabled span, metrics-on a
    # dict update; the snapshot emission is measured on a REALISTIC
    # registry (instruments + a CommStats-shaped provider) so the
    # cadence cost the smoke amortizes against the dp8 step is honest
    from distributed_pytorch_tpu.obs import metrics as dpxmon

    def ns_per_inc():
        t0 = time.perf_counter_ns()
        for _ in range(n):
            dpxmon.inc("bench.counter")
        return (time.perf_counter_ns() - t0) / n

    mon_rows = {}
    mon_log = os.path.join(os.path.dirname(log_path), "mon.jsonl")
    for name, on in (("off", False), ("on", True)):
        dpxmon.reset()
        dpxmon.configure(enabled=on, rank=0)
        mon_rows[name] = _stats.measure(ns_per_inc)
    # snapshot cost: ~20 gauges/counters, 2 populated histograms, one
    # provider with a comm-shaped payload — the production soak shape
    dpxmon.reset()
    dpxmon.configure(enabled=True, rank=0)
    for i in range(10):
        dpxmon.inc(f"bench.c{i}", i)
        dpxmon.set_gauge(f"bench.g{i}", i * 1.5)
    for i in range(256):
        dpxmon.observe("bench.h0", i * 0.1)
        dpxmon.observe("bench.h1", i * 0.2)
    dpxmon.register_provider("bench", lambda: {
        f"comm.op{i}.bytes": i * 1000 for i in range(8)})

    def ms_per_snapshot(m=50):
        t0 = time.perf_counter_ns()
        for _ in range(m):
            dpxmon.emit_snapshot(path=mon_log, step=0, source="bench")
        return (time.perf_counter_ns() - t0) / m / 1e6

    snap_stats = _stats.measure(ms_per_snapshot)
    dpxmon.reset()
    return {"n_spans_per_trial": n,
            "off_ns_per_span": round(rows["off"].median, 1),
            "on_ring_ns_per_span": round(rows["on_ring"].median, 1),
            "on_log_ns_per_span": round(rows["on_log"].median, 1),
            "off_trusted": rows["off"].trusted,
            "on_log_trusted": rows["on_log"].trusted,
            "log_bytes_per_span": round(
                sz / max(n * len(rows["on_log"].runs
                                 + rows["on_log"].warmup_discarded),
                         1), 1),
            "runs_off_ns": [round(r, 1) for r in rows["off"].runs],
            "runs_on_log_ns": [round(r, 1)
                               for r in rows["on_log"].runs],
            "mon_off_ns_per_inc": round(mon_rows["off"].median, 1),
            "mon_on_ns_per_inc": round(mon_rows["on"].median, 1),
            "mon_snapshot_ms": round(snap_stats.median, 4),
            "mon_snapshot_trusted": snap_stats.trusted,
            "runs_mon_off_ns": [round(r, 1)
                                for r in mon_rows["off"].runs]}


# ---------------------------------------------------------------------------


def _stage_main(stage: str) -> int:
    """Run ONE measurement in this process and print its JSON line
    (invoked by the orchestrator via _run_stage)."""
    if stage == "mfu":
        from benchmarks.mfu_transformer import run as mfu_run
        print(json.dumps(mfu_run()))
    elif stage == "mfu_medium":
        from benchmarks.mfu_transformer import MEDIUM
        from benchmarks.mfu_transformer import run as mfu_run
        print(json.dumps(mfu_run(steps=20, **MEDIUM)))
    elif stage == "mfu_host":
        from benchmarks.mfu_transformer import run_host_flagship
        print(json.dumps(run_host_flagship()))
    elif stage == "min_ddp":
        print(json.dumps(bench_min_ddp()))
    elif stage == "dp8_comm":
        print(json.dumps(bench_dp8_comm()))
    elif stage == "dp8_sharded":
        print(json.dumps(bench_dp8_sharded()))
    elif stage == "dp8_hier":
        print(json.dumps(bench_dp8_hier()))
    elif stage == "dp8_donate":
        print(json.dumps(bench_dp8_donate()))
    elif stage == "decode":
        from benchmarks.decode_tpu import run_gqa_compare
        print(json.dumps(run_gqa_compare()))
    elif stage == "decode_attn":
        print(json.dumps(bench_decode_attention()))
    elif stage == "obs_overhead":
        print(json.dumps(bench_obs_overhead()))
    elif stage == "scale_sweep":
        from benchmarks.scale_sweep import run_scale_sweep
        print(json.dumps(run_scale_sweep()))
    else:
        print(json.dumps({"error": f"unknown stage {stage!r}"}))
        return 2
    return 0


def _adopt_fresh_mfu(rec: dict, mfu_rec: dict, stage: str) -> bool:
    """Fold a fresh mfu-stage result into the headline record (value,
    provenance, trust from the per-run spread gate when trials exist,
    roofline + plausibility BEFORE the raw row lands) and append the
    raw row. Returns True when a measured mfu was adopted."""
    # `is not None`, not `in`: the mfu stage emits "mfu": null when
    # peak FLOPS for the device kind are unknown — that must fall
    # through to the carry-forward path, never become a "measured"
    # null headline (the r03-r05 failure mode)
    ok = mfu_rec.get("mfu") is not None
    if ok:
        runs = mfu_rec.get("mfu_runs") or []
        st = _stats.summarize(runs, warmup=0) if len(runs) > 1 else None
        rec["value"] = mfu_rec["mfu"]
        rec["provenance"] = "measured"
        rec["trusted"] = bool(st.trusted) if st is not None else True
        if rec["trusted"]:
            rec.pop("untrusted_reason", None)
        else:
            rec["untrusted_reason"] = st.untrusted_reason
        rec["device"] = mfu_rec.get("device", rec.get("device"))
        rec["tokens_per_sec"] = mfu_rec["tokens_per_sec"]
        rec["mfu_detail"] = mfu_rec
        rec["metrics"][HEADLINE_METRIC] = _record.make_metric(
            mfu_rec["mfu"], "mfu_fraction", stats=st)
        # plausibility verdict BEFORE the raw row lands: bench_mfu
        # rows are future last_good sources, so a roofline-poisoned
        # value must reach the store as ok=False, not as evidence
        attach_roofline(rec)
    append_result(stage, mfu_rec,
                  ok=ok and rec.get("trusted", False))
    return ok and rec.get("provenance") == "measured"


def _adopt_last_good(rec: dict) -> bool:
    """Fill an unmeasured headline from the newest last_good flagship
    row (explicit provenance, traceable source), or mark the record
    untrusted with the reason when none exists. The ONE carry-forward
    shape — main() and headline() both use it, so the two entry points
    can never drift into writing differently-shaped records into the
    same trajectory store."""
    lg = last_good_record()
    if lg:
        rec["value"] = lg["mfu"]
        rec["provenance"] = "last_good"
        rec["last_good"] = lg
        rec["trusted"] = True
        rec.pop("untrusted_reason", None)
        rec["metrics"][HEADLINE_METRIC] = _record.make_metric(
            lg["mfu"], "mfu_fraction", provenance="last_good",
            last_good=lg)
        return True
    rec["untrusted_reason"] = (
        "unmeasured and no last_good flagship row on file: "
        + rec.get("error", rec.get("tpu_backend", "?")))
    return False


def _host_flagship_fallback(rec: dict) -> bool:
    """No healthy TPU: measure the pinned HOST flagship arm
    (benchmarks/mfu_transformer.FLAGSHIP_CPU — the composed bf16-mp +
    remat + donation recipe against the CALIBRATED host peak) so the
    headline moves off the carry-forward with a fresh, gated, honestly
    labeled measurement (device + peak_source travel in mfu_detail).

    JAX_PLATFORMS=cpu explicitly: the child must choose the host on
    purpose, never reach it as a fallback."""
    host_rec = run_json_subprocess(
        [sys.executable, os.path.abspath(__file__), "--stage",
         "mfu_host"], 2400, label="stage mfu_host",
        env={"JAX_PLATFORMS": "cpu"})
    return _adopt_fresh_mfu(rec, host_rec, "bench_mfu_host")


def main():
    rec = _record.make_record(HEADLINE_METRIC, "mfu_fraction")

    info = probe_backend()
    rec["device"] = info.get("kind") or "none"

    if info:
        mfu_rec = _run_stage("mfu", timeout_s=1800)
        adopted = _adopt_fresh_mfu(rec, mfu_rec, "bench_mfu")
        if not adopted:
            rec["error"] = ("mfu stage: "
                            + str(mfu_rec.get("error")
                                  or ("returned null mfu (device kind "
                                      "without a known peak FLOPS?)"
                                      if "mfu" in mfu_rec
                                      else "no result")))
        # bigger matmuls, higher attainable MFU — a reporting arm, never
        # the headline (the flagship config is pinned for comparability)
        rec["mfu_medium"] = _run_stage("mfu_medium", timeout_s=1800)
        append_result("bench_mfu_medium", rec["mfu_medium"])
        rec["min_ddp"] = _run_stage("min_ddp", timeout_s=900)
        append_result("bench_min_ddp", rec["min_ddp"])
        if "steps_per_sec" in rec["min_ddp"]:
            rec["metrics"]["min_ddp_steps_per_sec"] = _record.make_metric(
                rec["min_ddp"]["steps_per_sec"], "steps_per_sec")
        # two full decode benchmarks (MHA + GQA arms) live in this stage
        rec["decode"] = _run_stage("decode", timeout_s=2400)
        append_result("bench_decode", rec["decode"])
    else:
        # no TPU: the pinned host flagship arm is still a REAL gated
        # measurement (calibrated peak, spread-gated trials) — only
        # when IT also fails does the carry-forward path below engage
        rec["tpu_backend"] = "the backend probe found no TPU"
        if not _host_flagship_fallback(rec):
            rec["error"] = rec["tpu_backend"] \
                + "; host flagship arm also failed"

    if "value" not in rec:
        # last_good carry-forward — covers BOTH failure modes: no TPU
        # was found, or one was and the mfu stage died mid-run.
        # Nothing was measured NOW, so the record
        # says so in provenance — but it always carries a value a reader
        # can trace to its raw on-chip row, never a null.
        _adopt_last_good(rec)

    rec["dp8"] = bench_dp8()
    rec["metrics"].update(_dp8_metric_blobs(rec["dp8"]))

    # dp8_sharded_adam flagship arm (ZeRO-1 on the quantized ring):
    # steps/s vs the replicated update as a gated ratio, wire bytes and
    # per-rank optimizer-state shrink — subprocess-isolated like every
    # other stage so a hang yields a parseable error field
    rec["dp8_sharded"] = run_json_subprocess(
        [sys.executable, os.path.abspath(__file__), "--stage",
         "dp8_sharded"], 600, label="dp8 sharded bench",
        env={"JAX_PLATFORMS": "cpu"})
    rec["metrics"].update(_dp8_sharded_metric_blobs(rec["dp8_sharded"]))
    append_result("bench_dp8_sharded", rec["dp8_sharded"],
                  ok="error" not in rec["dp8_sharded"])

    # dp8_donate flagship arm (whole-step buffer donation on the pjit
    # front door): paired donate-on/off steps/s as a gated ratio plus
    # XLA memory_analysis peak bytes per arm — subprocess-isolated like
    # every other stage
    rec["dp8_donate"] = run_json_subprocess(
        [sys.executable, os.path.abspath(__file__), "--stage",
         "dp8_donate"], 600, label="dp8 donate bench",
        env={"JAX_PLATFORMS": "cpu", "DPX_CPU_DEVICES": "8"})
    rec["metrics"].update(_dp8_donate_metric_blobs(rec["dp8_donate"]))
    append_result("bench_dp8_donate", rec["dp8_donate"],
                  ok="error" not in rec["dp8_donate"])

    # dp8_hier_adaptive flagship arm (adaptive-width two-level ring +
    # measured comm-overlap exposure): paired vs the flat q8 ring as a
    # gated ratio, q4/adaptive wire bytes vs formula, exposed_ms
    # with/without overlap — subprocess-isolated like every other stage
    rec["dp8_hier"] = run_json_subprocess(
        [sys.executable, os.path.abspath(__file__), "--stage",
         "dp8_hier"], 600, label="dp8 hier bench",
        env={"JAX_PLATFORMS": "cpu"})
    rec["metrics"].update(_dp8_hier_metric_blobs(rec["dp8_hier"]))
    append_result("bench_dp8_hier", rec["dp8_hier"],
                  ok="error" not in rec["dp8_hier"])

    # roofline anchoring + plausibility gate: may flip the record to
    # untrusted (an MFU above the overlapped ceiling cannot be real).
    # Already attached on the fresh-measured path (before the raw
    # bench_mfu row landed); this covers the carry-forward/error paths.
    if "roofline_flagship" not in rec:
        attach_roofline(rec)
    if not rec.get("trusted") and HEADLINE_METRIC in rec["metrics"]:
        blob = rec["metrics"][HEADLINE_METRIC]
        blob["trusted"] = False
        blob["untrusted_reason"] = rec.get("untrusted_reason",
                                           "record untrusted")

    # vs_baseline: printed only when BOTH sides pass the spread gate —
    # withheld with the gate's reason otherwise (never silently blank)
    try:
        lm_stats = bench_torch_cpu_lm()
        rec["torch_cpu_lm_tokens_per_sec"] = round(lm_stats.median, 1)
        rec["torch_cpu_lm_baseline_detail"] = _baseline_detail(
            lm_stats, "tokens_per_sec")
        rec["metrics"]["torch_cpu_lm_tokens_per_sec"] = \
            _record.make_metric(None, "tokens_per_sec", stats=lm_stats)
        if rec.get("provenance") != "measured":
            ratio, why = None, ("flagship side is "
                                f"{rec.get('provenance')}, not a fresh "
                                "measurement")
        elif not rec.get("trusted"):
            ratio, why = None, (f"flagship untrusted: "
                                f"{rec.get('untrusted_reason')}")
        else:
            ratio, why = _stats.gated_ratio(rec.get("tokens_per_sec"),
                                            lm_stats)
        if ratio is not None:
            rec["vs_baseline"] = round(ratio, 2)
        else:
            rec["vs_baseline_withheld"] = why
    except Exception as e:  # noqa: BLE001
        rec["vs_baseline_withheld"] = (
            f"torch lm baseline failed: {type(e).__name__}: {e}")
        rec.setdefault("warnings", []).append(
            rec["vs_baseline_withheld"])

    # only worth minutes of eager-torch stepping if there is a min_ddp
    # record to attach the ratio to (absent whenever the TPU was down)
    if "steps_per_sec" in rec.get("min_ddp", {}):
        try:
            mlp_stats = bench_torch_cpu_mlp()
            rec["min_ddp"]["torch_cpu_baseline"] = _baseline_detail(
                mlp_stats, "steps_per_sec")
            rec["metrics"]["torch_cpu_mlp_steps_per_sec"] = \
                _record.make_metric(None, "steps_per_sec",
                                    stats=mlp_stats)
            ratio, why = _stats.gated_ratio(
                rec["min_ddp"]["steps_per_sec"], mlp_stats)
            if ratio is not None:
                rec["min_ddp"]["vs_torch_cpu"] = round(ratio, 2)
            else:
                rec["min_ddp"]["vs_torch_cpu_withheld"] = why
        except Exception:  # noqa: BLE001
            pass

    # self-check the schema BEFORE printing: an invalid record is a bug,
    # and the record contract says emit it anyway — with the issues
    # attached loudly rather than silently shipped
    issues = _record.validate_record(rec, strict=False)
    if issues:
        rec["schema_issues"] = issues
        print(f"# WARNING: record failed schema self-validation: "
              f"{'; '.join(issues[:3])}", file=sys.stderr)

    # the composite headline record is itself a raw-JSON trace. ok=False
    # for carry-forward rows: they must never become a future last_good.
    if _env.get("DPX_BENCH_SELFLOG"):
        append_result("bench_record", rec,
                      ok=rec.get("provenance") == "measured"
                      and rec.get("trusted", False) and not issues)

    print(json.dumps(rec))


def headline() -> int:
    """``--headline``: measure and land ONLY the flagship headline.

    TPU mfu stage when the probe finds a TPU, else the pinned host
    flagship arm (``mfu_host``) — fresh gated measurement, roofline +
    plausibility attached, schema-validated, appended to the store.
    The dp8*/torch companion arms are NOT re-run: they are environment-
    sensitive (core count, neighbors) and re-measuring them on a
    changed container would manufacture spurious benchdiff verdicts —
    ``vs_baseline`` is withheld with exactly that reason, per the
    gate's never-silently-blank policy."""
    rec = _record.make_record(HEADLINE_METRIC, "mfu_fraction")
    info = probe_backend()
    rec["device"] = info.get("kind") or "none"
    if info:
        adopted = _adopt_fresh_mfu(rec, _run_stage("mfu", timeout_s=1800),
                                   "bench_mfu")
    else:
        rec["tpu_backend"] = "the backend probe found no TPU"
        adopted = _host_flagship_fallback(rec)
    if not adopted and "value" not in rec:
        if not _adopt_last_good(rec):
            rec["error"] = (rec.get("tpu_backend", "")
                            + "; flagship unmeasured and no last_good "
                              "row on file")
    if "roofline_flagship" not in rec:
        attach_roofline(rec)
    rec["vs_baseline_withheld"] = (
        "headline mode measures the flagship arm only — baselines and "
        "companion arms deliberately not re-run")
    issues = _record.validate_record(rec, strict=False)
    if issues:
        rec["schema_issues"] = issues
        print(f"# WARNING: record failed schema self-validation: "
              f"{'; '.join(issues[:3])}", file=sys.stderr)
    if _env.get("DPX_BENCH_SELFLOG"):
        append_result("bench_record", rec,
                      ok=rec.get("provenance") == "measured"
                      and rec.get("trusted", False) and not issues)
    print(json.dumps(rec))
    return 0 if rec.get("provenance") == "measured" and not issues else 1


# ---------------------------------------------------------------------------
# --smoke: the CPU-gated perfbench smoke (CI bench-smoke job)
# ---------------------------------------------------------------------------


def smoke() -> int:
    """Seconds-scale end-to-end exercise of the statistical policy:

    1. the spread gate structurally withholds a ratio built on synthetic
       noisy trials (the r05 70%-spread-baseline case, deterministic);
    2. the loopback dp8 smoke runs with affinity pinning + warmup
       discard and must come back TRUSTED — spread (IQR/median) under
       the 15% gate (the r05 dp8 fix, asserted);
    3. the resulting record is schema-valid and benchdiff-comparable.

    Exits nonzero on any violation (the CI gate)."""
    def gate(ok: bool, what: str) -> None:
        # explicit check, NOT assert: -O/PYTHONOPTIMIZE compiles
        # asserts out, and a gate whose checks never ran must not pass
        if not ok:
            print(f"# perfbench smoke FAILED: {what}", file=sys.stderr)
            raise SystemExit(1)

    progress("perfbench smoke: synthetic spread-gate check")
    noisy = _stats.summarize([100.0, 60.0, 100.0, 140.0, 101.0, 170.0],
                             warmup=1, max_spread=0.15)
    gate(not noisy.trusted, "70%-spread trials must fail the gate")
    ratio, why = _stats.gated_ratio(100.0, noisy)
    gate(ratio is None and "untrusted" in (why or ""),
         f"gated_ratio must withhold on an untrusted denominator: {why}")
    clean = _stats.summarize([100.0, 99.0, 101.0, 100.0], warmup=1)
    ratio, why = _stats.gated_ratio(200.0, clean)
    gate(ratio == 2.0 and why is None,
         f"gated_ratio must pass a clean 2x ratio: {ratio}, {why}")

    progress("perfbench smoke: dp8_sharded_adam (ZeRO-1 on the q8 ring)")
    sh = run_json_subprocess(
        [sys.executable, os.path.abspath(__file__), "--stage",
         "dp8_sharded"], 420, label="dp8 sharded smoke",
        env={"JAX_PLATFORMS": "cpu",
             # smoke sizing: 4 MiB bucket keeps the 8-proc arm seconds-
             # scale; byte accounting is size-independent
             "DPX_BENCH_SHARDED_ELEMS": str(1 << 20)})
    gate("error" not in sh, f"dp8 sharded arm failed: {sh.get('error')}")
    # the wire-byte claim is ASSERTED, not narrated: the sharded q8
    # update must move >= 3.5x fewer bytes than the f32 replicated
    # ring, and the runtime's per-op CommStats accounting must agree
    # with the wire.py formula for this bucket (protocol-level framed
    # bytes are pinned by the native bit-parity tests, not here)
    gate(sh["sharded_wire_bytes"] == sh["sharded_wire_bytes_expected"],
         f"CommStats-accounted sharded wire bytes "
         f"{sh['sharded_wire_bytes']} != wire.py formula "
         f"{sh['sharded_wire_bytes_expected']}")
    ratio = sh["replicated_f32_wire_bytes"] / sh["sharded_wire_bytes"]
    gate(ratio >= 3.5, f"sharded q8 wire reduction {ratio:.2f}x < 3.5x "
                       "vs the f32 replicated ring")
    gate(sh["opt_state_shrink"] >= 0.9 * (2 * sh["sharded_world"] / 3),
         f"opt-state shrink {sh['opt_state_shrink']}x below ~2W/3 "
         f"(W={sh['sharded_world']}: 2 moments/W + master vs 2 full)")
    blobs = _dp8_sharded_metric_blobs(sh)
    gate("dp8_sharded_adam_steps_per_sec" in blobs,
         "sharded arm produced no gated metric blob")
    gate(("vs_replicated" in sh) != ("vs_replicated_withheld" in sh),
         "dp8_sharded_adam must carry vs_replicated XOR its "
         "withhold reason")
    print(json.dumps({"smoke": "dp8_sharded_adam",
                      "ok": True,
                      "wire_ratio_vs_f32": round(ratio, 2),
                      "opt_state_shrink": sh["opt_state_shrink"],
                      **{k: sh[k] for k in ("vs_replicated",
                                            "vs_replicated_withheld")
                         if k in sh}}))

    progress("perfbench smoke: dp8_donate (whole-step buffer donation "
             "A/B on the pjit front door)")
    dn = run_json_subprocess(
        [sys.executable, os.path.abspath(__file__), "--stage",
         "dp8_donate"], 420, label="dp8 donate smoke",
        env={"JAX_PLATFORMS": "cpu", "DPX_CPU_DEVICES": "8"})
    gate("error" not in dn, f"dp8 donate arm failed: {dn.get('error')}")
    # the donation claim is XLA's own accounting, ASSERTED: the donated
    # build must alias its state buffers and its compiled peak bytes
    # must be STRICTLY below the copy build's
    gate(dn["donated_alias_bytes"] > 0,
         "donated build aliased zero bytes — donation silently dropped")
    gate(dn["copy_alias_bytes"] == 0,
         f"copy build aliased {dn['copy_alias_bytes']} bytes — the A/B "
         "arms are not a donation A/B")
    gate(dn["donated_peak_bytes"] < dn["copy_peak_bytes"],
         f"donated peak {dn['donated_peak_bytes']} not below copy peak "
         f"{dn['copy_peak_bytes']}")
    # one compiled program per arm (the front-door counter discipline)
    gate(dn["donated_compiles"] == 1 and dn["copy_compiles"] == 1,
         f"compile counters != 1: donated {dn['donated_compiles']}, "
         f"copy {dn['copy_compiles']}")
    blobs = _dp8_donate_metric_blobs(dn)
    gate("dp8_donate_steps_per_sec" in blobs,
         "donate arm produced no gated metric blob")
    gate(("vs_copy" in dn) != ("vs_copy_withheld" in dn),
         "dp8_donate must carry vs_copy XOR its withhold reason")
    print(json.dumps({"smoke": "dp8_donate", "ok": True,
                      "peak_bytes": {"donated": dn["donated_peak_bytes"],
                                     "copy": dn["copy_peak_bytes"]},
                      "peak_saved_frac": dn["peak_saved_frac"],
                      "alias_bytes": dn["donated_alias_bytes"],
                      **{k: dn[k] for k in ("vs_copy",
                                            "vs_copy_withheld")
                         if k in dn}}))

    progress("perfbench smoke: dp8_hier_adaptive (q4/adaptive two-level "
             "ring + overlap)")
    hr = run_json_subprocess(
        [sys.executable, os.path.abspath(__file__), "--stage",
         "dp8_hier"], 420, label="dp8 hier smoke",
        env={"JAX_PLATFORMS": "cpu",
             # smoke sizing: 4 MiB bucket keeps the 8-proc arm seconds-
             # scale; byte accounting is size-independent
             "DPX_BENCH_HIER_ELEMS": str(1 << 20)})
    gate("error" not in hr, f"dp8 hier arm failed: {hr.get('error')}")
    # the q4 byte claim is ASSERTED, not narrated: CommStats accounting
    # must equal the wire.py formula, and the q4 wire must move >= 6.5x
    # fewer bytes than the f32 ring on this bucket (protocol-level
    # framed bytes are pinned by the native bit-parity tests, not here)
    gate(hr["q4_wire_bytes"] == hr["q4_wire_bytes_expected"],
         f"CommStats-accounted q4 wire bytes {hr['q4_wire_bytes']} != "
         f"wire.py formula {hr['q4_wire_bytes_expected']}")
    q4_ratio = hr["f32_wire_bytes"] / hr["q4_wire_bytes"]
    gate(q4_ratio >= 6.5, f"q4 wire reduction {q4_ratio:.2f}x < 6.5x "
                          "vs the f32 ring")
    gate(hr["hier_slow_hop_bytes"] == hr["hier_slow_hop_bytes_expected"],
         f"hier slow-hop bytes {hr['hier_slow_hop_bytes']} != formula "
         f"{hr['hier_slow_hop_bytes_expected']} for the widths used")
    # topology cut at MATCHED widths (the pure two-level claim — the
    # q4 width cut is gated separately above, never double-counted)
    slow_x = (hr["flat_slow_hop_bytes_matched_width"]
              / hr["hier_slow_hop_bytes_total"])
    gate(slow_x > 1.5,
         f"two-level ring slow-hop topology reduction {slow_x:.2f}x — "
         "expected ~(W-1)/(nh-1) vs the same-width flat ring")
    # overlap is measured, not claimed: overlapped_ms only accrues when
    # the is_ready probe saw a dispatched bucket update GENUINELY still
    # executing at comm-issue time (a sleep-comm with instant updates
    # would book ~zero), so the gate is the ON mode's own measured
    # hidden fraction — cross-mode absolute exposed_ms comparisons are
    # reported but not gated (the two arms' total comm differs by >2x
    # run to run on this oversubscribed loopback world)
    ov, no_ov = hr["overlap"]["on"], hr["overlap"]["off"]
    gate(no_ov["overlapped_ms"] == 0,
         f"non-overlapped run booked hidden comm: {no_ov}")
    hidden_frac = ov["overlapped_ms"] / max(
        ov["overlapped_ms"] + ov["exposed_ms"], 1e-9)
    gate(ov["overlapped_ms"] > 0 and hidden_frac >= 0.2,
         f"overlap hid only {hidden_frac:.0%} of comm (measured via "
         f"is_ready): on={ov}")
    blobs = _dp8_hier_metric_blobs(hr)
    gate("dp8_hier_adaptive_steps_per_sec" in blobs,
         "hier arm produced no gated metric blob")
    gate(("vs_q8" in hr) != ("vs_q8_withheld" in hr),
         "dp8_hier_adaptive must carry vs_q8 XOR its withhold reason")
    print(json.dumps({"smoke": "dp8_hier_adaptive", "ok": True,
                      "q4_wire_ratio_vs_f32": round(q4_ratio, 2),
                      "slow_hop_reduction_x": round(slow_x, 2),
                      "exposed_ms": {"off": no_ov["exposed_ms"],
                                     "on": ov["exposed_ms"]},
                      "hidden_frac": round(hidden_frac, 3),
                      "step_ms": {"off": no_ov.get("step_ms"),
                                  "on": ov.get("step_ms")},
                      "width_hist": hr.get("hier_width_hist"),
                      **{k: hr[k] for k in ("vs_q8", "vs_q8_withheld")
                         if k in hr}}))

    progress("perfbench smoke: decode-attention arm (page-blockwise vs "
             "dense full pool)")
    da = run_json_subprocess(
        [sys.executable, os.path.abspath(__file__), "--stage",
         "decode_attn"], 600, label="decode attn smoke",
        env={"JAX_PLATFORMS": "cpu"})
    gate("error" not in da, f"decode-attn arm failed: {da.get('error')}")
    # (i) the kernel swap is invisible at the serving contract: token
    # streams bit-identical to generate() on a 2048-wide pool serving
    # ~12-token requests, with ONE decode compile
    gate(da["tokens_equal_generate"] is True,
         "long-pool engine streams diverged from generate()")
    gate(da["decode_compiles"] == 1,
         f"decode compiles {da['decode_compiles']} != 1")
    # (ii) the claimed win is MEASURED: at short resident length the
    # blockwise step must not be slower than the dense full-pool
    # baseline it replaced (it should be much faster — the scan visits
    # blocks_visited of blocks_total; the conservative gate is <=)
    gate(da["blocks_visited"] < da["blocks_total"],
         f"smoke config visits every block "
         f"({da['blocks_visited']}/{da['blocks_total']}) — the "
         "short-resident claim would be vacuous")
    gate(da["blockwise_step_ms"] <= da["dense_step_ms"],
         f"blockwise decode {da['blockwise_step_ms']}ms slower than "
         f"dense full-pool baseline {da['dense_step_ms']}ms")
    print(json.dumps({"smoke": "decode_attention", "ok": True,
                      "blockwise_step_ms": da["blockwise_step_ms"],
                      "dense_step_ms": da["dense_step_ms"],
                      "speedup_x": da["speedup_x"],
                      "blocks": f"{da['blocks_visited']}/"
                                f"{da['blocks_total']}"}))

    progress("perfbench smoke: loopback dp8 (pinned, warmup-discarded)")
    dp8 = run_json_subprocess(
        [sys.executable, "-c", _dp8_code(n_steps=15)], 420,
        label="dp8 smoke", env={"JAX_PLATFORMS": "cpu",
                                "DPX_CPU_DEVICES": "8"})
    if "error" in dp8:
        print(json.dumps({"smoke": "perfbench", "ok": False,
                          "error": dp8["error"]}))
        return 1

    rec = _record.make_record("dp8_smoke_steps_per_sec", "steps_per_sec",
                              device="cpu-loopback")
    if isinstance(dp8.get("metric_blob"), dict):
        rec["metrics"]["dp8_steps_per_sec"] = dp8["metric_blob"]
    rec["value"] = dp8["steps_per_sec"]
    rec["provenance"] = "measured"
    rec["trusted"] = bool(dp8.get("trusted"))
    if rec["trusted"]:
        rec.pop("untrusted_reason", None)
    else:
        rec["untrusted_reason"] = (dp8.get("metric_blob") or {}).get(
            "untrusted_reason", "dp8 smoke spread gate failed")
    _record.validate_record(rec)  # raises RecordInvalid on a schema bug

    # ONE spread verdict: the child's trust flag already encodes the
    # DPX_BENCH_MAX_SPREAD gate — re-checking a hard-coded 0.15 here
    # could contradict the policy it claims to enforce
    spread = dp8.get("spread_frac", 1.0)
    ok = rec["trusted"]
    print(json.dumps({"smoke": "perfbench", "ok": ok,
                      "dp8_steps_per_sec": dp8["steps_per_sec"],
                      "spread_frac": spread,
                      "runs": dp8.get("runs_steps_per_sec"),
                      "trusted": rec["trusted"]}))
    if not ok:
        gate_frac = float(_env.get("DPX_BENCH_MAX_SPREAD"))
        print(f"# dp8 smoke spread {spread:.0%} tripped the "
              f"{gate_frac:.0%} gate — the loopback dp8 must be quiet "
              "after pinning + warmup discard", file=sys.stderr)
        return 1

    progress("perfbench smoke: dpxtrace overhead (off ~zero, on a "
             "small fraction of the dp8 step)")
    ob = run_json_subprocess(
        [sys.executable, os.path.abspath(__file__), "--stage",
         "obs_overhead"], 300, label="obs overhead smoke",
        env={"JAX_PLATFORMS": "cpu"})
    gate("error" not in ob, f"obs overhead arm failed: {ob.get('error')}")
    # disabled tracing must be UNMEASURABLE next to any traced op: one
    # module-global read + one `if` — the bound is deliberately loose
    # (2 µs on a contended CI host) against a real cost of ~0.2-0.5 µs
    gate(ob["off_ns_per_span"] <= 2000,
         f"tracing-off span cost {ob['off_ns_per_span']}ns/span — the "
         "disabled path must be near-zero")
    # absolute per-span ceilings first — loose regression backstops
    # (a contended host doubles the measured cost: idle ~4/~12 µs,
    # under full tier-1 load ~8/~25 µs); the fraction gates below are
    # the tight ones and SELF-NORMALIZE (the dp8 denominator slows
    # down with the same contention)
    gate(ob["on_ring_ns_per_span"] <= 15000,
         f"ring-only span cost {ob['on_ring_ns_per_span']}ns/span "
         "exceeds the 15µs ceiling")
    gate(ob["on_log_ns_per_span"] <= 50000,
         f"sink span cost {ob['on_log_ns_per_span']}ns/span exceeds "
         "the 50µs ceiling")
    # then the fraction of the step it instruments — asserted against
    # the MEASURED dp8 step just above, which is a deliberately
    # PATHOLOGICAL denominator (a ~0.7-1.5 ms MLP micro-step; the host
    # flagship step is ~4 s, serve decode ~10 ms — there the same span
    # cost is noise). The non-overlapped host step emits 5 spans
    # (host_step + backward + bucket + comm + update): ring-only (the
    # always-on flight-recorder shape) within 5% of even this
    # micro-step, the full line-JSON sink within 15%.
    step_ns = 1e9 / dp8["steps_per_sec"]
    spans_per_step = 5
    ring_frac = spans_per_step * ob["on_ring_ns_per_span"] / step_ns
    log_frac = spans_per_step * ob["on_log_ns_per_span"] / step_ns
    gate(ring_frac <= 0.05,
         f"ring-only tracing cost {ring_frac:.2%} of the measured dp8 "
         f"micro-step ({ob['on_ring_ns_per_span']}ns/span x "
         f"{spans_per_step}) exceeds the 5% bound")
    gate(log_frac <= 0.15,
         f"tracing-on (line-JSON sink) cost {log_frac:.2%} of the "
         f"measured dp8 micro-step ({ob['on_log_ns_per_span']}ns/span "
         f"x {spans_per_step}) exceeds the 15% bound")
    # dpxmon counter hot path (docs/observability.md): metrics-off is
    # the same one-global-read shape as the disabled span (<= 2 µs),
    # metrics-on a dict update under a loose absolute backstop, and
    # the snapshot emission — measured on a realistic registry —
    # amortizes over the reference 50-step cadence to a small fraction
    # of even the pathological dp8 micro-step denominator
    gate(ob["mon_off_ns_per_inc"] <= 2000,
         f"metrics-off increment {ob['mon_off_ns_per_inc']}ns — the "
         "disabled path must be near-zero")
    gate(ob["mon_on_ns_per_inc"] <= 15000,
         f"metrics-on increment {ob['mon_on_ns_per_inc']}ns exceeds "
         "the 15µs ceiling")
    gate(ob["mon_snapshot_ms"] <= 20.0,
         f"snapshot emission {ob['mon_snapshot_ms']}ms exceeds the "
         "20ms absolute ceiling")
    snap_frac = (ob["mon_snapshot_ms"] * 1e6 / 50) / step_ns
    gate(snap_frac <= 0.05,
         f"snapshot cadence cost {snap_frac:.2%} of the measured dp8 "
         f"micro-step ({ob['mon_snapshot_ms']}ms / 50-step cadence) "
         "exceeds the 5% bound")
    print(json.dumps({"smoke": "obs_overhead", "ok": True,
                      "off_ns_per_span": ob["off_ns_per_span"],
                      "on_ring_ns_per_span": ob["on_ring_ns_per_span"],
                      "on_log_ns_per_span": ob["on_log_ns_per_span"],
                      "ring_frac_of_dp8_step": round(ring_frac, 6),
                      "log_frac_of_dp8_step": round(log_frac, 6),
                      "mon_off_ns_per_inc": ob["mon_off_ns_per_inc"],
                      "mon_on_ns_per_inc": ob["mon_on_ns_per_inc"],
                      "mon_snapshot_ms": ob["mon_snapshot_ms"],
                      "snap_frac_of_dp8_step": round(snap_frac, 6)}))
    return 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--stage":
        raise SystemExit(_stage_main(sys.argv[2]))
    if "--soak" in sys.argv[1:]:
        # the composed soak arm (benchmarks/soak.py): hier x adaptive x
        # overlap x sharded-elastic-ckpt under chaos at world 4, gated
        # by dpxmon's health verdict (docs/observability.md)
        from benchmarks.soak import run_soak
        raise SystemExit(run_soak(smoke="--smoke" in sys.argv[1:]))
    if "--smoke" in sys.argv[1:]:
        raise SystemExit(smoke())
    if "--headline" in sys.argv[1:]:
        raise SystemExit(headline())
    main()
