"""The harness end to end on the CPU at a tiny size: ``run.py``'s train
and serve paths through its test-only entry (which skips the look for a
chip and nothing else), the same paths with the timed path broken
underneath, the lower-precision control, the generator as a pure function
of the seed, and the references against the program in float32."""

import contextlib
import io
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from chipbench import lowprec, run, traffic_gen
from chipbench import weights as W
from chipbench.kinds import serve as serve_kind
from chipbench.kinds import train as train_kind
from chipbench.reference import serve_logits, train_steps

REPO = run.REPO
SEED = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    return tiny.write_root(str(tmp_path_factory.mktemp("tinygrid")), real)


def cell_args(name, trace=0, seed=SEED):
    return ["--workload", name, "--seed", str(seed), "--seconds", "1.5",
            "--trace", str(trace)]


RUNS = [("tiny-train-cell", 0), ("tiny-train-cell", 1),
        ("tiny-serve-cell", 0), ("tiny-serve-cell", 1),
        ("tiny-train4-cell", 0)]


@pytest.fixture(scope="module")
def ran(root):
    """(result, lines of standard output) of ``run.main`` for a cell, run
    once however many tests look at it."""
    made = {}

    def of(cell, trace):
        if (cell, trace) not in made:
            out = io.StringIO()
            with contextlib.redirect_stdout(out):
                run.main(cell_args(cell, trace), root=root,
                         require_chip=False)
            lines = out.getvalue().strip().splitlines()
            made[cell, trace] = (json.loads(lines[-1]), lines)
        return made[cell, trace]
    return of


@pytest.mark.parametrize("cell,trace", RUNS)
def test_run_end_to_end(root, ran, cell, trace):
    result, _ = ran(cell, trace)
    assert set(result) - {"breakdown"} == {"correct", "attempted", "failed",
                                           "metrics", "device", "checks"}
    assert list(result)[-1] == "checks" and all(
        c["ok"] and c["value"] <= c["limit"]
        for c in result["checks"].values())
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    section = "per_layer" if trace else "end_to_end"
    mine = {m["name"] for m in manifest[section]
            if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) <= mine
    if trace:
        # on the CPU there is no device plane: the device readers return
        # nothing and are left out; the counters are there and read 0
        name = [n for n in mine if n.startswith("compiles_in_window")]
        assert result["metrics"][name[0]]["value"] == 0
        assert {"busy_s", "window_s"} <= set(result["device"])
    else:
        assert set(result["metrics"]) == mine
        assert all(v["value"] > 0 for v in result["metrics"].values())


@pytest.mark.parametrize("cell,trace", RUNS)
def test_every_run_prints_where_its_wall_time_went(ran, cell, trace):
    """One line ``chipbench: phases <name>=<seconds> ... total=<seconds>``
    on an earlier line; the last line is still the one JSON object."""
    result, lines = ran(cell, trace)
    assert lines[-1].startswith("{") and set(result) >= {"correct", "metrics"}
    (line,) = [l for l in lines if l.startswith("chipbench: phases ")]
    assert lines.index(line) < len(lines) - 1
    parts = [kv.split("=") for kv in line.split()[2:]]
    seconds = {k: float(v) for k, v in parts}
    assert len(seconds) == len(parts), "a phase is named twice"
    total = seconds.pop("total")
    assert sum(seconds.values()) == pytest.approx(
        total, abs=0.005 * (len(seconds) + 1))
    kind = "train" if "train" in cell else "serve"
    want = {"imports", "devices", "build", "window", "reference", "result"}
    want |= {"serve": {"warm_up", "lead_in", "drain", "shutdown"},
             "train": {"first_steps", "trace_start"}}[kind]
    if trace:
        want |= {"trace_read", "readers"} | (
            {"trace_stop"} if kind == "serve" else set())
    assert set(seconds) == want
    # (a traced training run writes its trace out inside the window)
    assert seconds["window"] >= 1.49 and all(
        v >= 0 for v in seconds.values())


# the last arrivals of a window, in seconds before its end (the
# StarCoder2 mix's: chipbench/traffic/code-decode.json at 51 s)
ARRIVALS = [1.556, 3.770, 3.946, 4.019, 4.788]


@pytest.mark.parametrize("iters,admissions,before_end,want,by", [
    # a window that holds no arrival: the iterations' lead alone, as
    # before PR 40
    (416, 2, (), 32 * 47.0 / 416, "iterations"),      # 113 ms: 3.6 s
    (904, 2, (), 32 * 47.0 / 904, "iterations"),      # 52 ms: 1.7 s
    (300, 2, (), 4, "trace_seconds"),                 # slower: the cap
    (0, 2, (), 4, "iterations"),                      # an idle engine
    # 12 ms an iteration: 0.4 s would hold no arrival, so the lead is the
    # second-last arrival's (one: the last one's), 0.1 s before it
    (3800, 2, ARRIVALS, 3.770 + 0.1, "arrivals"),
    (3800, 1, ARRIVALS, 1.556 + 0.1, "arrivals"),
    # an engine slow enough to hold them anyway: the iterations' lead
    (904, 1, ARRIVALS, 32 * 47.0 / 904, "iterations"),
    (416, 2, [0.05, 0.2, 0.9], 32 * 47.0 / 416, "iterations"),
    # the cap stands above both
    (3800, 3, ARRIVALS, 4, "trace_seconds"),
    (300, 2, ARRIVALS, 4, "trace_seconds"),
    # fewer arrivals in the window than asked for: the iterations' lead
    (3800, 2, [1.5], 32 * 47.0 / 3800, "iterations"),
    (3800, 5, ARRIVALS[:4], 32 * 47.0 / 3800, "iterations"),
])
def test_trace_lead_follows_the_pace_up_to_the_cap(iters, admissions,
                                                   before_end, want, by):
    mix = {"trace_seconds": 4, "trace_iterations": 32,
           "trace_admissions": admissions}
    stats_, s0 = {"iterations": 100 + iters}, {"iterations": 100}
    assert serve_kind.trace_lead(mix, stats_, s0, 47.0, before_end) \
        == (pytest.approx(want), by)


@pytest.mark.parametrize("iterations,lead_s,admissions", [
    (3, None, 1), (10 ** 6, 1.0, 1), (1, None, 3), (10 ** 6, 1.0, 3)])
def test_traced_part_is_bounded_by_iterations_then_seconds(
        tmp_path, iterations, lead_s, admissions):
    """Counts from ``stats()`` and the start the kind chose, no device
    times. Every token costs the engine's thread 50 ms here (a slow
    client), so a pass of the loop takes 0.2 s at four rows and the
    profiler's own start (inside the lead) is short beside it. With
    ``trace_admissions`` the traced part also reaches back to 0.1 s
    before that many of the window's last arrivals, where the
    iterations' part is the shorter."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    root = tiny.write_root(str(tmp_path), real, serve={
        "trace_iterations": iterations, "trace_seconds": lead_s or 3.0,
        "rate_per_s": 4.0, "trace_admissions": admissions})

    def slow(on_token):
        def call(tok, i):
            time.sleep(0.05)
            return on_token(tok, i)
        return call

    cell, kind, devices = run.open_cell(root, "tiny-serve-cell", SEED, 4.0,
                                        1, False)
    out = kind.run(cell, devices, run.Tracer(cell), time.perf_counter(),
                   broken=slow)
    c = out["counters"]
    assert out["failed"] == 0
    pace = 4.0 / c["iterations"]
    assert 0.15 < pace < 0.4
    due = sorted(cell.traffic["lead_in_s"] + 4.0 - r["due_s"]
                 for r in traffic_gen.serve_requests(
                     cell.traffic, SEED, 4.0, cell.config["vocab_size"])
                 if r["in_window"])
    if due[admissions - 1] + 0.1 > iterations * 0.4:
        # the arrivals' lead is the longer whatever the pace
        assert c["trace_lead_by"] == "arrivals"
        assert c["trace_lead_s"] == pytest.approx(
            due[admissions - 1] + 0.1, abs=0.08)
    elif lead_s is None:
        assert c["trace_lead_by"] == "iterations"
        assert 2 <= c["traced_iterations"] <= 5, c
        assert c["trace_lead_s"] == pytest.approx(3 * pace, rel=0.35)
    else:
        assert c["trace_lead_by"] == "trace_seconds"
        assert c["trace_lead_s"] == pytest.approx(lead_s, abs=0.25)
        assert c["traced_iterations"] == pytest.approx(lead_s / pace, abs=2)
    # the profiler's start falls inside the lead; a sleep may overshoot
    assert c["traced_seconds"] <= c["trace_lead_s"] + 0.25
    assert os.path.exists(run.Tracer(cell).xplane())


def test_train_step_that_returns_its_state_unchanged_is_not_correct(
        root, monkeypatch):
    def broken(step):
        def call(params, opt_state, batch):
            out = step(params, opt_state, batch)
            return out._replace(params=params, opt_state=opt_state)
        return call

    real = train_kind.run
    monkeypatch.setattr(train_kind, "run",
                        lambda *a: real(*a, broken=broken))
    result = run.run_cell(cell_args("tiny-train-cell"), root=root,
                          require_chip=False)
    assert result["correct"] is False
    assert any(not c["ok"] for c in result["checks"].values())


def test_served_token_altered_where_it_is_produced_is_not_correct(
        root, monkeypatch):
    def broken(on_token):
        # every 7th token of a stream becomes its neighbour in the vocabulary
        return lambda tok, i: on_token(tok + 1 if i % 7 == 3 else tok, i)

    real = serve_kind.run
    monkeypatch.setattr(serve_kind, "run",
                        lambda *a: real(*a, broken=broken))
    result = run.run_cell(cell_args("tiny-serve-cell"), root=root,
                          require_chip=False)
    assert result["correct"] is False
    assert any(not c["ok"] for c in result["checks"].values())


def test_engine_is_freed_before_the_reference_walks_the_model(
        root, monkeypatch):
    """The peak memory a run reports is the program's: nothing may hold
    the engine (its weights and pages) while the reference runs. A traced
    run, whose load runs on a thread of its own."""
    import weakref

    engines, alive = [], []
    real_build, real_gaps = serve_kind.build, serve_logits.served_gaps

    def build(*a):
        eng = real_build(*a)
        engines.append(weakref.ref(eng))
        return eng

    def gaps(*a, **k):
        alive.append(engines[0]() is not None)
        return real_gaps(*a, **k)

    monkeypatch.setattr(serve_kind, "build", build)
    monkeypatch.setattr(serve_logits, "served_gaps", gaps)
    result = run.run_cell(cell_args("tiny-serve-cell", trace=1), root=root,
                          require_chip=False)
    assert result["correct"] is True and alive == [False]


def test_train_control_in_fp8_fails_a_limit():
    """The reference in the program's place, computed in fp8: one of the
    compared numbers has to pass its limit (on the chip at the cell's own
    size: PERF.md section 2)."""
    limits = tiny.LIMITS["tiny-train-cell"]
    feed = traffic_gen.TrainFeed(tiny.TRAIN, 5, tiny.GPT2["vocab_size"], 1)
    batches = [feed.batch(0)]
    opt = tiny.TRAIN["optimizer"]
    ref = train_steps.follow(tiny.GPT2, 5, batches, opt, 2)
    low = train_steps.follow(tiny.GPT2, 5, batches, opt, 2,
                             mm=lowprec.mm_fp8)
    loss_gap = abs(low["losses"][0] - ref["losses"][0]) / ref["losses"][0]
    grad_gap = train_kind.worst_leaf_gap(low["grad_norms"],
                                         ref["grad_norms"])
    assert (loss_gap > limits["loss_rel_gap"]
            or grad_gap > limits["grad_norm_worst_leaf"]), \
        (loss_gap, grad_gap)


def test_serve_control_in_fp8_fails_the_limit():
    """The token fp8 puts first lies further below the reference's best
    than the limit allows, somewhere in a few hundred positions."""
    cfg = dict(tiny.STARCODER2, initializer_range=0.15)   # logits of order 1
    rng = np.random.default_rng(3)
    samples = [(rng.integers(0, 211, 16).astype(np.int32),
                rng.integers(0, 211, 48).astype(np.int32))
               for _ in range(8)]
    out = serve_logits.served_gaps(cfg, SEED, samples, jnp.bfloat16,
                                   width=64, max_new=48,
                                   control_mm=lowprec.mm_fp8)
    worst = max(float(g.max()) for g in out["control"])
    assert worst > tiny.LIMITS["tiny-serve-cell"]["served_logit_gap_max"], \
        worst


@pytest.mark.parametrize("seed", [7, SEED])
def test_traffic_is_a_pure_function_of_the_seed(seed):
    a = traffic_gen.serve_requests(tiny.SERVE, seed, 2.0, 211)
    b = traffic_gen.serve_requests(tiny.SERVE, seed, 2.0, 211)
    c = traffic_gen.serve_requests(tiny.SERVE, seed + 1, 2.0, 211)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, b))
    # another seed: the same requests at the same times, other tokens
    wa = [r for r in a if r["in_window"]]
    wc = [r for r in c if r["in_window"]]
    sizes = lambda w: [(r["due_s"], len(r["prompt"]), r["max_new"])
                       for r in w]
    assert len(wa) == len(wc) == round(tiny.SERVE["rate_per_s"] * 2.0)
    assert sizes(wa) == sizes(wc)
    assert not np.array_equal(wa[0]["prompt"], wc[0]["prompt"])
    lead = tiny.SERVE["lead_in_s"]
    assert all(lead <= r["due_s"] < lead + 2.0 for r in wa)
    assert [r["due_s"] for r in a] == sorted(r["due_s"] for r in a)
    assert set(r["max_new"] for r in a) <= set(
        traffic_gen.answer_grid(tiny.SERVE))
    f = traffic_gen.TrainFeed(tiny.TRAIN, seed, 211, 1)
    assert np.array_equal(f.batch(3), f.batch(3))
    assert not np.array_equal(f.batch(3), f.batch(4))
    assert len({tuple(r) for r in f.batch(0)}) == f.rows


@pytest.mark.parametrize("cfg,name", [(tiny.GPT2, "gpt2"),
                                      (tiny.STARCODER2, "starcoder2")])
def test_reference_agrees_with_the_program_in_float32(cfg, name):
    import importlib

    from distributed_pytorch_tpu import models

    adapter = importlib.import_module(f"chipbench.adapters.{name}")
    fam = W.family(cfg)
    w = W.make(SEED, cfg, jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 24)), jnp.int32)
    x = fam.embed(w["globals"], tokens, cfg)
    for wl in w["layers"]:
        x = fam.block(wl, x, cfg)
    ref = fam.head(w["globals"], x, cfg)
    model = models.TransformerLM(**adapter.model_kwargs(cfg),
                                 dtype=jnp.float32)
    got = model.apply(adapter.to_program(w), tokens)
    # float32 on both sides, another order of operations; the window of
    # 4096 is inert at 24 positions
    np.testing.assert_allclose(got, ref, atol=2e-6)
    again = W.make_layer(SEED, cfg, 1, jnp.float32)
    assert all(np.array_equal(again[k], w["layers"][1][k]) for k in again)


def test_starcoder2_reference_window_binds_when_short():
    cfg = dict(tiny.STARCODER2, sliding_window=8)
    fam = W.family(cfg)
    w = W.make(SEED, cfg, jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 211, (1, 24)), jnp.int32)
    x = fam.embed(w["globals"], tokens, cfg)
    wide = fam.block(w["layers"][0], x, tiny.STARCODER2)
    narrow = fam.block(w["layers"][0], x, cfg)
    np.testing.assert_allclose(narrow[:, :8], wide[:, :8], atol=1e-6)
    assert float(jnp.max(jnp.abs(narrow[:, 8:] - wide[:, 8:]))) > 1e-4


def test_run_py_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
    assert not last.startswith("{"), "a result line was printed"
