"""The family ``joyai`` through the benchmark on the CPU at a tiny size:
the program (``TransformerLM(mtp=1)``, ``lm_mtp_loss``,
``make_train_step(buffers=...)``) against the independent float32
reference (forward, both losses, every leaf's gradient, three steps of
the follower), a tiny cell through ``run.py``'s test entry, the fp8
control, a step that returns its state unchanged and one given half of
its batch each failing a limit,
the real configuration file against the catalog's numbers, the count of
operations against the issue's table, and the by-hand split of a step."""

import contextlib
import io
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_joyai
from chipbench import flops_joyai, lowprec, run, traffic_gen
from chipbench import weights as W
from chipbench.adapters import joyai as adapter
from chipbench.kinds import train_mtp as kind
from chipbench.reference import joyai as reference
from chipbench.reference import train_steps_mtp
from distributed_pytorch_tpu import models, optim
from distributed_pytorch_tpu.ops.losses import lm_mtp_loss
from distributed_pytorch_tpu.parallel import Buffers, make_train_step

REPO = run.REPO
SEED = 2 ** 31 + 3232
CFG, JOB = tiny_joyai.JOYAI, tiny_joyai.TRAIN
REAL = os.path.join(REPO, "chipbench/configs/joyai-llm-flash-1chip.json")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    return tiny_joyai.write_root(str(tmp_path_factory.mktemp("tinyjoyai")),
                                 real)


@pytest.fixture(scope="module")
def batches():
    feed = traffic_gen.TrainFeed(JOB, SEED, CFG["vocab_size"], 1)
    return [feed.batch(i) for i in range(3)]


@pytest.fixture(scope="module")
def model():
    return models.TransformerLM(**adapter.model_kwargs(CFG, max_len=64))


def reference_loss(w, tokens):
    """The whole model in the reference's words: (total, main, mtp,
    loads of the walked layers then the module's)."""
    g = w["globals"]
    x, loads = reference.embed(g, tokens[:, :-1], CFG), []
    for layer in w["layers"]:
        x, load = reference.block(layer, x, CFG)
        loads.append(load)
    main = reference.main_loss_sum(g, x, tokens, CFG)
    mtp, load = reference.mtp_loss_sum(g, x, tokens, CFG)
    n = tokens.shape[0]
    main, mtp = main / (n * (tokens.shape[1] - 1)), \
        mtp / (n * (tokens.shape[1] - 2))
    return main + JOB["mtp_weight"] * mtp, (main, mtp,
                                            jnp.stack(loads + [load]))


def test_forward_losses_and_every_gradient_agree_in_float32(model, batches):
    w = W.make(SEED, CFG, jnp.float32)
    tokens = jnp.asarray(batches[0])
    with jax.default_matmul_precision("highest"):
        (want, (w_main, w_mtp, w_load)), g_want = jax.jit(jax.value_and_grad(
            reference_loss, has_aux=True))(w, tokens)
        (got, aux), g_got = jax.jit(jax.value_and_grad(
            lambda p: lm_mtp_loss(model, p, tokens,
                                  weight=JOB["mtp_weight"]),
            has_aux=True))(adapter.to_program(w))
    assert 4.0 < float(w_main) < 8.0 and 4.0 < float(w_mtp) < 8.0
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(aux["loss_main"], w_main, rtol=2e-6)
    np.testing.assert_allclose(aux["loss_mtp"], w_mtp, rtol=2e-6)
    # the router is whole: 2 rows x 32 positions x 4 a token a layer, the
    # module one position fewer a row
    assert np.array_equal(aux["moe_load"], w_load)
    assert np.asarray(w_load).sum(-1).tolist() == [256, 248]
    first, held = CFG["experts_held_first"], CFG["n_routed_experts"]
    assert float(aux["moe_pairs_here"]) == float(
        np.asarray(w_load)[:, first:first + held].sum())
    flat_got = adapter.from_program(g_got)
    for got_group, want_group in [(flat_got["globals"], g_want["globals"])] \
            + list(zip(flat_got["layers"], g_want["layers"])):
        assert set(got_group) == set(want_group)
        for name, x in want_group.items():
            scale = float(jnp.max(jnp.abs(x))) or 1.0
            np.testing.assert_allclose(got_group[name], x, rtol=0,
                                       atol=2e-5 * scale, err_msg=name)
            # the choice has no gradient, so the bias has none
            assert reference.is_router_bias(name) == (not np.asarray(x).any())


def three_steps_in_float32(model, batches, opt, loads=None):
    """The program's step with the buffers' contract, in float32 at
    ``highest``, from the seed's weights through ``batches`` under
    ``opt``: (losses, parameters after, parameters before, AdamW's
    state). ``loads``: the reference's, which every step's have to
    equal."""
    buffers = Buffers(mask=model.router_bias_mask,
                      update=lambda p, aux: model.balance_router_bias(
                          p, aux["moe_load"], JOB["bias_update_speed"]))
    params = adapter.to_program(W.make(SEED, CFG, jnp.float32))
    state = opt.init(buffers.trainable(params))
    step = make_train_step(
        lambda p, t: lm_mtp_loss(model, p, t, weight=JOB["mtp_weight"]),
        opt, mixed_precision="off", donate=False, buffers=buffers)
    losses, start = [], params
    with jax.default_matmul_precision("highest"):
        for i, batch in enumerate(batches):
            out = step(params, state, jnp.asarray(batch))
            params, state = out.params, out.opt_state
            losses.append(float(out.loss[0]))
            if loads is not None:
                assert np.array_equal(out.metrics["moe_load"], loads[i])
    return losses, params, start, getattr(state, "inner", state)


def leaf_changes(now, was, like):
    """The norm of every leaf's change, shaped as the reference's
    ``delta_norms`` (``like``) and under its names."""
    now, was = adapter.from_program(now), adapter.from_program(was)
    norm = lambda a, b: float(jnp.sqrt(jnp.sum(jnp.square(a - b))))
    group = lambda a, b, names: {n: norm(a[n], b[n]) for n in names}
    return {"globals": group(now["globals"], was["globals"],
                             like["globals"]),
            "layers": [group(a, b, names) for a, b, names in zip(
                now["layers"], was["layers"], like["layers"])]}


@pytest.mark.parametrize("job", [JOB, tiny_joyai.TRAIN_WARMUP],
                         ids=["constant", "warm_up"])
def test_three_steps_follow_the_reference_in_float32(model, batches, job):
    """The program's step with the buffers' contract against the
    follower: losses, every leaf after three steps, the biases moved by
    the rule alone and AdamW's state holding no moment for them. At the
    job's constant rate, and under a warm-up of a few steps, where the
    program runs ``with_schedule`` and the follower its own law."""
    ref = train_steps_mtp.follow(CFG, SEED, batches, job)
    losses, params, start, state = three_steps_in_float32(
        model, batches, kind.optimizer(job["optimizer"]), ref["loads"])
    np.testing.assert_allclose(losses, ref["losses"], rtol=5e-6)
    # no moments were made for a bias, and none came into being
    for moments in (state.mu, state.nu):
        flat = adapter.from_program(moments)
        assert not any(reference.is_router_bias(n)
                       for group in [flat["globals"]] + flat["layers"]
                       for n in group)
    want, got = ref["delta_norms"], leaf_changes(params, start,
                                                 ref["delta_norms"])
    for g, w in [(got["globals"], want["globals"])] + list(zip(
            got["layers"], want["layers"])):
        for name in w:
            assert g[name] == pytest.approx(float(w[name]), rel=2e-4), name
    now, was = adapter.from_program(params), adapter.from_program(start)
    # three steps of +-0.001 (or 0 where the load sat on the mean): the
    # rule alone moved the biases, by whole multiples of the speed. AdamW
    # with decay would have left other values
    moved = np.asarray(now["layers"][0]["b_router"]
                       - was["layers"][0]["b_router"], np.float64) / 0.001
    assert np.abs(moved - np.rint(moved)).max() < 1e-3
    assert set(np.rint(moved).astype(int)) <= {-3, -2, -1, 0, 1, 2, 3}
    assert np.abs(moved).max() >= 1


def test_program_at_the_constant_rate_fails_the_scheduled_reference(
        model, batches):
    """What holds a step to the job's schedule: the follower runs the
    warm-up's law, so a program that ignores it (the optimizer the job
    built before it named ``warmup_steps``) moves every leaf by 3 x the
    peak where the ramp's three steps sum to 1.5 x, and reads about 1 in
    ``param_change_worst_leaf`` (1.19 at this seed: the worst leaf's
    steps do not all point one way); the scheduled program reads
    rounding, 1.6e-5."""
    job = tiny_joyai.TRAIN_WARMUP
    ref = train_steps_mtp.follow(CFG, SEED, batches, job)
    gap = {}
    for name, o in (("scheduled", job["optimizer"]),
                    ("constant", JOB["optimizer"])):
        _, params, start, _ = three_steps_in_float32(
            model, batches, kind.optimizer(o))
        gap[name] = kind.worst_leaf_gap(
            leaf_changes(params, start, ref["delta_norms"]),
            ref["delta_norms"])
    print(gap)
    limit = tiny_joyai.LIMITS["param_change_worst_leaf"]
    assert gap["scheduled"] < 1e-3 < limit < gap["constant"]
    assert 0.8 < gap["constant"] < 1.5      # about double, on every leaf


def test_job_without_warmup_steps_builds_the_optimizer_it_built():
    """``kind.optimizer``: no ``warmup_steps``, the library's AdamW at the
    constant rate, state and updates to the last bit; with them, AdamW's
    own moments under ``with_schedule``, step ``k`` (from 0) moving by
    ``min(1, (k + 1) / warmup_steps)`` of what the constant job moves."""
    o = JOB["optimizer"]
    adamw = optim.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                        weight_decay=o["weight_decay"])
    rng = np.random.default_rng(0)
    draw = lambda: {"w": jnp.asarray(0.02 * rng.normal(size=(8, 4)),
                                     jnp.float32)}
    params, grads = draw(), [draw() for _ in range(6)]

    def moves(opt):
        """Every update's move of ``w``, and the state after the last."""
        p, s, out = params, opt.init(params), []
        for g in grads:
            new, s = opt.update(g, s, p)
            out.append(np.asarray(new["w"] - p["w"]))
            p = new
        return out, s

    same = lambda a, b: jax.tree_util.tree_all(jax.tree_util.tree_map(
        np.array_equal, a, b))
    want, s_want = moves(adamw)
    got, s_got = moves(kind.optimizer(o))
    assert type(s_got) is type(s_want)      # no schedule's wrapper
    assert same(got, want) and same(s_got, s_want)
    assert kind.moments_shown(s_got) is s_got
    ramp, s_ramp = moves(kind.optimizer(dict(o, warmup_steps=4)))
    assert int(s_ramp.step) == 6 and same(s_ramp.inner, s_want)
    assert kind.moments_shown(s_ramp).mu is s_ramp.inner.mu
    for k in range(6):
        np.testing.assert_allclose(ramp[k], min(1.0, (k + 1) / 4) * want[k],
                                   rtol=2e-3, atol=2e-8)
    # the first move is a quarter of the peak an element: AdamW's first
    # update is the gradient's sign
    assert float(np.abs(ramp[0]).mean()) == pytest.approx(o["lr"] / 4,
                                                          rel=0.02)


def run_once(root, seed, trace):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.main(["--workload", tiny_joyai.CELL, "--seed", str(seed),
                  "--seconds", "1.5", "--trace", str(trace)], root=root,
                 require_chip=False)
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_end_to_end(root, trace):
    result, lines = run_once(root, SEED + trace, trace)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["checks"]) == {
        "loss_rel_gap.step1", "loss_rel_gap.step2", "loss_rel_gap.step3",
        "grad_norm_worst_leaf", "param_change_worst_leaf",
        "window_losses_not_finite", "window_loss_last_minus_first",
        "router_pairs_elsewhere_share"}
    print({k: v["value"] for k, v in result["checks"].items()})
    (means,) = [l for l in lines if "window means of the step's" in l]
    means = json.loads(means[means.index("{"):])
    assert set(means) == set(kind.STEP_COUNTERS) | {"moe_pairs_routed"}
    assert 0 < means["moe_pairs_here"] < 2 * means["moe_pairs_routed"]
    assert means["moe_bias_abs_max"] > 0.005
    if trace:
        # on the CPU there is no device plane: the trace-reading metrics
        # find nothing, the counters' ones report
        assert result["metrics"]["compiles_in_window.train.moe"][
            "value"] == 0
        assert any("nothing to read" in l for l in lines)
        assert not {"train_mfu", "flash_roofline"} & set(result["metrics"])
    else:
        # the rate under the name the job gives it (``rate_metric``)
        assert set(result["metrics"]) == {"train_tokens_per_s.moe",
                                          "setup_s"}
        assert result["metrics"]["train_tokens_per_s.moe"]["value"] > 0


def test_step_that_returns_its_state_unchanged_is_not_correct(
        root, monkeypatch):
    def broken(step):
        def call(params, opt_state, batch):
            out = step(params, opt_state, batch)
            return out._replace(params=params, opt_state=opt_state)
        return call

    real = kind.run
    monkeypatch.setattr(kind, "run", lambda *a: real(*a, broken=broken))
    result = run.run_cell(["--workload", tiny_joyai.CELL, "--seed",
                           str(SEED), "--seconds", "1.5", "--trace", "0"],
                          root=root, require_chip=False)
    assert result["correct"] is False
    assert not result["checks"]["param_change_worst_leaf"]["ok"]


@pytest.mark.parametrize("left_out", ["rows", "positions"])
def test_half_of_the_batch_left_out_is_not_correct(root, monkeypatch,
                                                   left_out):
    """The step given half of what the feed hands it, the mean taken over
    the rest: one of the two rows, or the first half of every row's
    positions (what ``chipbench/faults_train.py --faults half_batch``
    plants at the cell's own size, where a step has one row). The run
    comes out not correct through the harness's own comparison, by the
    first gradient; the first loss alone need not see it (the two halves
    of a seeded batch lose alike)."""
    def broken(step):
        def call(params, opt_state, batch):
            half = batch[:1] if left_out == "rows" \
                else batch[:, :batch.shape[1] // 2 + 1]
            return step(params, opt_state, half)
        return call

    real = kind.run
    monkeypatch.setattr(kind, "run", lambda *a: real(*a, broken=broken))
    result = run.run_cell(["--workload", tiny_joyai.CELL, "--seed",
                           str(SEED + 11), "--seconds", "1.0", "--trace",
                           "0"], root=root, require_chip=False)
    print({k: v["value"] for k, v in result["checks"].items()})
    assert result["correct"] is False
    assert not result["checks"]["grad_norm_worst_leaf"]["ok"]
    assert result["checks"]["grad_norm_worst_leaf"]["value"] \
        > 3 * tiny_joyai.LIMITS["grad_norm_worst_leaf"]


def test_tiny_cell_under_a_warm_up_is_correct_and_is_held_to_it(
        tmp_path, monkeypatch):
    """The whole run with the job under a schedule: ``build`` takes
    ``with_schedule``, ``_drive`` finds the first moment one level down,
    the reference's process follows the ramp, and every check holds in
    bfloat16. Then the same run with the program built at the constant
    rate, the schedule ignored: not correct, by
    ``param_change_worst_leaf``."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    root = tiny_joyai.write_root(str(tmp_path), real,
                                 train=tiny_joyai.TRAIN_WARMUP)
    args = ["--workload", tiny_joyai.CELL, "--seed", str(SEED + 7),
            "--seconds", "1.0", "--trace", "0"]
    result = run.run_cell(args, root=root, require_chip=False)
    assert result["correct"], result["checks"]
    print({k: v["value"] for k, v in result["checks"].items()})
    built = kind.optimizer
    monkeypatch.setattr(kind, "optimizer", lambda o: built(
        {k: v for k, v in o.items() if k != "warmup_steps"}))
    result = run.run_cell(args, root=root, require_chip=False)
    assert result["correct"] is False
    assert not result["checks"]["param_change_worst_leaf"]["ok"]
    assert result["checks"]["grad_norm_worst_leaf"]["ok"]


def test_control_in_fp8_fails_a_limit_of_the_tiny_cell(batches):
    """The reference in the program's place, computed in fp8 (router,
    dense layer and norms unrounded): one of the compared numbers has to
    pass its limit."""
    ref = train_steps_mtp.follow(CFG, SEED, batches[:1], JOB)
    low = train_steps_mtp.follow(CFG, SEED, batches[:1], JOB,
                                 mm=lowprec.mm_fp8)
    loss_gap = abs(low["losses"][0] - ref["losses"][0]) / ref["losses"][0]
    grad_gap = kind.worst_leaf_gap(low["grad_norms"], ref["grad_norms"])
    moved = kind.pairs_elsewhere_share(low["loads"][0], ref["loads"][0],
                                       CFG, JOB, len(batches[0]))
    print(f"fp8 control reads loss {loss_gap:.4g} gradient {grad_gap:.4g} "
          f"pairs elsewhere {moved:.4g}")
    assert (loss_gap > tiny_joyai.LIMITS["loss_rel_gap"]
            or grad_gap > tiny_joyai.LIMITS["grad_norm_worst_leaf"]
            or moved > tiny_joyai.LIMITS["router_pairs_elsewhere_share"])


def test_configuration_file_keeps_every_published_number():
    with open(REAL) as f:
        cfg = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "JoyAI-LLM-Flash")
        assert cfg["published"] == row["config"]
        assert cfg["source"] == row["source_url"]
    reduced = set(cfg["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in cfg["published"].items():
        if key in reduced:
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 16, 129280 // 8)
    assert cfg["router_width"] == cfg["published"]["n_routed_experts"] == 256
    assert cfg["chips_sharing_a_layer"] * cfg["n_routed_experts"] == 256
    assert cfg["num_nextn_predict_layers"] == 1
    kw = adapter.model_kwargs(cfg, max_len=8192)
    assert kw["block_kinds"] == ("dense",) + ("moe",) * 4 and kw["mtp"] == 1
    assert kw["moe"]["held"] == (0, 16) and kw["moe"]["n_routed"] == 256
    assert kw["latent"]["nope_dim"] + kw["latent"]["rope_dim"] == 192
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == cfg["name"])
    assert set(entry["reduced"]) == reduced and "16" in entry["why"]
    # every leaf the reference names has a place in the program
    specs = reference.leaf_specs({k: v for k, v in cfg.items()
                                  if isinstance(v, (int, float, str, bool))})
    for name, *_ in specs["globals"]:
        assert (name in adapter.GLOBALS or name[2:] in adapter.DENSE
                or name[2:] in adapter.MODULE), name
    assert {n for n, *_ in specs["layer"]} == set(adapter.EXPERT)
    count = lambda group: sum(int(np.prod(shape)) for _, shape, *_ in group)
    n_params = count(specs["globals"]) + 4 * count(specs["layer"])
    assert abs(n_params - 680.4e6) < 1e5, n_params


def test_flops_against_the_issues_table():
    """Forward MFLOP a token at 8192: ISSUE.md (PR 32), Tentpole 4."""
    with open(REAL) as f:
        cfg = json.load(f)
    p = {k: v / 1e6 for k, v in flops_joyai.parts(
        cfg, 8192, padded_values=True).items()}
    layers = 6                              # five blocks and the module's
    assert p["attn_projections"] / layers == pytest.approx(52.7, abs=0.05)
    assert p["attn_core"] / layers == pytest.approx(100.7, abs=0.05)
    assert p["dense_mlp"] == pytest.approx(88.1, abs=0.05)
    assert p["router"] / 5 == pytest.approx(1.0, abs=0.05)
    assert p["shared_experts"] / 5 == pytest.approx(9.4, abs=0.05)
    assert p["routed_here"] / 5 == pytest.approx(4.7, abs=0.05)
    assert p["heads"] / 2 == pytest.approx(66.2, abs=0.05)
    assert p["mtp_merge"] == pytest.approx(16.8, abs=0.05)
    assert sum(p.values()) == pytest.approx(1233, abs=1)
    plain = flops_joyai.parts(cfg, 8192)
    assert plain["attn_core"] / layers / 1e6 == pytest.approx(83.9, abs=0.05)
    assert flops_joyai.train_flops_per_token(cfg, 8192) \
        == 3 * sum(plain.values())
    # what the router really sent replaces the uniform expectation
    assert flops_joyai.parts(cfg, 8192, pairs_here_per_token=1.0)[
        "routed_here"] == 2 * plain["routed_here"]


def test_scope_split_train_reads_a_step_by_pass_and_part(monkeypatch):
    """Two executions of a step program of hand-made operations: the
    grouped matmul's custom calls carry no name stack and are told by
    their name, the flash kernel is the Mosaic call that is not one of
    them, and forward, recompute and backward are JAX's own words."""
    import types

    from chipbench import program_trace, scope_split_train

    ms = 1_000_000
    pre = "jit(local_step)/"
    ops = [("fusion.1", pre + "jvp(loss)/loss/blocks/moe/route/dot:", 1, ""),
           ("fusion.2", pre + "jvp(loss)/loss/blocks/moe/dispatch/gather:",
            2, ""),
           ("ragged-dot-none.3", "", 3, "tpu_custom_call"),
           ("custom-call.4", pre + "jvp(loss)/loss/blocks/attn/core/"
            "pallas_call:", 5, "tpu_custom_call"),
           ("fusion.5", pre + "transpose(jvp(loss))/loss/rematted_"
            "computation/blocks/moe/combine/mul:", 2, ""),
           ("fusion.6", pre + "transpose(jvp(loss))/loss/blocks/moe/shared/"
            "mlp/dot:", 4, ""),
           ("fusion.7", pre + "jvp(loss)/loss/mtp/blocks/merge/dot:", 1, ""),
           ("fusion.8", pre + "transpose(jvp(loss))/loss/loss/mtp/dot:", 2,
            ""),
           ("fusion.9", pre + "optimizer/buffers/moe/bias_update/add:", 1,
            "")]
    per_step = sum(o[2] for o in ops)
    timeline, stats, t = [], [], 0
    modules = []
    for _ in range(2):
        modules.append(("jit_local_step(123)", t, t + per_step * ms))
        for short, stack, dur, target in ops:
            timeline.append((short, t, t + dur * ms, stack))
            stats.append({"hlo": f'%{short} = custom-call(), custom_call_'
                                 f'target="{target}"'})
            t += dur * ms
        t += 4 * ms                         # the device idles between steps
    pt = types.SimpleNamespace(ops={0: timeline}, stats={0: stats},
                               modules={0: modules}, memo={})
    monkeypatch.setattr(program_trace, "of", lambda cell: pt)
    with open(REAL) as f:
        cfg = json.load(f)
    cell = types.SimpleNamespace(
        config=cfg, traffic={"rows_per_chip": 1, "seq": 8192,
                             "remat": "full"},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    split = scope_split_train.step_scope_ms(cell)
    assert split["steps"] == 2 and split["total"] == per_step
    assert split["step_period_ms"] == per_step + 4
    assert split["flash"] == 5 and split["grouped_matmul"] == 3
    assert split["moe"]["fwd"] == {"route": 1, "dispatch": 2, "experts": 0,
                                   "shared": 0, "combine": 0, "all": 3}
    assert split["moe"]["remat"]["combine"] == 2
    assert split["moe"]["bwd"]["shared"] == 4
    assert split["moe"]["unplaced"]["experts"] == 3
    assert split["mtp"] == {"fwd": 1, "remat": 0, "bwd": 2, "other": 0}
    got = scope_split_train.readings(
        cell, {"moe_pairs_here": 5 * 8192 * 0.5, "moe_load_max": 600.0,
               "moe_load_mean": 256.0, "moe_pairs_routed": 8192 * 8},
        say=lambda *_: None)
    assert got["moe_train_device_ms"] == 3 + 3 + 2 + 4
    assert got["mtp_device_ms"] == 3
    assert got["moe_load_max_over_mean"] == 600 / 256
    assert got["moe_pairs_here_share"] == pytest.approx(100 / 16)
    tps = 8192 / ((per_step + 4) / 1e3)
    assert got["train_mfu"] == pytest.approx(
        100 * flops_joyai.train_flops_per_token(cfg, 8192) * tps / 197e12)
    assert got["train_mfu_as_run_padded"] > got["train_mfu"]
    assert 0 < got["flash_roofline"] and "flash_roofline" in got
    # a CPU run has no device plane: nothing to read, nothing raised
    monkeypatch.setattr(program_trace, "of", lambda cell: None)
    assert scope_split_train.readings(cell) == {}
