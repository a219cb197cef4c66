"""The family ``minicpm_sala`` through the benchmark on the CPU at a tiny
size: the reference's one-request-at-a-time walk (padded, in blocks of
rows) against its whole forward, a tiny cell through ``run.py``'s test
entry with both of its checks, the two seeded faults and the
lower-precision control failing the cell's limits, the dump's numbers on
hand-made operations, and the real configuration file against the
catalog's numbers. (The program against the reference:
``tests/test_linear_sparse_serving.py``.)"""

import contextlib
import io
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_minicpm_sala
from chipbench import lowprec, run
from chipbench import weights as W
from chipbench.adapters import minicpm_sala as adapter
from chipbench.kinds import serve_mixers
from chipbench.reference import minicpm_sala as reference
from chipbench.reference import serve_logits_mixers

REPO = run.REPO
SEED = 2 ** 31 + 4545
CFG = tiny_minicpm_sala.SALA


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    return tiny_minicpm_sala.write_root(
        str(tmp_path_factory.mktemp("tinysala")), real)


def test_the_walk_in_blocks_of_rows_is_the_whole_forward(monkeypatch):
    """A request padded to the mix's width and walked 16 rows a block
    (the last block of the per-token parts starting early, the linear
    layers' state carried over five blocks) against the same request
    whole, one block: float32 at ``highest``, the order of the sums
    differs (5e-5 of logits of order 1); and the state the walk leaves
    after row ``n - 1`` is the recurrence's."""
    rng = np.random.default_rng(0)
    p, t = (rng.integers(0, 211, n).astype(np.int32) for n in (31, 9))
    got = {}
    for rows, width in ((128, 40), (16, 72)):
        monkeypatch.setattr(reference, "ROWS", rows)
        with jax.default_matmul_precision("highest"):
            walk = serve_logits_mixers.Walk(CFG, SEED, jnp.float32)
            xs, states = walk.rows(p, t, width)
            got[rows] = (np.asarray(walk.head(walk.g, xs[0][:40])),
                         [np.asarray(s[0]) for s in states])
    np.testing.assert_allclose(got[16][0], got[128][0], atol=5e-5, rtol=0)
    assert len(got[16][1]) == CFG["n_layer"]
    for a, b in zip(got[16][1], got[128][1]):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=0)
    assert 0.5 < got[128][0].std() < 2.0


def run_once(root, seed, trace=0, fault=None):
    out = io.StringIO()
    patch = serve_mixers.FAULTS[fault]() if fault \
        else contextlib.nullcontext()
    with patch, contextlib.redirect_stdout(out):
        run.main(["--workload", tiny_minicpm_sala.CELL, "--seed", str(seed),
                  "--seconds", "1.5", "--trace", str(trace)], root=root,
                 require_chip=False)
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_end_to_end(root, trace):
    result, lines = run_once(root, SEED, trace)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    gap = result["checks"]["served_logit_gap_max"]
    assert gap["limit"] == tiny_minicpm_sala.LIMIT \
        and 0 <= gap["value"] < gap["limit"]
    state = result["checks"]["served_state_gap_max"]
    assert state["limit"] == tiny_minicpm_sala.STATE_LIMIT \
        and 0 < state["value"] < state["limit"]
    assert result["checks"]["served_state_bfloat16_share"]["value"] == 0
    if trace:
        # on the CPU there is no device plane: the trace-reading metrics
        # find nothing, the counters' ones report
        assert "decode_batch_mean" in result["metrics"]
        assert any("nothing to read" in l for l in lines)
    else:
        assert set(result["metrics"]) == {"tpot_p50_ms", "itl_p95_ms",
                                          "setup_s"}


@pytest.mark.parametrize("fault,check,sound", [
    ("dense_attention", "served_logit_gap_max",
     ("served_state_gap_max", "served_state_bfloat16_share")),
    ("bfloat16_state", "served_state_bfloat16_share",
     ("served_logit_gap_max",)),
])
def test_the_two_faults_read_not_correct(root, fault, check, sound):
    """A program that attends densely where it should select is told by
    the served tokens' logits (the probe request is a dense one: its
    state reads as in a sound run); one that keeps a linear layer's state
    in bfloat16 by the state the probe left, every value of which
    bfloat16 holds, and by no logit."""
    result, _ = run_once(root, SEED, fault=fault)
    checks = result["checks"]
    print(f"{fault}: {checks}")
    assert not result["correct"] and not checks[check]["ok"]
    assert all(checks[name]["ok"] for name in sound)
    assert checks[check]["value"] > 1.2 * checks[check]["limit"]


def test_control_in_fp8_fails_the_tiny_cells_limits():
    rng = np.random.default_rng(3)
    samples = [(rng.integers(0, 211, n).astype(np.int32),
                rng.integers(0, 211, 12).astype(np.int32))
               for n in (8, 30, 17, 60, 25, 41, 9, 52)]
    out = serve_logits_mixers.served_gaps(CFG, SEED, samples, jnp.bfloat16,
                                          width=72, max_new=12,
                                          control_mm=lowprec.mm_fp8)
    assert [len(g) for g in out["served"]] == [12] * 8
    worst = max(float(g.max()) for g in out["control"])
    states = serve_logits_mixers.served_states(
        CFG, SEED, samples[0][0], rng.integers(0, 211, 60).astype(np.int32),
        jnp.bfloat16, width=72, control_mm=lowprec.mm_fp8)
    off = serve_mixers.state_gap([c[-1:] for c in states["control"]],
                                 states["reference"])
    print(f"fp8 control reads {worst:.3f} and {off:.4f}")
    assert worst > 2 * tiny_minicpm_sala.LIMIT, worst
    assert off > 2 * tiny_minicpm_sala.STATE_LIMIT, off


def test_configuration_file_keeps_every_published_number():
    with open(os.path.join(
            REPO, "chipbench/configs/minicpm-sala-9b-1chip.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == cfg["name"])
    reduced = set(cfg["reduced"])
    assert reduced == set(entry["reduced"]) == {"num_hidden_layers",
                                                "mixer_types"}
    assert entry["source"] == cfg["source"]
    for key, value in cfg["published"].items():
        if key in reduced:
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"]) == (
                4096, 32, 2, 128, 16384, 73448)
    assert (cfg["lightning_nh"], cfg["lightning_nkv"],
            cfg["lightning_head_dim"]) == (32, 32, 128)
    assert (cfg["scale_emb"], cfg["scale_depth"], cfg["dim_model_base"],
            cfg["rms_norm_eps"], cfg["rope_theta"]) == (12, 1.4, 256, 1e-6,
                                                        10000)
    assert cfg["mixer_types"] == cfg["published"]["mixer_types"][9:17] \
        == ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]
    assert cfg["num_hidden_layers"] == cfg["n_layer"] \
        + cfg["n_sparse_layer"] == 8
    assert cfg["num_hidden_layers_published"] \
        == cfg["published"]["num_hidden_layers"] == 32
    assert set(cfg["assumed"]) >= {"sparse_config", "pooling", "decay",
                                   "dense_switch", "gates", "output_norm"}
    kw = adapter.model_kwargs(cfg, max_len=66048)
    assert kw["layer_mixers"] == ("sparse",) + ("linear",) * 6 + ("sparse",)
    assert kw["sparse"] == dict(kernel=32, stride=16, block=64, topk=64,
                                init_blocks=1, window=2048, dense_len=8192,
                                rope=False, out_gate=True)
    assert kw["emb_scale"] == 12 and kw["logit_scale"] == 16
    assert kw["branch_scale"] == pytest.approx(1.4 / 32 ** 0.5)
    with pytest.raises(ValueError, match="published switches"):
        adapter.model_kwargs(dict(cfg, attn_use_rope=True))
    # every leaf the reference names has a place in the program
    specs = reference.leaf_specs({k: v for k, v in cfg.items()
                                  if isinstance(v, (int, float, str, bool))})
    for name, *_ in specs["globals"]:
        assert name in adapter.GLOBALS or name[3:] in adapter.LAYER, name
    assert {n for n, *_ in specs["layer"]} == set(adapter.LAYER)
    n_params = sum(int(np.prod(shape)) for _, shape, *_ in specs["globals"]) \
        + 6 * sum(int(np.prod(shape)) for _, shape, *_ in specs["layer"])
    assert abs(n_params - 2.820e9) < 2e6, n_params
    # the mix: the parameters ISSUE 45 names, letter for letter
    with open(os.path.join(
            REPO, "chipbench/traffic/longctx-8k-64k.json")) as f:
        mix = json.load(f)
    assert mix["prompt_tokens"] == {"min": 8192, "max": 65536,
                                    "law": "log_uniform"}
    assert mix["answer_tokens"] == {"min": 64, "max": 512,
                                    "law": "log_uniform", "distinct": 24}
    assert mix["engine"] == {
        "paged": True, "n_slots": 32, "max_len": 66048, "page_len": 64,
        "buckets": [256, 512, 1024], "max_queue": 4096,
        "prefix_share": False}
    assert mix["prompt_tokens"]["min"] >= cfg["sparse_config"]["dense_len"]
    assert mix["state_probe"]["prompt_tokens"] \
        < cfg["sparse_config"]["dense_len"]
    assert mix["rate_per_s"] * mix["trace_seconds"] >= 1 \
        > mix["rate_per_s"] * (mix["trace_seconds"] - 1)


def test_scope_split_reads_linear_and_sparse_layers_apart(monkeypatch):
    """Two decode programs and a prefill of hand-made operations; the
    counters come from the two ``serve.stats`` marks."""
    from chipbench import program_trace, scope_split_mixers

    ms = 1_000_000
    da = "jit(_decode)/blocks/decode_attention/"
    dec = [("fusion.1", "jit(_decode)/blocks/attn/qkv/dot_general:", 2),
           ("fusion.2", "jit(_decode)/blocks/page_write/scatter:", 1),
           ("fusion.3", da + "linear_attention/state/dot_general:", 3),
           ("fusion.4", da + "sparse_attention/compress/gather:", 1),
           ("fusion.5", da + "sparse_attention/select/top_k:", 2),
           ("paged_decode_attention.6", da + "sparse_attention/attend/"
            "decode_attention/jit(paged_attention)/paged_decode_attention:",
            4),
           ("fusion.7", "jit(_decode)/blocks/mlp/dot_general:", 6)]
    core = "jit(prefill_b16)/blocks/attn/core/"
    pre = [("fusion.8", core + "linear_attention/while/body/intra/dot:", 5),
           ("fusion.9", core + "linear_attention/state/dynamic_slice:", 1),
           ("fusion.10", core + "sparse_attention/select/while/body/dot:", 3),
           ("fusion.11", core + "sparse_attention/attend/while/body/dot:",
            20)]
    ops, modules, t = [], [], 0
    for name, stacks in (("jit__decode(1)", dec), ("jit_prefill_b16(2)", pre),
                         ("jit__decode(1)", dec)):
        start = t
        for short, stack, dur in stacks:
            ops.append((short, t, t + dur * ms, stack))
            t += dur * ms
        modules.append((name, start, t))
        t += ms
    mark = lambda at, steps, chosen, resident, ctx, rows: (
        "serve.stats", 1, at, at,
        {"sparse_decode_steps": steps, "sparse_blocks_chosen": chosen,
         "sparse_blocks_resident": resident, "slots_state_reset": steps // 50,
         "state_resident_bytes": 400, "compressed_keys_resident_bytes": 130,
         "kv_resident_bytes_global": 4000, "kv_resident_bytes_window": 0,
         "state_layers": 6, "sparse_layers": 2,
         "context_tokens_mean": ctx, "context_tokens_max": 2 * ctx,
         "pages_in_use": 50, "active_slots": rows})
    pt = program_trace.ProgramTrace(
        [mark(0, 100, 50000, 400000, 29000.0, 18),
         mark(t, 102, 50000 + 2 * 7000, 400000 + 2 * 36000, 31000.0, 22)],
        {0: ops}, {0: modules}, {0: [{}] * len(ops)}, [])
    monkeypatch.setattr(program_trace, "of", lambda cell: pt)
    cell = types.SimpleNamespace(
        config={"num_key_value_heads": 2, "head_dim": 128,
                "lightning_nh": 32, "lightning_head_dim": 128,
                "sparse_config": {"block_size": 64, "kernel_stride": 16}},
        traffic={"engine": {"buckets": [8, 16]}},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    got = scope_split_mixers.readings(cell, say=lambda line: None)
    assert got["linear_attention_device_ms"] == 3.0
    assert got["sparse_attention_device_ms"] == 7.0
    assert got["sparse_select_device_ms"] == 2.0
    assert got["prefill_linear_attention_device_ms"] == 6.0
    assert got["prefill_sparse_attention_device_ms"] == 23.0
    assert got["state_resident_bytes"] == 400
    assert got["compressed_keys_resident_bytes"] == 130
    assert got["context_tokens_mean"] == 30000.0
    assert got["sparse_blocks_chosen_share"] == pytest.approx(
        100 * 14000 / 72000)
    # 7000 chosen blocks a program, each 64 x (K and V) x 128 x 2 B, and
    # the compressed keys of 20 rows of 30 k tokens in 2 layers
    least = (7000 * 64 * 2 * 128 * 2
             + 30000.0 * 20 / 16 * 2 * 2 * 128 * 2) / 819e9 * 1e3
    assert got["sparse_attention_roofline"] == pytest.approx(
        100 * least / 7.0)
    least = 2 * 20 * 6 * 32 * 128 * 128 * 4 / 819e9 * 1e3
    assert got["linear_state_roofline"] == pytest.approx(100 * least / 3.0)
    # a parent without the scopes or the marks: nothing to read
    bare = program_trace.ProgramTrace(
        [], {0: [("fusion.9", 0, ms, "jit(_decode)/blocks/mlp/dot:")]},
        {0: [("jit__decode(1)", 0, ms)]}, {0: [{}]}, [])
    monkeypatch.setattr(program_trace, "of", lambda cell: bare)
    assert scope_split_mixers.readings(cell, say=lambda line: None) == {}
