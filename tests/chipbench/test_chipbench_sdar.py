"""The family ``sdar`` through the benchmark on the CPU at a tiny size: the
program's full forward under the block-causal mask against the
independent float32 reference, the reference of generation against the
engine's own trajectory, a tiny cell of the kind ``serve_blocks`` through
``run.py``'s test entry, the lower-precision control and the two seeded
faults failing the cell's limits, and the real configuration file against
the catalog's numbers."""

import contextlib
import io
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_sdar
from chipbench import lowprec, run
from chipbench import weights as W
from chipbench.adapters import sdar as adapter
from chipbench.kinds import serve_blocks as kind
from chipbench.reference import sdar as reference
from chipbench.reference import serve_block_logits
from distributed_pytorch_tpu import models
from distributed_pytorch_tpu.serve import (EngineConfig, InferenceEngine,
                                           SamplingParams)

REPO = run.REPO
SEED = 2 ** 31 + 3737
CFG = tiny_sdar.SDAR


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    return tiny_sdar.write_root(str(tmp_path_factory.mktemp("tinysdar")),
                                real)


def test_full_forward_agrees_with_the_reference_in_float32():
    w = W.make(SEED, CFG, jnp.float32)
    model = models.TransformerLM(**adapter.model_kwargs(CFG, max_len=64))
    ids = np.random.default_rng(0).integers(0, 210, (2, 22)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        x = reference.embed(w["globals"], jnp.asarray(ids), CFG)
        for layer in w["layers"]:
            x = reference.block(layer, x, CFG)
        ref = np.asarray(reference.head(w["globals"], x, CFG))
        got = np.asarray(model.apply(adapter.to_program(w), jnp.asarray(ids)))
    assert 0.5 < ref.std() < 2.0            # logits of order 1, as assumed
    np.testing.assert_allclose(got, ref, atol=5e-5, rtol=0)
    # the mask is the block's: position 1 sees position 3, not position 4
    ids2 = ids.copy()
    ids2[:, 4:] = (ids2[:, 4:] + 1) % 210
    with jax.default_matmul_precision("highest"):
        moved = np.asarray(model.apply(adapter.to_program(w),
                                       jnp.asarray(ids2)))
    np.testing.assert_allclose(moved[:, :4], got[:, :4], atol=1e-6)
    ids2 = ids.copy()
    ids2[:, 3] = (ids2[:, 3] + 1) % 210
    with jax.default_matmul_precision("highest"):
        moved = np.asarray(model.apply(adapter.to_program(w),
                                       jnp.asarray(ids2)))
    assert np.abs(moved[:, 1] - got[:, 1]).max() > 1e-3


def served(requests, dtype=jnp.float32):
    """``requests`` (prompt length, max_new, denoise_steps) through the
    engine at ``dtype`` -> [(prompt, tokens, fill_pass)]."""
    w = W.make(SEED, CFG, dtype)
    model = models.TransformerLM(**adapter.model_kwargs(CFG, max_len=64),
                                 dtype=dtype)
    rng = np.random.default_rng(5)
    eng = InferenceEngine(model, adapter.to_program(w), EngineConfig(
        paged=True, n_slots=3, max_len=64, page_len=4, buckets=(8, 16),
        max_queue=32))
    out = []
    with jax.default_matmul_precision("highest"), eng:
        handles = [(rng.integers(0, 210, p).astype(np.int32), n, s)
                   for p, n, s in requests]
        handles = [(p, eng.submit(p, SamplingParams(max_new_tokens=n,
                                                    denoise_steps=s)))
                   for p, n, s in handles]
        for p, h in handles:
            out.append((p, h.result(timeout=300),
                        np.asarray(h.fill_pass, np.int32)))
    return out


REQUESTS = [(9, 12, 4), (16, 11, 2), (3, 9, 1), (21, 8, 4), (6, 10, 3)]


def test_states_rebuilt_from_the_served_tokens_and_their_fill_pass():
    prompt = np.asarray([5, 6, 7, 8, 9, 10], np.int32)      # 2 open block 1
    tokens = np.asarray([11, 12, 13, 14, 15, 16, 17], np.int32)
    fill = np.asarray([1, 0, 0, 1, 1, 0, 0], np.int32)
    ids, first, filled, masked = serve_block_logits.states_of(
        prompt, tokens, fill, 4, 99)
    # block at 4: [9, 10 | 11, 12] filled at passes 1, 0; block at 8:
    # [13, 14, 15, 16] at 0, 1, 1, 0; the block at 12 holds one streamed
    # token of four and is left out
    np.testing.assert_array_equal(first, [4, 4, 8, 8])
    np.testing.assert_array_equal(ids, [[9, 10, 99, 99], [9, 10, 99, 12],
                                        [99, 99, 99, 99], [13, 99, 99, 16]])
    np.testing.assert_array_equal(filled, [[0, 0, 0, 1], [0, 0, 1, 0],
                                           [1, 0, 0, 1], [0, 1, 1, 0]])
    np.testing.assert_array_equal(masked, [[0, 0, 1, 1], [0, 0, 1, 0],
                                           [1, 1, 1, 1], [0, 1, 1, 0]])


def test_reference_of_generation_reads_the_engines_trajectory_at_rounding():
    """Float32 engine, float32 reference: every filled position held the
    reference's best token and every pass filled the positions the
    reference is surest of, up to rounding. Then the two seeded faults at
    the reading: a token altered, a fill order altered."""
    samples = served(REQUESTS)
    read = lambda s: serve_block_logits.served_gaps(
        CFG, SEED, s, jnp.float32, width=40, max_new=12)
    out = read(samples)
    n_read = sum(len(g) for g in out["logit"])
    assert n_read >= sum(len(t) - 3 for _, t, _ in samples)
    assert max(g.max() for g in out["logit"]) < 1e-4
    assert max(g.max() for g in out["confidence"]) < 1e-4
    assert out["control_logit"] is None
    altered = [(p, np.where(np.arange(len(t)) % 7 == 3, (t + 1) % 210, t), f)
               for p, t, f in samples]
    assert max(g.max() for g in read(altered)["logit"]) > 1.0
    # a request of one position a pass, its order turned round
    p, t, f = samples[0]
    turned = read([(p, t, f.max() - f)])
    assert max(g.max() for g in turned["confidence"]) > 0.05


def run_once(root, seed, trace=0):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.main(["--workload", tiny_sdar.CELL, "--seed", str(seed),
                  "--seconds", "1.5", "--trace", str(trace)], root=root,
                 require_chip=False)
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_end_to_end(root, trace):
    result, lines = run_once(root, SEED + trace, trace)
    print("\n".join(l for l in lines if "check " in l))
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    for name, limit in tiny_sdar.LIMITS.items():
        c = result["checks"][name]
        assert c["limit"] == limit and 0 <= c["value"] < limit
    if trace:
        # on the CPU there is no device plane: the trace-reading metrics
        # find nothing, the counters' ones report
        assert "decode_batch_mean" in result["metrics"]
        assert any("nothing to read" in l for l in lines)
    else:
        assert set(result["metrics"]) == {"tpot_p50_ms", "itl_p95_ms",
                                          "setup_s"}


def test_the_cells_counters_follow_the_published_procedure(root, capsys):
    """A block of four masked positions costs five passes, so a window's
    row-passes over its tokens read near 1.25 (less where a request's
    last block skips its commit, more where it streams less than it
    filled). Run as ``chipbench/control.py`` runs a kind: the control's
    two readings are printed, the checks stay the sound run's."""
    cell, k, devices = run.open_cell(root, tiny_sdar.CELL, SEED, 1.5, 0,
                                     False)
    out = k.run(cell, devices, run.Tracer(cell), 0.0,
                control_mm=lowprec.mm_fp8)
    assert "chipbench: control served_logit_gap_max" in capsys.readouterr().out
    assert all(ch["value"] < ch["limit"] for ch in out["checks"])
    c = out["counters"]
    assert c["block_passes"] > c["tokens_emitted"] > 0
    assert c["block_fills"] >= c["tokens_emitted"]
    assert 1.0 < c["block_passes_per_token"] < 2.0
    assert c["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", ["token", "fill_order"])
def test_a_seeded_fault_in_the_timed_path_is_not_correct(root, monkeypatch,
                                                         fault):
    real = kind.run
    patch = kind.fault_fill_order() if fault == "fill_order" \
        else contextlib.nullcontext()
    if fault == "token":
        monkeypatch.setattr(kind, "run", lambda *a: real(
            *a, broken=kind.fault_token))
    with patch:
        result = run.run_cell(
            ["--workload", tiny_sdar.CELL, "--seed", str(SEED + 7),
             "--seconds", "1.5", "--trace", "0"], root=root,
            require_chip=False)
    print(result["checks"])
    assert result["correct"] is False
    failed = {n for n, c in result["checks"].items() if not c["ok"]}
    assert failed == {"served_logit_gap_max" if fault == "token"
                      else "served_confidence_gap_mean"}


def test_control_in_fp8_fails_the_tiny_cells_limits():
    samples = served([(16, 12, 4)] * 6 + [(9, 12, 4)] * 6, jnp.bfloat16)
    out = serve_block_logits.served_gaps(
        CFG, SEED, samples, jnp.bfloat16, width=40, max_new=12,
        control_mm=lowprec.mm_fp8)
    worst = {k: max(float(g.max()) for g in v) for k, v in out.items()}
    mean = {k: float(np.mean(np.concatenate(v))) for k, v in out.items()}
    print(f"sound and fp8 control read {worst}, in the mean {mean}")
    assert worst["logit"] < tiny_sdar.LIMITS["served_logit_gap_max"]
    assert mean["confidence"] < tiny_sdar.LIMITS["served_confidence_gap_mean"]
    assert worst["control_logit"] > \
        1.5 * tiny_sdar.LIMITS["served_logit_gap_max"] \
        or mean["control_confidence"] > \
        1.5 * tiny_sdar.LIMITS["served_confidence_gap_mean"], (worst, mean)


def test_configuration_file_keeps_every_published_number():
    with open(os.path.join(
            REPO, "chipbench/configs/sdar-30b-a3b-1chip.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == cfg["name"])
    assert entry["reduced"] == ["num_hidden_layers"] == list(cfg["reduced"])
    assert entry["source"] == cfg["source"]
    for key, value in cfg["published"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["num_hidden_layers"] in (6, 7)
    assert {"block_length", "denoising_steps", "mask_token_id",
            "remasking_strategy", "qk_norm"} <= set(cfg["assumed"])
    assert "seven stages" in cfg["deployment"]
    kw = adapter.model_kwargs(cfg, max_len=3072)
    assert kw["block_kinds"] == ("moe",) * cfg["num_hidden_layers"]
    assert (kw["n_heads"], kw["n_kv_heads"], kw["head_dim"]) == (32, 4, 128)
    assert kw["moe"] == dict(n_routed=128, width=768, top_k=8, n_shared=0,
                             score="softmax")
    assert (kw["gen_block"], kw["mask_id"]) == (4, 151669)
    # every leaf the reference names has a place in the program
    specs = reference.leaf_specs({k: v for k, v in cfg.items()
                                  if isinstance(v, (int, float, str, bool))})
    assert {n for n, *_ in specs["globals"]} == set(adapter.GLOBALS)
    assert {n for n, *_ in specs["layer"]} == set(adapter.LAYER)
    layer = sum(int(np.prod(shape)) for _, shape, *_ in specs["layer"])
    assert abs(layer - 623.1e6) < 1e5, layer
    n_params = sum(int(np.prod(shape)) for _, shape, *_ in specs["globals"]) \
        + cfg["num_hidden_layers"] * layer
    assert abs(n_params * 2 - 9.97e9) < 2e7 or cfg["num_hidden_layers"] == 6
    with open(os.path.join(REPO, "chipbench/traffic/chat-blocks.json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "serve_blocks"
    assert (mix["block_length"], mix["denoise_steps"]) == (
        cfg["block_length"], cfg["denoising_steps"])
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] \
        <= mix["engine"]["max_len"]
    assert not mix["engine"]["page_len"] % mix["block_length"]
