"""The family ``xing4`` through the benchmark on the CPU at a tiny size:
the program's full forward against the independent float32 reference, a
tiny cell through ``run.py``'s test entry, the lower-precision control
failing the cell's limit, how often bfloat16 routes a token to another
expert than float32 does, and the real configuration file against the
catalog's numbers."""

import contextlib
import io
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_xing4
from chipbench import lowprec, run
from chipbench import weights as W
from chipbench.adapters import xing4 as adapter
from chipbench.reference import serve_logits
from chipbench.reference import xing4 as reference
from distributed_pytorch_tpu import models

REPO = run.REPO
SEED = 2 ** 31 + 2828
CFG = tiny_xing4.XING4


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    return tiny_xing4.write_root(str(tmp_path_factory.mktemp("tinyxing4")),
                                 real)


def reference_logits(w, ids):
    with jax.default_matmul_precision("highest"):
        x = reference.embed(w["globals"], jnp.asarray(ids), CFG)
        for layer in w["layers"]:
            x = reference.block(layer, x, CFG)
        return np.asarray(reference.head(w["globals"], x, CFG))


def test_full_forward_agrees_with_the_reference_in_float32():
    w = W.make(SEED, CFG, jnp.float32)
    model = models.TransformerLM(**adapter.model_kwargs(CFG, max_len=64))
    ids = np.random.default_rng(0).integers(0, 211, (2, 24)).astype(np.int32)
    ref = reference_logits(w, ids)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(adapter.to_program(w), jnp.asarray(ids)))
    assert 0.5 < ref.std() < 2.0            # logits of order 1, as assumed
    np.testing.assert_allclose(got, ref, atol=5e-5, rtol=0)


def recorded_routes(model, params, ids):
    """The experts each token was routed to in every expert layer
    (the full forward run eagerly with ``route`` wrapped)."""
    seen = []
    for blk in model.blocks:
        if hasattr(blk.ffn, "route"):
            def spy(p, xt, inner=blk.ffn.__class__.route, layer=blk.ffn):
                out = inner(layer, p, xt)
                seen.append(np.sort(np.asarray(out[0]), -1))
                return out
            blk.ffn.route = spy
    model.apply(params, jnp.asarray(ids))
    for blk in model.blocks:
        blk.ffn.__dict__.pop("route", None)
    return np.stack(seen)


def test_bfloat16_reroutes_few_tokens_and_each_flip_moves_little():
    """The program routes on bfloat16 activations, the reference on
    float32 ones: among the scores a near-tie flips. Counted here at the
    tiny size and printed; the served logits stay near the reference's."""
    ids = np.random.default_rng(1).integers(0, 211, (4, 48)).astype(np.int32)
    kw = adapter.model_kwargs(CFG, max_len=64)
    w16 = W.make(SEED, CFG, jnp.bfloat16)
    w32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w16)
    r32 = recorded_routes(models.TransformerLM(**kw),
                          adapter.to_program(w32), ids)
    m16 = models.TransformerLM(**kw, dtype=jnp.bfloat16)
    r16 = recorded_routes(m16, adapter.to_program(w16), ids)
    flipped = (r32 != r16).any(-1).mean()
    ref = reference_logits(w32, ids)
    got = np.asarray(m16.apply(adapter.to_program(w16), jnp.asarray(ids)),
                     np.float32)
    err = np.abs(got - ref).max() / ref.std()
    print(f"bfloat16 rerouted {100 * flipped:.1f} % of token-layers; "
          f"worst logit error {err:.3f} of the logits' spread")
    assert flipped < 0.1
    assert err < 1.0


def run_once(root, seed, trace=0):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.main(["--workload", tiny_xing4.CELL, "--seed", str(seed),
                  "--seconds", "1.5", "--trace", str(trace)], root=root,
                 require_chip=False)
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_end_to_end(root, trace):
    result, lines = run_once(root, SEED + trace, trace)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    gap = result["checks"]["served_logit_gap_max"]
    assert gap["limit"] == tiny_xing4.LIMIT and 0 <= gap["value"] < gap["limit"]
    if trace:
        # on the CPU there is no device plane: the trace-reading metrics
        # find nothing, the counters' ones report
        assert "decode_batch_mean" in result["metrics"]
        assert any("nothing to read" in l for l in lines)
    else:
        assert set(result["metrics"]) == {"tpot_p50_ms", "itl_p95_ms",
                                          "setup_s"}


def test_control_in_fp8_fails_the_tiny_cells_limit():
    rng = np.random.default_rng(3)
    samples = [(rng.integers(0, 211, 16).astype(np.int32),
                rng.integers(0, 211, 48).astype(np.int32)) for _ in range(8)]
    out = serve_logits.served_gaps(CFG, SEED, samples, jnp.bfloat16,
                                   width=64, max_new=48,
                                   control_mm=lowprec.mm_fp8)
    worst = max(float(g.max()) for g in out["control"])
    print(f"fp8 control reads {worst:.3f}")
    assert worst > 2 * tiny_xing4.LIMIT, worst


def test_configuration_file_keeps_every_published_number():
    with open(os.path.join(
            REPO, "chipbench/configs/xing4-29b-a4b-1chip.json")) as f:
        cfg = json.load(f)
    reduced = set(cfg["reduced"])
    assert reduced == {"num_hidden_layers", "first_k_dense_replace",
                       "num_nextn_predict_layers"}
    for key, value in cfg["published"].items():
        if key in reduced:
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == cfg["first_k_dense_replace"] \
        + cfg["n_layer"] == 7
    kw = adapter.model_kwargs(cfg, max_len=3072)
    assert kw["block_kinds"] == ("dense",) + ("moe",) * 6
    assert kw["latent"]["kv_rank"] + kw["latent"]["rope_dim"] == 576
    # every leaf the reference names has a place in the program
    specs = reference.leaf_specs({k: v for k, v in cfg.items()
                                  if isinstance(v, (int, float, str, bool))})
    for name, *_ in specs["globals"]:
        assert name in adapter.GLOBALS or name[2:] in adapter.DENSE, name
    assert {n for n, *_ in specs["layer"]} == set(adapter.EXPERT)
    n_params = sum(int(np.prod(shape)) for _, shape, *_ in specs["globals"]) \
        + 6 * sum(int(np.prod(shape)) for _, shape, *_ in specs["layer"])
    assert abs(n_params - 5.5376e9) < 2e6, n_params


def test_scope_split_reads_the_expert_layer_and_the_residual_path(
        monkeypatch):
    """Two decode programs of hand-made operations: the grouped matmul's
    custom calls carry no name stack and are told by their name; the
    counters come from the two ``serve.stats`` marks."""
    import types

    from chipbench import program_trace, scope_split

    ms = 1_000_000
    stacks = [("fusion.1", "jit(_decode_moe)/blocks/hc/sinkhorn/div:", 1),
              ("fusion.2", "jit(_decode_moe)/blocks/moe/route/dot_general:", 1),
              ("fusion.3", "jit(_decode_moe)/blocks/moe/dispatch/sort:", 2),
              ("ragged-dot-none.4", "ragged-dot-none", 10),
              ("ragged-dot-metadata", "", 1),
              ("fusion.5", "jit(_decode_moe)/blocks/moe/shared/mlp/dot:", 3),
              ("fusion.6", "jit(_decode_moe)/blocks/moe/combine/gather:", 2),
              ("fusion.7", "jit(_decode_moe)/blocks/decode_attention/while:", 5),
              ("fusion.8", "jit(_decode_moe)/head/dot_general:", 1)]
    ops, modules, t = [], [], 0
    for run in range(2):
        start = t
        for short, stack, dur in stacks:
            ops.append((short, t, t + dur * ms, stack))
            t += dur * ms
        modules.append(("jit__decode_moe(1)", start, t))
        t += ms
    # a prefill in between must not be counted
    ops.append(("ragged-dot-none.4", t, t + 50 * ms, "ragged-dot-none"))
    modules.append(("jit_prefill_b512(2)", t, t + 50 * ms))
    mark = lambda at, steps, touched, routed: (
        "serve.stats", 1, at, at,
        {"moe_decode_steps": steps, "moe_layers": 6,
         "moe_experts_touched": touched, "moe_tokens_routed": routed,
         "moe_tokens_max_expert": 9})
    pt = program_trace.ProgramTrace(
        [mark(0, 100, 20000, 60000), mark(t, 102, 20000 + 2 * 240,
                                          60000 + 2 * 960)],
        {0: ops}, {0: modules}, {0: [{}] * len(ops)}, [])
    monkeypatch.setattr(program_trace, "of", lambda cell: pt)
    cell = types.SimpleNamespace(
        config={"n_routed_experts": 64, "hidden_size": 3584,
                "moe_intermediate_size": 1024},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    split = scope_split.decode_scope_ms(cell)
    assert split["total"] == 26.0 and split["hc"] == 1.0
    assert split["moe"] == 19.0 and split["experts"] == 11.0
    got = scope_split.readings(cell, say=lambda line: None)
    assert got["moe_device_ms"] == 19.0 and got["hc_device_ms"] == 1.0
    assert got["moe_dispatch_share"] == pytest.approx(100 * 5 / 19)
    assert got["moe_experts_touched_share"] == pytest.approx(
        100 * 240 / (64 * 6))
    least = 240 * 3 * 3584 * 1024 * 2 / 819e9 * 1e3     # bytes bound
    assert got["moe_experts_roofline"] == pytest.approx(100 * least / 11.0)
    # a parent without the scopes or the marks: nothing to read
    bare = program_trace.ProgramTrace(
        [], {0: [("fusion.9", 0, ms, "jit(_decode)/blocks/mlp/dot:")]},
        {0: [("jit__decode(1)", 0, ms)]}, {0: [{}]}, [])
    monkeypatch.setattr(program_trace, "of", lambda cell: bare)
    assert scope_split.readings(cell, say=lambda line: None) == {}
