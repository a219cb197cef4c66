"""The family ``kexaone`` through the benchmark on the CPU at a tiny size:
the program's full forward against the independent float32 reference
(whole, and in the blocks of rows that a long request forces), the
reference's one-request-at-a-time walk against the side-by-side one, a
tiny cell through ``run.py``'s test entry, the lower-precision control
failing the cell's limit, the dump's numbers on hand-made operations, and
the real configuration file against the catalog's numbers."""

import contextlib
import io
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_kexaone
from chipbench import lowprec, run
from chipbench import weights as W
from chipbench.adapters import kexaone as adapter
from chipbench.reference import kexaone as reference
from chipbench.reference import serve_logits_rows
from distributed_pytorch_tpu import models

REPO = run.REPO
SEED = 2 ** 31 + 4343
CFG = tiny_kexaone.KEXAONE


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    return tiny_kexaone.write_root(
        str(tmp_path_factory.mktemp("tinykexaone")), real)


def reference_logits(w, ids, n):
    """One request: ids (S,), its first ``n`` real."""
    with jax.default_matmul_precision("highest"):
        x = reference.embed(w["globals"], jnp.asarray(ids), CFG)
        x = reference.dense_layer(w["globals"], x, n, CFG)
        for i, layer in enumerate(w["layers"]):
            x = reference.expert_layer(layer, x, n, 1 + i, CFG)
        return np.asarray(reference.head(w["globals"], x, CFG))


@pytest.mark.parametrize("rows,length,real", [
    (128, 40, 40),      # one block: the equations as they are written
    (16, 70, 70),       # blocks of rows, the last one starting early
    (16, 72, 37),       # a padded request: only the blocks that hold a token
])
def test_full_forward_agrees_with_the_reference_in_float32(
        monkeypatch, rows, length, real):
    """Tolerance: float32 at ``highest`` on both sides, logits of order 1;
    what differs is the order of the sums (5e-5, as the other families)."""
    monkeypatch.setattr(reference, "ROWS", rows)
    w = W.make(SEED, CFG, jnp.float32)
    model = models.TransformerLM(**adapter.model_kwargs(CFG, max_len=72))
    ids = np.zeros(length, np.int32)
    ids[:real] = np.random.default_rng(0).integers(0, 211, real)
    ref = reference_logits(w, ids, real)[:real]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(adapter.to_program(w),
                                     jnp.asarray(ids[None, :real])))[0]
    assert 0.5 < ref.std() < 2.0            # logits of order 1, as assumed
    np.testing.assert_allclose(got, ref, atol=5e-5, rtol=0)


@pytest.mark.parametrize("n_prompt,slot", [
    (5, 0),      # shorter than the window (8)
    (8, 1),      # the window itself
    (16, 0),     # one whole chunk
    (37, 1),     # three chunks, the last a partial one: the ring (12) wraps
    (60, 0),     # four chunks
])
def test_pool_prefill_then_decode_agrees_with_the_reference(n_prompt, slot):
    """The served logits, prefill in chunks of 16 then 12 decode steps
    through both kinds of store (rings of 12 entries, pages of 4), against
    the reference's full forward over the same tokens. Tolerance: float32
    at ``highest`` on both sides; the online softmax and the bands sum in
    another order (5e-5 of logits of order 1)."""
    from distributed_pytorch_tpu.serve.pages import PagedSlotPool

    w = W.make(SEED, CFG, jnp.float32)
    params = adapter.to_program(w)
    model = models.TransformerLM(**adapter.model_kwargs(CFG, max_len=72))
    rng = np.random.default_rng(n_prompt)
    ids = rng.integers(0, 211, n_prompt + 12).astype(np.int32)
    ref = reference_logits(w, ids, len(ids))
    with jax.default_matmul_precision("highest"):
        pool = PagedSlotPool(model, 2, 72, page_len=4, n_pages=40,
                             prefix_share=False)
        got = [np.asarray(pool.admit(params, ids[:n_prompt], slot,
                                     (8, 16))[0][0])]
        active = np.arange(2) == slot
        for t in ids[n_prompt:-1]:
            pool.ensure_decode_capacity(slot)
            _, logits = pool.decode(params, np.full(2, t, np.int32), active)
            got.append(np.asarray(logits[slot]))
    np.testing.assert_allclose(np.stack(got), ref[n_prompt - 1:-1],
                               atol=5e-5, rtol=0)
    stats = pool.page_stats()
    assert stats["pages_in_use"] == -(-(len(ids) - 1) // 4)   # global only
    assert stats["context_tokens_max"] == len(ids) - 1


def test_a_window_layer_sees_its_window_and_a_global_layer_everything():
    """The reference's own masks: a token 8 or more positions back moves a
    window layer's output nowhere, a global layer's everywhere after."""
    w = W.make(SEED, CFG, jnp.float32)
    ids = np.random.default_rng(2).integers(0, 211, 40).astype(np.int32)
    other = ids.copy()
    other[5] = (other[5] + 1) % 211
    with jax.default_matmul_precision("highest"):
        def after(layer, tokens):
            x = reference.embed(w["globals"], jnp.asarray(tokens), CFG)
            return np.asarray(x + reference.attention(
                w["layers"][layer - 1], "", x, 40, CFG, jnp.matmul,
                reference.window_of(CFG, layer)))
        moved = lambda layer: np.abs(after(layer, ids)
                                     - after(layer, other)).max(-1) > 0
    assert reference.window_of(CFG, 3) is None
    assert reference.window_of(CFG, 4) == 8
    assert moved(4)[5:13].all() and not moved(4)[13:].any()
    assert moved(3)[5:].all() and not moved(3)[:5].any()


def test_eight_chips_shares_add_up_to_the_uncut_layer():
    """The cut leaves out what the other chips' experts would add: the
    routed parts of the shares [0, 4), [4, 8), ... of the 16 experts, each
    with its own experts' weights, plus the shared expert ONCE, are the
    layer with every expert held."""
    whole = dict(CFG, num_experts=16)
    w = W.make(SEED, whole, jnp.float32)["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(1), (24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        full = reference.expert_ffn(w, u, whole, jnp.matmul)
        un = reference.rms(u, w["ln2_g"], CFG["rms_norm_eps"])
        shared = jnp.matmul(reference.silu(jnp.matmul(un, w["ws_gate"]))
                            * jnp.matmul(un, w["ws_up"]), w["ws_down"])
        parts = []
        for first in range(0, 16, 4):
            share = dict(CFG, experts_held_first=first)
            ws = dict(w, **{k: w[k][first:first + 4]
                            for k in ("we_gate", "we_up", "we_down")})
            parts.append(reference.expert_ffn(ws, u, share, jnp.matmul)
                         - shared)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(full), atol=2e-6, rtol=0)
    assert float(jnp.abs(parts[0]).max()) > 1e-3     # a share is not nothing


def run_once(root, seed, trace=0):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.main(["--workload", tiny_kexaone.CELL, "--seed", str(seed),
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
    assert gap["limit"] == tiny_kexaone.LIMIT \
        and 0 <= gap["value"] < gap["limit"]
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
    samples = [(rng.integers(0, 211, n).astype(np.int32),
                rng.integers(0, 211, 40).astype(np.int32))
               for n in (4, 9, 17, 30, 25, 12, 8, 20)]
    out = serve_logits_rows.served_gaps(CFG, SEED, samples, jnp.bfloat16,
                                        width=72, max_new=40,
                                        control_mm=lowprec.mm_fp8)
    assert [len(g) for g in out["served"]] == [40] * 8
    worst = max(float(g.max()) for g in out["control"])
    print(f"fp8 control reads {worst:.3f}")
    assert worst > 2 * tiny_kexaone.LIMIT, worst


def test_configuration_file_keeps_every_published_number():
    with open(os.path.join(
            REPO, "chipbench/configs/kexaone-236b-a23b-1chip.json")) as f:
        cfg = json.load(f)
    reduced = set(cfg["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size",
                       "num_nextn_predict_layers"}
    for key, value in cfg["published"].items():
        if key in reduced:
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (6144, 64, 8, 128)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["num_shared_experts"],
            cfg["routed_scaling_factor"]) == (18432, 2048, 8, 1, 2.5)
    assert cfg["sliding_window"] == 128 and cfg["rms_norm_eps"] == 1e-5
    assert cfg["rope_parameters"]["rope_theta"] == 1000000
    assert cfg["num_hidden_layers"] == 1 + cfg["n_layer"] == 5
    assert cfg["router_width"] == cfg["published"]["num_experts"] == 128
    kw = adapter.model_kwargs(cfg, max_len=33280)
    assert kw["block_kinds"] == ("dense",) + ("moe",) * 4
    assert kw["layer_windows"] == (128, 128, 128, None, 128)
    assert kw["layer_rope"] == (True, True, True, False, True)
    assert kw["moe"]["held"] == (0, 16) and kw["moe"]["n_routed"] == 128
    # every leaf the reference names has a place in the program
    specs = reference.leaf_specs({k: v for k, v in cfg.items()
                                  if isinstance(v, (int, float, str, bool))})
    for name, *_ in specs["globals"]:
        assert name in adapter.GLOBALS or name[2:] in adapter.DENSE, name
    assert {n for n, *_ in specs["layer"]} == set(adapter.EXPERT)
    n_params = sum(int(np.prod(shape)) for _, shape, *_ in specs["globals"]) \
        + 4 * sum(int(np.prod(shape)) for _, shape, *_ in specs["layer"])
    assert abs(n_params - 3.712e9) < 2e6, n_params


def test_scope_split_reads_window_and_global_layers_apart(monkeypatch):
    """Two decode programs and a prefill of hand-made operations; the
    counters come from the two ``serve.stats`` marks."""
    from chipbench import program_trace, scope_split_mixed

    ms = 1_000_000
    dec = [("fusion.1", "jit(_decode)/blocks/attn/qkv/dot_general:", 2),
           ("fusion.2", "jit(_decode)/blocks/page_write/scatter:", 1),
           ("fusion.3", "jit(_decode)/blocks/decode_attention/"
            "window_attention/decode_attention/dot_general:", 3),
           ("paged_decode_attention.4", "jit(_decode)/blocks/decode_attention/global_attention/"
            "decode_attention/jit(paged_attention)/paged_decode_attention:",
            4),
           ("fusion.5", "jit(_decode)/blocks/moe/route/dot_general:", 1),
           ("ragged-dot-none.6", "ragged-dot-none", 8)]
    pre = [("fusion.7", "jit(prefill_b16)/blocks/attn/core/"
            "window_attention/dot_general:", 5),
           ("fusion.8", "jit(prefill_b16)/blocks/attn/core/"
            "global_attention/while/body/dot_general:", 20),
           ("grouped_matmul.9", "jit(prefill_b16)/blocks/moe/experts/grouped_matmul:",
            10)]
    ops, modules, t = [], [], 0
    for name, stacks in (("jit__decode(1)", dec), ("jit_prefill_b16(2)", pre),
                         ("jit__decode(1)", dec)):
        start = t
        for short, stack, dur in stacks:
            ops.append((short, t, t + dur * ms, stack))
            t += dur * ms
        modules.append((name, start, t))
        t += ms
    mark = lambda at, steps, touched, routed, ctx, rows: (
        "serve.stats", 1, at, at,
        {"moe_decode_steps": steps, "moe_layers": 4,
         "moe_experts_touched": touched, "moe_tokens_routed": routed,
         "kv_resident_bytes_window": 100, "kv_resident_bytes_global": 4000,
         "context_tokens_mean": ctx, "context_tokens_max": 2 * ctx,
         "pages_in_use": 50, "active_slots": rows})
    pt = program_trace.ProgramTrace(
        [mark(0, 100, 2000, 6000, 9000.0, 18),
         mark(t, 102, 2000 + 2 * 12, 6000 + 2 * 40, 11000.0, 22)],
        {0: ops}, {0: modules}, {0: [{}] * len(ops)}, [])
    monkeypatch.setattr(program_trace, "of", lambda cell: pt)
    cell = types.SimpleNamespace(
        config={"num_experts": 4, "hidden_size": 6144,
                "moe_intermediate_size": 2048, "num_key_value_heads": 8,
                "head_dim": 128, "num_hidden_layers": 5,
                "layer_types": tiny_kexaone.PERIOD * 2},
        traffic={"engine": {"buckets": [8, 16]}},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    got = scope_split_mixed.readings(cell, say=lambda line: None)
    assert got["window_attention_device_ms"] == 3.0
    assert got["global_attention_device_ms"] == 4.0
    assert got["moe_device_ms"] == 9.0
    assert got["prefill_window_attention_device_ms"] == 5.0
    assert got["prefill_global_attention_device_ms"] == 20.0
    assert got["kv_resident_bytes_window"] == 100
    assert got["kv_resident_bytes_global"] == 4000
    assert got["context_tokens_mean"] == 10000.0
    assert got["moe_experts_touched_share"] == pytest.approx(
        100 * 12 / (4 * 4))
    least = 10000.0 * 20 * 4096 / 819e9 * 1e3    # 20 rows of 10 k, 4 KB each
    assert got["global_attention_roofline"] == pytest.approx(
        100 * least / 4.0)
    least = 12 * 3 * 6144 * 2048 * 2 / 819e9 * 1e3        # bytes bound
    assert got["moe_experts_roofline"] == pytest.approx(100 * least / 8.0)
    # a parent without the scopes or the marks: nothing to read
    bare = program_trace.ProgramTrace(
        [], {0: [("fusion.9", 0, ms, "jit(_decode)/blocks/mlp/dot:")]},
        {0: [("jit__decode(1)", 0, ms)]}, {0: [{}]}, [])
    monkeypatch.setattr(program_trace, "of", lambda cell: bare)
    assert scope_split_mixed.readings(cell, say=lambda line: None) == {}
