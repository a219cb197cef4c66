"""The blocks made of parts on the serving path, at a tiny size in float32
on the CPU: multi-head latent attention over a latent page pool (absorbed
decode against the expanded full forward), the dropless expert layer
(no token dropped at any imbalance; shares add up), the four-stream
hyper-connection path (doubly stochastic mixes), YaRN's frequencies
against values computed by hand, and what the engine refuses for latent
blocks. The program against the independent reference lives in
tests/chipbench/test_chipbench_xing4.py."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import models
from distributed_pytorch_tpu.models.generate import LatentPagesUnsupported
from distributed_pytorch_tpu.nn.latent import LatentPages
from distributed_pytorch_tpu.nn.paged import KVPages
from distributed_pytorch_tpu.nn.hyper import HyperConnection, sinkhorn
from distributed_pytorch_tpu.nn.rotary import yarn_inv_freq, yarn_mscale
from distributed_pytorch_tpu.parallel.moe import DroplessMoE
from distributed_pytorch_tpu.serve import (EngineConfig, InferenceEngine,
                                           SamplingParams)
from distributed_pytorch_tpu.serve.pages import PagedSlotPool

YARN = dict(factor=64, original_max_position_embeddings=4096, beta_fast=32,
            beta_slow=1, mscale=1, mscale_all_dim=1)
TINY = dict(vocab=211, dim=64, n_layers=3, n_heads=4, max_seq=64, pos="none",
            block_kinds=("dense", "moe", "moe"), attention="latent",
            latent=dict(q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8,
                        v_dim=16, yarn=YARN),
            norm="rms", norm_eps=1e-6, ffn_dim=160,
            moe=dict(n_routed=8, width=32, top_k=2, n_shared=1, scale=2.0),
            hyper_connections=4, hc=dict(sinkhorn_iters=20, eps=1e-6))
PAGE = 4


@pytest.fixture(scope="module")
def tiny():
    model = models.TransformerLM(**TINY)
    params = model.init(jax.random.PRNGKey(7))
    # the default init mixes the streams by near-identities; give the
    # residual path something to do
    for i, blk in enumerate(params["blocks"]):
        for j, hc in enumerate((blk["hc1"], blk["hc2"])):
            k = jax.random.fold_in(jax.random.PRNGKey(11), 2 * i + j)
            hc["b_res"] = jax.random.normal(k, (4, 4))
            hc["a_res"] = jnp.float32(1.0)
            hc["b_pre"] = 0.5 * jax.random.normal(jax.random.fold_in(k, 1),
                                                  (4,))
    return model, params


def full_logits(model, params, tokens):
    return np.asarray(model.apply(params, jnp.asarray(tokens)[None])[0])


def serve_through_pool(model, params, pool, prompt, slot, steps):
    """Admit ``prompt``, then ``steps`` greedy decode steps; returns
    (tokens served, the logits each was chosen from, pages hit)."""
    logits, n_hit, _ = pool.admit(params, prompt, slot, (16, 32))
    rows, toks = [np.asarray(logits[0])], []
    cur = np.zeros(pool.n_slots, np.int32)
    active = np.zeros(pool.n_slots, bool)
    active[slot] = True
    for _ in range(steps):
        toks.append(int(np.argmax(rows[-1])))
        cur[slot] = toks[-1]
        pool.ensure_decode_capacity(slot)
        out, lg = pool.decode(params, cur, active)
        assert int(out[slot]) == int(np.argmax(np.asarray(lg[slot])))
        rows.append(np.asarray(lg[slot]))
    return toks, np.stack(rows[:-1]), n_hit


def test_paged_prefill_and_absorbed_decode_agree_with_the_full_forward(tiny):
    """Cold, then with a shared prefix of two pages (the traced offset):
    12 decode steps through the latent pages, each step's logits against
    the expanded full forward over prompt + served tokens."""
    model, params = tiny
    pool = PagedSlotPool(model, 3, 64, page_len=PAGE, n_pages=48)
    assert all(type(st) is LatentPages for st in pool.state)
    assert pool.state[0].entries.shape == (48, 1, PAGE, 128)  # 24 wide, padded
    rng = np.random.default_rng(0)
    first = rng.integers(0, 211, 13).astype(np.int32)
    second = np.concatenate([first[:2 * PAGE],
                             rng.integers(0, 211, 6).astype(np.int32)])
    for slot, prompt, want_hit in ((0, first, 0), (1, second, 2)):
        toks, rows, n_hit = serve_through_pool(model, params, pool, prompt,
                                               slot, 12)
        assert n_hit == want_hit
        ref = full_logits(model, params, np.concatenate([prompt, toks]))
        ref = ref[len(prompt) - 1:len(prompt) - 1 + 12]
        np.testing.assert_allclose(rows, ref, atol=2e-4, rtol=0)
    moe = pool.moe_stats()
    assert moe["moe_decode_steps"] == 24 and moe["moe_layers"] == 2
    # two rows active at most one at a time here: a step routes 1 token
    # through 2 experts in each of 2 layers
    assert moe["moe_tokens_routed"] == 24 * 2 * 2
    assert moe["moe_experts_touched"] == 24 * 2 * 2
    assert moe["moe_tokens_max_expert"] == 1
    entry = 128 * 4
    assert pool.page_stats()["bytes_per_resident_token"] == 3 * entry


def test_a_step_in_flight_leaves_the_counters_readable(tiny):
    """``stats()`` reads the expert counters from another thread while
    the engine's thread dispatches a decode step: the step donates the
    page stores, never the counters it was handed."""
    model, params = tiny
    pool = PagedSlotPool(model, 2, 32, page_len=PAGE, n_pages=16)
    pool.admit(params, np.arange(9, dtype=np.int32), 0, (16,))
    pool.ensure_decode_capacity(0)
    before, pages = pool.moe_counts, pool.state[0].entries
    pool.decode(params, np.asarray([5, 0], np.int32), np.asarray([True, False]))
    assert pages.is_deleted() and not before.is_deleted()
    assert np.asarray(before).tolist() == [0, 0, 0, 0]
    assert pool.moe_stats()["moe_decode_steps"] == 1


def test_dense_decode_path_agrees_with_the_blockwise_one(tiny):
    from distributed_pytorch_tpu.models.generate import (
        decode_step_slots_paged)
    model, params = tiny
    pool = PagedSlotPool(model, 2, 32, page_len=PAGE, n_pages=16)
    pool.admit(params, np.arange(9, dtype=np.int32), 0, (16,))
    pool.ensure_decode_capacity(0)
    args = (model, params, pool.state, jnp.array(pool.tables),
            jnp.array(pool.lengths), jnp.asarray([5, 0], jnp.int32),
            jnp.asarray([True, False]))
    a, _ = decode_step_slots_paged(*args, page_len=PAGE, blockwise=True)
    b, _ = decode_step_slots_paged(*args, page_len=PAGE, blockwise=False)
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), atol=1e-4)


def test_engine_serves_latent_blocks_with_one_fetch_an_iteration(tiny):
    model, params = tiny
    eng = InferenceEngine(model, params, EngineConfig(
        paged=True, n_slots=4, max_len=64, buckets=(16, 32),
        page_len=PAGE)).start()
    try:
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 211, n).astype(np.int32)
                   for n in (5, 11, 17)]
        handles = [eng.submit(p, SamplingParams(max_new_tokens=9))
                   for p in prompts]
        outs = [np.asarray(h.result(timeout=600)) for h in handles]
        st = eng.stats()
    finally:
        eng.shutdown()
    for p, t in zip(prompts, outs):
        ref = full_logits(model, params, np.concatenate([p, t]))
        gap = ref[len(p) - 1:-1].max(-1) - np.take_along_axis(
            ref[len(p) - 1:-1], t[:, None], -1)[:, 0]
        assert gap.max() < 1e-3          # the served token is the best
    assert st["decode_compiles"] == 1
    assert st["decode_fetches"] in (st["moe_decode_steps"],
                                    st["moe_decode_steps"] - 1)
    assert st["moe_tokens_routed"] == st["rows_decoded"] * 2 * 2
    assert 0 < st["moe_experts_touched"] <= st["moe_decode_steps"] * 2 * 8
    assert st["pages"]["bytes_per_resident_token"] == 3 * 128 * 4


# -- the expert layer ----------------------------------------------------------

def by_hand(layer, params, x, top_i, w):
    """Every chosen (token, expert) pair computed alone, nothing sorted."""
    e = jax.tree_util.tree_map(np.asarray, params["experts"])
    silu = lambda a: a / (1.0 + np.exp(-a))
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for j in range(top_i.shape[1]):
            i = int(top_i[t, j])
            h = silu(x[t] @ e["gate"][i]) * (x[t] @ e["up"][i])
            out[t] += w[t, j] * (h @ e["down"][i])
    return out


@pytest.mark.parametrize("favoured", [(3,), (2, 5), ()])
def test_no_token_is_dropped_at_any_imbalance(favoured):
    """Every token to one expert (or to the same two), the others empty;
    and the balanced case. The layer's routed part equals the pairs
    computed one by one."""
    k = max(len(favoured), 1) if favoured else 2
    layer = DroplessMoE(24, 8, 16, top_k=k, n_shared=1, scale=2.0)
    params = layer.init(jax.random.PRNGKey(3))
    bias = np.zeros(8, np.float32)
    bias[list(favoured)] = 100.0
    params["router"]["bias"] = jnp.asarray(bias)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (37, 24)))
    top_i, w, _ = layer.route(params, jnp.asarray(x))
    if favoured:
        assert set(np.asarray(top_i).ravel().tolist()) == set(favoured)
    y, counts, _ = layer.routed(params, jnp.asarray(x))
    np.testing.assert_allclose(
        np.asarray(y), by_hand(layer, params, x, np.asarray(top_i),
                               np.asarray(w)), atol=2e-5)
    routed, touched, fullest = (int(c) for c in counts)
    assert routed == 37 * k
    if favoured:
        assert touched == len(favoured) and fullest == 37
    # rows left out of the dispatch cost nothing and give nothing
    mask = np.arange(37) % 3 != 0
    ym, cm, _ = layer.routed(params, jnp.asarray(x), jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(ym)[mask], np.asarray(y)[mask],
                               atol=2e-5)
    assert not np.asarray(ym)[~mask].any()
    assert int(cm[0]) == int(mask.sum()) * k


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_shares_add_up_to_the_uncut_layer(score):
    """held=(0,4) + held=(4,4): the routed parts add up, and the whole
    outputs add up once the shared expert is counted once; so do their
    gradients (the router's and the input's add up over the shares, an
    expert's is its own share's, the shared expert's is counted once).
    Under either scoring rule: a share's weights come from the scores of
    ALL experts, whichever of them it holds."""
    whole = DroplessMoE(24, 8, 16, top_k=2, n_shared=1, scale=2.0,
                        score=score)
    params = whole.init(jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (29, 24))
    cot = jax.random.normal(jax.random.PRNGKey(7), (29, 24))
    y_whole = whole.apply(params, x)[0]
    shared = whole.shared.apply(params["shared"], x)
    loss = lambda layer: lambda p, x: jnp.sum(layer.apply(p, x)[0] * cot)
    g_whole, gx_whole = jax.jit(jax.grad(loss(whole), argnums=(0, 1)))(
        params, x)
    g_shared, gx_shared = jax.jit(jax.grad(
        lambda p, x: jnp.sum(whole.shared.apply(p, x) * cot),
        argnums=(0, 1)))(params["shared"], x)
    parts, outs, grads = [], [], []
    for first in (0, 4):
        share = DroplessMoE(24, 8, 16, top_k=2, n_shared=1, scale=2.0,
                            held=(first, 4), score=score)
        p = dict(params, experts=jax.tree_util.tree_map(
            lambda a: a[first:first + 4], params["experts"]))
        parts.append(share.routed(p, x)[0])
        outs.append(share.apply(p, x)[0])
        grads.append(jax.jit(jax.grad(loss(share), argnums=(0, 1)))(p, x))
    np.testing.assert_allclose(parts[0] + parts[1],
                               whole.routed(params, x)[0], atol=2e-5)
    np.testing.assert_allclose(outs[0] + outs[1] - shared, y_whole,
                               atol=2e-5)
    (g0, gx0), (g1, gx1) = grads
    np.testing.assert_allclose(gx0 + gx1 - gx_shared, gx_whole, atol=2e-5)
    np.testing.assert_allclose(g0["router"]["w"] + g1["router"]["w"],
                               g_whole["router"]["w"], atol=2e-5)
    for name in ("gate", "up", "down"):
        np.testing.assert_allclose(
            np.concatenate([g0["experts"][name], g1["experts"][name]]),
            g_whole["experts"][name], atol=2e-5)
        for g in (g0, g1, g_whole):     # every chip computes it alike
            np.testing.assert_allclose(g["shared"][name]["w"],
                                       g_shared[name]["w"], atol=2e-5)
    if score == "softmax":
        assert "bias" not in params["router"]
    else:
        # the bias corrects the choice only: no gradient reaches it
        assert not np.asarray(g_whole["router"]["bias"]).any()


def per_expert_loop(layer, params, x, top_i):
    """The routed part as a differentiable masked sum over ALL experts,
    one at a time, nothing sorted and no grouped matmul: the choice
    ``top_i`` is given, the weights are recomputed from the scores."""
    g = jax.nn.sigmoid(x @ params["router"]["w"])
    chosen = jnp.zeros(g.shape, bool).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(True)
    top = jnp.where(chosen, g, 0.0)
    w = top / (jnp.sum(top, -1, keepdims=True) + 1e-20) * layer.scale
    e, out = params["experts"], 0.0
    for i in range(layer.n_routed):
        h = jax.nn.silu(x @ e["gate"][i]) * (x @ e["up"][i])
        out = out + w[:, i:i + 1] * (h @ e["down"][i])
    return out


@pytest.mark.parametrize("favoured", [(3,), (2, 5), ()])
def test_gradients_match_a_per_expert_loop_at_any_imbalance(favoured):
    """``jax.lax.ragged_dot``'s own derivatives over the sorted pairs
    against a loop over experts: every pair to one expert (the others'
    groups empty), to the same two, and the balanced case. No pair is
    dropped in the backward pass either, and the load counts every pair."""
    k = max(len(favoured), 1) if favoured else 2
    layer = DroplessMoE(24, 8, 16, top_k=k, n_shared=1, scale=2.0)
    params = layer.init(jax.random.PRNGKey(3))
    bias = np.zeros(8, np.float32)
    bias[list(favoured)] = 100.0
    params["router"]["bias"] = jnp.asarray(bias)
    x = jax.random.normal(jax.random.PRNGKey(4), (37, 24))
    cot = jax.random.normal(jax.random.PRNGKey(9), (37, 24))
    top_i, _, _ = layer.route(params, x)
    got = jax.jit(jax.grad(
        lambda p, x: jnp.sum(layer.routed(p, x)[0] * cot),
        argnums=(0, 1)))(params, x)
    want = jax.jit(jax.grad(
        lambda p, x: jnp.sum(per_expert_loop(layer, p, x, top_i) * cot),
        argnums=(0, 1)))(params, x)
    np.testing.assert_allclose(got[1], want[1], atol=5e-5)
    np.testing.assert_allclose(got[0]["router"]["w"], want[0]["router"]["w"],
                               atol=5e-5)
    for name in ("gate", "up", "down"):
        np.testing.assert_allclose(got[0]["experts"][name],
                                   want[0]["experts"][name], atol=5e-5)
        if favoured:                    # an empty group has no gradient
            idle = [i for i in range(8) if i not in favoured]
            assert not np.asarray(got[0]["experts"][name])[idle].any()
    _, _, load = layer.routed(params, x)
    assert int(load.sum()) == 37 * k
    assert np.array_equal(load, np.bincount(np.asarray(top_i).ravel(),
                                            minlength=8))
    # masked rows are neither dispatched nor counted
    mask = jnp.arange(37) % 3 != 0
    _, _, load_m = layer.routed(params, x, mask)
    assert int(load_m.sum()) == int(mask.sum()) * k


def test_rows_no_group_computed_get_a_zero_gradient():
    """``grouped_matmul``: the rows past the last group are the pairs
    routed to experts this chip does not hold. On the chip the kernel
    leaves whatever the buffer held there, in the result and in ``dxs``
    alike; the derivative zeroes them whatever cotangent arrives."""
    from distributed_pytorch_tpu.parallel.moe import grouped_matmul
    xs = jax.random.normal(jax.random.PRNGKey(0), (12, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 5))
    sizes = jnp.asarray([4, 0, 3], jnp.int32)          # rows 7.. in no group
    cot = jax.random.normal(jax.random.PRNGKey(2), (12, 5)).at[7:].set(1e30)
    out, pull = jax.vjp(lambda a, b: grouped_matmul(a, b, sizes), xs, w)
    dxs, dw = pull(cot)
    want = jax.lax.ragged_dot(xs, w, sizes)
    np.testing.assert_allclose(out[:7], want[:7], atol=1e-5)
    assert not np.asarray(dxs)[7:].any()
    np.testing.assert_allclose(dxs[:4], cot[:4] @ w[0].T, atol=1e-4)
    np.testing.assert_allclose(dxs[4:7], cot[4:7] @ w[2].T, atol=1e-4)
    np.testing.assert_allclose(dw[0], xs[:4].T @ cot[:4], atol=1e-4)
    assert not np.asarray(dw[1]).any()                 # an empty group


def _kernel_on_this_cpu(monkeypatch):
    """Put the rule's answer where a TPU would give it: the kernel,
    interpreted. Returns the kernel's entry point wrapped to count the
    calls that reach it."""
    from distributed_pytorch_tpu.parallel import moe
    calls = []
    real = moe.grouped_matmul_kernel.grouped_matmul

    def counted(xs, w, sizes, **kw):
        calls.append(xs.shape)
        return real(xs, w, sizes, **kw)
    monkeypatch.setattr(moe, "_kernel_interpret", lambda xs, w: True)
    monkeypatch.setattr(moe.grouped_matmul_kernel, "grouped_matmul", counted)
    return calls


def test_the_primal_takes_the_kernel_and_the_derivative_ragged_dot(
        monkeypatch):
    """What ``grouped_matmul`` observes is whether it is differentiated:
    a call that takes no gradient (a serving program's) goes through the
    kernel, ``jax.vjp`` of the same call never reaches it."""
    from distributed_pytorch_tpu.parallel import moe
    calls = _kernel_on_this_cpu(monkeypatch)
    xs = jax.random.normal(jax.random.PRNGKey(0), (32, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 128, 128))
    sizes = jnp.asarray([9, 0, 12], jnp.int32)         # rows 21.. in no group
    want = jax.lax.ragged_dot(xs, w, sizes,
                              precision=jax.lax.Precision.HIGHEST)
    before = moe.kernel_traces()
    out = jax.jit(moe.grouped_matmul)(xs, w, sizes)
    assert len(calls) == 1 and moe.kernel_traces() == before + 1
    np.testing.assert_allclose(out[:21], want[:21], atol=1e-4)
    assert not np.asarray(out)[21:].any()               # zeros, not leftovers
    out, pull = jax.vjp(lambda a, b: moe.grouped_matmul(a, b, sizes), xs, w)
    dxs, _ = pull(jnp.ones_like(out))
    assert len(calls) == 1 and moe.kernel_traces() == before + 1
    np.testing.assert_allclose(out[:21], want[:21], atol=1e-4)
    assert not np.asarray(dxs)[21:].any()


@pytest.mark.parametrize("held", [None, (2, 4)])
def test_the_layer_agrees_on_both_paths_under_a_row_mask(monkeypatch, held):
    """``DroplessMoE.apply`` with idle rows masked out (and, with
    ``held``, pairs routed to experts that are elsewhere): the result
    through the kernel is the result through ``ragged_dot``, and so is
    the load."""
    layer = DroplessMoE(128, 8, 128, top_k=2, n_shared=1, held=held)
    params = layer.init(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (6, 4, 128))
    row_mask = jnp.asarray(np.random.default_rng(5).random((6, 4)) < 0.6)
    apply = lambda: jax.jit(layer.apply)(params, x, row_mask=row_mask)
    y_ragged, load_ragged = apply()
    calls = _kernel_on_this_cpu(monkeypatch)
    jax.clear_caches()
    y_kernel, load_kernel = apply()
    assert len(calls) == 3                              # gate, up, down
    np.testing.assert_allclose(y_kernel, y_ragged, atol=2e-5)
    np.testing.assert_array_equal(load_kernel, load_ragged)
    masked = ~np.asarray(row_mask)
    shared = layer.shared.apply(params["shared"], x.reshape(-1, 128)) \
        .reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y_kernel)[masked],
                               np.asarray(shared)[masked], atol=2e-5)


def test_engine_counts_the_matmuls_that_took_the_kernel(monkeypatch):
    """``stats()["moe_kernel_matmuls"]``: the decode program's grouped
    matmul call sites that lowered through the kernel (three a layer),
    none on a CPU, and the greedy streams are ``ragged_dot``'s."""
    model = models.TransformerLM(**{**TINY, "dim": 128, "moe": dict(
        n_routed=8, width=128, top_k=2, n_shared=1, scale=2.0)})
    params = model.init(jax.random.PRNGKey(7))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 211, n).astype(np.int32) for n in (5, 11)]

    def serve():
        with InferenceEngine(model, params, EngineConfig(
                paged=True, n_slots=2, max_len=64, buckets=(16,),
                page_len=PAGE)) as eng:
            hs = [eng.submit(p, SamplingParams(max_new_tokens=6))
                  for p in prompts]
            return [list(h.result(timeout=600)) for h in hs], eng.stats()
    ragged_tokens, st = serve()
    assert st["moe_kernel_matmuls"] == 0
    _kernel_on_this_cpu(monkeypatch)
    kernel_tokens, st = serve()
    assert st["moe_kernel_matmuls"] == 3 * st["moe_layers"] == 6
    assert st["decode_compiles"] == 1
    assert kernel_tokens == ragged_tokens


def test_bias_rule_against_values_worked_by_hand():
    """b_e <- b_e + speed * sign(mean(c) - c_e): mean 4, so the experts
    with 1 and 3 pairs rise, the one with 4 stays, those with 5 and 7
    fall."""
    bias = jnp.asarray([0.0, 0.01, -0.02, 0.003, 0.0])
    load = jnp.asarray([1, 3, 4, 5, 7], jnp.int32)
    np.testing.assert_allclose(
        DroplessMoE.balance(bias, load, 0.001),
        [0.001, 0.011, -0.02, 0.002, -0.001], atol=1e-9)


# -- the residual path -----------------------------------------------------------

def test_h_res_is_doubly_stochastic():
    # 20 rounds reach 1e-4 where the logits' spread is about 1, which is
    # what the configuration's leaves give (chipbench/configs/xing4-*.json)
    logits = jax.random.normal(jax.random.PRNGKey(8), (50, 4, 4))
    m = np.asarray(sinkhorn(logits, 20, 1e-6, (-30.0, 30.0)))
    assert (m > 0).all()
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-4)
    # and as the block computes it, from streams
    hc = HyperConnection(16, 4)
    p = hc.init(jax.random.PRNGKey(9))
    p["a_res"], p["b_res"] = jnp.float32(1.0), jax.random.normal(
        jax.random.PRNGKey(10), (4, 4))
    xs = jax.random.normal(jax.random.PRNGKey(12), (2, 5, 4, 16))
    h_pre, h_post, h_res = hc.coeffs(p, xs)
    assert h_res.shape == (2, 5, 4, 4) and h_pre.shape == (2, 5, 4)
    np.testing.assert_allclose(np.asarray(h_res).sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_res).sum(-2), 1.0, atol=1e-4)
    assert 0.05 < np.asarray(h_res).std()        # not uniform
    assert np.asarray(h_res).max() < 0.99        # not a permutation
    assert ((np.asarray(h_pre) > 0) & (np.asarray(h_pre) < 1)).all()
    assert ((np.asarray(h_post) > 0) & (np.asarray(h_post) < 2)).all()


def test_yarn_frequencies_against_values_computed_by_hand():
    """rope 64, theta 10000, factor 64, original 4096, beta 32/1: the
    ramp runs from dimension 10 to 23. i = 3 is untouched, i = 30 is
    divided by 64, i = 16 is blended 6/13 of the way."""
    f = np.asarray(yarn_inv_freq(64, 10000.0, factor=64, original_max=4096,
                                 beta_fast=32, beta_slow=1))
    lo = math.floor(64 * math.log(4096 / (2 * math.pi * 32))
                    / (2 * math.log(10000)))
    hi = math.ceil(64 * math.log(4096 / (2 * math.pi * 1))
                   / (2 * math.log(10000)))
    assert (lo, hi) == (10, 23)
    plain = lambda i: 10000.0 ** (-2 * i / 64)
    np.testing.assert_allclose(f[3], 0.421696503, rtol=1e-5)     # plain(3)
    np.testing.assert_allclose(f[3], plain(3), rtol=1e-5)
    np.testing.assert_allclose(f[30], plain(30) / 64, rtol=1e-5)
    np.testing.assert_allclose(f[30], 2.7788e-06, rtol=1e-3)
    r = (16 - 10) / 13
    np.testing.assert_allclose(f[16], plain(16) * (r / 64 + 1 - r), rtol=1e-5)
    np.testing.assert_allclose(f[16], 0.0054565, rtol=1e-3)
    assert abs(yarn_mscale(64, 1) - 1.41589) < 1e-4
    assert yarn_mscale(1, 1) == 1.0


# -- what has not been carried over says so by name ----------------------------

def test_refusals_for_latent_blocks(tiny):
    model, params = tiny
    # the engine's one pool serves it: a configuration that does not say
    # ``paged`` builds latent pages
    eng = InferenceEngine(model, params, EngineConfig(n_slots=2, max_len=32))
    assert all(type(st).__name__ == "LatentPages" for st in eng.pool.state)
    for kv in ("q8", "q4"):
        with pytest.raises(LatentPagesUnsupported, match="quantized pages"):
            InferenceEngine(model, params, EngineConfig(
                paged=True, n_slots=2, max_len=32, kv_dtype=kv))
    draft = models.TransformerLM(vocab=211, dim=32, n_layers=1, n_heads=2,
                                 max_seq=64)
    with pytest.raises(LatentPagesUnsupported, match="serve/spec"):
        InferenceEngine(model, params, EngineConfig(
            paged=True, n_slots=2, max_len=32, spec_decode=True,
            draft_model=draft, draft_params=draft.init(jax.random.PRNGKey(0))))
    pool = PagedSlotPool(model, 2, 32, page_len=PAGE, n_pages=16)
    with pytest.raises(LatentPagesUnsupported, match="serve/disagg"):
        pool.extract(0)
    with pytest.raises(LatentPagesUnsupported, match="serve/disagg"):
        pool.adopt(0, 4, [], [])
    from distributed_pytorch_tpu.serve.disagg import DecodeEngine
    with pytest.raises(LatentPagesUnsupported, match="serve/disagg"):
        DecodeEngine(model, params, None, None, n_slots=2, max_len=32,
                     page_len=PAGE, n_pages=16)


def test_the_fixed_block_is_untouched_by_the_new_keywords():
    """A model built without them has the fixed block, its parameter
    names and no stream axis."""
    from distributed_pytorch_tpu.nn.attention import TransformerBlock
    m = models.TransformerLM(vocab=97, dim=32, n_layers=2, n_heads=4,
                             n_kv_heads=2, max_seq=32, pos="rope")
    assert all(type(b) is TransformerBlock for b in m.blocks)
    p = m.init(jax.random.PRNGKey(0))
    assert set(p["blocks"][0]) == {"ln1", "attn", "ln2", "fc1", "fc2"}
    assert m.streams == 0 and m.streams_in(jnp.ones((1, 2, 32))).ndim == 3
    # a block made of parts with multi-head attention keeps K and V pages
    parts = models.TransformerLM(vocab=97, dim=32, n_layers=2, n_heads=4,
                                 n_kv_heads=2, max_seq=32, pos="rope",
                                 norm="rms", ffn_dim=48)
    pool = PagedSlotPool(parts, 2, 32, page_len=PAGE, n_pages=16)
    assert all(type(st) is KVPages for st in pool.state)
    pp = parts.init(jax.random.PRNGKey(1))
    prompt = np.arange(7, dtype=np.int32)
    toks, rows, _ = serve_through_pool(parts, pp, pool, prompt, 0, 6)
    ref = full_logits(parts, pp, np.concatenate([prompt, toks]))[6:12]
    np.testing.assert_allclose(rows, ref, atol=2e-4, rtol=0)


# -- the prediction module ------------------------------------------------------

MTP_TINY = {**{k: v for k, v in TINY.items()
               if k not in ("hyper_connections", "hc")},
            "moe": dict(TINY["moe"], held=(2, 4))}


def test_mtp_zero_builds_what_was_built_before_and_apply_never_runs_it():
    plain = models.TransformerLM(**MTP_TINY)
    zero = models.TransformerLM(**MTP_TINY, mtp=0)
    one = models.TransformerLM(**MTP_TINY, mtp=1)
    key = jax.random.PRNGKey(2)
    p_plain, p_zero, p_one = plain.init(key), zero.init(key), one.init(key)
    assert plain.mtp is None and zero.mtp is None and "mtp" not in p_zero
    same = lambda a, b: jax.tree_util.tree_all(
        jax.tree_util.tree_map(lambda x, y: bool(jnp.array_equal(x, y)),
                               a, b))
    assert same(p_plain, p_zero)
    # the module is one more subtree: every other leaf is the same array
    assert set(p_one) == set(p_plain) | {"mtp"}
    assert same({k: v for k, v in p_one.items() if k != "mtp"}, p_plain)
    assert set(p_one["mtp"]) == {"norm_e", "norm_h", "proj", "block", "norm"}
    assert p_one["mtp"]["proj"]["w"].shape == (128, 64)
    assert set(p_one["mtp"]["block"]) == set(p_plain["blocks"][-1])
    tokens = jnp.asarray(np.arange(24).reshape(2, 12) % 211)
    np.testing.assert_array_equal(jax.jit(one.apply)(p_one, tokens),
                                  jax.jit(plain.apply)(p_plain, tokens))
    # and the entry for a loss over both heads
    main, mtp, load = jax.jit(one.heads_hidden)(p_one, tokens)
    np.testing.assert_allclose(
        main, jax.jit(lambda p, t: plain.apply(p, t, return_hidden=True))(
            p_plain, tokens[:, :-1]), atol=1e-6)
    assert mtp.shape == (2, 10, 64) and load.shape == (3, 8)
    # 2 rows x 11 positions x 2 a token; the module one position fewer
    assert np.asarray(load).sum(-1).tolist() == [44, 44, 40]
    main0, mtp0, load0 = jax.jit(plain.heads_hidden)(p_plain, tokens)
    assert mtp0 is None and np.array_equal(load0, load[:2])
    mask = one.router_bias_mask(p_one)
    assert sum(jax.tree_util.tree_leaves(mask)) == 3
    assert mask["mtp"]["block"]["ffn"]["router"]["bias"] is True


@pytest.mark.parametrize("kw,match", [
    (dict(mtp=2), "0 or 1"),
    (dict(mtp=1, hyper_connections=4), "plain residual"),
    (dict(mtp=1, block_kinds=None, attention="mha", norm="layer",
          pos="learned"), "made of parts")])
def test_mtp_refusals(kw, match):
    with pytest.raises(ValueError, match=match):
        models.TransformerLM(**{**MTP_TINY, **kw})


def test_the_modules_last_position_is_cut_not_counted_and_unseen():
    """The module runs over all S positions and cuts the last: what it
    returns and counts equals a run over S - 1 positions alone."""
    one = models.TransformerLM(**MTP_TINY, mtp=1)
    p = one.init(jax.random.PRNGKey(2))
    tokens = jnp.asarray((np.arange(26).reshape(2, 13) * 7) % 211)
    h, _ = one._trunk(p, tokens[:, :-1], positions=jnp.arange(12))
    _, mtp, load = one.heads_hidden(p, tokens)
    m, q = one.mtp, p["mtp"]
    x = m["proj"].apply(q["proj"], jnp.concatenate(
        [m["norm_e"].apply(q["norm_e"], one.tok.apply(p["tok"],
                                                      tokens[:, 1:-1])),
         m["norm_h"].apply(q["norm_h"], h[:, :-1])], -1))
    y, load_m = m["block"].apply(q["block"], x, positions=jnp.arange(11))
    np.testing.assert_allclose(mtp, m["norm"].apply(q["norm"], y),
                               atol=1e-5)
    assert np.array_equal(load[-1], load_m)


@pytest.mark.parametrize("core", ["dense", "flash"])
def test_narrow_values_through_the_module_equal_the_padded_form(core):
    """``LatentAttention._core`` hands a core that says ``narrow_values``
    (the dense einsum, the flash kernel) its values at their own width;
    any other gets them padded to the keys' width and its result cut
    back, which is what every core got before PR 36. Output and the
    gradients of the input and of every parameter agree."""
    from distributed_pytorch_tpu.nn.attention import dense_attention
    from distributed_pytorch_tpu.nn.latent import LatentAttention
    from distributed_pytorch_tpu.ops import make_flash_attn_fn

    narrow = dense_attention if core == "dense" else make_flash_attn_fn(
        32, 32, min_seq_flash=None)
    seen = []

    def padded(q, k, v, **kw):       # says nothing: takes equal widths
        seen.append((k.shape[-1], v.shape[-1]))
        return narrow(q, k, v, **kw)

    kw = dict(q_rank=16, kv_rank=16, nope_dim=16, rope_dim=8, v_dim=16)
    a, b = (LatentAttention(48, 3, attn_fn=f, **kw) for f in (narrow, padded))
    params = a.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 80, 48))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, 80, 48))

    def loss(attn):
        return lambda p, x: jnp.sum(attn.apply(p, x) * w)

    np.testing.assert_allclose(np.asarray(a.apply(params, x)),
                               np.asarray(b.apply(params, x)),
                               atol=1e-6, rtol=1e-6)
    assert seen and set(seen) == {(24, 24)}
    ga = jax.grad(loss(a), argnums=(0, 1))(params, x)
    gb = jax.grad(loss(b), argnums=(0, 1))(params, x)
    for u, v in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                   atol=1e-5, rtol=1e-5)
