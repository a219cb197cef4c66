"""Generation by diffusion over blocks (docs/serving.md "Generation by
blocks"; PERF.md, Findings, PR 37), at a tiny size in float32 on the CPU.

The parts, each against its own equation: multi-head attention with a
free head size, q/k norms and the block-causal mask; the softmax scoring
rule of the dropless experts and its shares. The step: prefill in chunks
and block passes through the paged pool against a plain loop that
recomputes the whole sequence every pass with no cache, logits compared.
The engine: rows in different passes in one program, prompts and answers
of any length, ``denoise_steps`` 1, 2 and 4, page growth, a pool exhausted
mid-block, streaming order, the counters, and the refusals by name. One
block pass in flight (``InferenceEngine._block_all``), case for case as
``tests/test_serve_inflight.py`` holds the token path to it: the blocks stay
on the device, pass k+1 is dispatched from what the host counts before pass
k is read, and ``stats()``, ``shutdown()``, ``crash()``, a failed read, a
deadline, an exhausted pool and an idle engine each meet a pass in flight
without a hang, a stranded future or a token streamed twice."""

import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import models
from distributed_pytorch_tpu.models.generate import (
    BlockGenerationUnsupported, block_step_slots_paged, make_generate_fn)
from distributed_pytorch_tpu.nn.attention import (MultiHeadAttention,
                                                  block_causal_mask)
from distributed_pytorch_tpu.obs import trace as dpxtrace
from distributed_pytorch_tpu.ops import make_flash_attn_fn
from distributed_pytorch_tpu.parallel.moe import DroplessMoE
from distributed_pytorch_tpu.runtime import compile_cache, faults
from distributed_pytorch_tpu.serve import (AdmissionRejected, EngineConfig,
                                           EngineStopped, InferenceEngine,
                                           PagePoolExhausted,
                                           RequestDeadlineExceeded,
                                           SamplingParams)
from distributed_pytorch_tpu.serve.disagg import DisaggConfig, DisaggEngine
from distributed_pytorch_tpu.serve.pages import PagedSlotPool
from distributed_pytorch_tpu.serve.sampling import (carry_blocks, fill_block,
                                                    fill_counts, open_blocks)

L = 4                      # the block
MASK = 96                  # the mask id: the vocabulary's last
KW = dict(vocab=97, dim=32, n_layers=2, n_heads=8, n_kv_heads=2, head_dim=8,
          attn_bias=False, qk_norm=1e-6, pos="rope", rope_base=1e6,
          max_seq=128, norm="rms", norm_eps=1e-6,
          block_kinds=("moe", "moe"),
          moe=dict(n_routed=8, width=16, top_k=2, n_shared=0,
                   score="softmax"),
          gen_block=L, mask_id=MASK)


@pytest.fixture(scope="module")
def lm():
    model = models.TransformerLM(**KW)
    return model, model.init(jax.random.PRNGKey(11))


@pytest.fixture(autouse=True)
def _clean_faults_and_spans():
    faults.reset()
    dpxtrace.reset()
    yield
    faults.reset()
    dpxtrace.reset()


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, MASK, n) \
        .astype(np.int32)


def _engine(model, params, **kw):
    cfg = dict(paged=True, n_slots=3, max_len=64, page_len=8,
               buckets=(8, 16, 32), max_queue=32)
    cfg.update(kw)
    return InferenceEngine(model, params, EngineConfig(**cfg))


def plain_generate(model, params, prompt, max_new, steps, trail=None):
    """SDAR's ``block_diffusion_generate`` with NO cache: every pass runs
    the whole sequence so far and the block through ``model.apply`` under
    the block-causal mask and reads the block's logits. Returns (tokens,
    the pass of its block that filled each). ``trail``: a list every
    pass's (sequence before the block, block, float32 logits of the
    block) is appended to."""
    mid = model.mask_id
    sched = fill_counts(L, steps)
    prompt = [int(t) for t in prompt]
    seq = prompt[:len(prompt) - len(prompt) % L]
    blk = prompt[len(seq):] + [mid] * (L - len(prompt) % L)
    at = [-1] * (len(prompt) % L) + [None] * (L - len(prompt) % L)
    out, out_at = [], []
    while len(out) < max_new:
        s = 0
        while True:
            lg = np.asarray(model.apply(params, jnp.asarray([seq + blk])),
                            np.float32)[0, -L:]
            if trail is not None:
                trail.append((list(seq), list(blk), lg))
            if mid not in blk:
                break                                    # the commit pass
            conf = lg.max(-1) - np.log(np.exp(
                lg.astype(np.float64) - lg.max(-1, keepdims=True)).sum(-1)) \
                - lg.max(-1)
            masked = [j for j in range(L) if blk[j] == mid]
            for j in sorted(masked, key=lambda j: (-conf[j], j))[:sched[s]]:
                blk[j], at[j] = int(lg[j].argmax()), s
            s += 1
        for j in range(L):
            if at[j] >= 0 and len(out) < max_new:
                out.append(blk[j])
                out_at.append(at[j])
        seq += blk
        blk, at = [mid] * L, [None] * L
    return out, out_at


# -- the parts, each against its own equation ---------------------------------

def test_block_causal_mask_is_causal_over_blocks_and_full_inside_one():
    pos = jnp.arange(10)
    m = np.asarray(block_causal_mask(pos, pos, 4))
    for i in range(10):
        for j in range(10):
            assert m[i, j] == (j // 4 <= i // 4)
    # absolute positions: a tail that starts at a block's edge
    tail = jnp.arange(8, 14)
    np.testing.assert_array_equal(
        np.asarray(block_causal_mask(tail, tail, 4)), m[:6, :6])


def test_attention_with_free_head_size_and_qk_norms_against_its_equation():
    """32 wide, 8 heads of 8 (not 32 / 8 = 4), 2 KV heads, no biases, an
    RMSNorm with one gain vector over each head's 8 values of q and k
    before the rotation, block-causal."""
    attn = MultiHeadAttention(32, 8, n_kv_heads=2, head_dim=8, bias=False,
                              qk_norm=1e-6, rope=True, rope_base=1e4,
                              gen_block=4)
    p = attn.init(jax.random.PRNGKey(0))
    assert p["qkv"]["w"].shape == (32, (8 + 4) * 8) and "b" not in p["qkv"]
    assert p["out"]["w"].shape == (64, 32) and "b" not in p["out"]
    p["q_norm"]["scale"] = 1 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(1), (8,))
    p["k_norm"]["scale"] = 1 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(2), (8,))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 10, 32))
    got = np.asarray(attn.apply(p, x))[0]

    w = np.asarray(p["qkv"]["w"], np.float64)
    xs = np.asarray(x[0], np.float64)
    q, k, v = np.split(xs @ w, [64, 80], axis=-1)
    heads = lambda t, n: t.reshape(10, n, 8).transpose(1, 0, 2)
    rms = lambda t, g: t / np.sqrt((t ** 2).mean(-1, keepdims=True) + 1e-6) \
        * np.asarray(g, np.float64)

    def rope(t):
        ang = np.arange(10)[:, None] * 1e4 ** (-np.arange(4) / 4)[None, :]
        a, b = t[..., :4], t[..., 4:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang)], -1)
    q = rope(rms(heads(q, 8), p["q_norm"]["scale"]))
    k = rope(rms(heads(k, 2), p["k_norm"]["scale"]))
    v = heads(v, 2)
    out = np.zeros((10, 8, 8))
    for h in range(8):
        s = q[h] @ k[h // 4].T / math.sqrt(8)
        seen = (np.arange(10)[None, :] // 4) <= (np.arange(10)[:, None] // 4)
        s = np.where(seen, s, -np.inf)
        pr = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = pr / pr.sum(-1, keepdims=True) @ v[h // 4]
    want = out.reshape(10, 64) @ np.asarray(p["out"]["w"], np.float64)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_softmax_routing_against_its_equation():
    """p = softmax(x W_r) over all experts, the k largest, renormalised
    over the chosen; no bias leaf, no shared expert."""
    layer = DroplessMoE(24, 16, 8, top_k=4, n_shared=0, score="softmax")
    params = layer.init(jax.random.PRNGKey(0))
    assert "bias" not in params["router"] and "shared" not in params
    x = jax.random.normal(jax.random.PRNGKey(1), (19, 24))
    top_i, w, p = (np.asarray(a) for a in layer.route(params, x))
    logits = np.asarray(x, np.float64) @ np.asarray(params["router"]["w"],
                                                    np.float64)
    prob = np.exp(logits - logits.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    np.testing.assert_allclose(p, prob, atol=1e-6)
    want_i = np.argsort(-prob, -1)[:, :4]
    np.testing.assert_array_equal(np.sort(top_i, -1), np.sort(want_i, -1))
    chosen = np.take_along_axis(prob, top_i, -1)
    np.testing.assert_allclose(w, chosen / chosen.sum(-1, keepdims=True),
                               atol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    # the layer is the weighted sum of the chosen experts' SwiGLU
    y = np.asarray(layer.apply(params, x)[0], np.float64)
    e = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64),
                               params["experts"])
    xs = np.asarray(x, np.float64)
    want = np.zeros_like(xs)
    for t in range(19):
        for i, wt in zip(top_i[t], w[t]):
            g = xs[t] @ e["gate"][i]
            want[t] += wt * ((g / (1 + np.exp(-g)) * (xs[t] @ e["up"][i]))
                             @ e["down"][i])
    np.testing.assert_allclose(y, want, atol=2e-5)


def test_softmax_shares_of_128_experts_add_up_to_the_whole_layer():
    """held=(0, 32) .. (96, 32): four chips' parts of one layer."""
    kw = dict(top_k=8, n_shared=0, score="softmax")
    whole = DroplessMoE(16, 128, 8, **kw)
    params = whole.init(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (23, 16))
    total = 0.0
    for first in (0, 32, 64, 96):
        share = DroplessMoE(16, 128, 8, held=(first, 32), **kw)
        p = dict(params, experts=jax.tree_util.tree_map(
            lambda a: a[first:first + 32], params["experts"]))
        total = total + share.apply(p, x)[0]
    np.testing.assert_allclose(total, whole.apply(params, x)[0], atol=2e-5)


def test_fill_block_takes_the_most_confident_masked_positions():
    """Confidence is the max softmax; ties go to the lowest position; a
    filled position is never touched; ``n_fill`` 0 fills nothing."""
    v = 7
    peak = lambda at, h: np.where(np.arange(v) == at, h, 0.0)
    logits = np.asarray([
        [peak(1, 2.0), peak(2, 5.0), peak(3, 5.0), peak(4, 9.0)],
        [peak(5, 1.0), peak(5, 1.0), peak(6, 3.0), peak(0, 0.5)],
        [peak(1, 4.0), peak(2, 4.0), peak(3, 4.0), peak(4, 4.0)]],
        np.float32)
    tokens = np.full((3, 4), 99, np.int32)
    masked = np.asarray([[1, 1, 1, 0], [1, 1, 1, 1], [0, 0, 0, 0]], bool)
    out = np.asarray(fill_block(jnp.asarray(logits), jnp.asarray(tokens),
                                jnp.asarray(masked),
                                jnp.asarray([1, 2, 0], np.int32)))
    # row 0: position 3 is the surest but filled already; 1 and 2 tie
    np.testing.assert_array_equal(out[0, 1], [0, 1, 0, 0])
    np.testing.assert_array_equal(out[0, 0], [99, 2, 99, 99])
    # row 1: the surest, then the tie's lowest position
    np.testing.assert_array_equal(out[1, 1], [1, 0, 1, 0])
    np.testing.assert_array_equal(out[1, 0], [5, 99, 6, 99])
    assert not out[2, 1].any() and (out[2, 0] == 99).all()
    np.testing.assert_array_equal(fill_counts(4, 4), [1, 1, 1, 1])
    np.testing.assert_array_equal(fill_counts(4, 3), [2, 1, 1])
    np.testing.assert_array_equal(fill_counts(4, 1), [4])


def test_blocks_carried_on_the_device_open_and_commit_inside_the_program():
    """What one pass leaves, (B, 3, L) tokens, this pass's fills and the
    masks, the next pass's blocks and the host's read in one: a row the
    host opens a block for takes its given tokens and then masked
    positions, whatever it carried (the middle row, the last pass's
    fills, is nobody's input); a row that ran its commit pass leaves with
    a fresh block; every other row keeps what the pick left."""
    carried = jnp.asarray([[[5, 99, 7, 99], [1, 0, 1, 0], [0, 1, 0, 1]],
                           [[1, 2, 3, 4], [1, 1, 1, 1], [0, 0, 0, 0]],
                           [[9, 9, 9, 9], [0, 1, 0, 0], [1, 0, 1, 1]]],
                          jnp.int32)
    given = jnp.asarray([[0] * 4, [0] * 4, [11, 12, 0, 0]], jnp.int32)
    tokens, masked = open_blocks(carried, given,
                                 jnp.asarray([-1, -1, 2], jnp.int32), 99)
    np.testing.assert_array_equal(
        tokens, [[5, 99, 7, 99], [1, 2, 3, 4], [11, 12, 99, 99]])
    np.testing.assert_array_equal(
        masked, [[0, 1, 0, 1], [0, 0, 0, 0], [0, 0, 1, 1]])
    # a block opened with nothing given is all masked
    tokens0, masked0 = open_blocks(carried, given,
                                   jnp.asarray([0, -1, -1], jnp.int32), 99)
    assert (np.asarray(tokens0)[0] == 99).all() and np.asarray(masked0)[0].all()
    # the pick filled row 0's position 3 and row 2's position 2; row 1 is
    # clean and ran its commit pass
    out = jnp.asarray([[[5, 99, 7, 8], [0, 0, 0, 1]],
                       [[1, 2, 3, 4], [0, 0, 0, 0]],
                       [[11, 12, 6, 99], [0, 0, 1, 0]]], jnp.int32)
    nxt = np.asarray(carry_blocks(out, masked,
                                  jnp.asarray([False, True, False]), 99))
    np.testing.assert_array_equal(nxt[:, 0], [[5, 99, 7, 8], [99] * 4,
                                              [11, 12, 6, 99]])
    np.testing.assert_array_equal(nxt[:, 1], out[:, 1])
    np.testing.assert_array_equal(nxt[:, 2], [[0, 1, 0, 0], [1] * 4,
                                              [0, 0, 0, 1]])


# -- the step through the pool --------------------------------------------------

@pytest.mark.parametrize("n_prompt,chunked", [(13, False), (37, True)])
def test_chunked_prefill_and_block_passes_equal_the_uncached_loop(
        lm, n_prompt, chunked):
    """The prompt's whole blocks prefilled through the pool (in two
    chunks where ``chunked``), then every pass of two blocks through
    ``block_step_slots_paged`` beside another row in another state: each
    pass's logits equal those of the whole sequence recomputed under the
    block-causal mask, a commit pass's those of the finished sequence's
    full forward at the block's positions."""
    model, params = lm
    prompt = _prompt(n_prompt)
    trail = []
    plain_generate(model, params, prompt, 8 - n_prompt % L, 4, trail)
    buckets = (8, 16) if chunked else (8, 16, 64)
    pool = PagedSlotPool(model, 2, 64, page_len=8, n_pages=16)
    whole = n_prompt - n_prompt % L
    pool.admit(params, prompt[:whole], 1, buckets)
    assert pool.lengths[1] == whole
    assert (pool.compiles.prefill.keys() == {8, 16}) == chunked
    step = jax.jit(lambda st, tables, lengths, toks, active:
                   block_step_slots_paged(model, params, st, tables, lengths,
                                          toks, active, page_len=8))
    finished = np.asarray(trail[-1][0] + trail[-1][1])
    full = np.asarray(model.apply(params, jnp.asarray([finished])))[0]
    worst = 0.0
    for seq, blk, want in trail:
        assert len(seq) == pool.lengths[1]
        pool.ensure_spec_capacity(1, L)
        toks = np.full((2, L), MASK, np.int32)
        toks[1] = blk
        logits, pool.state = step(
            pool.state, jnp.asarray(pool.tables), jnp.asarray(pool.lengths),
            jnp.asarray(toks), jnp.asarray([False, True]))
        got = np.asarray(logits)[1]
        worst = max(worst, np.abs(got - want).max())
        if MASK not in blk:                   # its commit pass
            np.testing.assert_allclose(
                got, full[len(seq):len(seq) + L], atol=5e-5)
            pool.lengths[1] += L
    assert len(trail) == (L - n_prompt % L + 1) + 5
    assert worst < 5e-5, worst


def test_a_shared_prefix_reused_gives_the_same_logits(lm):
    """A page's keys depend on nothing past the page's end (a page is
    whole blocks), so a prompt admitted over another's resident pages
    reads the logits of the same prompt admitted cold."""
    model, params = lm
    a = _prompt(24, seed=1)
    b = np.concatenate([a[:16], _prompt(8, seed=2)])
    cold = PagedSlotPool(model, 2, 64, page_len=8, n_pages=16)
    want, n_hit, _ = cold.admit(params, b, 0, (8, 16, 32))
    assert n_hit == 0
    warm = PagedSlotPool(model, 2, 64, page_len=8, n_pages=16)
    warm.admit(params, a, 0, (8, 16, 32))
    got, n_hit, offset = warm.admit(params, b, 1, (8, 16, 32))
    assert (n_hit, offset) == (2, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


# -- the engine --------------------------------------------------------------------

CASES = [(9, 10, 4), (16, 7, 2), (3, 9, 1), (21, 12, 4), (12, 5, 3),
         (7, 4, 4), (32, 16, 2)]


def test_engine_streams_equal_the_uncached_loop_for_every_shape(lm):
    """Seven requests over three slots, so that rows in different passes
    share one program, one finishes while others go on and slots are
    reused: prompts whose length is and is not a multiple of the block
    (the remainder opens the first block), ``max_new_tokens`` that is
    not, ``denoise_steps`` 1 to 4. Same tokens, same fill order."""
    model, params = lm
    streamed = {}
    with _engine(model, params) as eng:
        handles = []
        for k, (n_prompt, n_new, steps) in enumerate(CASES):
            streamed[k] = []
            handles.append(eng.submit(
                _prompt(n_prompt, seed=k),
                SamplingParams(max_new_tokens=n_new, denoise_steps=steps),
                on_token=lambda tok, i, k=k: streamed[k].append((i, tok))))
        outs = [h.result(timeout=300) for h in handles]
        stats = eng.stats()
    for k, ((n_prompt, n_new, steps), h, out) in enumerate(
            zip(CASES, handles, outs)):
        want, want_at = plain_generate(model, params,
                                       _prompt(n_prompt, seed=k), n_new,
                                       steps)
        assert out.tolist() == want, k
        assert h.fill_pass == want_at, k
        # streamed in position order, every index once
        assert streamed[k] == list(enumerate(want)), k
    assert stats["decode_compiles"] == 1 and stats["sample_compiles"] == 0
    assert stats["tokens_emitted"] == sum(n for _, n, _ in CASES)
    assert stats["completed"] == len(CASES) and stats["failed"] == 0
    # every filled position was streamed or lay past max_new_tokens
    assert stats["block_fills"] >= stats["tokens_emitted"]
    assert stats["block_passes"] == stats["rows_decoded"]
    assert stats["blocks_emitted"] >= stats["block_commits"]
    assert stats["decode_fetches"] == stats["iterations"] - 1
    assert stats["decode_passes_ahead"] == stats["decode_fetches"] - 1
    assert stats["decode_rows_dropped"] == 0
    assert eng.pool.pool.live_pages() == 0


@pytest.mark.parametrize("steps,passes", [(1, 2), (2, 3), (4, 5)])
def test_a_block_costs_its_denoising_passes_and_one_commit(lm, steps,
                                                           passes):
    """One request alone, prompt and answer whole blocks: a block of L
    masked positions costs ``denoise_steps + 1`` passes, the last block
    no commit pass (nobody reads its keys); its tokens are streamed
    together, in the iteration that fills its last masked position."""
    model, params = lm
    seen = []
    with _engine(model, params, n_slots=1) as eng:
        def on_token(tok, i):
            seen.append((i, eng.stats()["iterations"]))
        h = eng.submit(_prompt(8), SamplingParams(max_new_tokens=12,
                                                  denoise_steps=steps),
                       on_token=on_token)
        h.result(timeout=300)
        stats = eng.stats()
    assert stats["block_passes"] == 3 * passes - 1
    assert stats["block_commits"] == 2 and stats["blocks_emitted"] == 3
    assert stats["block_fills"] == 12 == stats["tokens_emitted"]
    assert stats["decode_fetches"] == stats["block_passes"]
    iters = [it for _, it in seen]
    # a block's four tokens in one iteration, `passes` iterations apart
    assert iters[0] == iters[3] and iters[4] == iters[7]
    assert iters[4] - iters[0] == passes == iters[8] - iters[4]
    assert sorted(set(h.fill_pass)) == list(range(steps))


def test_pages_grow_at_a_block_on_a_pages_edge(lm):
    """page_len 8, prompt 8: the first block opens a new page, and every
    second block after it another."""
    model, params = lm
    held = []
    with _engine(model, params, n_slots=1, max_len=32) as eng:
        h = eng.submit(_prompt(8), SamplingParams(max_new_tokens=17),
                       on_token=lambda tok, i: held.append(
                           len(eng.pool.owned[0])))
        out = h.result(timeout=300)
        assert eng.pool.pool.live_pages() == 0
    want, _ = plain_generate(model, params, _prompt(8), 17, 4)
    assert out.tolist() == want
    # the prompt's page, then one more at blocks 1, 3 and 5 (the last
    # token retires its request before the callback reads the table)
    assert held[:16] == [2] * 8 + [3] * 8


def test_pool_exhausted_mid_block_fails_that_request_typed(lm):
    """Five pages of 8 between two requests that each grow to three at
    their third block: the first takes the fifth, the second finds none.
    It fails typed, attributed to itself; its neighbour's stream is bit
    for bit what it is alone."""
    model, params = lm
    a, b = _prompt(8, seed=5), _prompt(8, seed=6)
    with _engine(model, params, n_slots=1) as eng:
        alone = eng.submit(a, SamplingParams(max_new_tokens=15)).result(
            timeout=300)
    with _engine(model, params, n_slots=2, max_len=32, n_pages=5,
                 prefix_share=False) as eng:
        ha = eng.submit(a, SamplingParams(max_new_tokens=15))
        hb = eng.submit(b, SamplingParams(max_new_tokens=20))
        with pytest.raises(PagePoolExhausted) as ei:
            hb.result(timeout=300)
        out_a = ha.result(timeout=300)
        assert eng.pool.pool.live_pages() == 0
    assert ei.value.request_id == hb.request_id
    assert ei.value.iteration is not None and "mid-block" in str(ei.value)
    np.testing.assert_array_equal(out_a, alone)
    # the pool was asked ahead, B's pass before still in flight: that pass
    # is dropped where it is read, and B's blocks so far are its stream's
    stats = eng.stats()
    assert stats["decode_rows_dropped"] == 1 and stats["failed"] == 1
    want_b, _ = plain_generate(model, params, b, 20, 4)
    assert 0 < len(hb.tokens) < 20 and hb.tokens == want_b[:len(hb.tokens)]


def test_an_eos_token_ends_the_stream_inside_its_block(lm):
    model, params = lm
    want, _ = plain_generate(model, params, _prompt(9), 12, 4)
    eos = want[5]
    stop = want.index(eos)
    with _engine(model, params) as eng:
        out = eng.submit(_prompt(9), SamplingParams(
            max_new_tokens=12, eos_token=eos)).result(timeout=300)
    assert out.tolist() == want[:stop + 1]


# -- one block pass in flight ------------------------------------------------------

def _spy_blocks(eng):
    """Every block pass ``eng`` dispatches from here on, as ``(ahead,
    {slot: (request id, its pass of its block; -1: the commit pass)})``."""
    seen, inner = [], eng._dispatch_blocks

    def spied(slots, ahead):
        inner(slots, ahead)
        seen.append((ahead, {s: (r.request_id, at)
                             for s, (r, at, _) in eng._inflight.rows.items()}))
    eng._dispatch_blocks = spied
    return seen


def _passes(n_prompt, n_new, steps):
    """The passes a request costs: of every block its fill passes 0, 1,
    .. and -1, its commit pass, but none after the block that streams the
    request's last token."""
    sched, out = fill_counts(L, steps), []
    owed, masked = 0, L - n_prompt % L
    while True:
        owed += masked
        at = 0
        while masked > 0:
            masked -= sched[at]
            out.append(at)
            at += 1
        if owed >= n_new:
            return out
        out.append(-1)
        masked = L


def test_staggered_block_streams_are_the_loops_and_run_ahead(lm):
    """Requests of mixed prompt and answer lengths and ``denoise_steps``
    arriving while others generate (two before the loop starts, the rest
    from the first one's token callbacks) through three slots: every
    stream and every ``fill_pass`` is the uncached loop's, every pass but
    each busy period's first was dispatched before the pass before it was
    read, and no row was dropped."""
    model, params = lm
    cases = [(9, 22, 4), (16, 7, 2), (3, 9, 1), (21, 12, 4), (6, 5, 3),
             (12, 10, 4)]
    eng = _engine(model, params)
    seen = _spy_blocks(eng)
    handles = {}

    def submit(k):
        n_prompt, n_new, steps = cases[k]
        handles[k] = eng.submit(
            _prompt(n_prompt, seed=k),
            SamplingParams(max_new_tokens=n_new, denoise_steps=steps),
            on_token=arrive if k == 0 else None)

    def arrive(tok, at):
        for k in {3: (2,), 7: (3, 4), 15: (5,)}.get(at, ()):
            submit(k)
    submit(0)
    submit(1)
    with eng:
        handles[0].result(timeout=300)
        outs = [handles[k].result(timeout=300) for k in range(len(cases))]
        stats = eng.stats()
    for k, (n_prompt, n_new, steps) in enumerate(cases):
        want, want_at = plain_generate(model, params,
                                       _prompt(n_prompt, seed=k), n_new,
                                       steps)
        assert outs[k].tolist() == want, k
        assert handles[k].fill_pass == want_at, k
        # the passes each request ran, in order, and none past its last
        rid = handles[k].request_id
        assert [at for _, rows in seen for r, at in rows.values()
                if r == rid] == _passes(n_prompt, n_new, steps), k
    assert stats["decode_rows_dropped"] == 0 and stats["failed"] == 0
    assert stats["block_passes"] == stats["rows_decoded"] \
        == sum(len(_passes(*c)) for c in cases)
    assert stats["block_commits"] == sum(_passes(*c).count(-1) for c in cases)
    assert stats["decode_fetches"] == len(seen)
    assert stats["decode_passes_ahead"] == sum(a for a, _ in seen)
    assert stats["decode_passes_ahead"] / stats["decode_fetches"] > 0.9, stats
    assert stats["decode_compiles"] == 1 and stats["sample_compiles"] == 0


def test_a_block_row_is_not_run_past_its_last_token_nor_asks_a_page(lm):
    """Requests that end by ``max_new_tokens`` alone, on a block's last
    position and inside one, one filling its slot row to the last block
    ``_validate`` admits: no pass is dispatched for a block past the one
    that streams the last token, no commit pass for that block, nothing
    is dropped, and the pool is asked for pages only at a length whose
    block a pass then writes."""
    model, params = lm
    cases = [(8, 12, 4), (9, 10, 2), (5, 27, 4)]        # 5 + 27 = 32
    eng = _engine(model, params, max_len=32)
    seen = _spy_blocks(eng)
    asked, grow = [], eng.pool.ensure_spec_capacity

    def spied(slot, n_new):
        asked.append((eng._running[slot].request_id,
                      int(eng.pool.lengths[slot])))
        return grow(slot, n_new)
    eng.pool.ensure_spec_capacity = spied
    hs = [eng.submit(_prompt(n, seed=40 + k),
                     SamplingParams(max_new_tokens=new, denoise_steps=steps))
          for k, (n, new, steps) in enumerate(cases)]
    with eng:
        outs = [h.result(timeout=300) for h in hs]
        stats = eng.stats()
    for k, ((n, new, steps), h, out) in enumerate(zip(cases, hs, outs)):
        want, want_at = plain_generate(model, params, _prompt(n, seed=40 + k),
                                       new, steps)
        assert out.tolist() == want and h.fill_pass == want_at, k
        passes = _passes(n, new, steps)
        assert [at for _, rows in seen for r, at in rows.values()
                if r == h.request_id] == passes, k
        # a block is written at the row's length: the last one asked for
        # is the last block's, inside max_len
        starts = sorted({at for r, at in asked if r == h.request_id})
        assert starts == list(range(n - n % L, n - n % L
                                    + L * (passes.count(-1) + 1), L)), k
        assert starts[-1] + L <= 32
    assert stats["decode_rows_dropped"] == 0
    assert stats["block_commits"] == sum(_passes(*c).count(-1) for c in cases)
    assert stats["rows_decoded"] == sum(len(_passes(*c)) for c in cases)


@pytest.mark.parametrize("share", [True, False])
def test_a_block_row_that_ends_on_eos_is_dropped_and_its_slot_reused(lm,
                                                                     share):
    """A ends on its ``eos_token`` inside a block whose commit pass is
    already in flight: the block's later tokens are in no stream and no
    callback, the pass is dropped where it is read and counted, and B,
    which takes A's only slot (and its pages; ``share``: A's first page
    by the prefix index) and opens its own block over what A's left on
    the device, streams exactly."""
    model, params = lm
    a = _prompt(9)
    want, want_at = plain_generate(model, params, a, 12, 4)
    eos = want[5]
    stop = want.index(eos)
    b = np.concatenate([a[:8], _prompt(5, seed=3)])
    eng = _engine(model, params, n_slots=1, prefix_share=share)
    seen = _spy_blocks(eng)
    calls = []
    ha = eng.submit(a, SamplingParams(max_new_tokens=12, eos_token=eos),
                    on_token=lambda tok, i: calls.append((tok, i)))
    hb = eng.submit(b, SamplingParams(max_new_tokens=9))
    with eng:
        out_a, out_b = ha.result(timeout=300), hb.result(timeout=300)
        stats = eng.stats()
    assert out_a.tolist() == want[:stop + 1] == ha.tokens
    assert ha.fill_pass == want_at[:stop + 1]
    assert calls == [(t, i) for i, t in enumerate(want[:stop + 1])]
    # the commit pass of the eos's block WAS dispatched, for A, and dropped
    mine = [(ahead, rows[0][1]) for ahead, rows in seen
            if rows[0][0] == ha.request_id]
    assert mine[-1] == (True, -1)
    assert stats["decode_rows_dropped"] == 1
    want_b, at_b = plain_generate(model, params, b, 9, 4)
    assert out_b.tolist() == want_b and hb.fill_pass == at_b
    assert hb.metrics["prefix_hit_pages"] == (1 if share else 0)
    assert stats["rows_decoded"] == len(mine) + len(_passes(13, 9, 4))
    assert eng.pool.pool.live_pages() == 0


def test_a_deadline_fails_only_its_block_row_with_its_pass_in_flight(lm):
    """An injected stall runs the loop past A's deadline while A and B
    generate: the sweep fails A typed with A's pass in flight (it is
    dropped), and B's stream is the uncached loop's."""
    model, params = lm
    eng = _engine(model, params, n_slots=2).start()
    try:
        # every program first: a compile must not eat the deadline
        eng.submit(_prompt(8), SamplingParams(max_new_tokens=5)).result(
            timeout=300)
        for n in (24, 13):                  # and submit()'s key splits
            jax.random.split(jax.random.PRNGKey(0), n)
        before = eng.stats()
        # the sixth iteration from here: both rows are in their blocks
        faults.install("delay@op=serve_step,call=6,ms=1500")
        a, b = _prompt(8, seed=51), _prompt(11, seed=52)
        with eng._cond:                     # both queued before it wakes
            ha = eng.submit(a, SamplingParams(max_new_tokens=24,
                                              deadline_ms=900.0))
            hb = eng.submit(b, SamplingParams(max_new_tokens=13))
        with pytest.raises(RequestDeadlineExceeded) as ei:
            ha.result(timeout=300)
        assert ei.value.stage == "running"
        assert ei.value.request_id == ha.request_id
        got = len(ha.tokens)
        out_b = hb.result(timeout=300)
        stats = eng.stats()
    finally:
        eng.shutdown()
    want_b, at_b = plain_generate(model, params, b, 13, 4)
    assert out_b.tolist() == want_b and hb.fill_pass == at_b
    assert stats["decode_rows_dropped"] - before["decode_rows_dropped"] == 1
    # A's blocks so far are its stream's, and nothing came after
    want_a, _ = plain_generate(model, params, a, 24, 4)
    assert 0 < got == len(ha.tokens) < 24 and ha.tokens == want_a[:got]
    assert eng.pool.pool.live_pages() == 0


class _Unreadable:
    """A pass's output whose read fails."""

    def __array__(self, *a, **kw):
        raise RuntimeError("device lost")


@pytest.mark.parametrize("how", ["stats", "shutdown", "crash", "fetch",
                                 "fault"])
def test_no_hang_and_no_stranded_future_with_a_block_pass_in_flight(lm, how):
    """With pass k+1 dispatched and the block pass k cleaned being
    streamed: ``stats()`` (here and from another thread) returns;
    ``shutdown()``, ``crash()``, a read that raises and a fault at the
    next iteration's start each end the loop, every future resolved
    exactly once, typed, and the engine's thread gone."""
    model, params = lm
    eng = _engine(model, params, n_slots=2)
    inflight, other = [], []
    boom = RuntimeError("killed")

    def on_token(tok, i):
        if i != 4:
            return
        inflight.append(eng._inflight is not None)
        if how == "stats":
            t = threading.Thread(target=lambda: other.append(eng.stats()),
                                 name="stats-reader")
            t.start()
            other.append(eng.stats())
            t.join(timeout=60)
            assert not t.is_alive()
        elif how == "shutdown":
            eng.shutdown(wait=False)
        elif how == "crash":
            eng.crash(boom, wait=False)
        elif how == "fetch":
            eng._inflight = eng._inflight._replace(out=_Unreadable())
        else:
            faults.install("flaky@op=serve_step,call=1")
    hs = [eng.submit(_prompt(8, seed=70), SamplingParams(max_new_tokens=12),
                     on_token=on_token),
          eng.submit(_prompt(12, seed=71), SamplingParams(max_new_tokens=12))]
    eng.start()
    try:
        if how == "stats":
            for h in hs:
                assert len(h.result(timeout=300)) == 12
            assert len(other) == 2 and all(
                s["decode_fetches"] > 0 for s in other)
        else:
            for h in hs:
                with pytest.raises(EngineStopped) as ei:
                    h.result(timeout=300)
                cause = ei.value.__cause__
                if how == "crash":
                    assert cause is boom
                elif how == "fetch":
                    assert "device lost" in str(cause)
                else:
                    assert (type(cause) is faults.FlakyFault) \
                        == (how == "fault")
            thread = eng._thread
            if thread is not None:
                thread.join(timeout=60)
                assert not thread.is_alive()
            assert eng._inflight is None
    finally:
        eng.shutdown()
    assert inflight == [True]
    st = eng.stats()
    assert st["completed"] + st["failed"] == 2
    assert all(h.future.done() for h in hs)
    assert st["active_slots"] == 0 and sorted(eng._free) == [0, 1]
    assert eng.pool.pool.live_pages() == 0


def test_a_block_counted_clean_that_the_device_holds_masked_stops_the_engine(
        lm, monkeypatch):
    """The host streams a block when ITS count says the pass filled the
    last masked position; the array it reads carries the device's own
    mask, and the read holds the two together: a pick that fills fewer
    positions than it was asked to stops the engine by name, with no
    ``mask_id`` streamed."""
    from distributed_pytorch_tpu.serve.pages import cache
    model, params = lm
    monkeypatch.setattr(
        cache, "fill_block", lambda logits, tokens, masked, n_fill:
        fill_block(logits, tokens, masked, jnp.maximum(n_fill - 1, 0)))
    got = []
    eng = _engine(model, params, n_slots=2)
    with eng:
        h = eng.submit(_prompt(9), SamplingParams(max_new_tokens=8),
                       on_token=lambda tok, i: got.append(tok))
        with pytest.raises(EngineStopped) as ei:
            h.result(timeout=300)
    assert "holds masked positions" in str(ei.value.__cause__)
    assert got == [] and eng._inflight is None
    assert eng.stats()["blocks_emitted"] == 0


def test_no_block_pass_is_in_flight_across_an_idle_engine(lm):
    """The engine goes idle between two requests, the first ended by its
    ``eos_token`` (its dropped commit pass is the pass in flight when no
    row is left): every pass dispatched was read before a ``serve.idle``
    began, the first pass after it finds none to read, and the
    block-step program XLA built for the first request's FIRST pass
    (whose blocks no program has made yet) is the one every later pass
    runs, over blocks a program left, through admissions, commits and
    retirements: nothing is built after it."""
    model, params = lm
    dpxtrace.configure(enabled=True, ring=8192, log_path=None)
    a = _prompt(9)
    want, _ = plain_generate(model, params, a, 12, 4)
    eos = want[5]
    stop = want.index(eos)
    built = lambda: sum(compile_cache.compile_events()[k]
                        for k in ("compiles", "cache_hits"))
    eng = _engine(model, params, n_slots=2)
    seen = _spy_blocks(eng)
    after, step = [], eng.pool.block_step

    def counted(*args, **kw):       # programs built, after every pass
        out = step(*args, **kw)
        after.append(built())
        return out
    eng.pool.block_step = counted
    with eng:
        ha = eng.submit(a, SamplingParams(max_new_tokens=12, eos_token=eos))
        assert ha.result(timeout=300).tolist() == want[:stop + 1]
        mine = len(_passes(9, stop + 1, 4)) + 1    # and the dropped one
        # the future resolves inside the read of A's last block; the
        # loop then reads the dropped pass and goes idle
        until = time.monotonic() + 60
        while eng.stats()["decode_fetches"] < mine:
            assert time.monotonic() < until
            time.sleep(0.005)
        idle, programs = eng.stats(), built()
        # A's prefill bucket and number of keys: no other program
        b = _prompt(10, seed=91)
        hb = eng.submit(b, SamplingParams(max_new_tokens=12))
        out_b = hb.result(timeout=300)
        st, programs_b = eng.stats(), built()
    ring, dropped = dpxtrace.flight_snapshot()
    want_b, at_b = plain_generate(model, params, b, 12, 4)
    assert out_b.tolist() == want_b and hb.fill_pass == at_b
    assert programs_b == programs and st["decode_compiles"] == 1
    assert len(after) == st["decode_fetches"] and set(after) == {programs}
    assert idle["decode_rows_dropped"] == st["decode_rows_dropped"] == 1
    yours = len(_passes(10, 12, 4))
    assert idle["decode_fetches"] == mine
    assert st["decode_fetches"] == mine + yours
    # the first pass of each request had none before it to read
    assert [ahead for ahead, _ in seen] == \
        [False] + [True] * (mine - 1) + [False] + [True] * (yours - 1)
    assert dropped == 0
    unread, idles = 0, 0
    for rec in ring:                        # in the order they ended
        unread += rec["name"] == "serve.decode.dispatch"
        unread -= rec["name"] == "serve.decode.fetch"
        assert 0 <= unread <= 2
        if rec["name"] == "serve.idle":
            idles += 1
            assert unread == 0
    assert idles >= 2 and unread == 0


# -- what refuses such a model, by name ----------------------------------------------

def test_every_other_path_refuses_a_block_generator_by_name(lm):
    model, params = lm
    with pytest.raises(ValueError, match="contiguous slot pool is gone"):
        _engine(model, params, paged=False)
    with pytest.raises(BlockGenerationUnsupported, match="quantized"):
        _engine(model, params, kv_dtype="q8")
    with pytest.raises(BlockGenerationUnsupported, match="speculative"):
        _engine(model, params, spec_decode=True, draft_model=model,
                draft_params=params)
    with pytest.raises(BlockGenerationUnsupported, match="disagg"):
        DisaggEngine(model, params, DisaggConfig(
            n_slots=2, max_len=32, page_len=8, buckets=(8, 16)))
    with pytest.raises(BlockGenerationUnsupported, match="generate"):
        make_generate_fn(model, 4)
    with pytest.raises(ValueError, match="multiple of the model's"):
        _engine(model, params, page_len=6, buckets=(12, 24))
    with pytest.raises(ValueError, match="multiples of the model's"):
        _engine(model, params, buckets=(8, 18))
    with pytest.raises(ValueError, match="block-causal"):
        models.TransformerLM(**KW, attn_fn=make_flash_attn_fn())
    with pytest.raises(ValueError, match="attention='mha'"):
        models.TransformerLM(**dict(KW, pos="learned"))
    eng = _engine(model, params)
    with pytest.raises(BlockGenerationUnsupported, match="temperature"):
        eng.submit(_prompt(8), SamplingParams(temperature=0.7))
    for steps in (0, 5):
        with pytest.raises(AdmissionRejected, match="denoise_steps"):
            eng.submit(_prompt(8), SamplingParams(denoise_steps=steps))
    with pytest.raises(AdmissionRejected, match="exceeds the slot cache"):
        # 61 + 4 = 65 positions round up to 68: the whole of the last
        # block is written, whatever of it is streamed
        eng.submit(_prompt(61), SamplingParams(max_new_tokens=4))
    # a token-a-step model has no denoise_steps
    plain = models.TransformerLM(vocab=97, dim=32, n_layers=1, n_heads=4,
                                 max_seq=64)
    eng = InferenceEngine(plain, plain.init(jax.random.PRNGKey(0)),
                          EngineConfig(n_slots=1, max_len=32))
    with pytest.raises(AdmissionRejected, match="generates by blocks"):
        eng.submit(_prompt(8), SamplingParams(denoise_steps=2))
