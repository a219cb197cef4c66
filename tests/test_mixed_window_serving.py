"""Window and global layers in one paged cache (docs/serving.md "Window
and global layers in one cache"; PERF.md, Findings, PR 43), at a tiny size
in float32 on the CPU.

The parts, each against its own equation: the banded attention of a
prompt's tail under a window, and a tail's attention over resident pages
in blocks, against one dense softmax under the mask. The engine: greedy
streams against the model's full forward over the whole sequence with no
cache, for prompts shorter than, equal to and several times
the window, through chunk boundaries and ring wrap, rows of different
lengths in one decode program; a slot another request has just left; the
same prompt twice; the counters; and the refusals by name."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import models
from distributed_pytorch_tpu.models.generate import (
    MixedStoresUnsupported, layer_windows, make_generate_fn)
from distributed_pytorch_tpu.nn.attention import (banded_window_attention,
                                                  dense_attention)
from distributed_pytorch_tpu.nn.paged import (ExactSide, KVPages, PrefillCtx,
                                              WindowPages)
from distributed_pytorch_tpu.serve import (EngineConfig, InferenceEngine,
                                           SamplingParams)
from distributed_pytorch_tpu.serve.disagg import DisaggConfig, DisaggEngine
from distributed_pytorch_tpu.serve.pages import PagedSlotPool

W = 8                      # the window
WINDOWS = (W, W, W, None, W)
KW = dict(vocab=97, dim=32, n_layers=5, n_heads=8, n_kv_heads=2, head_dim=8,
          attn_bias=False, qk_norm=1e-5, pos="rope", rope_base=1e6,
          max_seq=256, norm="rms", norm_eps=1e-5, ffn_dim=64,
          block_kinds=("dense",) + ("moe",) * 4,
          moe=dict(n_routed=8, width=16, top_k=2, n_shared=1, scale=2.5,
                   held=(0, 4)),
          layer_windows=WINDOWS,
          layer_rope=tuple(w is not None for w in WINDOWS))
ENGINE = dict(paged=True, n_slots=3, max_len=96, page_len=4, buckets=(8, 16),
              prefix_share=False)


@pytest.fixture(scope="module")
def lm():
    model = models.TransformerLM(**KW)
    return model, model.init(jax.random.PRNGKey(43))


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, 97, n) \
        .astype(np.int32)


def plain_greedy(model, params, prompt, n_new, served):
    """What a greedy stream has to be, with no cache: ONE forward of the
    whole sequence (prompt and the served tokens) through the model, whose
    best token after each prefix is the next one served (a first
    difference is where the stream left the greedy one)."""
    seq = np.concatenate([prompt, served])
    logits = model.apply(params, jnp.asarray(seq[None]))[0]
    return np.asarray(jnp.argmax(logits, -1), np.int32)[
        len(prompt) - 1:len(prompt) - 1 + n_new]


def dense_under(mask, q, k, v):
    """softmax(q k^T / sqrt(d)) v under ``mask`` (Sq, Sk), grouped."""
    _, h, sq, dh = q.shape
    g = h // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(dh)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# -- the parts ----------------------------------------------------------------

def test_a_layer_told_its_window_sees_that_many_keys():
    model = models.TransformerLM(**KW)
    assert layer_windows(model) == WINDOWS
    assert [blk.attn.rope for blk in model.blocks] \
        == [True, True, True, False, True]
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 8, 20, 8))
    k, v = (jax.random.normal(kk, (1, 2, 20, 8)) for kk in ks[1:])
    i, j = jnp.arange(20)[:, None], jnp.arange(20)[None, :]
    np.testing.assert_allclose(
        dense_attention(q, k, v, causal=True, window=W),
        dense_under((j <= i) & (i - j < W), q, k, v), atol=1e-6)


@pytest.mark.parametrize("s,offset", [(16, 0), (16, 4), (16, 48), (11, 24),
                                      (5, 8)])
def test_banded_attention_is_the_dense_softmax_under_the_window(s, offset):
    """[the 8 entries before the tail | the tail], at any offset, a tail
    that is no multiple of the window among them; positions below 0 hold
    poison and are never seen."""
    ks = jax.random.split(jax.random.PRNGKey(s + offset), 3)
    q = jax.random.normal(ks[0], (1, 8, s, 8))
    k, v = (jax.random.normal(kk, (1, 2, W + s, 8)) for kk in ks[1:])
    below = (offset - W + jnp.arange(W + s) < 0)[None, None, :, None]
    k, v = jnp.where(below, 1e4, k), jnp.where(below, 1e4, v)
    i, c = jnp.arange(s)[:, None], jnp.arange(W + s)[None, :]
    mask = (c > i) & (c <= i + W) & (offset - W + c >= 0)
    got = banded_window_attention(q, k, v, jnp.int32(offset), W, 8 ** -0.5)
    np.testing.assert_allclose(got, dense_under(mask, q, k, v), atol=2e-6)


@pytest.mark.parametrize("offset,true_len,block", [(0, 16, 8), (24, 16, 8),
                                                   (40, 9, 16), (64, 16, 12)])
def test_a_tail_over_resident_pages_in_blocks_is_the_dense_softmax(
        offset, true_len, block):
    """``KVPages.attend_tail``: the trips follow the offset, a table whose
    length is no multiple of the block's pages is padded, pages past the
    prompt hold poison and are never read into the result."""
    page_len, n_pages, s, per_row = 4, 40, 16, 23
    ks = jax.random.split(jax.random.PRNGKey(offset), 3)
    q = jax.random.normal(ks[0], (1, 8, s, 8))
    k, v = (jax.random.normal(kk, (n_pages, 2, page_len, 8))
            for kk in ks[1:])
    row = np.random.default_rng(1).permutation(n_pages)[:per_row] \
        .astype(np.int32)
    live = -(-(offset + true_len) // page_len)
    dead = jnp.zeros(n_pages, bool).at[row[live:]].set(True)
    k = jnp.where(dead[:, None, None, None], jnp.nan, k)
    v = jnp.where(dead[:, None, None, None], jnp.nan, v)
    pages = KVPages(ExactSide(k), ExactSide(v))
    ctx = PrefillCtx(jnp.asarray(row), offset + jnp.arange(s),
                     jnp.int32(offset), jnp.int32(true_len), jnp.int32(0),
                     None, None, None, None, per_row * page_len)
    got = pages.attend_tail(ctx, q, 8 ** -0.5, block)[:, :, :true_len]
    rows = lambda t: t[row[:live]].transpose(1, 0, 2, 3) \
        .reshape(1, 2, live * page_len, 8)
    mask = jnp.arange(live * page_len)[None, :] \
        <= (offset + jnp.arange(true_len))[:, None]
    want = dense_under(mask, q[:, :, :true_len], rows(k), rows(v))
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_a_ring_is_the_window_in_pages_plus_one_whatever_max_len(lm):
    """A window layer's resident bytes a slot are the ring's at every
    ``max_len``; the allocator, the tables and ``pages_in_use`` count the
    global layer's pages only."""
    model, _ = lm
    ring = (W // 4 + 1) * 4
    for max_len, n_pages in ((64, 48), (1024, 768)):
        pool = PagedSlotPool(model, 3, max_len, page_len=4, n_pages=n_pages,
                             prefix_share=False)
        kinds = [type(st) for st in pool.state]
        assert kinds == [WindowPages] * 3 + [KVPages, WindowPages]
        in_global, in_window = pool.kv_resident_bytes()
        one_token = 2 * 2 * 8 * 4                  # K and V, 2 heads of 8, f32
        assert in_window == 4 * 3 * ring * one_token
        assert in_global == n_pages * 4 * one_token
        assert pool.kv_pool_bytes() == in_global + in_window
        assert pool.bytes_per_resident_token() == one_token
        stats = pool.page_stats()
        assert stats["window_layers"] == 4
        assert stats["kv_resident_bytes_window"] == in_window
        assert pool.tables.shape == (3, -(-max_len // 4))


# -- the engine ---------------------------------------------------------------

def test_greedy_streams_equal_the_plain_loop(lm):
    """Prompts shorter than, equal to and several times the window, in
    three slots at once (rows of different lengths in one decode program,
    prefill chunks of 16 between decode steps, every ring wrapping), then
    two more into the slots the first ones left."""
    model, params = lm
    eng = InferenceEngine(model, params, EngineConfig(**ENGINE))
    eng.start()
    try:
        asked = [(5, 20), (8, 14), (37, 18), (60, 12), (16, 25)]
        handles = [eng.submit(_prompt(n), SamplingParams(max_new_tokens=m))
                   for n, m in asked]
        got = [h.result(timeout=300) for h in handles]
        stats = eng.stats()
    finally:
        eng.shutdown()
    for (n, m), tokens in zip(asked, got):
        np.testing.assert_array_equal(
            tokens, plain_greedy(model, params, _prompt(n), m, tokens))
    assert stats["decode_compiles"] == 1 and stats["completed"] == 5
    pages = stats["pages"]
    assert pages["window_layers"] == 4 and pages["prefix_hit_pages"] == 0
    assert pages["pages_in_use"] == 0 and pages["context_tokens_max"] == 0


def test_a_slot_another_request_has_just_left_shows_no_stale_ring(lm):
    """One slot: a long request fills its rings, then a short one whose
    window reaches below position 0 of the ring's stale entries."""
    model, params = lm
    eng = InferenceEngine(model, params, EngineConfig(**{**ENGINE,
                                                        "n_slots": 1}))
    eng.start()
    try:
        eng.submit(_prompt(50, 1), SamplingParams(max_new_tokens=16)) \
            .result(timeout=300)
        got = [eng.submit(_prompt(n, 2), SamplingParams(max_new_tokens=10))
               .result(timeout=300) for n in (3, 9)]
    finally:
        eng.shutdown()
    for n, tokens in zip((3, 9), got):
        np.testing.assert_array_equal(
            tokens, plain_greedy(model, params, _prompt(n, 2), 10, tokens))


def test_the_same_prompt_twice_shares_no_page(lm):
    model, params = lm
    eng = InferenceEngine(model, params, EngineConfig(**ENGINE))
    eng.start()
    try:
        a, b = (eng.submit(_prompt(24, 3), SamplingParams(max_new_tokens=8))
                for _ in range(2))
        a, b = a.result(timeout=300), b.result(timeout=300)
        pages = eng.stats()["pages"]
    finally:
        eng.shutdown()
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        a, plain_greedy(model, params, _prompt(24, 3), 8, a))
    assert pages["prefix_lookups"] == 2 and pages["prefix_hit_pages"] == 0
    assert pages["indexed_pages"] == 0 and pages["prefill_tokens_saved"] == 0


@pytest.mark.parametrize("what,build", [
    ("prefix sharing",
     lambda m, p: InferenceEngine(m, p, EngineConfig(
         **{**ENGINE, "prefix_share": True}))),
    ("a quantized page pool",
     lambda m, p: InferenceEngine(m, p, EngineConfig(
         **ENGINE, kv_dtype="q8"))),
    ("speculative decoding",
     lambda m, p: InferenceEngine(m, p, EngineConfig(
         **ENGINE, spec_decode=True, draft_model=m, draft_params=p))),
    ("the disaggregated hand-off",
     lambda m, p: DisaggEngine(m, p, DisaggConfig(n_slots=2, max_len=96))),
    # a configuration that says nothing of sharing takes the default
    ("prefix sharing",
     lambda m, p: InferenceEngine(m, p, EngineConfig(n_slots=2, max_len=96))),
    ("generate()", lambda m, p: make_generate_fn(m, 4)),
])
def test_what_a_mixed_model_cannot_do_yet_is_refused_by_name(lm, what, build):
    with pytest.raises(MixedStoresUnsupported, match=what.replace(
            "(", r"\(").replace(")", r"\)")):
        build(*lm)


def test_the_constructor_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="one entry for each"):
        models.TransformerLM(**{**KW, "layer_windows": (W, None)})
    with pytest.raises(ValueError, match="pos must be 'rope'"):
        models.TransformerLM(**{**KW, "pos": "none"})
    with pytest.raises(ValueError, match="attention='mha'"):
        models.TransformerLM(**{**KW, "gen_block": 4, "mask_id": 96})


def test_every_layer_a_window_layer_takes_no_page_by_table():
    """A uniform window told a layer (``layer_windows=(W,) * n``) is the
    paged form of a model whose every layer keeps a ring: the tables
    address nothing, and the stream is still the greedy one."""
    kw = {**KW, "n_layers": 2, "block_kinds": ("dense", "dense"),
          "layer_windows": (W, W), "layer_rope": (True, True)}
    del kw["moe"]
    model = models.TransformerLM(**kw)
    params = model.init(jax.random.PRNGKey(7))
    eng = InferenceEngine(model, params, EngineConfig(**ENGINE))
    eng.start()
    try:
        tokens = eng.submit(_prompt(29, 4), SamplingParams(
            max_new_tokens=15)).result(timeout=300)
        pages = eng.stats()["pages"]
    finally:
        eng.shutdown()
    np.testing.assert_array_equal(
        tokens, plain_greedy(model, params, _prompt(29, 4), 15, tokens))
    assert pages["window_layers"] == 2
    assert pages["kv_resident_bytes_global"] == 0
