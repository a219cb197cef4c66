"""Chunked prefill (PERF.md, Findings, PR 31), at a tiny size in float32 on
the CPU. The pool: a prompt admitted in chunks of ``chunk_tokens`` gives
the logits and the greedy stream of the same prompt admitted whole and of
``generate()`` (for latent blocks, which ``generate()`` does not run: of
the expanded full forward), for a K/V store and for a latent one, cold and
from a prefix hit whose pages no chunk writes. The engine: one chunk and
one decode an iteration while rows are running, chunks back to back while
none is, and a request that a deadline, a shutdown or a crash finds between
two chunks gives back its slot and every page."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import models
from distributed_pytorch_tpu.models.generate import make_generate_fn
from distributed_pytorch_tpu.runtime import faults
from distributed_pytorch_tpu.serve import (EngineConfig, EngineStopped,
                                           InferenceEngine,
                                           RequestDeadlineExceeded,
                                           SamplingParams)
from distributed_pytorch_tpu.serve.pages import PagedSlotPool, chunk_tokens

L = 8                     # page_len
BUCKETS = (8, 16)         # so a chunk holds C = 16 tokens
C = 16
WHOLE = (8, 16, 32, 64)   # every prompt here is one chunk of these
MAX_LEN = 128
STEPS = 6

YARN = dict(factor=64, original_max_position_embeddings=4096, beta_fast=32,
            beta_slow=1, mscale=1, mscale_all_dim=1)
KINDS = {
    "kv": dict(vocab=61, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
               pos="rope", max_seq=MAX_LEN),
    "latent": dict(vocab=61, dim=32, n_layers=2, n_heads=4, max_seq=MAX_LEN,
                   pos="none", block_kinds=("dense", "moe"),
                   attention="latent",
                   latent=dict(q_rank=16, kv_rank=16, nope_dim=8, rope_dim=8,
                               v_dim=8, yarn=YARN),
                   norm="rms", norm_eps=1e-6, ffn_dim=64,
                   moe=dict(n_routed=4, width=16, top_k=2, n_shared=1,
                            scale=2.0))}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module", params=sorted(KINDS))
def lm(request):
    model = models.TransformerLM(**KINDS[request.param])
    return request.param, model, model.init(jax.random.PRNGKey(3))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 61, n).astype(np.int32)


def _pool(model, **kw):
    return PagedSlotPool(model, 2, MAX_LEN, page_len=L, n_pages=48, **kw)


def _greedy(pool, params, logits, slot):
    """``STEPS`` greedy tokens from an admission's logits on."""
    toks = [int(np.argmax(np.asarray(logits)[0]))]
    cur = np.zeros(pool.n_slots, np.int32)
    active = np.zeros(pool.n_slots, bool)
    active[slot] = True
    for _ in range(STEPS - 1):
        cur[slot] = toks[-1]
        pool.ensure_decode_capacity(slot)
        out, _ = pool.decode(params, cur, active)
        toks.append(int(out[slot]))
    return toks


def _serve(pool, params, prompt, buckets, slot=0):
    """Admit ``prompt`` and decode: (the admission's logits, the tokens,
    pages hit)."""
    logits, n_hit, _ = pool.admit(params, prompt, slot, buckets)
    return np.asarray(logits)[0], _greedy(pool, params, logits, slot), n_hit


def _is_reference_stream(kind, model, params, prompt, toks):
    """``toks`` is ``generate()``'s greedy stream; for latent blocks,
    which ``generate()`` does not run, each is the expanded full
    forward's best token after the ones before it."""
    if kind == "kv":
        fn = make_generate_fn(model, STEPS, temperature=0.0, max_len=MAX_LEN)
        return toks == np.asarray(jax.jit(fn)(
            params, jnp.asarray(prompt[None]),
            jax.random.PRNGKey(0)))[0].tolist()
    seq = np.concatenate([prompt, toks])
    best = np.argmax(np.asarray(
        model.apply(params, jnp.asarray(seq)[None])[0]), -1)
    return toks == best[len(prompt) - 1:-1].tolist()


def test_chunk_is_the_largest_buckets_whole_pages():
    assert chunk_tokens(BUCKETS, L) == C
    assert chunk_tokens((128, 256, 512), 16) == 512
    assert chunk_tokens((512, 1024, 2048), 64) == 1024
    assert chunk_tokens((8, 100), 16) == 96
    with pytest.raises(ValueError, match="less than one page"):
        chunk_tokens((4,), 8)


@pytest.mark.parametrize("n", [C - 1, C, C + 1, 2 * C + 7, 4 * C])
def test_chunked_admission_is_the_whole_one(lm, n):
    kind, model, params = lm
    prompt = _prompt(n, seed=n)
    chunked, whole = _pool(model), _pool(model)
    logits, toks, _ = _serve(chunked, params, prompt, BUCKETS)
    ref_logits, ref_toks, _ = _serve(whole, params, prompt, WHOLE)
    np.testing.assert_allclose(logits, ref_logits, rtol=2e-5, atol=2e-5)
    assert toks == ref_toks
    assert _is_reference_stream(kind, model, params, prompt, toks)
    # every chunk through the bucket that holds it, none above C compiled
    sizes = [min(C, n - done) for done in range(0, n, C)]
    assert chunked.compiles.prefill == {
        next(b for b in BUCKETS if b >= size): 1 for size in sizes}
    assert int(chunked.lengths[0]) == n + STEPS - 1
    assert chunked.prefilling == {}
    assert len(chunked.index) == n // L == len(whole.index)


def test_chunks_start_at_the_hit_and_never_write_its_pages(lm):
    kind, model, params = lm
    shared = _prompt(3 * L, seed=1)
    first = np.concatenate([shared, _prompt(5, seed=2)])
    second = np.concatenate([shared, _prompt(2 * C + 3, seed=3)])
    pool = _pool(model)
    pool.admit(params, first, 0, BUCKETS)
    hit = pool.owned[0][:3]
    leaf = jax.tree.leaves(pool.state[0])[0]          # k pages / entries
    before = np.asarray(leaf)[hit].copy()
    assert pool.begin(second, 1, BUCKETS) == (3, 3 * L)
    seen = []
    while 1 in pool.prefilling:
        ch = pool.chunk(params, 1)
        seen.append((ch.index, ch.offset, ch.tokens, ch.bucket,
                     ch.logits is not None))
    assert seen == [(0, 24, 16, 16, False), (1, 40, 16, 16, False),
                    (2, 56, 3, 8, True)]
    assert pool.owned[1][:3] == hit
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(pool.state[0])[0])[hit], before)
    ref_logits, ref_toks, _ = _serve(_pool(model, prefix_share=False),
                                     params, second, WHOLE)
    np.testing.assert_allclose(np.asarray(ch.logits)[0], ref_logits,
                               rtol=2e-5, atol=2e-5)
    toks = _greedy(pool, params, ch.logits, 1)
    assert toks == ref_toks
    assert _is_reference_stream(kind, model, params, second, toks)


# -- the engine's scheduling ---------------------------------------------------

def _engine(model, params, **kw):
    return InferenceEngine(model, params, EngineConfig(
        paged=True, n_slots=3, max_len=MAX_LEN, page_len=L, buckets=BUCKETS,
        **kw))


def _stream(model, params, prompt, n):
    fn = make_generate_fn(model, n, temperature=0.0, max_len=MAX_LEN)
    return np.asarray(jax.jit(fn)(params, jnp.asarray(prompt[None]),
                                  jax.random.PRNGKey(0)))[0]


@pytest.fixture(scope="module")
def kv():
    model = models.TransformerLM(**{**KINDS["kv"], "n_layers": 1})
    return model, model.init(jax.random.PRNGKey(5))


def test_one_chunk_and_one_decode_an_iteration_beside_running_rows(kv):
    """A (5 tokens) is running when B's four chunks are prefilled: every
    iteration runs one chunk and one decode, A gets a token in each (a
    decode pass is read in the iteration after the one that dispatched
    it), B's first token follows its last chunk, and nothing of B is in
    the prefix index or in the decode program until then."""
    model, params = kv
    eng = _engine(model, params)
    a, b = _prompt(5, seed=4), _prompt(4 * C, seed=5)
    at = {"a": [], "b": []}

    def note(name):
        def on_token(tok, i):
            at[name].append((eng._iteration, len(eng.pool.index),
                             dict(eng.pool.prefilling),
                             eng.pool.lengths.tolist()))
        return on_token
    ha = eng.submit(a, SamplingParams(max_new_tokens=12), on_token=note("a"))
    hb = eng.submit(b, SamplingParams(max_new_tokens=4), on_token=note("b"))
    with eng:
        out_a, out_b = ha.result(timeout=120), hb.result(timeout=120)
    np.testing.assert_array_equal(out_a, _stream(model, params, a, 12))
    np.testing.assert_array_equal(out_b, _stream(model, params, b, 4))
    # A: its prefill's token in iteration 1, where its first decode pass
    # is dispatched too; that is read in 2, then one an iteration, none
    # skipped while B prefills in iterations 2 .. 5. B's first token and
    # the dispatch of its first pass in 5
    assert [t[0] for t in at["a"]] == list(range(1, 13))
    assert [t[0] for t in at["b"]] == [5, 6, 7, 8]
    for it, indexed, prefilling, lengths in at["a"][1:4]:   # iterations 2-4
        assert indexed == 0                       # A's 5 tokens: no full page
        assert prefilling[1].done == (it - 1) * C and lengths[1] == 0
    assert at["b"][0][1] == 4 * C // L and at["b"][0][2] == {}
    st = eng.stats()
    assert st["admitted"] == 2 and st["prefill_chunks"] == 1 + 4
    assert st["prefill_chunk_iterations"] == 5
    assert st["decode_fetches"] == 11 and st["iterations"] == 12
    assert st["decode_passes_ahead"] == 10 and st["decode_rows_dropped"] == 0
    assert st["prefill_compiles"] == {8: 1, 16: 1}
    assert hb.metrics["admit_iteration"] == 2
    assert st["pages"]["pages_in_use"] == st["pages"]["indexed_pages"] == 8


def test_chunks_run_back_to_back_while_no_row_is_running(kv):
    model, params = kv
    eng = _engine(model, params)
    b = _prompt(4 * C, seed=5)
    at = []
    hb = eng.submit(b, SamplingParams(max_new_tokens=4),
                    on_token=lambda tok, i: at.append(eng._iteration))
    with eng:
        out = hb.result(timeout=120)
    np.testing.assert_array_equal(out, _stream(model, params, b, 4))
    st = eng.stats()
    assert at == [1, 2, 3, 4]       # a pass is read an iteration later
    assert st["prefill_chunks"] == 4 and st["prefill_chunk_iterations"] == 1
    assert st["decode_fetches"] == 3


def test_a_prompt_longer_than_every_bucket_is_served(kv):
    """No prompt is too long for the buckets: the engine admits whatever
    its slot row holds."""
    model, params = kv
    prompt = _prompt(100, seed=6)
    with _engine(model, params) as eng:
        out = eng.submit(prompt, SamplingParams(max_new_tokens=5)).result(
            timeout=120)
        assert eng.stats()["prefill_chunks"] == 7
    np.testing.assert_array_equal(out, _stream(model, params, prompt, 5))


@pytest.mark.parametrize("what", ["deadline", "shutdown", "fault"])
def test_a_request_lost_between_two_chunks_gives_everything_back(kv, what):
    """B shares two pages with an earlier prompt and needs three chunks
    more. After its first, a deadline, a shutdown or an injected fault
    takes it: its future fails typed, its slot is free, every refcount
    and the prefix index are as before its ``begin``."""
    model, params = kv
    eng = _engine(model, params).start()
    try:
        p = _prompt(2 * C + 8, seed=7)          # compiles both buckets
        eng.submit(p, SamplingParams(max_new_tokens=3)).result(timeout=120)
        pool = eng.pool
        before = (list(pool.pool.refcount), pool.pool.free_pages,
                  len(pool.index))
        a = _prompt(5, seed=8)
        b = np.concatenate([p[:2 * L], _prompt(3 * C, seed=9)])
        sp_b = SamplingParams(max_new_tokens=4)
        on_a = None
        if what == "deadline":
            # iteration 3 of the two (the 2nd of B's chunks) stalls past
            # B's deadline; the sweep of that iteration finds it
            faults.install("delay@op=serve_step,call=3,ms=2500")
            sp_b = SamplingParams(max_new_tokens=4, deadline_ms=2000.0)
        elif what == "fault":
            faults.install("flaky@op=serve_step,call=3")
        else:
            def on_a(tok, i):
                if i == 1:      # A's first decode pass, read in iteration
                    eng.shutdown(wait=False)    # 2: B has a chunk
        with eng._cond:                         # both queued before it wakes
            ha = eng.submit(a, SamplingParams(max_new_tokens=8),
                            on_token=on_a)
            hb = eng.submit(b, sp_b)
        if what == "deadline":
            with pytest.raises(RequestDeadlineExceeded) as ei:
                hb.result(timeout=120)
            assert ei.value.stage == "prefilling"
            np.testing.assert_array_equal(ha.result(timeout=120),
                                          _stream(model, params, a, 8))
        else:
            for h in (ha, hb):
                with pytest.raises(EngineStopped) as ei:
                    h.result(timeout=120)
                assert (type(ei.value.__cause__) is faults.FlakyFault) \
                    == (what == "fault")
            eng.shutdown()
        st = eng.stats()
        assert st["prefill_chunks"] == 3 + 1 + 1    # p's, a's, one of b's
        assert hb.metrics["prefix_hit_pages"] == 2
        assert (list(pool.pool.refcount), pool.pool.free_pages,
                len(pool.index)) == before
        assert pool.prefilling == {} and eng._prefilling is None
        assert sorted(eng._free) == [0, 1, 2]
    finally:
        eng.shutdown()
