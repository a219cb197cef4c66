"""Continuous-batching inference engine (serve/) — the acceptance suite.

The headline contract: with requests arriving at STAGGERED iterations
(mixed prompt lengths, mixed max-tokens, mid-stream slot retirement +
admission), every request's token sequence is bit-identical to a
standalone ``generate()`` call with the same params/rng, the jitted
decode step compiles exactly once, prefill compiles at most once per
length bucket — and an injected ``DPX_FAULT`` delay surfaces a typed
per-request deadline error without corrupting the other in-flight
requests.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import models, serve
from distributed_pytorch_tpu.models.generate import (
    decode_step, decode_step_slots_paged, make_generate_fn, prefill,
    prefill_partial_paged)
from distributed_pytorch_tpu.nn.paged import ExactSide, KVPages
from distributed_pytorch_tpu.runtime import faults
from distributed_pytorch_tpu.serve import (AdmissionRejected, EngineConfig,
                                           EngineStopped, InferenceEngine,
                                           PagedSlotPool,
                                           RequestDeadlineExceeded,
                                           SamplingParams)
from distributed_pytorch_tpu.utils.logging import MetricsLogger

MAX_LEN = 64


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _lm(**kw):
    kw.setdefault("vocab", 61)
    kw.setdefault("dim", 32)
    kw.setdefault("n_layers", 2)
    kw.setdefault("n_heads", 4)
    kw.setdefault("n_kv_heads", 2)
    kw.setdefault("pos", "rope")
    kw.setdefault("max_seq", 128)
    return models.TransformerLM(**kw)


def _dense_window_fn(w):
    """A sliding-window attention core on the DENSE path (exact same
    function the flash kernel computes — tests/test_flash_attention.py
    proves that equivalence) advertising ``window`` the way
    make_flash_attn_fn does, so _model_window detects it. Used here
    because interpret-mode pallas on CPU is ~10x slower per compile
    and the serving engine only cares about the window ATTRIBUTE."""
    from distributed_pytorch_tpu.nn.attention import dense_attention

    def fn(q, k, v, *, causal=False, scale=None):
        return dense_attention(q, k, v, causal=causal, scale=scale,
                               window=w)
    fn.window = w
    return fn


def _windowed_lm(w=8):
    return _lm(vocab=64, attn_fn=_dense_window_fn(w))


def _lm1(**kw):
    """1-layer variant for engine-BEHAVIOR tests (queue, deadlines,
    shutdown, callbacks): depth adds only compile seconds there —
    the numeric/bit-identity contracts all run on 2-layer models."""
    kw.setdefault("n_layers", 1)
    return _lm(**kw)


def _standalone(model, params, prompt, sp, key, max_len=MAX_LEN):
    """The reference: one-request models.generate with the same
    params/rng (and the same cache width as the engine's slot rows)."""
    fn = make_generate_fn(model, sp.max_new_tokens,
                          temperature=sp.temperature, top_k=sp.top_k,
                          top_p=sp.top_p, max_len=max_len)
    return np.asarray(jax.jit(fn)(params, jnp.asarray(prompt[None]),
                                  key))[0]


# ---------------------------------------------------------------------------
# slot-level cache ops (models/generate.py)
# ---------------------------------------------------------------------------


PAGE = 8      # page_len of the slot-level tests: MAX_LEN is 8 pages


def _stores(model, n_pages, n_slots=1, page_len=PAGE):
    """One empty page store a layer, as the pool makes them."""
    return [blk.attn.make_pages(n_pages, n_slots, page_len, None,
                                model.dtype) for blk in model.blocks]


def _paged(cache, n_rows=1, rng=None):
    """``prefill``'s contiguous (1, Hkv, MAX_LEN, Dh) rows cut into pages:
    a pool of ``n_rows`` rows, row r holding pages ``r * P .. r * P + P -
    1``, the cache in row 0 and noise (``rng``) in the others. Returns
    (one KVPages a layer, tables (n_rows, P))."""
    per_row = MAX_LEN // PAGE

    def cut(row):
        _, h, _, d = row.shape
        pages = row[0].reshape(h, per_row, PAGE, d).transpose(1, 0, 2, 3)
        if n_rows == 1:
            return ExactSide(pages)
        noise = jnp.asarray(rng.standard_normal(
            ((n_rows - 1) * per_row,) + pages.shape[1:]), pages.dtype)
        return ExactSide(jnp.concatenate([pages, noise]))
    tables = jnp.arange(n_rows * per_row, dtype=jnp.int32).reshape(
        n_rows, per_row)
    return [KVPages(cut(k), cut(v))
            for k, v in zip(cache.k, cache.v)], tables


class TestSlotCacheOps:
    def test_prefill_partial_matches_prefill(self):
        """Right-padding is inert under causality: the logits at the
        last real position pick the same token as an exact-length
        prefill, and they and the K/V written into the row's pages
        (in table order, which is not the pool's) agree to a few f32
        ulps at their O(1) magnitude (2e-6). The 7-wide and the 16-wide
        programs are two XLA programs that reduce in different orders,
        so bit-identity across them is not a contract; the first
        layer's K/V — projections of identical rows — are bit-identical."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompt = jnp.asarray(rng.integers(0, 61, (1, 7)), jnp.int32)
        logits, cache = jax.jit(
            lambda p, t: prefill(model, p, t, MAX_LEN))(params, prompt)
        padded = jnp.zeros((1, 16), jnp.int32).at[:, :7].set(prompt)
        table = jnp.asarray([5, 2, 7, 0], jnp.int32)
        logits_p, state = jax.jit(
            lambda p, st, tr, t, n: prefill_partial_paged(
                model, p, st, tr, t, 0, n, page_len=4))(
            params, _stores(model, 8, page_len=4), table, padded, 7)
        logits, logits_p = np.asarray(logits), np.asarray(logits_p)
        assert logits.argmax() == logits_p.argmax()
        np.testing.assert_allclose(logits, logits_p, rtol=0, atol=2e-6)
        ks = [np.asarray(st.k.rows(table)) for st in state]
        vs = [np.asarray(st.v.rows(table)) for st in state]
        np.testing.assert_array_equal(np.asarray(cache.k[0])[:, :, :7],
                                      ks[0][:, :, :7])
        np.testing.assert_array_equal(np.asarray(cache.v[0])[:, :, :7],
                                      vs[0][:, :, :7])
        for i in range(model.n_layers):
            np.testing.assert_allclose(
                np.asarray(cache.k[i])[:, :, :7], ks[i][:, :, :7],
                rtol=0, atol=2e-6)
            np.testing.assert_allclose(
                np.asarray(cache.v[i])[:, :, :7], vs[i][:, :, :7],
                rtol=0, atol=2e-6)
            # the pad tail was routed out of bounds: nothing past the
            # prompt was written
            assert not ks[i][:, :, 7:].any() and not vs[i][:, :, 7:].any()

    def test_prefill_partial_window_layout(self):
        """A window layer's ring (position p at ``p % ring``, the last
        ``ring`` of them; a traced true_len) holds in the first layer,
        over the window, what prefill's rolling cache holds (position p
        at ``p % W``) of a model with the same embedding and first
        attention and the window in its attn_fn; and the logits, and
        those of a decode step over every layer's ring, are the model's
        full forward's: for prompts shorter AND longer than the window
        (one compile serves both)."""
        W = 8
        model = _lm(vocab=64, layer_windows=(W, W))
        params = model.init(jax.random.PRNGKey(0))
        rolling = _windowed_lm(W)
        theirs = rolling.init(jax.random.PRNGKey(1))
        theirs["tok"] = params["tok"]
        theirs["blocks"][0].update(attn=params["blocks"][0]["attn"],
                                   ln1=params["blocks"][0]["ln1"])
        rng = np.random.default_rng(1)
        traces = []

        def paged(p, st, t, n):
            traces.append(1)
            return prefill_partial_paged(
                model, p, st, jnp.zeros((8,), jnp.int32), t, 0, n, slot=1,
                page_len=4)
        partial_fn = jax.jit(paged)
        step = jax.jit(lambda p, st, ln, t: decode_step_slots_paged(
            model, p, st, jnp.zeros((2, 8), jnp.int32), ln, t,
            jnp.asarray([False, True]), page_len=4))
        last = lambda seq: np.asarray(model.apply(params, seq)[0, -1])
        for s in (5, 20):
            prompt = jnp.asarray(rng.integers(0, 64, (1, s)), jnp.int32)
            _, cache = prefill(rolling, theirs, prompt, MAX_LEN, window=W)
            padded = jnp.zeros((1, 32), jnp.int32).at[:, :s].set(prompt)
            got, state = partial_fn(params, _stores(model, 0, 2, 4),
                                    padded, s)
            ring = state[0].ring
            assert ring == 12       # the window in whole pages, and one
            live = np.arange(max(0, s - W), s)
            np.testing.assert_allclose(
                np.asarray(cache.k[0])[0][:, live % W],
                np.asarray(state[0].k)[1][:, live % ring], atol=1e-6)
            np.testing.assert_allclose(
                np.asarray(cache.v[0])[0][:, live % W],
                np.asarray(state[0].v)[1][:, live % ring], atol=1e-6)
            assert not any(np.asarray(st.k[0]).any() for st in state)
            np.testing.assert_allclose(np.asarray(got)[0], last(prompt),
                                       rtol=0, atol=1e-5)
            tok = jnp.argmax(got, -1).astype(jnp.int32)
            after, _ = step(params, state, jnp.asarray([0, s], jnp.int32),
                            jnp.concatenate([tok, tok]))
            np.testing.assert_allclose(
                np.asarray(after)[1],
                last(jnp.concatenate([prompt, tok[None]], axis=1)),
                rtol=0, atol=1e-5)
        assert len(traces) == 1

    def test_decode_step_slots_b1_bitwise(self):
        """At the same batch shape the per-row formulation over pages IS
        decode_step over the contiguous row: logits and cache writes
        bit-identical under the dense softmax; under the blockwise one
        the two walk blocks of different sizes (a page; 128 positions),
        and the logits agree to a few f32 ulps."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(2)
        prompt = jnp.asarray(rng.integers(0, 61, (1, 9)), jnp.int32)
        logits, cache = jax.jit(
            lambda p, t: prefill(model, p, t, MAX_LEN))(params, prompt)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        state, tables = _paged(cache)
        for blockwise in (False, True):
            ref_l, ref_c = jax.jit(lambda p, c, t: decode_step(
                model, p, c, t, blockwise=blockwise))(params, cache, tok)
            got_l, new = jax.jit(
                lambda p, st, tb, ln, t, a: decode_step_slots_paged(
                    model, p, st, tb, ln, t, a, page_len=PAGE,
                    blockwise=blockwise))(
                params, state, tables, jnp.asarray([9], jnp.int32), tok,
                jnp.asarray([True]))
            if blockwise:
                np.testing.assert_allclose(np.asarray(ref_l),
                                           np.asarray(got_l), rtol=0,
                                           atol=2e-6)
            else:
                np.testing.assert_array_equal(np.asarray(ref_l),
                                              np.asarray(got_l))
            np.testing.assert_array_equal(
                np.asarray(ref_c.k[0]), np.asarray(new[0].k.rows(tables)))
            np.testing.assert_array_equal(
                np.asarray(ref_c.v[0]), np.asarray(new[0].v.rows(tables)))

    def test_decode_step_slots_row_isolation(self):
        """Changing ANOTHER row's pages/token/length leaves a row's
        logits bitwise unchanged — the slot-independence precondition
        of continuous batching."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        prompt = jnp.asarray(rng.integers(0, 61, (1, 6)), jnp.int32)
        _, cache = jax.jit(
            lambda p, t: prefill(model, p, t, MAX_LEN))(params, prompt)
        f = jax.jit(lambda p, st, tb, ln, t: decode_step_slots_paged(
            model, p, st, tb, ln, t, jnp.ones((3,), bool), page_len=PAGE))
        # a garbage pool with the real row's pages first
        state_a, tables = _paged(cache, n_rows=3, rng=rng)
        own = MAX_LEN // PAGE
        state_b = [KVPages(ExactSide(st.k.pages.at[own:].add(1.5)),
                           ExactSide(st.v.pages.at[own:].add(-0.5)))
                   for st in state_a]
        la = f(params, state_a, tables, jnp.asarray([6, 3, 11], jnp.int32),
               jnp.asarray([7, 1, 2], jnp.int32))[0]
        lb = f(params, state_b, tables, jnp.asarray([6, 9, 0], jnp.int32),
               jnp.asarray([7, 5, 60], jnp.int32))[0]
        np.testing.assert_array_equal(np.asarray(la)[0], np.asarray(lb)[0])


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class TestEngine:
    def test_staggered_mix_bit_identical(self):
        """THE acceptance case: staggered arrivals, mixed prompt
        lengths / max-tokens / sampling configs, mid-stream retirement
        + admission — every stream equals standalone generate(), with
        one decode compile and ≤ one prefill compile per bucket."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        eng = InferenceEngine(model, params,
                              EngineConfig(n_slots=3, max_len=MAX_LEN))
        # (prompt_len, max_new, sampling): three sampler configs, two
        # prefill buckets, short + long requests
        mix = [
            (5, 30, SamplingParams(max_new_tokens=30)),
            (9, 3, SamplingParams(max_new_tokens=3, temperature=0.7,
                                  top_k=8)),
            (3, 12, SamplingParams(max_new_tokens=12, temperature=0.9,
                                   top_p=0.9)),
            (12, 6, SamplingParams(max_new_tokens=6)),          # queued
            (7, 8, SamplingParams(max_new_tokens=8, temperature=0.7,
                                  top_k=8)),
        ]
        prompts = [rng.integers(0, 61, (s,)).astype(np.int32)
                   for s, _, _ in mix]
        keys = [jax.random.PRNGKey(100 + i) for i in range(len(mix))]
        with eng:
            handles = [eng.submit(prompts[i], mix[i][2], rng=keys[i])
                       for i in range(4)]
            # stagger: the second wave arrives only after an early
            # retirement freed a slot mid-run
            handles[1].result(timeout=60)
            handles += [eng.submit(prompts[i], mix[i][2], rng=keys[i])
                        for i in (4,)]
            outs = [h.result(timeout=60) for h in handles]
        for i, ((s, n, sp), out) in enumerate(zip(mix, outs)):
            ref = _standalone(model, params, prompts[i], sp, keys[i])
            np.testing.assert_array_equal(out, ref, err_msg=f"request {i}")
        st = eng.stats()
        assert st["decode_compiles"] == 1, st
        assert all(v == 1 for v in st["prefill_compiles"].values()), st
        # a one-row program a setting at admission (3), and one
        # n_slots-wide program a setting that samples, in decode (2)
        assert st["sample_compiles"] == 3 + 2, st
        # continuous batching really happened: request 3 (queued beyond
        # the 3 slots) was admitted only after request 1's mid-stream
        # retirement freed one — while request 0 (30 tokens) was STILL
        # in flight
        admits = [h.metrics["admit_iteration"] for h in handles]
        retires = [h.metrics["retire_iteration"] for h in handles]
        assert admits[3] > retires[1], (admits, retires)  # slot reuse
        assert admits[3] < retires[0], (admits, retires)  # overlap

    def test_windowed_model_rolling_pool(self):
        """A model told a window for every layer: each slot keeps a ring
        of O(window) a layer whatever ``max_len``, generation runs
        several times past the window, and the streams are the greedy
        ones of the model's full forward with no cache (``generate()``
        refuses a model told its windows)."""
        W = 8
        model = _lm(vocab=64, layer_windows=(W, W))
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)
        eng = InferenceEngine(model, params, EngineConfig(
            n_slots=2, max_len=64, page_len=4, buckets=(8, 16),
            prefix_share=False))
        ring = W + 4                            # whole pages, and one
        assert [st.k.shape for st in eng.pool.state] == [(2, 2, ring, 8)] * 2
        pages = eng.stats()["pages"]
        assert pages["kv_resident_bytes_global"] == 0
        assert pages["kv_resident_bytes_window"] == \
            2 * 2 * (2 * 2 * ring * 8 * 4)      # layers, K and V, f32
        cases = [(4, 40), (20, 36)]             # 5 and 4.5 windows past
        with eng:
            hs, prompts = [], []
            for s, n in cases:
                prompts.append(rng.integers(0, 64, (s,)).astype(np.int32))
                hs.append(eng.submit(prompts[-1],
                                     SamplingParams(max_new_tokens=n)))
            outs = [h.result(timeout=60) for h in hs]
        for prompt, out in zip(prompts, outs):
            seq = jnp.asarray(np.concatenate([prompt, out])[None])
            want = np.asarray(jnp.argmax(model.apply(params, seq)[0], -1))
            np.testing.assert_array_equal(
                out, want[len(prompt) - 1:len(prompt) - 1 + len(out)])
        assert eng.stats()["decode_compiles"] == 1

    def test_window_only_in_attn_fn_is_refused_by_name(self):
        """A width only the attn_fn carries says nothing to the page
        pool: the engine names what serves such a model."""
        model = _windowed_lm(8)
        params = model.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match=r"only its attn_fn carries.*"
                           r"layer_windows=\(W,\) \* n_layers"):
            InferenceEngine(model, params, EngineConfig(n_slots=2,
                                                        max_len=32))

    def test_default_config_builds_the_page_pool(self):
        """``EngineConfig()`` and ``EngineConfig(paged=True)`` build the
        same engine, over the page pool, field for field."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        a = InferenceEngine(model, params, EngineConfig())
        b = InferenceEngine(model, params, EngineConfig(paged=True))
        assert type(a.pool) is type(b.pool) is PagedSlotPool
        assert a.buckets == b.buckets
        for name in ("n_slots", "max_len", "page_len", "n_pages",
                     "prefix_share", "kv_dtype", "pages_per_slot"):
            assert getattr(a.pool, name) == getattr(b.pool, name), name
        assert "pages" in a.stats() and "paged" not in a.stats()

    def test_paged_false_raises_and_names_what_took_its_place(self):
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match=r"paged=False.*contiguous slot "
                           r"pool is gone.*PagedSlotPool.*layer_windows"):
            InferenceEngine(model, params, EngineConfig(paged=False))

    def test_eos_truncates_stream(self):
        """eos_token stops the request early (eos included); the
        truncated stream is a prefix of the standalone stream."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        prompt = np.arange(5, dtype=np.int32)
        key = jax.random.PRNGKey(42)
        sp = SamplingParams(max_new_tokens=10)
        ref = _standalone(model, params, prompt, sp, key)
        eos = int(ref[4])                         # stop mid-stream
        with InferenceEngine(model, params,
                             EngineConfig(n_slots=1,
                                          max_len=MAX_LEN)) as eng:
            out = eng.submit(prompt,
                             SamplingParams(max_new_tokens=10,
                                            eos_token=eos),
                             rng=key).result(timeout=60)
        k = int(np.argmax(ref == eos)) + 1
        np.testing.assert_array_equal(out, ref[:k])
        assert out[-1] == eos

    def test_bounded_queue_typed_rejection(self):
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        eng = InferenceEngine(model, params,
                              EngineConfig(n_slots=1, max_len=MAX_LEN,
                                           max_queue=2))
        # engine NOT started: the queue only fills
        eng.submit(np.arange(4, dtype=np.int32), SamplingParams())
        eng.submit(np.arange(4, dtype=np.int32), SamplingParams())
        with pytest.raises(AdmissionRejected) as ei:
            eng.submit(np.arange(4, dtype=np.int32), SamplingParams())
        assert ei.value.reason == "queue_full"
        assert ei.value.request_id == 2
        eng.shutdown(wait=False)

    def test_unservable_requests_rejected(self):
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        eng = InferenceEngine(model, params,
                              EngineConfig(n_slots=1, max_len=32))
        # no prompt is too long for its bucket (the pool chunks): a
        # request is refused for what its slot cannot hold
        with pytest.raises(AdmissionRejected) as ei:
            eng.submit(np.zeros(40, np.int32), SamplingParams())
        assert ei.value.reason == "too_long"
        with pytest.raises(AdmissionRejected) as ei:
            eng.submit(np.zeros(20, np.int32),
                       SamplingParams(max_new_tokens=20))
        assert ei.value.reason == "too_long"

    def test_priority_over_fcfs(self):
        """With all three queued up front, the priority-0 request is
        admitted first even though it arrived LAST; the two priority-5
        requests then run in arrival order (FCFS within a class)."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        eng = InferenceEngine(model, params,
                              EngineConfig(n_slots=1, max_len=MAX_LEN))
        p = np.arange(4, dtype=np.int32)
        ha = eng.submit(p, SamplingParams(max_new_tokens=8, priority=5))
        hb = eng.submit(p, SamplingParams(max_new_tokens=4, priority=5))
        hc = eng.submit(p, SamplingParams(max_new_tokens=4, priority=0))
        with eng:
            for h in (ha, hb, hc):
                h.result(timeout=60)
        assert hc.metrics["admit_iteration"] \
            < ha.metrics["admit_iteration"] \
            < hb.metrics["admit_iteration"]

    def test_queued_deadline_typed_error(self):
        """A request that expires while QUEUED surfaces
        RequestDeadlineExceeded(stage='queued') without occupying a
        slot; the running request is unaffected."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(5)
        prompt = np.arange(6, dtype=np.int32)
        sp_long = SamplingParams(max_new_tokens=50)
        with InferenceEngine(model, params,
                             EngineConfig(n_slots=1,
                                          max_len=MAX_LEN)) as eng:
            ha = eng.submit(prompt, sp_long, rng=key)
            hb = eng.submit(np.arange(4, dtype=np.int32),
                            SamplingParams(max_new_tokens=4,
                                           deadline_ms=40.0))
            with pytest.raises(RequestDeadlineExceeded) as ei:
                hb.result(timeout=60)
            assert len(ha.result(timeout=60)) == 50  # unaffected
        assert ei.value.stage == "queued"
        assert ei.value.deadline_ms == 40.0
        assert ei.value.request_id == hb.request_id

    def test_chaos_delay_surfaces_running_deadline(self):
        """THE chaos acceptance case: an injected DPX_FAULT delay at a
        known engine iteration stalls the loop past a running request's
        deadline — that request fails TYPED (attributed to request and
        iteration) while the other in-flight request's stream stays
        bit-identical and the engine keeps serving."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(7)
        eng = InferenceEngine(model, params,
                              EngineConfig(n_slots=2, max_len=128))
        eng.start()
        try:
            sp_b = SamplingParams(max_new_tokens=20, temperature=0.7,
                                  top_k=8)
            # warm up EVERY compile (bucket-8 prefill, decode, both
            # sampler configs) so post-install iterations are ms-scale:
            # compile time must not eat the deadline
            eng.submit(np.arange(4, dtype=np.int32),
                       SamplingParams(max_new_tokens=2)).result(timeout=60)
            eng.submit(np.arange(4, dtype=np.int32),
                       SamplingParams(max_new_tokens=2, temperature=0.7,
                                      top_k=8)).result(timeout=60)
            # the serve_step op-call counter only advances while specs
            # are installed, so call=3 is the THIRD engine iteration
            # from now — one after the admissions below
            faults.install("delay@op=serve_step,call=3,ms=1200")
            prompt_a = rng.integers(0, 61, (5,)).astype(np.int32)
            prompt_b = rng.integers(0, 61, (8,)).astype(np.int32)
            key_b = jax.random.PRNGKey(9)
            ha = eng.submit(prompt_a,
                            SamplingParams(max_new_tokens=100,
                                           deadline_ms=700.0))
            hb = eng.submit(prompt_b, sp_b, rng=key_b)
            with pytest.raises(RequestDeadlineExceeded) as ei:
                ha.result(timeout=60)
            assert ei.value.stage == "running"
            assert ei.value.request_id == ha.request_id
            assert ei.value.iteration is not None
            assert any(f.startswith("delay@") for f in faults.fired())
            ref_b = _standalone(model, params, prompt_b, sp_b, key_b,
                                max_len=128)
            # the other in-flight request is NOT corrupted
            np.testing.assert_array_equal(hb.result(timeout=60), ref_b)
            # and the engine still serves after the failure
            hc = eng.submit(prompt_b, sp_b, rng=key_b)
            np.testing.assert_array_equal(hc.result(timeout=60), ref_b)
        finally:
            eng.shutdown()

    def test_slo_metrics_flow_to_logger(self, tmp_path):
        """Per-request TTFT/TPOT events and periodic queue-depth /
        slot-occupancy snapshots land in the line-JSON metrics stream —
        the periodic records now ride the ONE dpxmon registry path
        (rank-attributed metrics_snapshot events, obs/metrics.py), and
        every snapshot passes the strict dpxmon validator."""
        from distributed_pytorch_tpu.obs import metrics as dpxmon
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        log = tmp_path / "serve_metrics.jsonl"
        logger = MetricsLogger(path=str(log))
        cfg = EngineConfig(n_slots=2, max_len=MAX_LEN, metrics=logger,
                           log_every=2)
        dpxmon.reset()
        try:
            with InferenceEngine(model, params, cfg) as eng:
                hs = [eng.submit(np.arange(5, dtype=np.int32),
                                 SamplingParams(max_new_tokens=8))
                      for _ in range(3)]
                for h in hs:
                    h.result(timeout=60)
        finally:
            logger.close()
            dpxmon.reset()
        rows = [json.loads(ln) for ln in log.read_text().splitlines()]
        reqs = [r for r in rows if r.get("event") == "serve_request"]
        assert len(reqs) == 3
        for r in reqs:
            assert r["outcome"] == "ok" and r["n_tokens"] == 8
            assert r["ttft_ms"] > 0 and r["tpot_ms"] > 0
            assert r["queue_ms"] is not None
        snaps = [r for r in rows if r.get("event") == "metrics_snapshot"
                 and r.get("source") == "serve_engine"]
        assert snaps, rows
        for r in snaps:
            assert dpxmon.validate_snapshot(r) == []
            m = r["metrics"]
            assert 0.0 <= m["serve.slot_occupancy"] <= 1.0
            assert "serve.queue_depth" in m
        # the SLO histograms feed the health rules: completed requests
        # land TTFT/TPOT summaries in the final snapshots
        last = snaps[-1]["metrics"]
        assert last["serve.completed"] >= 1
        assert last["serve.ttft_ms"]["count"] >= 1

    def test_shutdown_fails_inflight_typed(self):
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        eng = InferenceEngine(model, params,
                              EngineConfig(n_slots=1, max_len=128))
        eng.start()
        h = eng.submit(np.arange(4, dtype=np.int32),
                       SamplingParams(max_new_tokens=100))
        h2 = eng.submit(np.arange(4, dtype=np.int32),
                        SamplingParams(max_new_tokens=4))
        time.sleep(0.05)
        eng.shutdown()
        for handle in (h, h2):
            with pytest.raises(EngineStopped):
                handle.result(timeout=10)

    def test_engine_loop_crash_fails_futures_typed(self):
        """An exception escaping the engine loop must not strand
        futures: every in-flight request fails as EngineStopped with
        the crash chained as the cause."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        eng = InferenceEngine(model, params,
                              EngineConfig(n_slots=1, max_len=MAX_LEN))

        def boom(*a, **k):
            raise RuntimeError("injected engine bug")
        eng.pool.begin = boom
        eng.start()
        h = eng.submit(np.arange(4, dtype=np.int32),
                       SamplingParams(max_new_tokens=4))
        with pytest.raises(EngineStopped) as ei:
            h.result(timeout=30)
        assert isinstance(ei.value.__cause__, RuntimeError)
        with pytest.raises(EngineStopped):
            eng.submit(np.arange(4, dtype=np.int32), SamplingParams())
        eng.shutdown()

    def test_streaming_callback_order(self):
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        seen = []
        with InferenceEngine(model, params,
                             EngineConfig(n_slots=1,
                                          max_len=MAX_LEN)) as eng:
            h = eng.submit(np.arange(5, dtype=np.int32),
                           SamplingParams(max_new_tokens=6),
                           on_token=lambda t, i: seen.append((i, t)))
            out = h.result(timeout=60)
        assert [i for i, _ in seen] == list(range(6))
        np.testing.assert_array_equal(np.asarray([t for _, t in seen]),
                                      out)


# ---------------------------------------------------------------------------
# where a token is chosen: greedy inside the decode program, one batched
# sampler a setting, one fetch an iteration (serve/sampling.py)
# ---------------------------------------------------------------------------

SP_A = dict(temperature=0.7, top_k=8)
SP_B = dict(temperature=0.9, top_p=0.9)


def _decode_rows(specs):
    """Which requests of ``specs`` hold a row of the decode program in
    each iteration it runs in, when all were queued before the loop
    started: ``{iteration: [request index, ...]}``. A request's first
    token comes from its prefill, in the iteration ``a`` that admits it,
    and it decodes from ``a`` to ``a + max_new - 2``. The engine prefills
    ONE chunk an iteration once a row is running, so request ``i`` (a
    prompt of one chunk) is admitted in iteration ``i + 1``."""
    rows = {}
    for i, (_, sp) in enumerate(specs):
        a = i + 1
        for t in range(a, a + sp.max_new_tokens - 1):
            rows.setdefault(t, []).append(i)
    return rows


def _sampler_calls(specs):
    """The batched sampler programs ``_decode_rows`` implies, in the
    order they are dispatched: ``(iteration, sampler_key, [request
    index, ...])``, one a distinct sampling setting an iteration."""
    calls = []
    for t, rows in sorted(_decode_rows(specs).items()):
        groups = {}
        for i in rows:
            if specs[i][1].temperature > 0:
                groups.setdefault(specs[i][1].sampler_key, []).append(i)
        calls += [(t, key, members) for key, members in groups.items()]
    return calls


def _serve_together(model, params, specs, n_slots, record=None):
    """Every request of ``specs`` ((prompt_len, SamplingParams) each)
    queued BEFORE the loop starts (``n_slots`` >= their number), so that
    they are admitted in slot order on the schedule ``_decode_rows``
    gives and decode side by side. Returns (prompts, keys, handles,
    stats)."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 61, (s,)).astype(np.int32)
               for s, _ in specs]
    keys = [jax.random.PRNGKey(300 + i) for i in range(len(specs))]
    eng = InferenceEngine(model, params, EngineConfig(
        n_slots=n_slots, max_len=MAX_LEN, page_len=8))
    if record is not None:
        build = eng._sampler._build_rows

        def recording(key):
            fn = build(key)

            def call(logits, keys_, mask, tokens):
                record.append((key, np.array(keys_), np.array(mask)))
                return fn(logits, keys_, mask, tokens)
            return call
        eng._sampler._build_rows = recording
    handles = [eng.submit(p, sp, rng=k)
               for p, (_, sp), k in zip(prompts, specs, keys)]
    with eng:
        for h in handles:
            h.result(timeout=120)
    return prompts, keys, handles, eng.stats()


class TestRowSampling:
    def test_mixed_batch_bit_identical_one_fetch_an_iteration(self):
        """Greedy rows and rows of two sampling settings in ONE batch:
        every stream is generate()'s, token for token; the tokens of an
        iteration come to the host in one read, and a sampler program
        runs once a setting that has a row in the iteration. The two
        short rows retire mid-batch (slots 1 and 3 of 0..4) and leave
        their neighbours' streams alone."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        specs = [(5, SamplingParams(max_new_tokens=10)),
                 (9, SamplingParams(max_new_tokens=4, **SP_B)),
                 (3, SamplingParams(max_new_tokens=10, **SP_A)),
                 (7, SamplingParams(max_new_tokens=4, **SP_B)),
                 (12, SamplingParams(max_new_tokens=10, **SP_A))]
        prompts, keys, handles, st = _serve_together(
            model, params, specs, n_slots=5)
        for i, (h, (_, sp)) in enumerate(zip(handles, specs)):
            np.testing.assert_array_equal(
                h.result(), _standalone(model, params, prompts[i], sp,
                                        keys[i]), err_msg=f"request {i}")
        assert [h.metrics["admit_iteration"] for h in handles] == \
            [1, 2, 3, 4, 5]
        # the first token of each comes from its admission; the rows
        # start an iteration apart: the 9 decode iterations of a long
        # row span 13, setting A's two rows 9 + 2, setting B's 3 + 2
        rows = _decode_rows(specs)
        assert len(rows) == 13
        assert st["decode_fetches"] == len(rows), st
        assert st["sample_dispatches"] == len(_sampler_calls(specs)) \
            == (9 + 2) + (3 + 2), st
        assert st["rows_decoded"] == 3 * 9 + 2 * 3, st
        assert st["decode_compiles"] == 1, st
        # three settings admitted, two of them sample in decode
        assert st["sample_compiles"] == 3 + 2, st

    def test_all_greedy_dispatches_no_sampler(self):
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        record = []
        specs = [(4 + i, SamplingParams(max_new_tokens=3 + 2 * i))
                 for i in range(3)]
        _, _, handles, st = _serve_together(model, params, specs, 3,
                                            record=record)
        assert [len(h.result()) for h in handles] == [3, 5, 7]
        # the longest row's 6, admitted in iteration 3
        assert st["decode_fetches"] == 8, st
        assert st["sample_dispatches"] == 0 and record == [], st
        assert st["sample_compiles"] == 1, st     # admission's, greedy

    @pytest.mark.parametrize("rows", [1, 2, 4])
    def test_no_program_follows_the_rows(self, rows):
        """One row, half the slots, all of them: one decode program,
        one sampler program a setting at admission and one a sampling
        setting in decode — every shape is n_slots wide."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        settings = [SP_A, SP_B, {}, SP_A][:rows]
        specs = [(4 + i, SamplingParams(max_new_tokens=5, **kw))
                 for i, kw in enumerate(settings)]
        _, _, _, st = _serve_together(model, params, specs, 4)
        sampling = len({tuple(kw.items()) for kw in settings if kw})
        assert st["decode_compiles"] == 1, st
        assert st["sample_compiles"] == \
            len({tuple(kw.items()) for kw in settings}) + sampling, st
        assert st["decode_fetches"] == len(_decode_rows(specs)) \
            == 4 + rows - 1, st
        assert st["sample_dispatches"] == len(_sampler_calls(specs)), st

    def test_only_rows_that_sample_upload_a_key(self):
        """What each batched sampler was given: the mask holds exactly
        the running rows of its setting, each with the key of its next
        token (generate()'s split schedule); every other row's key,
        the greedy rows' among them, is never uploaded (zeros)."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        record = []
        # long enough that no slot is free again before the last
        # request is admitted: request i holds slot i
        specs = [(4, SamplingParams(max_new_tokens=6)),
                 (5, SamplingParams(max_new_tokens=6, **SP_A)),
                 (6, SamplingParams(max_new_tokens=5, **SP_B)),
                 (7, SamplingParams(max_new_tokens=6, **SP_A))]
        _, keys, _, st = _serve_together(model, params, specs, 4,
                                         record=record)
        calls = _sampler_calls(specs)
        assert st["sample_dispatches"] == len(record) == len(calls)
        splits = [np.asarray(jax.random.split(k, sp.max_new_tokens))
                  for k, (_, sp) in zip(keys, specs)]
        for (key, row_keys, mask), (t, want_key, want) in zip(record, calls):
            assert key == want_key
            assert np.flatnonzero(mask).tolist() == want
            for slot in range(4):
                # request ``slot`` was admitted in iteration a: its token
                # of iteration t has the index t - a + 1
                step = t - slot
                np.testing.assert_array_equal(
                    row_keys[slot],
                    splits[slot][step] if slot in want else 0)

    def test_failed_row_leaves_co_residents_alone(self):
        """A row that misses its deadline mid-decode and a row whose
        callback raises: the greedy and the sampled co-resident still
        get generate()'s tokens."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(7)
        eng = InferenceEngine(model, params, EngineConfig(
            n_slots=4, max_len=128, page_len=8))
        sp_g = SamplingParams(max_new_tokens=12)
        sp_s = SamplingParams(max_new_tokens=12, **SP_A)
        prompts = [rng.integers(0, 61, (5 + i,)).astype(np.int32)
                   for i in range(4)]
        key = jax.random.PRNGKey(9)

        def boom(tok, i):
            raise RuntimeError("a client's callback")
        with eng:
            # warm every program: compile time must not eat the deadline
            for sp in (sp_g, sp_s):
                eng.submit(np.arange(4, dtype=np.int32), sp).result(
                    timeout=60)
            faults.install("delay@op=serve_step,call=3,ms=1200")
            victim = eng.submit(prompts[0], SamplingParams(
                max_new_tokens=100, deadline_ms=700.0))
            hg = eng.submit(prompts[1], sp_g)
            hs = eng.submit(prompts[2], sp_s, rng=key)
            hb = eng.submit(prompts[3], sp_s, rng=key, on_token=boom)
            with pytest.raises(RequestDeadlineExceeded) as ei:
                victim.result(timeout=60)
            assert ei.value.stage == "running"
            outs = [h.result(timeout=60) for h in (hg, hs, hb)]
        for out, p, sp in zip(outs, prompts[1:], (sp_g, sp_s, sp_s)):
            np.testing.assert_array_equal(
                out, _standalone(model, params, p, sp, key, max_len=128))
