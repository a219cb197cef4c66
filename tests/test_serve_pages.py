"""Paged, prefix-shared KV cache (serve/pages/) — the acceptance suite.

The headline contract extends PR 3's: for a mixed batch of COLD,
PARTIALLY shared, and FULLY shared prompts, every engine token stream
is bit-identical to a standalone ``generate()`` call — with exactly ONE
jitted decode program and one prefill program per tail-length bucket —
while shared full prefix pages are computed once, refcounted across
slots, LRU-evicted only at refcount zero, and pool exhaustion surfaces
as typed back-pressure (admission) or a typed, attributed per-request
failure (mid-decode growth) that never corrupts co-resident streams.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import models
from distributed_pytorch_tpu.models.generate import (make_generate_fn,
                                                     prefill,
                                                     prefill_partial_paged)
from distributed_pytorch_tpu.nn.paged import ExactSide, KVPages
from distributed_pytorch_tpu.runtime import faults
from distributed_pytorch_tpu.serve import (AdmissionRejected, EngineConfig,
                                           EngineStopped, InferenceEngine,
                                           PagePool, PagePoolExhausted,
                                           PrefixIndex,
                                           RequestDeadlineExceeded,
                                           SamplingParams)
from distributed_pytorch_tpu.serve.pages import PagedSlotPool
from distributed_pytorch_tpu.utils.logging import MetricsLogger

MAX_LEN = 64
L = 8  # page_len used by most engine tests


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


def _lm1(**kw):
    kw.setdefault("n_layers", 1)
    return _lm(**kw)


def _standalone(model, params, prompt, sp, key, max_len=MAX_LEN):
    fn = make_generate_fn(model, sp.max_new_tokens,
                          temperature=sp.temperature, top_k=sp.top_k,
                          top_p=sp.top_p, max_len=max_len)
    return np.asarray(jax.jit(fn)(params, jnp.asarray(prompt[None]),
                                  key))[0]


def _paged_engine(model, params, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("page_len", L)
    return InferenceEngine(model, params, EngineConfig(paged=True, **kw))


# ---------------------------------------------------------------------------
# host-side policy units: PagePool + PrefixIndex
# ---------------------------------------------------------------------------


class TestPagePoolUnits:
    def test_refcount_free_list_lifecycle(self):
        pool = PagePool(4, 8)
        a = pool.take_free()
        b = pool.take_free()
        assert pool.refcount[a] == 1 and pool.free_pages == 2
        pool.incref(a)
        pool.decref(a)
        assert pool.free_pages == 2          # still referenced
        pool.decref(a)
        assert pool.free_pages == 3          # back on the free list
        with pytest.raises(ValueError, match="double release"):
            pool.decref(a)
        # an indexed page parks as RESIDENT at refcount zero, not free
        pool.indexed[b] = True
        pool.decref(b)
        assert pool.free_pages == 3 and pool.refcount[b] == 0

    def test_match_caps_and_partial_pages_never_indexed(self):
        pool = PagePool(8, 4)
        idx = PrefixIndex(4)
        toks = np.arange(14, dtype=np.int32)     # 3 full pages + 2 tail
        pages = [pool.take_free() for _ in range(4)]
        idx.insert(toks, 14 // 4, pages, pool)   # only 3 full pages
        assert len(idx) == 3
        assert not pool.indexed[pages[3]]        # the partial tail page
        # a shorter prompt that is a strict prefix: the lookup is capped
        # at (S-1)//L so the LAST full page is never consumed whole —
        # at least one token remains for the tail prefill
        assert idx.match(toks[:12], (12 - 1) // 4, pool) == pages[:2]
        assert idx.match(toks[:13], (13 - 1) // 4, pool) == pages[:3]
        # divergent second chunk stops the walk after one page
        other = toks.copy()
        other[5] += 1
        assert idx.match(other, 3, pool) == pages[:1]

    def test_evict_lru_leaf_first_never_live(self):
        pool = PagePool(8, 4)
        idx = PrefixIndex(4)
        live = np.arange(8, dtype=np.int32)
        cold = np.arange(8, dtype=np.int32) + 20
        live_pages = [pool.take_free() for _ in range(2)]
        cold_pages = [pool.take_free() for _ in range(2)]
        idx.insert(live, 2, live_pages, pool)
        idx.insert(cold, 2, cold_pages, pool)
        # cold chain fully released; live chain keeps its readers
        for p in cold_pages:
            pool.decref(p)
        # leaf first: depth-1 page goes before its parent, and the LIVE
        # chain is never a candidate no matter how stale its clock is
        assert idx.evict_lru(pool) == cold_pages[1]
        assert idx.evict_lru(pool) == cold_pages[0]
        assert idx.evict_lru(pool) is None
        assert all(pool.refcount[p] == 1 for p in live_pages)
        assert pool.evictions == 2

    def test_page_fault_ops_registered(self):
        assert "page_admit" in faults.COMM_OPS
        assert "page_evict" in faults.COMM_OPS
        specs = faults.parse_fault_spec(
            "delay@op=page_admit,ms=5;kill@op=page_evict,call=2")
        assert specs[0].op == "page_admit" and specs[1].op == "page_evict"


# ---------------------------------------------------------------------------
# the paged ops (models/generate.py)
# ---------------------------------------------------------------------------


class TestPagedOps:
    @pytest.mark.slow
    def test_cold_paged_prefill_matches_prefill(self):
        """offset=0 through the paged program computes the same last-
        position logits as an exact-length prefill over a contiguous
        cache (pad tail and fully-masked prefix both causally inert)."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        s, bucket, page_len, n_pages = 11, 16, 4, 8
        prompt = rng.integers(0, 61, (s,)).astype(np.int32)
        padded = jnp.zeros((1, bucket), jnp.int32).at[0, :s].set(prompt)
        ref, _ = jax.jit(lambda p, t: prefill(model, p, t, MAX_LEN))(
            params, jnp.asarray(prompt[None]))
        dh = model.dim // model.n_heads
        shape = (n_pages, model.n_kv_heads, page_len, dh)
        kp = [jnp.zeros(shape, model.dtype) for _ in range(model.n_layers)]
        vp = [jnp.zeros(shape, model.dtype) for _ in range(model.n_layers)]
        table = jnp.arange(4, dtype=jnp.int32)
        state = [KVPages(ExactSide(k), ExactSide(v)) for k, v in zip(kp, vp)]
        got, _ = jax.jit(
            lambda p, st, tr, t, o, n: prefill_partial_paged(
                model, p, st, tr, t, o, n, page_len=page_len))(
            params, state, table, padded, 0, s)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-6)
        assert int(jnp.argmax(ref)) == int(jnp.argmax(got))


# ---------------------------------------------------------------------------
# the paged engine
# ---------------------------------------------------------------------------


class TestPagedEngine:
    def test_shared_mix_bit_identical(self):
        """The tier-1 acceptance kernel: a cold, a partially shared,
        and a fully shared prompt through the paged engine — every
        stream equals standalone generate(), ONE decode compile, one
        prefill per tail bucket, hit accounting exact. Deliberately
        compile-lean (1 layer, one prompt length, one sampler → a
        single standalone reference program) so tier-1 stays near the
        seed's budget; the wider staggered 2-layer mix with mixed
        sampling runs in the slow tier, and serve_bench --smoke
        re-asserts this contract in CI on every push."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(12)
        eng = _paged_engine(model, params, n_slots=3)
        pfx = rng.integers(0, 61, (16,)).astype(np.int32)   # 2 full pages
        prompts = [
            np.concatenate([pfx, rng.integers(0, 61, (4,))]).astype(np.int32),
            np.concatenate([pfx, rng.integers(0, 61, (4,))]).astype(np.int32),
            None,
        ]
        prompts[2] = prompts[0].copy()                      # full share
        sp = SamplingParams(max_new_tokens=8)
        keys = [jax.random.PRNGKey(100 + i) for i in range(3)]
        fn = jax.jit(make_generate_fn(model, sp.max_new_tokens,
                                      max_len=MAX_LEN))
        with eng:
            hs = [eng.submit(prompts[i], sp, rng=keys[i])
                  for i in range(3)]
            outs = [h.result(timeout=120) for h in hs]
        for i in range(3):
            ref = np.asarray(fn(params, jnp.asarray(prompts[i][None]),
                                keys[i]))[0]
            np.testing.assert_array_equal(outs[i], ref,
                                          err_msg=f"request {i}")
        st = eng.stats()
        assert st["decode_compiles"] == 1, st
        assert all(v == 1 for v in st["prefill_compiles"].values()), st
        assert [h.metrics["prefix_hit_pages"] for h in hs] == [0, 2, 2]
        assert [h.metrics["prefill_tokens_saved"] for h in hs] == [0, 16, 16]

    def test_shared_prefix_rows_of_mixed_settings(self):
        """Rows that share prefix pages and differ in sampling: a
        greedy row, two of one sampling setting and one of another
        decode side by side — every stream is generate()'s, with one
        token read an iteration and one sampler program a setting."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(13)
        pfx = rng.integers(0, 61, (16,)).astype(np.int32)   # 2 full pages
        prompts = [np.concatenate(
            [pfx, rng.integers(0, 61, (3 + i,))]).astype(np.int32)
            for i in range(4)]
        a, b = dict(temperature=0.7, top_k=8), dict(temperature=0.9,
                                                    top_p=0.9)
        sps = [SamplingParams(max_new_tokens=6, **kw)
               for kw in ({}, a, b, a)]
        keys = [jax.random.PRNGKey(200 + i) for i in range(4)]
        eng = _paged_engine(model, params, n_slots=4)
        hs = [eng.submit(p, sp, rng=k)      # queued before the loop
              for p, sp, k in zip(prompts, sps, keys)]   # starts: one
        with eng:                           # prompt admitted an iteration
            outs = [h.result(timeout=120) for h in hs]
        for i in range(4):
            np.testing.assert_array_equal(
                outs[i], _standalone(model, params, prompts[i], sps[i],
                                     keys[i]), err_msg=f"request {i}")
        st = eng.stats()
        assert [h.metrics["prefix_hit_pages"] for h in hs] == [0, 2, 2, 2]
        # request i holds a row in iterations i + 1 .. i + 5: 8 decode
        # iterations; setting a has a row in 2 .. 8, setting b in 3 .. 7
        assert [h.metrics["admit_iteration"] for h in hs] == [1, 2, 3, 4]
        assert st["decode_fetches"] == 8, st
        assert st["sample_dispatches"] == 7 + 5, st
        assert st["decode_compiles"] == 1, st
        assert st["sample_compiles"] == 3 + 2, st

    # slow tier: the staggered 2-layer wide mix (five standalone
    # generate compiles); the contract kernel above stays tier-1 and
    # serve_bench --smoke re-asserts it in CI on every push
    @pytest.mark.slow
    def test_mixed_cold_partial_full_bit_identical(self):
        """THE acceptance case: cold / partially shared / fully shared /
        sub-page prompts, staggered admission past the slot count, mixed
        sampling — every stream equals standalone generate(), decode
        compiles once, one prefill per tail bucket, and the hit
        accounting matches the share structure exactly."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        eng = _paged_engine(model, params, n_slots=3)
        pfx = rng.integers(0, 61, (16,)).astype(np.int32)   # 2 full pages
        prompts = [
            np.concatenate([pfx, rng.integers(0, 61, (4,))]).astype(np.int32),
            np.concatenate([pfx, rng.integers(0, 61, (9,))]).astype(np.int32),
            None,                                           # dup of 0
            rng.integers(0, 61, (5,)).astype(np.int32),     # sub-page cold
            np.concatenate([pfx[:8], rng.integers(0, 61, (6,))]).astype(np.int32),
        ]
        prompts[2] = prompts[0].copy()
        sps = [SamplingParams(max_new_tokens=24),
               SamplingParams(max_new_tokens=5, temperature=0.7, top_k=8),
               SamplingParams(max_new_tokens=8),
               SamplingParams(max_new_tokens=6, temperature=0.9, top_p=0.9),
               SamplingParams(max_new_tokens=6)]
        keys = [jax.random.PRNGKey(100 + i) for i in range(5)]
        with eng:
            hs = [eng.submit(prompts[i], sps[i], rng=keys[i])
                  for i in range(4)]
            hs[1].result(timeout=120)     # slot frees mid-run
            hs.append(eng.submit(prompts[4], sps[4], rng=keys[4]))
            outs = [h.result(timeout=120) for h in hs]
        for i in range(5):
            ref = _standalone(model, params, prompts[i], sps[i], keys[i])
            np.testing.assert_array_equal(outs[i], ref,
                                          err_msg=f"request {i}")
        st = eng.stats()
        assert st["decode_compiles"] == 1, st
        assert all(v == 1 for v in st["prefill_compiles"].values()), st
        hits = [h.metrics["prefix_hit_pages"] for h in hs]
        saved = [h.metrics["prefill_tokens_saved"] for h in hs]
        # 0 cold; 1 shares both prefix pages; 2 (identical prompt, len
        # 20) shares both; 3 has no full page; 4 shares only page 0
        assert hits == [0, 2, 2, 0, 1], (hits, st["pages"])
        assert saved == [0, 16, 16, 0, 8]
        # overlap really happened: request 0 (24 tokens) outlived 1's
        # retirement, and everything was bit-exact anyway
        assert (hs[0].metrics["retire_iteration"]
                > hs[1].metrics["retire_iteration"])

    @pytest.mark.slow
    def test_prefix_longer_than_resident_entry(self):
        """A prompt that is a strict PREFIX of a resident chain: the
        match is capped at the request's own (S-1)//L full pages, so
        the tail prefill always has at least one real token
        (slow tier: five standalone-generate compiles; the cap math is
        also covered by TestPagePoolUnits::test_match_caps... in the
        fast tier)."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        long = rng.integers(0, 61, (33,)).astype(np.int32)  # 4 full pages
        cases = [(17, 2), (16, 1), (8, 0), (5, 0)]
        eng = _paged_engine(model, params, n_slots=2)
        with eng:
            h0 = eng.submit(long, SamplingParams(max_new_tokens=4),
                            rng=jax.random.PRNGKey(0))
            h0.result(timeout=120)
            for s, want_hit in cases:
                sp = SamplingParams(max_new_tokens=4)
                key = jax.random.PRNGKey(s)
                h = eng.submit(long[:s], sp, rng=key)
                out = h.result(timeout=120)
                ref = _standalone(model, params, long[:s], sp, key)
                np.testing.assert_array_equal(out, ref, err_msg=f"S={s}")
                assert h.metrics["prefix_hit_pages"] == want_hit, s

    @pytest.mark.slow   # divergent-chunk cap is tier-1 via test_match_caps
    def test_partial_page_tail_never_shared(self):
        """Two prompts agreeing on 12 tokens share exactly the one FULL
        page (8 tokens) — the 4-token partial tail is private."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(4)
        common = rng.integers(0, 61, (12,)).astype(np.int32)
        a = np.concatenate([common, rng.integers(0, 61, (3,))]).astype(np.int32)
        b = np.concatenate([common, rng.integers(0, 61, (5,))]).astype(np.int32)
        eng = _paged_engine(model, params, n_slots=2)
        with eng:
            ka, kb = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
            sp = SamplingParams(max_new_tokens=5)
            ha = eng.submit(a, sp, rng=ka)
            ha.result(timeout=120)
            hb = eng.submit(b, sp, rng=kb)
            np.testing.assert_array_equal(
                hb.result(timeout=120), _standalone(model, params, b, sp, kb))
        assert ha.metrics["prefix_hit_pages"] == 0
        assert hb.metrics["prefix_hit_pages"] == 1
        assert hb.metrics["prefill_tokens_saved"] == 8

    @pytest.mark.slow   # release-path coverage is tier-1 via crash-drain + chaos
    def test_refcount_release_on_retirement(self):
        """After every request retires, no page has a live reader;
        indexed prompt pages stay RESIDENT (evictable), private pages
        return to the free list."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(5)
        eng = _paged_engine(model, params, n_slots=2)
        with eng:
            for i in range(3):
                prompt = rng.integers(0, 61, (18,)).astype(np.int32)
                eng.submit(prompt, SamplingParams(max_new_tokens=6),
                           rng=jax.random.PRNGKey(i)).result(timeout=120)
        pool = eng.pool.pool
        assert pool.live_pages() == 0
        assert len(eng.pool.index) == pool.pages_in_use
        assert pool.free_pages + pool.pages_in_use == pool.n_pages

    def test_refcount_release_on_crash_drain(self):
        """An engine-loop crash fails futures typed AND drops every page
        reference — a dead engine cannot pin pool pages."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        eng = _paged_engine(model, params, n_slots=2)

        def boom(*a, **k):
            raise RuntimeError("injected engine bug")
        eng.pool.decode = boom
        eng.start()
        h = eng.submit(np.arange(10, dtype=np.int32),
                       SamplingParams(max_new_tokens=8))
        with pytest.raises(EngineStopped):
            h.result(timeout=60)
        eng.shutdown()
        assert eng.pool.pool.live_pages() == 0

    # slow tier: the deadline path is tier-1 in test_serve.py and the
    # release path is tier-1 via the chaos + crash-drain cases
    @pytest.mark.slow
    def test_midstream_failure_releases_and_others_unharmed(self):
        """A queued-deadline failure mid-run releases the victim's
        references while the co-resident stream stays bit-exact."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(6)
        prompt = rng.integers(0, 61, (9,)).astype(np.int32)
        key = jax.random.PRNGKey(3)
        sp = SamplingParams(max_new_tokens=30)
        eng = _paged_engine(model, params, n_slots=1)
        with eng:
            ha = eng.submit(prompt, sp, rng=key)
            hb = eng.submit(np.arange(4, dtype=np.int32),
                            SamplingParams(max_new_tokens=4,
                                           deadline_ms=40.0))
            with pytest.raises(RequestDeadlineExceeded):
                hb.result(timeout=60)
            np.testing.assert_array_equal(
                ha.result(timeout=120),
                _standalone(model, params, prompt, sp, key))
        assert eng.pool.pool.live_pages() == 0

    # slow tier: the LRU/liveness invariants are unit-tested tier-1 and
    # eviction-under-load is also exercised by the backpressure test
    @pytest.mark.slow
    def test_eviction_pressure_admissions_evict_lru_only(self):
        """Distinct prompts churn a small pool: refcount-zero indexed
        pages are LRU-evicted to make room, a LIVE long-running request
        is never a victim, and its stream stays bit-exact."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(7)
        # pool: 8 pages of 4 — a live request + churn must evict
        eng = _paged_engine(model, params, n_slots=2, max_len=32,
                            page_len=4, n_pages=8)
        long_prompt = rng.integers(0, 61, (8,)).astype(np.int32)
        key = jax.random.PRNGKey(9)
        sp_long = SamplingParams(max_new_tokens=20)
        with eng:
            hl = eng.submit(long_prompt, sp_long, rng=key)
            churn = []
            for i in range(5):
                p = rng.integers(0, 61, (9,)).astype(np.int32)
                churn.append((p, jax.random.PRNGKey(20 + i)))
                eng.submit(p, SamplingParams(max_new_tokens=2),
                           rng=churn[-1][1]).result(timeout=120)
            out = hl.result(timeout=120)
        np.testing.assert_array_equal(
            out, _standalone(model, params, long_prompt, sp_long, key,
                             max_len=32))
        assert eng.pool.pool.evictions > 0
        assert eng.pool.pool.live_pages() == 0

    @pytest.mark.parametrize("co_resident", [
        pytest.param({}, id="greedy"),
        pytest.param(dict(temperature=0.7, top_k=8), id="sampled")])
    def test_chaos_pool_exhaustion_mid_decode_typed_victim(self,
                                                           co_resident):
        """THE chaos satellite: every page held by a live reader when a
        slot's decode crosses a page boundary — the victim fails with a
        typed, attributed PagePoolExhausted (request + iteration) while
        the co-resident stream (greedy: the decode program's own
        tokens; sampled: its group's) is bit-identical to generate(),
        and the page-op fault grammar demonstrably fired."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(8)
        faults.install("delay@op=page_admit,call=1,ms=1")
        eng = _paged_engine(model, params, n_slots=2, max_len=16,
                            page_len=4, n_pages=4)
        a = rng.integers(0, 61, (4,)).astype(np.int32)   # 1 page
        b = rng.integers(0, 61, (8,)).astype(np.int32)   # 2 pages
        ka, kb = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
        # grows to page 1, stops
        sp_a = SamplingParams(max_new_tokens=4, **co_resident)
        sp_b = SamplingParams(max_new_tokens=6)   # needs page 2 mid-decode
        with eng:
            ha = eng.submit(a, sp_a, rng=ka)
            hb = eng.submit(b, sp_b, rng=kb)
            with pytest.raises(PagePoolExhausted) as ei:
                hb.result(timeout=120)
            out_a = ha.result(timeout=120)
        assert ei.value.request_id == hb.request_id
        assert ei.value.iteration is not None
        assert ei.value.free_pages == 0
        np.testing.assert_array_equal(
            out_a, _standalone(model, params, a, sp_a, ka, max_len=16))
        assert any(f.startswith("delay@op=page_admit")
                   for f in faults.fired()), faults.fired()
        # the victim's references were dropped with it
        assert eng.pool.pool.live_pages() == 0

    @pytest.mark.slow   # exhaustion-with-typed-failure is tier-1 via the chaos case
    def test_admission_backpressure_requeues_then_serves(self):
        """Admission that cannot get pages while another request runs
        stays QUEUED (typed back-pressure, FCFS-stable) and is served
        bit-exactly once the retirement frees pages."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(9)
        eng = _paged_engine(model, params, n_slots=2, max_len=12,
                            page_len=4, n_pages=3)
        a = rng.integers(0, 61, (8,)).astype(np.int32)
        b = rng.integers(0, 61, (8,)).astype(np.int32)
        ka, kb = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
        sp = SamplingParams(max_new_tokens=4)
        with eng:
            ha = eng.submit(a, sp, rng=ka)
            hb = eng.submit(b, sp, rng=kb)
            out_a = ha.result(timeout=120)
            out_b = hb.result(timeout=120)
        np.testing.assert_array_equal(
            out_a, _standalone(model, params, a, sp, ka, max_len=12))
        np.testing.assert_array_equal(
            out_b, _standalone(model, params, b, sp, kb, max_len=12))
        # b could only start after a's retirement freed pages
        assert (hb.metrics["admit_iteration"]
                >= ha.metrics["retire_iteration"])
        assert eng.pool.pool.evictions > 0   # a's indexed pages reclaimed

    def test_submit_rejects_worst_case_page_need(self):
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        eng = _paged_engine(model, params, n_slots=1, max_len=32,
                            page_len=4, n_pages=2)
        with pytest.raises(AdmissionRejected) as ei:
            eng.submit(np.arange(10, dtype=np.int32),
                       SamplingParams(max_new_tokens=10))
        assert ei.value.reason == "no_free_pages"
        eng.shutdown(wait=False)

    @pytest.mark.slow
    def test_prefix_share_off_still_bit_exact(self):
        """DPX_SERVE_PREFIX_SHARE=0 semantics: paged layout, zero hits,
        streams still equal generate()."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(10)
        prompt = rng.integers(0, 61, (18,)).astype(np.int32)
        eng = _paged_engine(model, params, n_slots=2, prefix_share=False)
        sp = SamplingParams(max_new_tokens=6)
        with eng:
            hs = [eng.submit(prompt, sp, rng=jax.random.PRNGKey(i))
                  for i in range(2)]
            outs = [h.result(timeout=120) for h in hs]
        for i, h in enumerate(hs):
            np.testing.assert_array_equal(
                outs[i], _standalone(model, params, prompt, sp,
                                     jax.random.PRNGKey(i)))
            assert h.metrics["prefix_hit_pages"] == 0
        assert len(eng.pool.index) == 0

    def test_windowed_model_rejects_paged(self):
        from distributed_pytorch_tpu.nn.attention import dense_attention

        def fn(q, k, v, *, causal=False, scale=None):
            return dense_attention(q, k, v, causal=causal, scale=scale,
                                   window=8)
        fn.window = 8
        model = _lm1(vocab=64, attn_fn=fn)
        params = model.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="sliding-window"):
            _paged_engine(model, params)

    @pytest.mark.slow   # hit-rate/occupancy flow also CI-gated by serve_bench --smoke
    def test_paged_metrics_flow_to_logger(self, tmp_path):
        """serve_request events carry the prefix fields; periodic
        engine rows carry pool occupancy and hit rate; the fleet
        aggregate sums prefill_tokens_saved."""
        from distributed_pytorch_tpu.serve import aggregate
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(11)
        log = tmp_path / "serve_pages.jsonl"
        logger = MetricsLogger(path=str(log))
        eng = InferenceEngine(model, params, EngineConfig(
            n_slots=2, max_len=MAX_LEN, paged=True, page_len=L,
            metrics=logger, log_every=2))
        pfx = rng.integers(0, 61, (16,)).astype(np.int32)
        with eng:
            hs = [eng.submit(
                np.concatenate([pfx,
                                rng.integers(0, 61, (3,))]).astype(np.int32),
                SamplingParams(max_new_tokens=6),
                rng=jax.random.PRNGKey(i)) for i in range(3)]
            for h in hs:
                h.result(timeout=120)
        logger.close()
        rows = [json.loads(ln) for ln in log.read_text().splitlines()]
        reqs = [r for r in rows if r.get("event") == "serve_request"]
        assert len(reqs) == 3
        assert sorted(r["prefix_hit_pages"] for r in reqs) == [0, 2, 2]
        assert sorted(r["prefill_tokens_saved"] for r in reqs) == [0, 16, 16]
        engine_rows = [r for r in rows
                       if r.get("event") == "metrics_snapshot"
                       and r.get("source") == "serve_engine"]
        assert engine_rows
        for r in engine_rows:
            m = r["metrics"]
            assert 0.0 <= m["serve.pool_occupancy"] <= 1.0
            assert "serve.free_pages" in m
            assert "serve.page_evictions" in m
        agg = aggregate([h.metrics for h in hs])
        assert agg["prefill_tokens_saved"] == 32
        assert 0.0 < agg["prefix_hit_rate"] < 1.0
        assert agg["prefix_hit_pages"] == 4
