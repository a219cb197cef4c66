"""Speculative decoding (``serve/spec/``; docs/serving.md "Speculative
decoding").

What must hold (ISSUE 19):

- the GREEDY CONTRACT: the accepted token stream is bit-identical to
  ``generate()``'s for the page pool, at two page lengths, and the
  disaggregated split — speculation is a latency optimization, never a
  behavior change. In a quantized (q8) pool the reference is the same
  engine WITHOUT speculation: the pool's argmax stream is whatever the
  quantized cache produces, and spec must reproduce it exactly;
- ONE verify and one commit program per draft-length bucket
  (``CompileCounts.verify`` / ``.commit``), asserted, not trusted;
- acceptance extremes are exact: a self-draft on matching pool layouts
  accepts everything (rate 1.0, k+1 tokens per iteration), an
  all-zeros draft whose constant proposal never appears in the target
  stream accepts nothing (rate 0.0, 1 token per iteration) — and both
  are STILL bit-exact, because acceptance only affects speed;
- rollback never corrupts the quantize-once discipline: a rejection at
  a page boundary leaves the next page unallocated and unquantized, a
  partially-filled page stays in the exact f32 tail until an ACCEPTED
  token completes it;
- the draft model's keys live in a page pool of its own, which holds
  every slot's worst case at once: a rollback rewinds its lengths and
  keeps its pages, every exit of a request gives them all back, a
  prompt of several chunks speculates, and a propose from a row's last
  position writes past ``max_len`` into room of its own;
- failures are contained: ``flaky@op=spec_verify`` fails ONLY the
  speculating victim (typed ``SpecDecodeError``, request + iteration +
  stage attributed) while a co-resident non-spec stream stays
  bit-identical to its standalone reference; an injected verify delay
  trips the victim's OWN deadline, typed;
- the per-tenant quota front door: the (max+1)-th inflight submit for
  a tenant is rejected synchronously (``reason="tenant_quota"``,
  tenant attributed) and the credit returns at retirement.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_pytorch_tpu import models
from distributed_pytorch_tpu.models.generate import (generate,
                                                     make_generate_fn)
from distributed_pytorch_tpu.runtime import faults
from distributed_pytorch_tpu.serve import (AdmissionRejected,
                                           DisaggConfig, DisaggEngine,
                                           EngineConfig, EngineStopped,
                                           InferenceEngine,
                                           RequestDeadlineExceeded,
                                           SamplingParams,
                                           SpecDecodeError, aggregate)
from distributed_pytorch_tpu.serve.pages import PagedSlotPool
from distributed_pytorch_tpu.serve.spec import SpecConfig, SpecState

MAX_LEN = 64
BUCKETS = (8, 16, 32)
L = 8


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


def _draft(**kw):
    """The cheap proposer: same vocab, a fraction of the stack."""
    kw.setdefault("dim", 16)
    kw.setdefault("n_layers", 1)
    kw.setdefault("n_heads", 2)
    kw.setdefault("n_kv_heads", 1)
    return _lm(**kw)


def _spec_cfg(dm, dp, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("buckets", BUCKETS)
    return EngineConfig(spec_decode=True, draft_model=dm,
                        draft_params=dp, draft_len=3, **kw)


def _prompts():
    """Cold + shared-prefix mix: the last two share their first 8
    tokens (one full page), so the paged runs exercise prefix reuse
    under speculation."""
    base = np.arange(1, 25, dtype=np.int32) % 61
    return [base[:5].copy(), base[:13].copy(),
            np.concatenate([base[:8], base[8:11] * 0 + 7]),
            np.concatenate([base[:8], base[8:12] * 0 + 9])]


def _standalone(model, params, prompt, sp, key):
    fn = make_generate_fn(model, sp.max_new_tokens,
                          temperature=sp.temperature, top_k=sp.top_k,
                          top_p=sp.top_p, max_len=MAX_LEN)
    return np.asarray(jax.jit(fn)(params, jnp.asarray(prompt[None]),
                                  key))[0]


# ---------------------------------------------------------------------------
# the acceptance rule itself (pure host code)
# ---------------------------------------------------------------------------


class TestAcceptGreedy:
    def _logits(self, g, vocab=16):
        """Verify logits whose per-position argmax is ``g``."""
        lg = np.zeros((len(g), vocab), np.float32)
        lg[np.arange(len(g)), g] = 1.0
        return lg

    def test_full_acceptance_emits_k_plus_one(self):
        from distributed_pytorch_tpu.serve.spec import accept_greedy
        g = np.array([3, 5, 7, 9], np.int32)    # k = 3
        out, e = accept_greedy(g[:3], self._logits(g), 10, None)
        assert e == 4 and out == [3, 5, 7, 9]   # bonus token rides free

    def test_first_mismatch_truncates(self):
        from distributed_pytorch_tpu.serve.spec import accept_greedy
        g = np.array([3, 5, 7, 9], np.int32)
        drafts = np.array([3, 6, 7], np.int32)  # d_2 wrong
        out, e = accept_greedy(drafts, self._logits(g), 10, None)
        assert e == 2 and out == [3, 5]

    def test_remaining_caps_acceptance(self):
        from distributed_pytorch_tpu.serve.spec import accept_greedy
        g = np.array([3, 5, 7, 9], np.int32)
        out, e = accept_greedy(g[:3], self._logits(g), 2, None)
        assert e == 2 and out == [3, 5]

    def test_eos_truncates_inclusive(self):
        from distributed_pytorch_tpu.serve.spec import accept_greedy
        g = np.array([3, 5, 7, 9], np.int32)
        out, e = accept_greedy(g[:3], self._logits(g), 10, 5)
        assert e == 2 and out == [3, 5]         # eos kept, suffix cut


# ---------------------------------------------------------------------------
# the greedy bit-exact contract
# ---------------------------------------------------------------------------


class TestGreedyContract:
    @pytest.mark.parametrize("pool_kw", [
        {"page_len": 4}, {}, {"kv_dtype": "q8"},
    ], ids=["pages-of-4", "paged", "q8"])
    def test_stream_matches_reference(self, pool_kw):
        """Spec output == the SAME engine's non-spec output; for exact
        pools that is ``generate()`` itself, for q8 it is a non-spec
        q8 engine (speculation must be invisible at every kv_dtype)."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        dm = _draft()
        dp = dm.init(jax.random.PRNGKey(1))
        prompts = _prompts()
        n = 12
        if pool_kw.get("kv_dtype"):
            refs = []
            ref_eng = InferenceEngine(model, params, EngineConfig(
                n_slots=4, max_len=MAX_LEN, buckets=BUCKETS, **pool_kw))
            with ref_eng:
                hs = [ref_eng.submit(p, SamplingParams(max_new_tokens=n))
                      for p in prompts]
                refs = [np.asarray(h.result(timeout=120)) for h in hs]
        else:
            refs = [np.asarray(generate(model, params,
                                        jnp.asarray(p[None]), n)[0])
                    for p in prompts]
        eng = InferenceEngine(model, params,
                              _spec_cfg(dm, dp, **pool_kw))
        with eng:
            hs = [eng.submit(p, SamplingParams(max_new_tokens=n),
                             tenant="acme")
                  for p in prompts]
            outs = [np.asarray(h.result(timeout=120)) for h in hs]
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        st = eng.stats()
        assert st["spec_decode"] is True
        assert type(eng.pool) is type(eng._spec.pool) is PagedSlotPool
        sp = st["spec"]
        # ONE verify + ONE commit program for the single k+1=4 bucket
        assert sp["verify_compiles"] == {4: 1}
        assert sp["commit_compiles"] == {4: 1}
        assert sp["proposed"] > 0
        # per-request accounting rides the SLO record + aggregate view
        recs = [h.metrics for h in hs]
        assert all(r["tenant"] == "acme" for r in recs)
        assert sum(r["spec_proposed"] for r in recs) == sp["proposed"]
        agg = aggregate(recs)
        assert agg["spec_proposed"] == sp["proposed"]
        assert 0.0 <= agg["spec_acceptance_rate"] <= 1.0

    def test_disagg_stream_matches_generate(self):
        """The same contract across the prefill/decode split: the
        draft lives on the decode side and the accepted stream is
        bit-identical to ``generate()`` through the handoff."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        dm = _draft()
        dp = dm.init(jax.random.PRNGKey(1))
        prompts = _prompts()
        n = 12
        refs = [np.asarray(generate(model, params,
                                    jnp.asarray(p[None]), n)[0])
                for p in prompts]
        eng = DisaggEngine(model, params, DisaggConfig(
            n_slots=4, max_len=MAX_LEN, buckets=BUCKETS,
            spec_decode=True, draft_model=dm, draft_params=dp,
            draft_len=3))
        with eng:
            hs = [eng.submit(p, SamplingParams(max_new_tokens=n))
                  for p in prompts]
            outs = [np.asarray(h.result(timeout=120)) for h in hs]
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        d = eng.stats()["decode"]
        assert d["spec"]["verify_compiles"] == {4: 1}
        assert d["prefill_compiles"] == {}     # the split held

    @pytest.mark.parametrize("pool_kw", [
        pytest.param(dict(page_len=4), id="pages-of-4"),
        pytest.param(dict(page_len=L), id="paged")])
    def test_mixed_spec_and_sampled_batch(self, pool_kw):
        """A speculating (greedy) row and sampled rows of two settings
        share the batch: every stream is bit-identical to its
        standalone reference — speculation next door is invisible, and
        the rows that do not speculate get their tokens from the one
        decode program and one sampler a setting, in one read an
        iteration."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        dm = _draft()
        dp = dm.init(jax.random.PRNGKey(1))
        prompts = _prompts()
        n = 10
        sps = [SamplingParams(max_new_tokens=n),
               SamplingParams(max_new_tokens=n, temperature=0.7, top_k=8),
               SamplingParams(max_new_tokens=n, temperature=0.9,
                              top_p=0.9),
               SamplingParams(max_new_tokens=n, temperature=0.7, top_k=8)]
        keys = [jax.random.PRNGKey(5 + i) for i in range(4)]
        refs = [_standalone(model, params, p, sp, k)
                for p, sp, k in zip(prompts, sps, keys)]
        eng = InferenceEngine(model, params, _spec_cfg(dm, dp, **pool_kw))
        hs = [eng.submit(p, sp, rng=k)      # queued before the loop
              for p, sp, k in zip(prompts, sps, keys)]   # starts
        with eng:
            outs = [np.asarray(h.result(timeout=120)) for h in hs]
        for i in range(4):
            np.testing.assert_array_equal(outs[i], refs[i],
                                          err_msg=f"request {i}")
        st = eng.stats()
        assert st["spec"]["proposed"] > 0      # the greedy row DID spec
        # the three rows that sample: 9 decode iterations each, two
        # settings; the speculating row's tokens come from its verify.
        # The engine admits a prompt an iteration, so rows 1-3 decode in
        # iterations 2-10, 3-11, 4-12: setting A in 2-12, B in 3-11
        assert st["decode_fetches"] == n + 1, st
        assert st["sample_dispatches"] == (n + 1) + (n - 1), st
        assert st["rows_decoded"] == 3 * (n - 1), st
        assert st["decode_compiles"] == 1, st


# ---------------------------------------------------------------------------
# acceptance extremes — exact, and still bit-exact
# ---------------------------------------------------------------------------


class TestAcceptanceExtremes:
    def test_self_draft_accepts_everything(self):
        """Draft == target on the SAME pool layout: every
        proposal matches, rate is exactly 1.0 and every iteration
        commits k+1 tokens. max_new = 1 + 3*(k+1) so no iteration is
        truncated by the remaining budget."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        prompts = _prompts()[:2]
        n = 13
        refs = [np.asarray(generate(model, params,
                                    jnp.asarray(p[None]), n)[0])
                for p in prompts]
        eng = InferenceEngine(model, params,
                              _spec_cfg(model, params, n_slots=2))
        with eng:
            hs = [eng.submit(p, SamplingParams(max_new_tokens=n))
                  for p in prompts]
            outs = [np.asarray(h.result(timeout=120)) for h in hs]
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        st = eng.stats()["spec"]
        assert st["acceptance_rate"] == 1.0
        assert st["tokens_per_iteration"] == 4.0

    def test_zero_draft_accepts_nothing(self):
        """An all-zeros draft proposes token 0 forever; the target's
        greedy stream never contains 0 (asserted precondition), so the
        rate is exactly 0.0, each iteration commits exactly the ONE
        verified token — and the stream is still bit-exact, just not
        faster."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        dp0 = jax.tree_util.tree_map(jnp.zeros_like, params)
        prompts = _prompts()[:2]
        n = 13
        refs = [np.asarray(generate(model, params,
                                    jnp.asarray(p[None]), n)[0])
                for p in prompts]
        for p, r in zip(prompts, refs):
            assert not (r[len(p):] == 0).any()   # precondition
        eng = InferenceEngine(model, params,
                              _spec_cfg(model, dp0, n_slots=2))
        with eng:
            hs = [eng.submit(p, SamplingParams(max_new_tokens=n))
                  for p in prompts]
            outs = [np.asarray(h.result(timeout=120)) for h in hs]
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        st = eng.stats()["spec"]
        assert st["acceptance_rate"] == 0.0
        assert st["tokens_per_iteration"] == 1.0


# ---------------------------------------------------------------------------
# rollback edges
# ---------------------------------------------------------------------------


class TestRollbackEdges:
    def test_page_boundary_rejection_never_quantizes_partial(self):
        """Pool-level q8: acceptance that ends exactly at a page
        boundary quantizes THAT page (complete, from accepted tokens)
        and leaves the next page unallocated; a later commit that only
        starts the next page leaves it in the exact f32 tail with its
        quant scales untouched."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        pool = PagedSlotPool(model, 1, MAX_LEN, page_len=L, n_pages=8,
                             kv_dtype="q8")
        prompt = (np.arange(1, 7, dtype=np.int32) % 61)   # 6 tokens
        pool.admit(params, prompt, 0, BUCKETS)
        pid0 = pool.owned[0][0]
        ones = np.ones_like(np.asarray(pool.state[0].k.scales[pid0]))
        # page 0 incomplete: still tail-resident, scales untouched
        np.testing.assert_array_equal(
            np.asarray(pool.state[0].k.scales[pid0]), ones)
        toks = np.array([[2, 3, 4, 5]], np.int32)
        _, sk, sv = pool.spec_verify(params, toks)
        # accept 2 of 4: positions 6,7 — ends EXACTLY at the boundary,
        # drafts for positions 8,9 rejected
        pool.ensure_spec_capacity(0, 2)
        pool.spec_commit(sk, sv, np.array([2], np.int32))
        assert int(pool.lengths[0]) == 8
        # page 0 completed from accepted tokens → quantized now
        assert not np.array_equal(
            np.asarray(pool.state[0].k.scales[pid0]), ones)
        # the rejected suffix never demanded (or touched) page 1
        assert len(pool.owned[0]) == 1
        # next iteration: accept ONE token into a fresh page — it must
        # stay in the f32 tail, unquantized, until the page completes
        _, sk, sv = pool.spec_verify(params, toks)
        pool.ensure_spec_capacity(0, 1)
        pool.spec_commit(sk, sv, np.array([1], np.int32))
        assert int(pool.lengths[0]) == 9
        pid1 = pool.owned[0][1]
        np.testing.assert_array_equal(
            np.asarray(pool.state[0].k.scales[pid1]), ones)
        assert np.abs(np.asarray(pool.state[0].k.tail[0, :, 0, :])).sum() > 0

    def test_draft_len_longer_than_remaining(self):
        """k = 6 against max_new = 3: acceptance is capped by the
        remaining budget every iteration, the stream is exact, and the
        request retires at exactly max_new tokens."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        dm = _draft()
        dp = dm.init(jax.random.PRNGKey(1))
        prompt = _prompts()[0]
        n = 3
        ref = np.asarray(generate(model, params,
                                  jnp.asarray(prompt[None]), n)[0])
        eng = InferenceEngine(model, params, EngineConfig(
            n_slots=2, max_len=MAX_LEN, buckets=BUCKETS,
            spec_decode=True, draft_model=dm, draft_params=dp,
            draft_len=6))
        with eng:
            out = np.asarray(
                eng.submit(prompt, SamplingParams(max_new_tokens=n))
                .result(timeout=120))
        np.testing.assert_array_equal(out, ref)
        assert len(out) == n
        assert eng.stats()["spec"]["verify_compiles"] == {7: 1}


# ---------------------------------------------------------------------------
# the draft model's page pool
# ---------------------------------------------------------------------------


def _draft_rows(spec, slot):
    """The draft's resident keys of ``slot``, one (Hkv, positions, Dh)
    array a layer, in position order."""
    table = jnp.asarray(spec.pool.tables[slot])
    return [np.asarray(st.k.rows(table))[0] for st in spec.pool.state]


class TestDraftPool:
    def _state(self, max_len=32, page_len=4, k=3, n_slots=2):
        dm = _draft()
        dp = dm.init(jax.random.PRNGKey(1))
        return SpecState(SpecConfig(dm, dp, draft_len=k), n_slots, max_len,
                         page_len)

    def test_holds_every_slots_worst_case(self):
        """``draft_max = max_len + draft_len + 1`` positions a slot, in
        whole pages, for every slot at once: nothing the draft asks for
        can be refused."""
        spec = self._state(max_len=32, page_len=4, k=3, n_slots=2)
        pool = spec.pool
        assert type(pool) is PagedSlotPool
        assert pool.max_len == 36 and pool.pages_per_slot == 9
        assert pool.n_pages == 18 and not pool.prefix_share
        assert pool.kv_dtype == "f32" and pool.page_len == 4

    def test_rollback_rewinds_lengths_and_keeps_pages(self):
        """After a rollback the pool's length is the accepted one, the
        pages the rejected suffix lies in stay the slot's (the next
        propose writes them again), and that propose's drafts are those
        of a draft that was given the accepted stream as its prompt."""
        spec = self._state()
        prompt = _prompts()[1][:6]
        spec.admit(prompt, 1, (8, 16))
        assert spec.len[1] == spec.pool.lengths[1] == 6
        assert len(spec.pool.owned[1]) == 2 and spec.active[1]
        first = spec.propose([1], np.array([5], np.int32))
        assert spec.pool.lengths[1] == 10        # k + 1 steps ahead
        owned = list(spec.pool.owned[1])
        assert len(owned) == 3                   # positions 6 .. 9
        free = spec.pool.pool.free_pages
        spec.rollback([1], np.array([2], np.int32))
        assert spec.len[1] == spec.pool.lengths[1] == 8
        assert spec.pool.owned[1] == owned
        assert spec.pool.pool.free_pages == free
        assert spec.pool.lengths[0] == 0
        again = spec.propose([1], np.array([9], np.int32))
        assert spec.pool.owned[1] == owned       # 8 .. 11: no new page
        fresh = self._state()
        fresh.admit(np.concatenate([prompt, [5], first[0, :1]])
                    .astype(np.int32), 0, (8, 16))
        np.testing.assert_array_equal(
            again, fresh.propose([0], np.array([9], np.int32)))

    def test_a_propose_from_the_last_position_touches_no_accepted_key(self):
        """A row two positions short of ``max_len`` (the furthest a row
        that still has a token to emit can be): the propose's ``k + 1``
        keys land past ``max_len`` in room of the draft's own, not
        clamped into the row's last page over keys it has accepted."""
        spec = self._state(max_len=16, page_len=4, k=3, n_slots=1)
        spec.admit((np.arange(14, dtype=np.int32) * 7) % 61, 0, (8, 16))
        before = _draft_rows(spec, 0)
        spec.propose([0], np.array([3], np.int32))
        assert spec.pool.lengths[0] == 18 and len(spec.pool.owned[0]) == 5
        after = _draft_rows(spec, 0)
        for b, a in zip(before, after):
            np.testing.assert_array_equal(a[:, :14], b[:, :14])
            assert np.abs(a[:, 14:18]).sum(axis=(0, 2)).all()  # four keys

    def test_a_row_that_fills_its_slot_speculates_to_the_end(self):
        """Engine level, a self-draft: prompt + max_new == max_len and
        the last propose starts two positions short of it (10 + 4 * 5);
        every proposal is accepted but the last iteration's, which the
        one token left caps."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        prompt, n = _prompts()[1][:10], 22       # 10 + 22 == 32
        ref = np.asarray(generate(model, params, jnp.asarray(prompt[None]),
                                  n)[0])
        eng = InferenceEngine(model, params, _spec_cfg(
            model, params, n_slots=1, max_len=32, page_len=4))
        starts, propose = [], eng._spec.propose

        def spied(slots, cur):
            starts.append(int(eng._spec.len[slots[0]]))
            return propose(slots, cur)
        eng._spec.propose = spied
        with eng:
            out = eng.submit(prompt, SamplingParams(max_new_tokens=n)) \
                .result(timeout=120)
        np.testing.assert_array_equal(out, ref)
        assert starts == [10, 14, 18, 22, 26, 30]
        st = eng.stats()["spec"]
        assert (st["proposed"], st["accepted"]) == (18, 15)

    def test_a_prompt_of_several_chunks_speculates(self):
        """A prompt longer than the largest bucket is admitted to the
        draft in chunks, as to the target: it speculates, and its stream
        is ``generate()``'s."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        dm = _draft()
        dp = dm.init(jax.random.PRNGKey(1))
        prompt = (np.arange(3, 24, dtype=np.int32) * 5) % 61    # 21 > 8
        n = 10
        ref = np.asarray(generate(model, params, jnp.asarray(prompt[None]),
                                  n)[0])
        eng = InferenceEngine(model, params, _spec_cfg(
            dm, dp, n_slots=2, buckets=(8,), page_len=4))
        speculating = []
        with eng:
            h = eng.submit(prompt, SamplingParams(max_new_tokens=n),
                           on_token=lambda t, i: speculating.append(
                               bool(eng._spec.active.any())))
            out = h.result(timeout=120)
        np.testing.assert_array_equal(out, ref)
        st = eng.stats()
        assert all(speculating) and st["spec"]["proposed"] > 0
        assert h.metrics["spec_proposed"] == st["spec"]["proposed"]
        assert st["prefill_chunks"] == 3         # 8 + 8 + 5, the target's
        assert eng._spec.pool.compiles.prefill == {8: 1}

    @pytest.mark.parametrize("how", ["retire", "failure", "crash"])
    def test_every_exit_gives_the_drafts_pages_back(self, how):
        """Retirement, a typed failure (an injected verify fault) and
        the crash drain each release the draft's slot: its free pages
        are back to ``n_pages``, no slot active, every length 0."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        dm = _draft()
        dp = dm.init(jax.random.PRNGKey(1))
        eng = InferenceEngine(model, params, _spec_cfg(
            dm, dp, n_slots=2, page_len=4))
        draft = eng._spec.pool
        held = []

        def on_token(tok, i):
            if i != 2:
                return
            held.append(draft.n_pages - draft.pool.free_pages)
            if how == "failure":
                faults.install("flaky@op=spec_verify,count=1")
            elif how == "crash":
                eng.crash(RuntimeError("killed"), wait=False)
        hs = [eng.submit(p, SamplingParams(max_new_tokens=12),
                         on_token=on_token if i == 0 else None)
              for i, p in enumerate(_prompts()[:2])]
        eng.start()
        try:
            if how == "retire":
                for h in hs:
                    assert len(h.result(timeout=120)) == 12
            else:
                for h in hs:
                    with pytest.raises(SpecDecodeError if how == "failure"
                                       else EngineStopped):
                        h.result(timeout=120)
        finally:
            eng.shutdown()
        assert held and held[0] > 0              # it did hold pages
        assert draft.pool.free_pages == draft.n_pages
        assert not eng._spec.active.any() and not eng._spec.len.any()
        assert not draft.lengths.any() and not any(draft.owned)
        assert eng.pool.pool.live_pages() == 0   # and the target's


# ---------------------------------------------------------------------------
# chaos: failure containment
# ---------------------------------------------------------------------------


class TestChaos:
    def test_flaky_verify_fails_only_the_victim(self):
        """``flaky@op=spec_verify`` fails the speculating request as a
        typed ``SpecDecodeError`` (stage/request/iteration attributed)
        while the co-resident SAMPLED stream completes bit-identical
        to its standalone reference."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        dm = _draft()
        dp = dm.init(jax.random.PRNGKey(1))
        sp_s = SamplingParams(max_new_tokens=12, temperature=0.7,
                              top_k=8)
        key = jax.random.PRNGKey(9)
        prompt_a = _prompts()[0]
        prompt_b = _prompts()[1]
        ref_b = _standalone(model, params, prompt_b, sp_s, key)
        eng = InferenceEngine(model, params, _spec_cfg(dm, dp,
                                                       n_slots=2))
        eng.start()
        try:
            # warm every compile so the fault lands mid-steady-state
            eng.submit(prompt_a, SamplingParams(max_new_tokens=6)) \
                .result(timeout=120)
            eng.submit(prompt_a, SamplingParams(max_new_tokens=2,
                                                temperature=0.7,
                                                top_k=8)) \
                .result(timeout=120)
            faults.install("flaky@op=spec_verify,count=1")
            ha = eng.submit(prompt_a,
                            SamplingParams(max_new_tokens=12))
            hb = eng.submit(prompt_b, sp_s, rng=key)
            out_b = np.asarray(hb.result(timeout=120))
            with pytest.raises(SpecDecodeError) as ei:
                ha.result(timeout=120)
            assert ei.value.stage == "verify"
            assert ei.value.request_id == ha.request_id
            assert ei.value.iteration is not None
            np.testing.assert_array_equal(out_b, ref_b)
        finally:
            eng.shutdown()

    def test_delay_verify_trips_victim_deadline(self):
        """A stalled verify (``delay@op=spec_verify``) is charged to
        the speculating victim's own deadline — typed
        ``RequestDeadlineExceeded`` at the next sweep, stage
        ``running``."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        dm = _draft()
        dp = dm.init(jax.random.PRNGKey(1))
        prompt = _prompts()[0]
        eng = InferenceEngine(model, params, _spec_cfg(dm, dp,
                                                       n_slots=2))
        eng.start()
        try:
            eng.submit(prompt, SamplingParams(max_new_tokens=6)) \
                .result(timeout=120)   # warm all spec compiles
            faults.install("delay@op=spec_verify,ms=600")
            h = eng.submit(prompt, SamplingParams(max_new_tokens=40,
                                                  deadline_ms=300))
            with pytest.raises(RequestDeadlineExceeded) as ei:
                h.result(timeout=120)
            assert ei.value.stage == "running"
            assert ei.value.request_id == h.request_id
        finally:
            eng.shutdown()


# ---------------------------------------------------------------------------
# per-tenant quota
# ---------------------------------------------------------------------------


class TestTenantQuota:
    def test_quota_rejects_then_releases(self, monkeypatch):
        monkeypatch.setenv("DPX_SERVE_TENANT_MAX_INFLIGHT", "1")
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        eng = InferenceEngine(model, params,
                              EngineConfig(n_slots=2, max_len=MAX_LEN,
                                           buckets=BUCKETS))
        prompt = _prompts()[0]
        eng.start()
        try:
            h1 = eng.submit(prompt, SamplingParams(max_new_tokens=24),
                            tenant="t0")
            with pytest.raises(AdmissionRejected) as ei:
                eng.submit(prompt, SamplingParams(max_new_tokens=4),
                           tenant="t0")
            assert ei.value.reason == "tenant_quota"
            assert ei.value.tenant == "t0"
            # a DIFFERENT tenant is not throttled by t0's quota
            h2 = eng.submit(prompt, SamplingParams(max_new_tokens=4),
                            tenant="t1")
            h1.result(timeout=120)
            h2.result(timeout=120)
            # the credit came back at retirement
            h3 = eng.submit(prompt, SamplingParams(max_new_tokens=4),
                            tenant="t0")
            assert h3.result(timeout=120).shape == (4,)
            assert h3.metrics["tenant"] == "t0"
        finally:
            eng.shutdown()


# ---------------------------------------------------------------------------
# construction-time guard rails
# ---------------------------------------------------------------------------


class TestConstruction:
    def test_spec_without_draft_raises(self):
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="draft_model"):
            InferenceEngine(model, params,
                            EngineConfig(spec_decode=True))
        with pytest.raises(ValueError, match="draft_model"):
            DisaggEngine(model, params,
                         DisaggConfig(spec_decode=True))

    def test_draft_len_must_be_positive(self):
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="draft_len"):
            SpecState(SpecConfig(draft_model=model,
                                 draft_params=params, draft_len=0),
                      2, MAX_LEN, L)
