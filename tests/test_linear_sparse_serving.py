"""Linear-attention layers that keep one state a slot beside
sparse-attention layers that choose their pages inside the step
(docs/serving.md "A state a slot, and chosen pages"; PERF.md, Findings,
PR 45), at a tiny size in float32 on the CPU, against the independent
reference ``chipbench/reference/minicpm_sala.py``.

The model's full forward against the reference on both sides of
``dense_len``; the chunk form of the recurrence against the recurrence;
the pool: a prompt prefilled in chunks of uneven sizes and then decoded,
logits at every served position against the reference's full forward, in
a store poisoned with NaN first (dead pages, a state and compressed keys
another request left); a decode step and a prompt's chunk reading the
CHOSEN pages alone; the engine's streams, slots reused; the counters and
the scopes; the two faults failing the same comparisons; and the refusals
by name."""

import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "chipbench"))

import tiny_minicpm_sala                                      # noqa: E402
from chipbench import weights as W                            # noqa: E402
from chipbench.adapters import minicpm_sala as adapter        # noqa: E402
from chipbench.kinds import serve_mixers                      # noqa: E402
from chipbench.reference import minicpm_sala as reference     # noqa: E402
from distributed_pytorch_tpu import models                    # noqa: E402
from distributed_pytorch_tpu.models.generate import (         # noqa: E402
    MixerStoresUnsupported, make_generate_fn)
from distributed_pytorch_tpu.nn import linear_attention, paged  # noqa: E402
from distributed_pytorch_tpu.nn.paged import (DecodeCtx,      # noqa: E402
                                              PrefillCtx, SelectedPages,
                                              StatePages)
from distributed_pytorch_tpu.nn.sparse_attention import (     # noqa: E402
    Selection, block_scores, choose_blocks, choose_scored,
    chunk_block_scores, window_probs)
from distributed_pytorch_tpu.serve import (EngineConfig,      # noqa: E402
                                           InferenceEngine, SamplingParams)
from distributed_pytorch_tpu.serve.disagg import (            # noqa: E402
    DisaggConfig, DisaggEngine)
from distributed_pytorch_tpu.serve.pages import PagedSlotPool  # noqa: E402

CFG = tiny_minicpm_sala.SALA
SEED = 2 ** 31 + 4545
DENSE_LEN = CFG["sparse_config"]["dense_len"]                 # 24
#: float32 at ``highest`` on both sides, logits of order 1: what differs is
#: the order of the sums (the chunk form, the online softmax), as for the
#: other families
TOL = 5e-5
ENGINE = dict(paged=True, n_slots=3, max_len=72, page_len=4, buckets=(8, 16),
              prefix_share=False)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def lm():
    w = W.make(SEED, CFG, jnp.float32)
    model = models.TransformerLM(**adapter.model_kwargs(CFG, max_len=72))
    return model, adapter.to_program(w, CFG), w


def reference_logits(w, ids, n_prompt):
    """One request, every row real: ids (S,), of which the first
    ``n_prompt`` are the prompt (it decides dense or sparse)."""
    g, n = w["globals"], len(ids)
    x = reference.embed(g, jnp.asarray(ids), CFG)
    dense = jnp.asarray(reference.is_dense(CFG, n_prompt))
    walked = iter(w["layers"])
    n_sparse = 0
    for mixer in reference.mixers(CFG):
        if mixer == reference.SPARSE:
            x = reference.sparse_layer(g, f"s{n_sparse}_", x, n, dense, CFG)
            n_sparse += 1
        else:
            x, _ = reference.linear_layer(next(walked), x, n, CFG)
    return np.asarray(reference.head(g, x, CFG))


def _ids(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, 211, n) \
        .astype(np.int32)


@pytest.mark.parametrize("length", [12, 40, 70])
def test_full_forward_agrees_with_the_reference(lm, length):
    """``TransformerLM.apply`` of a stack with both mixers, a sequence
    shorter than ``dense_len`` (24: dense) and two longer ones (every
    query selects)."""
    model, params, w = lm
    ids = _ids(length)
    ref = reference_logits(w, ids, length)
    got = np.asarray(model.apply(params, jnp.asarray(ids[None])))[0]
    assert 0.5 < ref.std() < 2.0            # logits of order 1, as assumed
    np.testing.assert_allclose(got, ref, atol=TOL, rtol=0)


def test_chunk_form_equals_the_recurrence():
    """The reference's chunk form, two chunks carrying the state, and the
    program's scan (steps of 16, 37 real rows of 48) against the
    recurrence position by position: outputs and the state left."""
    h, s, d = 8, 48, 8
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (h, s, d))
               for i in range(3))
    lam = reference.decays(dict(lightning_nh=h))
    want, state = reference.recurrence(q, k, v, lam)
    o1, s1 = reference.chunk(q[:, :32], k[:, :32], v[:, :32], lam,
                             jnp.zeros((h, d, d)))
    o2, s2 = reference.chunk(q[:, 32:], k[:, 32:], v[:, 32:], lam, s1)
    np.testing.assert_allclose(np.concatenate([o1, o2], 1), want, atol=1e-5)
    np.testing.assert_allclose(s2, state, atol=1e-5)
    # the program's: only the first 37 rows count for the state
    n = 37
    want, state = reference.recurrence(q[:, :n], k[:, :n], v[:, :n], lam)
    got, left = linear_attention.scan_sequence(
        q, k, v, jnp.zeros((h, d, d)), jnp.asarray(n),
        linear_attention.head_decays(h), 1.0 / math.sqrt(d), chunk=16)
    np.testing.assert_allclose(got[:, :n], want, atol=1e-5)
    np.testing.assert_allclose(left, state, atol=1e-5)
    np.testing.assert_allclose(np.exp(linear_attention.head_decays(h)), lam,
                               rtol=1e-6)


def poisoned(pool):
    """Every page, every slot's state and compressed keys NaN, and every
    slot marked dense: what a request finds must be its own."""
    nan = lambda a: jnp.full(a.shape, jnp.nan, a.dtype)
    out = []
    for st in pool.state:
        if isinstance(st, StatePages):
            out.append(StatePages(nan(st.s)))
        else:
            kv = st.kv._replace(k=st.kv.k._replace(pages=nan(st.kv.k.pages)),
                                v=st.kv.v._replace(pages=nan(st.kv.v.pages)))
            out.append(SelectedPages(kv, nan(st.ck), ~st.dense))
    pool.state = out
    return pool


def served_logits(pool, params, ids, n_prompt, slot):
    """Prefill ``ids[:n_prompt]`` into ``slot`` (chunks of 16, the last a
    partial one), then decode the rest: the logits at every served
    position."""
    got = [np.asarray(pool.admit(params, ids[:n_prompt], slot,
                                 (8, 16))[0][0])]
    active = np.arange(pool.n_slots) == slot
    for t in ids[n_prompt:-1]:
        pool.ensure_decode_capacity(slot)
        _, logits = pool.decode(params, np.full(pool.n_slots, t, np.int32),
                                active)
        got.append(np.asarray(logits[slot]))
    return np.stack(got)


def new_pool(model, n_slots=2):
    return PagedSlotPool(model, n_slots, 72, page_len=4, n_pages=40,
                         prefix_share=False)


@pytest.mark.parametrize("n_prompt,slot", [
    (5, 0),      # one partial chunk, dense
    (23, 1),     # the longest dense prompt: 16 + 7
    (24, 0),     # dense_len itself: every position selects
    (37, 1),     # 16 + 16 + 5
    (60, 0),     # four chunks, the last of 12
])
def test_pool_prefill_then_decode_agrees_with_the_reference(lm, n_prompt,
                                                            slot):
    """Logits, not tokens, at every served position: the prompt's last and
    12 decode steps, through both kinds of store, in a pool whose every
    array held NaN before the request came."""
    model, params, w = lm
    ids = _ids(n_prompt + 12, seed=1)
    ref = reference_logits(w, ids, n_prompt)
    pool = poisoned(new_pool(model))
    got = served_logits(pool, params, ids, n_prompt, slot)
    np.testing.assert_allclose(got, ref[n_prompt - 1:-1], atol=TOL, rtol=0)
    stats, pages = pool.mixer_stats(), pool.page_stats()
    assert pages["pages_in_use"] == -(-(len(ids) - 1) // 4)
    assert pages["context_tokens_max"] == len(ids) - 1
    assert stats["slots_state_reset"] == 1
    assert stats["state_resident_bytes"] == 2 * 2 * 8 * 8 * 8 * 4
    assert stats["compressed_keys_resident_bytes"] == 2 * 2 * 2 * 36 * 8 * 4
    assert pages["kv_resident_bytes_global"] == 2 * 2 * 40 * 2 * 4 * 8 * 4
    # 12 steps x 2 sparse layers x 2 KV heads: a dense request reads all it
    # has, a selecting one at most 1 + 3 + 2 blocks of them
    chosen, resident = (stats["sparse_blocks_chosen"],
                        stats["sparse_blocks_resident"])
    if n_prompt < DENSE_LEN:
        assert chosen == resident > 0
    else:
        assert 0 < chosen <= 12 * 4 * 6 and chosen < resident


def test_a_reused_slot_starts_from_a_zero_state(lm):
    """A second request in the slot the first has left: its state starts
    from nothing, and the first's compressed keys and dense mark are not
    read."""
    model, params, w = lm
    pool = new_pool(model, n_slots=1)
    first = _ids(9 + 4, seed=2)
    served_logits(pool, params, first, 9, 0)        # a dense one
    pool.release(0)
    ids = _ids(41 + 6, seed=3)
    got = served_logits(pool, params, ids, 41, 0)
    np.testing.assert_allclose(got, reference_logits(w, ids, 41)[40:-1],
                               atol=TOL, rtol=0)
    assert pool.mixer_stats()["slots_state_reset"] == 2


SEL = Selection(kernel=4, stride=2, block=4, topk=2, init_blocks=1, window=8,
                dense_len=24)


def _store(n_pages=40, n_slots=3, hkv=2, dh=8, windows=24, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    st = SelectedPages.zeros((hkv, 4, dh), n_pages, n_slots, windows,
                             jnp.float32)
    kv = st.kv._replace(
        k=st.kv.k._replace(pages=jax.random.normal(ks[0], st.kv.k.pages.shape)),
        v=st.kv.v._replace(pages=jax.random.normal(ks[1], st.kv.v.pages.shape)))
    return SelectedPages(kv, jax.random.normal(ks[2], st.ck.shape), st.dense)


def _dense_softmax(q, k, v, seen, scale):
    """q (g, d), k, v (n, d), seen (n,) -> (g, d), in numpy float64."""
    s = np.where(seen[None], q @ k.T * scale, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ v


def test_a_decode_step_reads_the_chosen_pages_alone():
    """Rows of three lengths in one step: every (row, KV head) chooses its
    blocks; its set holds the first block and those of its last 8
    positions, and no more than 2 others; every page it did NOT choose is
    NaN, and the result is the softmax over the chosen positions."""
    b, hkv, g, dh, n_blocks = 3, 2, 4, 8, 12
    st = _store()
    tables = jnp.asarray(np.random.default_rng(0).permutation(40)[:36]
                         .reshape(b, n_blocks).astype(np.int32))
    idx = jnp.asarray([47, 30, 13], jnp.int32)
    hq = jax.random.normal(jax.random.PRNGKey(9), (b, hkv * g, 1, dh))
    scale = 1.0 / math.sqrt(dh)
    t = jnp.broadcast_to(idx[:, None], (b, hkv))
    chosen, exists = choose_blocks(
        window_probs(hq.reshape(b, hkv, g, dh), st.ck, t, SEL, scale), t,
        SEL, n_blocks)
    chosen, exists = np.asarray(chosen), np.asarray(exists)
    for r in range(b):
        last = int(idx[r]) // 4
        first_of_window = max(0, (int(idx[r]) - 7) // 4)
        for n in range(hkv):
            assert chosen[r, n, 0] and chosen[r, n, first_of_window:last + 1] \
                .all() and not chosen[r, n, last + 1:].any()
            assert chosen[r, n].sum() <= 1 + 3 + 2
    assert (chosen.sum(-1) < exists.sum(-1))[:2].all()    # a real choice
    k, v = np.array(st.kv.k.pages), np.array(st.kv.v.pages)
    keep = np.zeros((40, hkv), bool)
    for r in range(b):
        for n in range(hkv):
            keep[np.asarray(tables)[r][chosen[r, n]], n] = True
    k[~keep], v[~keep] = np.nan, np.nan
    poisoned_st = st._replace(kv=st.kv._replace(
        k=st.kv.k._replace(pages=jnp.asarray(k)),
        v=st.kv.v._replace(pages=jnp.asarray(v))))
    at = lambda pages: jnp.stack([
        pages[tables[r, idx[r] // 4], :, idx[r] % 4] for r in range(b)])[
            :, :, None, :]
    stats = []
    ctx = DecodeCtx(tables=tables, idx=idx, dest=None, wo=None,
                    active=jnp.ones(b, bool), pos_mask=None, write_mask=None,
                    page_len=4, sel_stats=stats)
    o = np.asarray(poisoned_st.attend(ctx, hq, at(poisoned_st.kv.k.pages),
                                      at(poisoned_st.kv.v.pages), scale, SEL))
    assert np.isfinite(o).all()
    assert [int(x) for x in stats[0]] == [chosen.sum(), exists.sum()]
    for r in range(b):
        pos = np.arange(n_blocks * 4)
        for n in range(hkv):
            rows = lambda pages: pages[np.asarray(tables)[r], n].reshape(-1, dh)
            seen = chosen[r, n][pos // 4] & (pos <= int(idx[r]))
            want = _dense_softmax(
                np.asarray(hq[r, n * g:(n + 1) * g, 0], np.float64),
                np.where(seen[:, None], rows(k), 0.0),
                np.where(seen[:, None], rows(v), 0.0), seen, scale)
            np.testing.assert_allclose(o[r, n * g:(n + 1) * g, 0], want,
                                       atol=1e-5)


def test_a_chunk_reads_what_its_queries_chose_and_no_more():
    """A prompt's chunk of 8 queries at offset 32: a page that no query of
    the chunk chose (a KV head) is NaN, and every query's result is the
    softmax over ITS chosen positions."""
    hkv, g, dh, n_blocks, s, offset = 2, 4, 8, 12, 8, 32
    st = _store(seed=1)
    row = jnp.asarray(np.random.default_rng(1).permutation(40)[:n_blocks]
                      .astype(np.int32))
    hq = jax.random.normal(jax.random.PRNGKey(5), (1, hkv * g, s, dh))
    positions = offset + jnp.arange(s)
    scale = 1.0 / math.sqrt(dh)
    t = jnp.broadcast_to(positions[None, :], (hkv, s))
    q = jnp.moveaxis(hq[0].reshape(hkv, g, s, dh), 1, 2)
    chosen = np.asarray(choose_blocks(
        window_probs(q, st.ck[1][:, None], t, SEL, scale), t, SEL,
        n_blocks)[0])                                    # (Hkv, S, blocks)
    assert (chosen.sum(-1) <= 6).all() and chosen[:, :, 0].all()
    k, v = np.array(st.kv.k.pages), np.array(st.kv.v.pages)
    keep = np.zeros((40, hkv), bool)
    for n in range(hkv):
        keep[np.asarray(row)[chosen[n].any(0)], n] = True
    assert not keep[np.asarray(row)].all()               # something to poison
    k[~keep], v[~keep] = np.nan, np.nan
    poisoned_st = st._replace(kv=st.kv._replace(
        k=st.kv.k._replace(pages=jnp.asarray(k)),
        v=st.kv.v._replace(pages=jnp.asarray(v))))
    ctx = PrefillCtx(table_row=row, positions=positions,
                     offset=jnp.asarray(offset), true_len=jnp.asarray(s),
                     slot=jnp.asarray(1), dest=None, dest_off=None, mask=None,
                     row_mask=None, width=n_blocks * 4,
                     dense=jnp.asarray(False))
    o = np.asarray(poisoned_st.attend_tail(ctx, hq, scale, SEL, 8))
    assert np.isfinite(o).all()
    pos = np.arange(n_blocks * 4)
    for n in range(hkv):
        rows = lambda pages: pages[np.asarray(row), n].reshape(-1, dh)
        for i in range(s):
            seen = chosen[n, i][pos // 4] & (pos <= offset + i)
            want = _dense_softmax(
                np.asarray(hq[0, n * g:(n + 1) * g, i], np.float64),
                np.where(seen[:, None], rows(k), 0.0),
                np.where(seen[:, None], rows(v), 0.0), seen, scale)
            np.testing.assert_allclose(o[0, n * g:(n + 1) * g, i], want,
                                       atol=1e-5)


@pytest.mark.parametrize("offset,true_len,dense", [
    (0, 8, False),      # nothing closed before t = 3
    (8, 8, False),      # inside the first trip of 8 windows
    (24, 8, False),     # two trips: window 7 starts in block 3, ends in 4
    (88, 8, False),     # the slot's last pages, every trip
    (40, 5, False),     # a partial chunk: three pad rows
    (24, 8, True),      # a prompt under dense_len chooses every block
])
def test_a_chunk_walks_the_closed_windows_to_the_plain_choice(
        monkeypatch, offset, true_len, dense):
    """The chunk's walk (8 windows = 4 blocks a trip, as many trips as the
    prompt so far has closed windows for) against the plain form, every
    window of the slot scored at once: the same block scores and exactly
    the same sets for the real rows; and through ``attend_tail``, every
    query's result the softmax over ITS chosen positions, with every
    compressed key past the closed count, every other slot's and every
    position past the prompt so far NaN."""
    hkv, g, dh, n_blocks, s, wb = 2, 4, 8, 24, 8, 8
    monkeypatch.setattr(paged, "WINDOW_BLOCK", wb)
    st = _store(windows=2 * n_blocks, seed=2)
    row = np.random.default_rng(2).permutation(40)[:n_blocks].astype(np.int32)
    hq = jax.random.normal(jax.random.PRNGKey(6), (1, hkv * g, s, dh))
    positions = offset + jnp.arange(s)
    scale = 1.0 / math.sqrt(dh)
    t = jnp.broadcast_to(positions[None, :], (hkv, s))
    q = hq[0].reshape(hkv, g, s, dh)
    p = window_probs(jnp.moveaxis(q, 1, 2), st.ck[1][:, None], t, SEL, scale)
    want = np.asarray(block_scores(p, SEL, n_blocks))
    chosen = np.asarray(choose_blocks(p, t, SEL, n_blocks)[0])
    n_closed = (offset + true_len - SEL.kernel) // SEL.stride + 1
    # what the request does not own: NaN
    ck = np.full(st.ck.shape, np.nan, np.float32)
    ck[1, :, :max(n_closed, 0)] = np.asarray(st.ck)[1, :, :max(n_closed, 0)]
    k, v = np.array(st.kv.k.pages), np.array(st.kv.v.pages)
    for pages in (k, v):
        held = np.zeros(pages.shape[:1] + pages.shape[2:3], bool)
        pos = np.arange(offset + true_len)
        held[row[pos // 4], pos % 4] = True
        pages[~held[:, None, :, None] & np.ones(pages.shape, bool)] = np.nan
    got = np.asarray(chunk_block_scores(
        q, jnp.asarray(ck[1]), positions, jnp.asarray(n_closed), SEL, scale,
        n_blocks, wb))
    assert np.isfinite(got).all()                       # the pad rows too
    np.testing.assert_allclose(got[:, :true_len], want[:, :true_len],
                               atol=1e-6, rtol=1e-5)
    sets = np.asarray(choose_scored(jnp.asarray(got), t, SEL)[0])
    assert (sets[:, :true_len] == chosen[:, :true_len]).all()
    if offset == 24:
        # block 4 is the second trip's first: for some query its score is
        # window 7's, which the first trip handed on
        pn = np.asarray(p)
        carried = (pn[..., 7] > np.maximum(pn[..., 8], pn[..., 9])) \
            & (pn[..., 7] > 0)
        assert carried.any() and (got[..., 4][carried]
                                  == pytest.approx(pn[..., 7][carried]))
    poisoned_st = SelectedPages(
        st.kv._replace(k=st.kv.k._replace(pages=jnp.asarray(k)),
                       v=st.kv.v._replace(pages=jnp.asarray(v))),
        jnp.asarray(ck), st.dense)
    ctx = PrefillCtx(table_row=jnp.asarray(row), positions=positions,
                     offset=jnp.asarray(offset),
                     true_len=jnp.asarray(true_len), slot=jnp.asarray(1),
                     dest=None, dest_off=None, mask=None, row_mask=None,
                     width=n_blocks * 4, dense=jnp.asarray(dense))
    o = np.asarray(poisoned_st.attend_tail(ctx, hq, scale, SEL, 8))
    assert np.isfinite(o).all()
    pos = np.arange(n_blocks * 4)
    clean = lambda pages, n: np.nan_to_num(pages[row, n].reshape(-1, dh))
    for n in range(hkv):
        for i in range(true_len):
            seen = (chosen[n, i][pos // 4] | dense) & (pos <= offset + i)
            want_o = _dense_softmax(
                np.asarray(hq[0, n * g:(n + 1) * g, i], np.float64),
                clean(k, n), clean(v, n), seen, scale)
            np.testing.assert_allclose(o[0, n * g:(n + 1) * g, i], want_o,
                                       atol=1e-5)


def test_engine_streams_agree_with_the_reference(lm):
    """Seven greedy requests through three slots (every slot reused),
    prompts on both sides of ``dense_len``, prefill chunks between decode
    passes, one decode pass in flight: every served token is the
    reference's best at its position (its reference logit within TOL of
    the best), and the counters tell the stores apart."""
    model, params, w = lm
    prompts = [_ids(n, seed=4) for n in (5, 37, 23, 60, 24, 9, 50)]
    with InferenceEngine(model, params, EngineConfig(**ENGINE)) as eng:
        handles = [eng.submit(p, SamplingParams(max_new_tokens=6 + i % 3))
                   for i, p in enumerate(prompts)]
        outs = [np.asarray(h.result(timeout=600)) for h in handles]
        stats = eng.stats()
    for p, out in zip(prompts, outs):
        ref = reference_logits(w, np.concatenate([p, out]), len(p))
        at = len(p) - 1 + np.arange(len(out))
        gap = ref[at].max(-1) - ref[at, out]
        assert gap.max() < TOL, (len(p), gap)
    assert stats["state_layers"] == 2 and stats["sparse_layers"] == 2
    assert stats["slots_state_reset"] == 7
    assert 0 < stats["sparse_blocks_chosen"] < stats["sparse_blocks_resident"]
    assert stats["state_resident_bytes"] == 2 * 3 * 8 * 8 * 8 * 4
    assert stats["pages"]["kv_resident_bytes_window"] == 0
    assert stats["pages"]["prefix_hit_pages"] == 0
    assert stats["decode_compiles"] == 1
    assert stats["decode_passes_ahead"] > 0


def test_the_engine_counts_the_windows_its_chunks_scored(lm):
    """A short and a long prompt, chunks of 16: every chunk of a sparse
    layer scored the windows that the prompt so far had closed (window j
    closes at position 2 j + 3), of the 36 the store keeps a slot."""
    model, params, _ = lm
    with InferenceEngine(model, params, EngineConfig(**ENGINE)) as eng:
        for n in (5, 50):
            eng.submit(_ids(n, seed=5), SamplingParams(max_new_tokens=2)) \
                .result(timeout=600)
        stats = eng.stats()
    ends = (5, 16, 32, 48, 50)                  # where each chunk stopped
    assert stats["sparse_prefill_windows_scored"] \
        == 2 * sum((n - 4) // 2 + 1 for n in ends) == 140
    assert stats["sparse_prefill_windows_kept"] == len(ends) * 2 * 36


@pytest.mark.parametrize("fault,n_prompt", [
    ("dense_attention", 37),    # a prompt that has to select
    ("bfloat16_state", 9),      # any prompt: the state is every request's
])
def test_the_two_faults_fail_the_comparison(lm, fault, n_prompt):
    """A program that attends densely where it should select, and one
    that keeps a linear layer's state in bfloat16
    (``chipbench/kinds/serve_mixers.py``), through the comparison of
    ``test_pool_prefill_then_decode_agrees_with_the_reference``: twenty
    and more times its tolerance."""
    model, params, w = lm
    ids = _ids(n_prompt + 12, seed=1)
    ref = reference_logits(w, ids, n_prompt)
    with serve_mixers.FAULTS[fault]():
        got = served_logits(new_pool(model), params, ids, n_prompt, 0)
    assert np.abs(got - ref[n_prompt - 1:-1]).max() > 20 * TOL


def test_the_programs_name_the_new_mechanisms(lm):
    """The scopes the benchmark's readers key on, in the decode program
    and in a prefill program."""
    model, params, _ = lm
    pool = new_pool(model)
    # every location's name; the body of a scan is a call of its own,
    # whose names start again at the scope inside it
    names = lambda lowered: "\n".join(re.findall(
        r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))
    decode = names(pool._decode_fn.lower(
        params, pool.state, None, jnp.array(pool.tables),
        jnp.array(pool.lengths), jnp.zeros(2, jnp.int32), jnp.ones(2, bool),
        pool.sel_counts))
    for want in ("blocks/decode_attention/linear_attention/state",
                 "blocks/decode_attention/sparse_attention/compress",
                 "blocks/decode_attention/sparse_attention/select",
                 "blocks/decode_attention/sparse_attention/attend",
                 "blocks/page_write"):
        assert want in decode, want
    pool.admit(params, _ids(20), 0, (8, 16))
    prefill = names(pool._admit_fns[16].lower(
        params, pool.state, jnp.array(pool.tables[0]),
        jnp.zeros((1, 16), jnp.int32), jnp.asarray(0), jnp.asarray(16),
        jnp.asarray(0), jnp.asarray(False)))
    for want in ("blocks/attn/core/linear_attention/state",
                 "blocks/attn/core/linear_attention/while", "\nintra/",
                 "blocks/attn/core/sparse_attention/compress",
                 "blocks/attn/core/sparse_attention/select",
                 "blocks/attn/core/sparse_attention/attend"):
        assert want in prefill, want


def test_a_chunk_program_walks_the_windows_and_forms_no_slot_of_them(lm):
    """Compile-only: the prefill program of a store that keeps 2048
    windows a slot (4096 positions) holds a loop under the selection's
    scope and no float32 value as large as one slot's windows for every
    query and head of a group (the plain form's scores were twice that, a
    KV head each); the decode program keeps the plain form: its scopes,
    and no loop under ``select``."""
    _, params, _ = lm
    model = models.TransformerLM(**adapter.model_kwargs(CFG, max_len=4096))
    pool = PagedSlotPool(model, 2, 4096, page_len=4, n_pages=40,
                         prefix_share=False)
    windows = pool.state[0].ck.shape[2]
    assert windows == 2048 == 4 * paged.WINDOW_BLOCK
    pool.admit(params, _ids(20), 0, (8, 16))
    lowered = pool._admit_fns[16].lower(
        params, pool.state, jnp.array(pool.tables[0]),
        jnp.zeros((1, 16), jnp.int32), jnp.asarray(0), jnp.asarray(16),
        jnp.asarray(0), jnp.asarray(False))
    text = lowered.as_text(debug_info=True)
    assert "blocks/attn/core/sparse_attention/select/while" in text
    sizes = [math.prod(int(d) for d in dims.split("x"))
             for dims in re.findall(r"tensor<([0-9x]+)xf32>", text)]
    g = CFG["num_attention_heads"] // CFG["num_key_value_heads"]
    assert 16 * g * paged.WINDOW_BLOCK <= max(sizes) < 16 * g * windows
    decode = pool._decode_fn.lower(
        params, pool.state, None, jnp.array(pool.tables),
        jnp.array(pool.lengths), jnp.zeros(2, jnp.int32), jnp.ones(2, bool),
        pool.sel_counts).as_text(debug_info=True)
    for scope in ("select", "attend", "compress"):
        assert f"blocks/decode_attention/sparse_attention/{scope}" in decode
    assert "sparse_attention/select/while" not in decode


def _refuses(what, make):
    with pytest.raises(MixerStoresUnsupported, match=what):
        make()


@pytest.mark.parametrize("what,build", [
    ("prefix sharing", lambda m, p: InferenceEngine(
        m, p, EngineConfig(**{**ENGINE, "prefix_share": True}))),
    ("quantized page pool", lambda m, p: InferenceEngine(
        m, p, EngineConfig(**ENGINE, kv_dtype="q8"))),
    ("speculative decoding", lambda m, p: InferenceEngine(
        m, p, EngineConfig(**ENGINE, spec_decode=True, draft_model=m,
                           draft_params=p))),
    # a configuration that says nothing of sharing takes the default
    ("prefix sharing", lambda m, p: InferenceEngine(
        m, p, EngineConfig(n_slots=2, max_len=72, page_len=4,
                           buckets=(8, 16)))),
    ("hand-off", lambda m, p: DisaggEngine(m, p, DisaggConfig())),
    ("generate", lambda m, p: make_generate_fn(m, 4)),
    ("hand-off", lambda m, p: new_pool(m).require("export")),
    ("hand-off", lambda m, p: new_pool(m).require("adopt")),
    ("snapshot", lambda m, p: new_pool(m).require("snapshot")),
    ("generation by blocks", lambda m, p: new_pool(m).require("block_step")),
])
def test_what_the_new_stores_cannot_do_is_refused_by_name(lm, what, build):
    model, params, _ = lm
    _refuses(what, lambda: build(model, params))


def test_the_model_refuses_what_its_mixers_cannot_be_built_with():
    kw = adapter.model_kwargs(CFG, max_len=72)
    for bad in (dict(layer_mixers=("linear",) * 3),
                dict(layer_mixers=("linear", "mha", "linear", "sparse")),
                dict(sparse=None), dict(mtp=1), dict(gen_block=4, mask_id=0),
                dict(hyper_connections=2), dict(pos="learned"),
                dict(layer_windows=(None,) * 4)):
        with pytest.raises(ValueError):
            models.TransformerLM(**{**kw, **bad})
    with pytest.raises(ValueError, match="a block has to be a page"):
        PagedSlotPool(models.TransformerLM(**kw), 2, 72, page_len=8,
                      n_pages=20, prefix_share=False)
    with pytest.raises(ValueError, match="describe the layers"):
        models.TransformerLM(vocab=97, dim=32, n_heads=4,
                             linear=dict(rope=False))
