"""One decode pass in flight (``InferenceEngine._decode_all``): the engine
dispatches pass k+1, whose tokens argument is pass k's output on the
device, before it reads pass k.

What must hold: every stream is ``generate()``'s token for token; a row
whose request finished (``eos_token``) or failed (deadline, page pool)
between the two programs is dropped where it is read, never emitted, and
the request that takes its slot and pages next streams correctly; what
the host knows ahead (``max_new_tokens``) it decides ahead, so such a
row is never dispatched and asks for no page; speculating rows are never
run ahead; and ``stats()``, ``shutdown()``, ``crash()``, the loop's own
exception path and an engine that goes idle each meet a program in
flight without a hang, a stranded future or a token emitted twice.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import models
from distributed_pytorch_tpu.models.generate import make_generate_fn
from distributed_pytorch_tpu.obs import trace as dpxtrace
from distributed_pytorch_tpu.runtime import faults
from distributed_pytorch_tpu.serve import (EngineConfig, EngineStopped,
                                           InferenceEngine,
                                           PagePoolExhausted,
                                           RequestDeadlineExceeded,
                                           SamplingParams)

MAX_LEN = 64
# pages of 4: a row claims a page every fourth token, each time with the
# pass before still unread
POOLS = [pytest.param(dict(page_len=8), id="paged"),
         pytest.param(dict(page_len=4), id="pages-of-4")]


@pytest.fixture(autouse=True)
def _clean_faults_and_spans():
    faults.reset()
    dpxtrace.reset()
    yield
    faults.reset()
    dpxtrace.reset()


def _lm(**kw):
    return models.TransformerLM(**{**dict(
        vocab=61, dim=32, n_layers=1, n_heads=4, n_kv_heads=2, pos="rope",
        max_seq=128), **kw})


@pytest.fixture(scope="module")
def lm():
    model = _lm()
    return model, model.init(jax.random.PRNGKey(0))


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 61, (n,)).astype(np.int32)


def _standalone(model, params, prompt, sp, key=None, max_len=MAX_LEN):
    fn = make_generate_fn(model, sp.max_new_tokens,
                          temperature=sp.temperature, top_k=sp.top_k,
                          top_p=sp.top_p, max_len=max_len)
    key = jax.random.PRNGKey(0) if key is None else key
    return np.asarray(jax.jit(fn)(params, jnp.asarray(prompt[None]), key))[0]


def _engine(model, params, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", MAX_LEN)
    return InferenceEngine(model, params, EngineConfig(**kw))


def _spy_dispatch(eng):
    """Every pass ``eng`` dispatches from here on, as ``(ahead, {slot:
    (request id, token index)})``."""
    seen, inner = [], eng._dispatch_pass

    def spied(rows, ahead):
        seen.append((ahead, {s: (r.request_id, i) for s, r, i in rows}))
        return inner(rows, ahead)
    eng._dispatch_pass = spied
    return seen


# -- (1) the streams ----------------------------------------------------------


@pytest.mark.parametrize("pool_kw", POOLS)
def test_staggered_mixed_streams_are_generates_and_run_ahead(pool_kw):
    """Requests of mixed prompt and answer lengths, greedy and of two
    sampling settings, arriving while others decode (two before the
    loop starts, the rest from the first one's token callbacks) through
    three slots: every stream is ``generate()``'s, and nearly every
    pass was dispatched before the pass before it was read."""
    model = _lm(n_layers=2)
    params = model.init(jax.random.PRNGKey(0))
    sps = [SamplingParams(max_new_tokens=24),
           SamplingParams(max_new_tokens=9, temperature=0.7, top_k=8),
           SamplingParams(max_new_tokens=14, temperature=0.9, top_p=0.9),
           SamplingParams(max_new_tokens=6),
           SamplingParams(max_new_tokens=17, temperature=0.7, top_k=8),
           SamplingParams(max_new_tokens=11)]
    prompts = [_prompt(n, 20 + i) for i, n in enumerate((5, 13, 3, 9, 7, 16))]
    keys = [jax.random.PRNGKey(100 + i) for i in range(len(sps))]
    eng = _engine(model, params, n_slots=3, **pool_kw)
    handles = {}

    def submit(i):
        handles[i] = eng.submit(prompts[i], sps[i], rng=keys[i],
                                on_token=arrive if i == 0 else None)

    def arrive(tok, at):
        for i in {3: (2,), 7: (3, 4), 12: (5,)}.get(at, ()):
            submit(i)
    submit(0)
    submit(1)
    with eng:
        handles[0].result(timeout=120)
        outs = [handles[i].result(timeout=120) for i in range(len(sps))]
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(
            out, _standalone(model, params, prompts[i], sps[i], keys[i]),
            err_msg=f"request {i}")
    st = eng.stats()
    assert st["decode_rows_dropped"] == 0
    assert st["rows_decoded"] == sum(sp.max_new_tokens - 1 for sp in sps)
    assert st["decode_passes_ahead"] / st["decode_fetches"] > 0.8, st
    assert st["decode_compiles"] == 1 and st["place_compiles"] == 1, st


# -- (2) what only the token tells --------------------------------------------


@pytest.mark.parametrize("pool_kw", [
    pytest.param(dict(page_len=4, prefix_share=True), id="paged-shared"),
    pytest.param(dict(page_len=4, prefix_share=False), id="paged-unshared"),
    pytest.param(dict(page_len=32, prefix_share=False), id="one-page")])
def test_a_row_that_ends_on_eos_is_dropped_and_its_slot_reused(lm, pool_kw):
    """A ends on its ``eos_token`` with its next row-step already in
    flight: that token is in no stream and no callback, the counter
    counts it, and B, which takes A's only slot and its pages (shared:
    A's first page by the prefix index; one-page: the one page that held
    all of A, the stale step's key among B's own) streams correctly over
    what the stale step wrote."""
    model, params = lm
    a = _prompt(6, 31)
    full = _standalone(model, params, a, SamplingParams(max_new_tokens=12))
    j = next(j for j in range(2, 10) if full[j] not in full[:j])
    sp_a = SamplingParams(max_new_tokens=12, eos_token=int(full[j]))
    b = np.concatenate([a[:4], _prompt(5, 32)])
    sp_b = SamplingParams(max_new_tokens=8)
    eng = _engine(model, params, n_slots=1, **pool_kw)
    seen = _spy_dispatch(eng)
    calls = []
    ha = eng.submit(a, sp_a, on_token=lambda tok, i: calls.append((tok, i)))
    hb = eng.submit(b, sp_b)
    with eng:
        out_a, out_b = ha.result(timeout=120), hb.result(timeout=120)
    np.testing.assert_array_equal(out_a, full[:j + 1])
    assert ha.tokens == full[:j + 1].tolist()
    assert calls == [(int(t), i) for i, t in enumerate(full[:j + 1])]
    # the step after the eos WAS dispatched, for A, and dropped
    assert (True, {0: (ha.request_id, j + 1)}) in seen
    st = eng.stats()
    assert st["decode_rows_dropped"] == 1
    assert st["rows_decoded"] == (j + 1) + (sp_b.max_new_tokens - 1)
    np.testing.assert_array_equal(out_b,
                                  _standalone(model, params, b, sp_b))
    if pool_kw.get("prefix_share"):
        assert hb.metrics["prefix_hit_pages"] == 1


# -- (3), (4) what the host knows ahead ---------------------------------------


@pytest.mark.parametrize("fit", ["short", "to_max_len"])
@pytest.mark.parametrize("pool_kw", POOLS)
def test_a_row_is_not_run_past_its_last_token(lm, pool_kw, fit):
    """Requests that end by ``max_new_tokens`` alone (``to_max_len``:
    the longest fills its slot row to the last position ``_validate``
    admits): no row-step is dispatched for a token past the last, none
    is dropped, and the pool is asked for a page only for a position
    that a pass then writes."""
    model, params = lm
    max_len = 32
    lens = (5, 11, 8)
    news = (9, 6, max_len - 8 if fit == "to_max_len" else 13)
    prompts = [_prompt(n, 40 + i) for i, n in enumerate(lens)]
    sps = [SamplingParams(max_new_tokens=n) for n in news]
    eng = _engine(model, params, n_slots=3, max_len=max_len, **pool_kw)
    seen = _spy_dispatch(eng)
    asked = []
    grow = eng.pool.ensure_decode_capacity

    def spied(slot):
        asked.append((eng._running[slot].request_id,
                      int(eng.pool.lengths[slot])))
        return grow(slot)
    eng.pool.ensure_decode_capacity = spied
    hs = [eng.submit(p, sp) for p, sp in zip(prompts, sps)]
    with eng:
        outs = [h.result(timeout=120) for h in hs]
    for p, sp, out in zip(prompts, sps, outs):
        np.testing.assert_array_equal(
            out, _standalone(model, params, p, sp, max_len=max_len))
    st = eng.stats()
    assert st["decode_rows_dropped"] == 0
    assert st["rows_decoded"] == sum(n - 1 for n in news)
    # token indices 1 .. max_new - 1 of each request, each once
    steps = sorted(v for _, rows in seen for v in rows.values())
    assert steps == sorted((h.request_id, i) for h, n in zip(hs, news)
                           for i in range(1, n))
    # a pass that gives token i writes position s + i - 1: the last
    # written is s + max_new - 2, under max_len - 1
    last = {h.request_id: s + n - 2 for h, s, n in zip(hs, lens, news)}
    assert all(pos <= last[rid] < max_len - 1 for rid, pos in asked)
    assert sorted(asked) == sorted(
        (h.request_id, pos) for h, s, n in zip(hs, lens, news)
        for pos in range(s, s + n - 1))


# -- (5) a failure between the two programs ----------------------------------


def test_a_deadline_fails_only_its_row_with_its_step_in_flight(lm):
    """An injected stall runs the loop past A's deadline while A and B
    decode: the sweep fails A typed with A's row-step in flight (it is
    dropped), and B's sampled stream is bit for bit ``generate()``'s."""
    model, params = lm
    eng = _engine(model, params, n_slots=2, page_len=8).start()
    try:
        sp_b = SamplingParams(max_new_tokens=20, temperature=0.7, top_k=8)
        # every program first: a compile must not eat the deadline
        for sp in (SamplingParams(max_new_tokens=3),
                   SamplingParams(max_new_tokens=3, temperature=0.7,
                                  top_k=8)):
            eng.submit(np.arange(4, dtype=np.int32), sp).result(timeout=120)
        for n in (40, 20):                  # and submit()'s key splits
            jax.random.split(jax.random.PRNGKey(0), n)
        before = eng.stats()
        # the sixth iteration from here: both rows are decoding
        faults.install("delay@op=serve_step,call=6,ms=1500")
        a, b, key_b = _prompt(5, 51), _prompt(7, 52), jax.random.PRNGKey(9)
        with eng._cond:                     # both queued before it wakes
            ha = eng.submit(a, SamplingParams(max_new_tokens=40,
                                              deadline_ms=900.0))
            hb = eng.submit(b, sp_b, rng=key_b)
        with pytest.raises(RequestDeadlineExceeded) as ei:
            ha.result(timeout=120)
        assert ei.value.stage == "running"
        assert ei.value.request_id == ha.request_id
        got = len(ha.tokens)
        np.testing.assert_array_equal(
            hb.result(timeout=120),
            _standalone(model, params, b, sp_b, key_b))
        st = eng.stats()
        assert st["decode_rows_dropped"] - before["decode_rows_dropped"] == 1
        # A's tokens so far are its stream's, and nothing came after
        assert 0 < got == len(ha.tokens) < 40
        np.testing.assert_array_equal(
            ha.tokens, _standalone(model, params, a, SamplingParams(
                max_new_tokens=40))[:got])
    finally:
        eng.shutdown()


def test_page_pool_exhausted_ahead_fails_only_its_row(lm):
    """Every page has a live reader when B's next pass needs a third
    page, B's pass before still in flight: B alone fails, typed, its
    step in flight is dropped, and A beside it, which grows into one of
    B's freed pages later, streams bit for bit."""
    model, params = lm
    eng = _engine(model, params, n_slots=2, max_len=16, page_len=4,
                  n_pages=4)
    a, b = _prompt(4, 61), _prompt(6, 62)        # 1 page, 2 pages
    sp_a = SamplingParams(max_new_tokens=8)      # a second at 4, third at 8
    sp_b = SamplingParams(max_new_tokens=6)      # a third at position 8
    ha, hb = eng.submit(a, sp_a), eng.submit(b, sp_b)
    with eng:
        with pytest.raises(PagePoolExhausted) as ei:
            hb.result(timeout=120)
        out_a = ha.result(timeout=120)
    assert ei.value.request_id == hb.request_id
    assert ei.value.free_pages == 0
    # B's first token and one decoded; its second step, which wrote
    # position 7, was in flight when the third found no page for 8
    np.testing.assert_array_equal(
        hb.tokens, _standalone(model, params, b, sp_b, max_len=16)[:2])
    np.testing.assert_array_equal(
        out_a, _standalone(model, params, a, sp_a, max_len=16))
    st = eng.stats()
    assert st["decode_rows_dropped"] == 1 and st["failed"] == 1
    assert eng.pool.pool.live_pages() == 0


# -- (6) who meets a program in flight ----------------------------------------


class _Unreadable:
    """A pass's tokens whose read fails."""

    def __array__(self, *a, **kw):
        raise RuntimeError("device lost")


@pytest.mark.parametrize("how", ["stats", "shutdown", "crash", "fetch",
                                 "fault"])
@pytest.mark.parametrize("pool_kw", POOLS)
def test_no_hang_and_no_stranded_future_with_a_pass_in_flight(lm, pool_kw,
                                                              how):
    """With pass k+1 dispatched and pass k being emitted: ``stats()``
    (here and from another thread) returns; ``shutdown()``, ``crash()``,
    a read that raises and a fault at the next iteration's start each
    end the loop, every future resolved exactly once, typed, and the
    engine's thread gone."""
    model, params = lm
    eng = _engine(model, params, n_slots=2, **pool_kw)
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
            eng._inflight = eng._inflight._replace(tokens=_Unreadable())
        else:
            faults.install("flaky@op=serve_step,call=1")
    hs = [eng.submit(_prompt(5, 70), SamplingParams(max_new_tokens=12),
                     on_token=on_token),
          eng.submit(_prompt(8, 71), SamplingParams(max_new_tokens=12))]
    eng.start()
    try:
        if how == "stats":
            for h in hs:
                assert len(h.result(timeout=120)) == 12
            assert len(other) == 2 and all(
                s["decode_fetches"] > 0 for s in other)
        else:
            for h in hs:
                with pytest.raises(EngineStopped) as ei:
                    h.result(timeout=120)
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


# -- (7) speculating rows ------------------------------------------------------


@pytest.mark.parametrize("pool_kw", POOLS)
def test_speculating_rows_are_never_run_ahead(pool_kw):
    """A draft model: the greedy rows speculate, the sampled ones share
    the batch through the decode program. While a speculating row is
    running nothing is dispatched ahead and no pass holds one; once
    they have retired the rows that are left run ahead again; every
    stream is its standalone reference's."""
    model = _lm(n_layers=2)
    params = model.init(jax.random.PRNGKey(0))
    dm = _lm(dim=16, n_heads=2, n_kv_heads=1)
    dp = dm.init(jax.random.PRNGKey(1))
    sps = [SamplingParams(max_new_tokens=10),
           SamplingParams(max_new_tokens=30, temperature=0.7, top_k=8),
           SamplingParams(max_new_tokens=10),
           SamplingParams(max_new_tokens=30, temperature=0.9, top_p=0.9)]
    prompts = [_prompt(n, 80 + i) for i, n in enumerate((5, 9, 12, 4))]
    keys = [jax.random.PRNGKey(200 + i) for i in range(4)]
    eng = InferenceEngine(model, params, EngineConfig(
        n_slots=4, max_len=MAX_LEN, buckets=(8, 16), spec_decode=True,
        draft_model=dm, draft_params=dp, draft_len=3, **pool_kw))
    seen, inner = [], eng._dispatch_pass

    def spied(rows, ahead):
        spec = [s for s in eng._running if eng._spec.active[s]]
        seen.append((ahead, bool(spec),
                     any(s in spec for s, _, _ in rows)))
        return inner(rows, ahead)
    eng._dispatch_pass = spied
    hs = [eng.submit(p, sp, rng=k) for p, sp, k in zip(prompts, sps, keys)]
    with eng:
        outs = [h.result(timeout=120) for h in hs]
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(
            out, _standalone(model, params, prompts[i], sps[i], keys[i]),
            err_msg=f"request {i}")
    st = eng.stats()
    assert st["spec"]["proposed"] > 0
    assert not any(holds for _, _, holds in seen)
    assert not any(ahead for ahead, spec, _ in seen if spec)
    assert any(ahead for ahead, spec, _ in seen if not spec)
    assert st["decode_passes_ahead"] == sum(a for a, _, _ in seen) > 0
    assert st["decode_rows_dropped"] == 0


# -- (8) idle ------------------------------------------------------------------


def test_nothing_is_in_flight_across_an_idle_engine(lm):
    """The engine goes idle between two requests, the first ended by its
    ``eos_token`` (its dropped step is the pass in flight when no row is
    left): every pass dispatched was read before a ``serve.idle`` began,
    and the first pass after it finds none to read."""
    model, params = lm
    dpxtrace.configure(enabled=True, ring=8192, log_path=None)
    a = _prompt(6, 31)
    full = _standalone(model, params, a, SamplingParams(max_new_tokens=12))
    j = next(j for j in range(2, 10) if full[j] not in full[:j])
    eng = _engine(model, params, n_slots=2, page_len=8)
    seen = _spy_dispatch(eng)
    with eng:
        out = eng.submit(a, SamplingParams(
            max_new_tokens=12, eos_token=int(full[j]))).result(timeout=120)
        np.testing.assert_array_equal(out, full[:j + 1])
        # the future resolves inside the read of A's last pass; the loop
        # then reads the dropped step and goes idle
        until = time.monotonic() + 60
        while eng.stats()["decode_fetches"] < j + 1:
            assert time.monotonic() < until
            time.sleep(0.005)
        idle = eng.stats()
        sp = SamplingParams(max_new_tokens=7)
        b = _prompt(9, 91)
        np.testing.assert_array_equal(
            eng.submit(b, sp).result(timeout=120),
            _standalone(model, params, b, sp))
    st = eng.stats()
    assert idle["decode_rows_dropped"] == st["decode_rows_dropped"] == 1
    assert idle["decode_fetches"] == j + 1 and st["decode_fetches"] == j + 7
    # the first pass of each request had none before it to read
    assert [ahead for ahead, _ in seen] == \
        [False] + [True] * j + [False] + [True] * 5
    ring, dropped = dpxtrace.flight_snapshot()
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
