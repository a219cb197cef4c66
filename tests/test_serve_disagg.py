"""Disaggregated prefill/decode serving (serve/disagg/) — acceptance.

The headline contracts: (1) exact-handoff (f32) disagg token streams
are BIT-IDENTICAL to standalone ``generate()`` — with exactly ONE
jitted decode program across the whole split and one prefill program
per tail bucket; (2) the q8 handoff stays within an explicit asserted
quality bound (per-element KV error <= scale/2, one-decode-step logit
delta <= 0.05, token divergence <= 25%, first token always exact) at
>= 3.5x fewer handoff bytes than f32, with CommStats booking EQUAL to
the ``wire.handoff_page_wire_bytes`` formula; (3) a prefill engine
killed mid-handoff fails ONLY its in-flight requests — typed
``PrefillEngineDied`` with request + engine attribution — while
co-resident decode streams finish bit-exact.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import models
from distributed_pytorch_tpu.comm import wire
from distributed_pytorch_tpu.models.generate import make_generate_fn
from distributed_pytorch_tpu.runtime import faults
from distributed_pytorch_tpu.serve import (AdmissionRejected,
                                           DisaggConfig, DisaggEngine,
                                           EngineStopped, HandoffCorrupt,
                                           HandoffTimeout,
                                           PrefillEngineDied,
                                           SamplingParams, aggregate)
from distributed_pytorch_tpu.serve.disagg import (LocalTransport,
                                                  decode_frame,
                                                  encode_frame,
                                                  kv_wire_bytes,
                                                  resolve_handoff_bits)
from distributed_pytorch_tpu.serve.pages import PagedSlotPool
from distributed_pytorch_tpu.serve.types import Request
from distributed_pytorch_tpu.utils.logging import MetricsLogger

MAX_LEN = 64
L = 8   # page_len


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


def _disagg(model, params, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("page_len", L)
    transport = kw.pop("transport", None)
    return DisaggEngine(model, params, DisaggConfig(**kw),
                        transport=transport)


def _pages(model, params, prompt, bucket=32):
    """Prefill ``prompt`` into a scratch paged pool and extract its
    pages — frame-codec test material with real KV statistics."""
    pool = PagedSlotPool(model, 1, MAX_LEN, page_len=L, n_pages=8,
                         prefix_share=False)
    logits, _, _ = pool.admit(params, prompt, 0, (bucket,))
    length, ks, vs = pool.extract(0)
    return np.asarray(logits)[0], length, ks, vs


# ---------------------------------------------------------------------------
# the frame codec
# ---------------------------------------------------------------------------


class TestHandoffFrames:
    def test_exact_roundtrip_and_accounting(self):
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, 61, (20,)).astype(np.int32)
        logits, length, ks, vs = _pages(model, params, prompt)
        buf, kv_bytes = encode_frame(7, length, logits, ks, vs, None)
        pe = ks[0][0].size
        want = kv_wire_bytes(model.n_layers, len(ks[0]), pe, None)
        assert kv_bytes == want == model.n_layers * 2 * 3 * pe * 4
        assert want == wire.handoff_page_wire_bytes(
            pe, model.n_layers * 2 * 3, bits=None)
        fr = decode_frame(buf)
        assert fr.request_id == 7 and fr.length == length
        assert fr.bits is None and fr.kv_bytes == kv_bytes
        np.testing.assert_array_equal(fr.logits, logits)
        for i in range(model.n_layers):
            np.testing.assert_array_equal(fr.ks[i], ks[i])
            np.testing.assert_array_equal(fr.vs[i], vs[i])

    def test_quant_roundtrip_bound_and_byte_cut(self):
        """The codec quality bound, asserted elementwise: every
        dequantized value is within scale/2 of the original, scale
        local to ITS page (amax/levels) — plus the byte-cut claims the
        CI gates on (q8 >= 3.5x, q4 >= 6.5x under f32)."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)
        prompt = rng.integers(0, 61, (20,)).astype(np.int32)
        logits, length, ks, vs = _pages(model, params, prompt)
        pe = ks[0][0].size
        n_tensors = model.n_layers * 2 * len(ks[0])
        f32_bytes = kv_wire_bytes(model.n_layers, len(ks[0]), pe, None)
        for bits, min_ratio in ((8, 3.5), (4, 6.5)):
            buf, kv_bytes = encode_frame(3, length, logits, ks, vs, bits)
            assert kv_bytes == n_tensors * wire.quant_wire_bytes(
                pe, bits=bits)
            assert f32_bytes / kv_bytes >= min_ratio
            fr = decode_frame(buf)
            np.testing.assert_array_equal(fr.logits, logits)  # always exact
            levels = wire.quant_levels(bits)
            for i in range(model.n_layers):
                for src, got in ((ks[i], fr.ks[i]), (vs[i], fr.vs[i])):
                    for p in range(src.shape[0]):
                        bound = np.abs(src[p]).max() / levels / 2 + 1e-6
                        assert np.abs(src[p] - got[p]).max() <= bound

    def test_corrupt_frames_typed_with_page_attribution(self):
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        prompt = np.arange(12, dtype=np.int32)
        logits, length, ks, vs = _pages(model, params, prompt, bucket=16)
        buf, kv_bytes = encode_frame(5, length, logits, ks, vs, 8)
        # flip a byte in the LAST page tensor's payload
        bad = bytearray(buf)
        bad[-1] ^= 0xFF
        with pytest.raises(HandoffCorrupt) as ei:
            decode_frame(bytes(bad))
        n_tensors = model.n_layers * 2 * len(ks[0])
        assert ei.value.request_id == 5
        assert ei.value.page == n_tensors - 1
        assert ei.value.engine == "prefill"
        # damaged logits attribute as header/logits section (page -1)
        bad = bytearray(buf)
        bad[12 * 8 + 4 * (1 + n_tensors)] ^= 0xFF
        with pytest.raises(HandoffCorrupt) as ei:
            decode_frame(bytes(bad))
        assert ei.value.page == -1 and ei.value.request_id == 5
        # bad magic / truncation are typed too (unattributable)
        with pytest.raises(HandoffCorrupt):
            decode_frame(b"\x00" * len(buf))
        with pytest.raises(HandoffCorrupt):
            decode_frame(buf[:40])
        # damaged GEOMETRY words must be typed HandoffCorrupt as well,
        # never an untyped ValueError/MemoryError that would escape the
        # decode loop's victim-only handling and crash every stream
        for word, value in ((3, 9), (3, -8), (5, 1 << 40), (4, 0),
                            (9, 10_000), (10, -1)):
            bad = bytearray(buf)
            bad[word * 8:(word + 1) * 8] = np.int64(value).tobytes()
            with pytest.raises(HandoffCorrupt):
                decode_frame(bytes(bad))

    def test_width_resolution_and_fault_ops(self):
        assert resolve_handoff_bits("f32") is None
        assert resolve_handoff_bits("q8") == 8
        assert resolve_handoff_bits("q4") == 4
        with pytest.raises(ValueError, match="handoff width"):
            resolve_handoff_bits("q2")
        assert "handoff_send" in faults.COMM_OPS
        assert "handoff_recv" in faults.COMM_OPS
        specs = faults.parse_fault_spec(
            "drop_conn@op=handoff_send,call=2;delay@op=handoff_recv,ms=5")
        assert specs[0].op == "handoff_send"
        assert specs[1].op == "handoff_recv"


# ---------------------------------------------------------------------------
# the split engine
# ---------------------------------------------------------------------------


class TestDisaggEngine:
    def test_exact_streams_bit_identical(self):
        """THE acceptance kernel: cold + shared-prefix + sub-page
        prompts through the split — every stream equals standalone
        generate(), ONE decode program across the split (and ZERO on
        the prefill side), one prefill per tail bucket, prefill-side
        radix reuse accounted, and the handoff bytes booked in
        CommStats equal to the wire formula exactly."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(12)
        eng = _disagg(model, params)
        pfx = rng.integers(0, 61, (16,)).astype(np.int32)  # 2 full pages
        prompts = [
            np.concatenate([pfx, rng.integers(0, 61, (4,))]).astype(np.int32),
            np.concatenate([pfx, rng.integers(0, 61, (4,))]).astype(np.int32),
            rng.integers(0, 61, (7,)).astype(np.int32),
        ]
        sp = SamplingParams(max_new_tokens=8)
        keys = [jax.random.PRNGKey(100 + i) for i in range(3)]
        with eng:
            hs = [eng.submit(prompts[i], sp, rng=keys[i])
                  for i in range(3)]
            outs = [h.result(timeout=120) for h in hs]
        for i in range(3):
            np.testing.assert_array_equal(
                outs[i], _standalone(model, params, prompts[i], sp,
                                     keys[i]), err_msg=f"request {i}")
        st = eng.stats()
        assert st["decode"]["decode_compiles"] == 1, st
        assert st["prefill"]["decode_compiles"] == 0, st
        assert all(v == 1
                   for v in st["prefill"]["prefill_compiles"].values())
        assert st["decode"]["prefill_compiles"] == {}
        # prefill-side radix reuse: request 1 shares both prefix pages
        assert [h.metrics["prefix_hit_pages"] for h in hs] == [0, 2, 0]
        assert [h.metrics["prefill_tokens_saved"] for h in hs] == [0, 16, 0]
        # byte accounting: CommStats == sum of per-request formula bytes
        pe = model.n_kv_heads * L * (model.dim // model.n_heads)
        want = sum(kv_wire_bytes(model.n_layers, -(-len(p) // L), pe,
                                 None) for p in prompts)
        assert st["handoff"]["bytes_sent"] == want
        assert st["handoff"]["bytes_recv"] == want
        assert want == sum(h.metrics["handoff_bytes"] for h in hs)
        assert st["handoff"]["frames_sent"] == 3
        # all pages released on both sides
        assert eng.decode.pool.pool.live_pages() == 0
        assert eng.prefill.pool.pool.live_pages() == 0

    def test_q8_handoff_quality_bound(self):
        """The q8 contract, asserted: >= 3.5x fewer handoff bytes than
        f32 (CommStats == formula), first token EXACT (logits ship
        f32), and token divergence vs generate() <= 25% — measured 0%
        for this model/population; the bound leaves margin, it does
        not hide drift."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 61, (s,)).astype(np.int32)
                   for s in (20, 12, 7, 17)]
        sp = SamplingParams(max_new_tokens=10)
        keys = [jax.random.PRNGKey(200 + i) for i in range(len(prompts))]
        refs = [_standalone(model, params, p, sp, k)
                for p, k in zip(prompts, keys)]
        eng = _disagg(model, params, handoff_width="q8")
        with eng:
            hs = [eng.submit(prompts[i], sp, rng=keys[i])
                  for i in range(len(prompts))]
            outs = [h.result(timeout=120) for h in hs]
        st = eng.stats()
        pe = model.n_kv_heads * L * (model.dim // model.n_heads)
        q8_want = sum(kv_wire_bytes(model.n_layers, -(-len(p) // L),
                                    pe, 8) for p in prompts)
        f32_want = sum(kv_wire_bytes(model.n_layers, -(-len(p) // L),
                                     pe, None) for p in prompts)
        assert st["handoff"]["bytes_sent"] == q8_want
        assert f32_want / q8_want >= 3.5
        divergence = [float(np.mean(o != r))
                      for o, r in zip(outs, refs)]
        for i, (o, r) in enumerate(zip(outs, refs)):
            assert o[0] == r[0], f"request {i}: first token must be exact"
        assert max(divergence) <= 0.25, divergence

    def test_q8_one_step_logit_delta_bound(self):
        """Unit-level quality bound: the same extracted pages adopted
        exact vs through the q8 frame, one decode step — max logit
        delta <= 0.05 (measured ~3.5e-3 here; the bound is explicit
        and asserted, not folklore)."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(4)
        prompt = rng.integers(0, 61, (20,)).astype(np.int32)
        logits, length, ks, vs = _pages(model, params, prompt)
        out = {}
        for bits in (None, 8):
            fr = decode_frame(encode_frame(1, length, logits, ks, vs,
                                           bits)[0])
            pool = PagedSlotPool(model, 1, MAX_LEN, page_len=L,
                                 n_pages=8, prefix_share=False)
            pool.adopt(0, fr.length, fr.ks, fr.vs)
            _, lg = pool.decode(params, np.asarray([prompt[-1]], np.int32),
                                np.asarray([True]))
            out[bits] = np.asarray(lg)[0]
        assert np.abs(out[8] - out[None]).max() <= 0.05

    def test_chaos_prefill_death_mid_handoff_victim_only(self):
        """THE chaos satellite: the transport severed entering request
        1's handoff (the in-process analog of killing the prefill
        engine mid-handoff). The victim AND the still-queued request
        fail typed PrefillEngineDied with request + blamed-engine
        attribution, new submissions are refused with reason
        prefill_dead, and the co-resident DECODING stream finishes
        bit-identical to generate()."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(8)
        faults.install("drop_conn@op=handoff_send,call=2")
        eng = _disagg(model, params, n_slots=2)
        a = rng.integers(0, 61, (9,)).astype(np.int32)
        b = rng.integers(0, 61, (12,)).astype(np.int32)
        ka, kb = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
        sp = SamplingParams(max_new_tokens=20)
        with eng:
            ha = eng.submit(a, sp, rng=ka)
            while not ha.tokens:   # a must be decoding before b's handoff
                time.sleep(0.005)
            hb = eng.submit(b, sp, rng=kb)
            with pytest.raises(PrefillEngineDied) as ei:
                hb.result(timeout=60)
            out_a = ha.result(timeout=60)
            with pytest.raises(AdmissionRejected) as rej:
                eng.submit(a, sp, rng=ka)
        assert ei.value.request_id == hb.request_id
        assert ei.value.engine == "prefill"
        assert rej.value.reason == "prefill_dead"
        np.testing.assert_array_equal(
            out_a, _standalone(model, params, a, sp, ka))
        assert any(f.startswith("drop_conn@op=handoff_send")
                   for f in faults.fired()), faults.fired()
        assert eng.decode.pool.pool.live_pages() == 0

    def test_handoff_timeout_typed(self):
        """A frame that never materializes (send stalled past
        DPX_HANDOFF_TIMEOUT_MS by an injected delay) fails its request
        as a typed HandoffTimeout with the deadline attributed; the
        co-resident stream is untouched."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(9)
        faults.install("delay@op=handoff_send,call=2,ms=600")
        eng = _disagg(model, params, n_slots=2, handoff_timeout_ms=80)
        a = rng.integers(0, 61, (9,)).astype(np.int32)
        b = rng.integers(0, 61, (6,)).astype(np.int32)
        ka, kb = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
        sp = SamplingParams(max_new_tokens=24)
        with eng:
            ha = eng.submit(a, sp, rng=ka)
            while not ha.tokens:
                time.sleep(0.005)
            hb = eng.submit(b, sp, rng=kb)
            with pytest.raises(HandoffTimeout) as ei:
                hb.result(timeout=60)
            out_a = ha.result(timeout=60)
        assert ei.value.request_id == hb.request_id
        assert ei.value.deadline_ms == 80.0
        assert ei.value.engine == "transport"
        np.testing.assert_array_equal(
            out_a, _standalone(model, params, a, sp, ka))

    def test_corrupt_frame_fails_victim_only(self):
        """A frame damaged in flight fails ITS request typed
        (HandoffCorrupt, page-attributed) — the co-resident stream
        decodes on bit-exact and later handoffs flow normally."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(10)

        class Flipper(LocalTransport):
            def send(self, frame, kv_bytes):
                if self.frames_sent == 1:     # damage the 2nd frame
                    frame = bytearray(frame)
                    frame[-1] ^= 0xFF
                    frame = bytes(frame)
                super().send(frame, kv_bytes)

        eng = _disagg(model, params, n_slots=2, transport=Flipper())
        a = rng.integers(0, 61, (9,)).astype(np.int32)
        b = rng.integers(0, 61, (6,)).astype(np.int32)
        c = rng.integers(0, 61, (11,)).astype(np.int32)
        ka, kb, kc = (jax.random.PRNGKey(i) for i in (1, 2, 3))
        sp = SamplingParams(max_new_tokens=16)
        with eng:
            ha = eng.submit(a, sp, rng=ka)
            while not ha.tokens:
                time.sleep(0.005)
            hb = eng.submit(b, sp, rng=kb)
            with pytest.raises(HandoffCorrupt) as ei:
                hb.result(timeout=60)
            hc = eng.submit(c, sp, rng=kc)
            out_c = hc.result(timeout=60)
            out_a = ha.result(timeout=60)
        assert ei.value.request_id == hb.request_id
        assert ei.value.page >= 0
        np.testing.assert_array_equal(
            out_a, _standalone(model, params, a, sp, ka))
        np.testing.assert_array_equal(
            out_c, _standalone(model, params, c, sp, kc))

    def test_submit_validation_typed(self):
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        eng = _disagg(model, params, n_slots=1)
        with pytest.raises(AdmissionRejected) as ei:
            eng.submit(np.arange(80, dtype=np.int32),
                       SamplingParams(max_new_tokens=4))
        assert ei.value.reason == "prompt_too_long"
        with pytest.raises(AdmissionRejected) as ei:
            eng.submit(np.arange(40, dtype=np.int32),
                       SamplingParams(max_new_tokens=40))
        assert ei.value.reason == "too_long"
        small = _disagg(model, params, n_slots=1, max_len=32, n_pages=2)
        with pytest.raises(AdmissionRejected) as ei:
            small.submit(np.arange(10, dtype=np.int32),
                         SamplingParams(max_new_tokens=10))
        assert ei.value.reason == "no_free_pages"

    def test_shutdown_drains_typed(self):
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        eng = _disagg(model, params)
        h = eng.submit(np.arange(6, dtype=np.int32),
                       SamplingParams(max_new_tokens=4))
        eng.shutdown()           # never started: queued request drains
        with pytest.raises(EngineStopped) as ei:
            h.result(timeout=10)
        assert ei.value.request_id == h.request_id

    def test_nonpollable_transport_rejected(self):
        """A transport whose recv can only block (the cross-process
        HostCommTransport shape) would stall the decode loop's token
        cadence on the handoff channel — refused at construction."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))

        class Blocking(LocalTransport):
            pollable = False

        with pytest.raises(ValueError, match="not pollable"):
            _disagg(model, params, transport=Blocking())

    def test_windowed_model_rejected(self):
        from distributed_pytorch_tpu.nn.attention import dense_attention

        def fn(q, k, v, *, causal=False, scale=None):
            return dense_attention(q, k, v, causal=causal, scale=scale,
                                   window=8)
        fn.window = 8
        model = _lm1(vocab=64, attn_fn=fn)
        params = model.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="sliding-window"):
            _disagg(model, params)


# ---------------------------------------------------------------------------
# metrics: the TTFT decomposition and decode-only TPOT attribution
# ---------------------------------------------------------------------------


class TestHandoffMetrics:
    def _req(self, rid, t0=100.0, queue=0.010, prefill=0.020,
             handoff=0.005, decode=0.003, n_tokens=4, tpot=0.002,
             nbytes=1000):
        r = Request(request_id=rid, prompt=np.arange(9, dtype=np.int32),
                    params=SamplingParams(max_new_tokens=n_tokens),
                    rngs=None, submit_t=t0, deadline_t=None)
        r.admit_t = t0 + queue
        r.handoff_send_t = r.admit_t + prefill
        r.handoff_recv_t = r.handoff_send_t + handoff
        r.first_token_t = r.handoff_recv_t + decode
        r.last_token_t = r.first_token_t + tpot * (n_tokens - 1)
        r.out_tokens = list(range(n_tokens))
        r.handoff_bytes = nbytes
        return r

    def test_record_decomposition_sums_to_ttft(self):
        from distributed_pytorch_tpu.serve import request_record
        rec = request_record(self._req(1), "ok")
        assert rec["queue_ms"] == pytest.approx(10.0)
        assert rec["prefill_ms"] == pytest.approx(20.0)
        assert rec["handoff_ms"] == pytest.approx(5.0)
        assert rec["decode_ms"] == pytest.approx(3.0)
        assert rec["handoff_bytes"] == 1000
        assert (rec["queue_ms"] + rec["prefill_ms"] + rec["handoff_ms"]
                + rec["decode_ms"]) == pytest.approx(rec["ttft_ms"])
        # TPOT spans decode-engine time ONLY: first->last token, both
        # emitted by the decode loop — a 100x longer prefill leaves it
        # untouched
        assert rec["tpot_ms"] == pytest.approx(2.0)
        slow = request_record(self._req(2, prefill=2.0), "ok")
        assert slow["tpot_ms"] == pytest.approx(2.0)
        assert slow["prefill_ms"] == pytest.approx(2000.0)

    def test_aggregate_handoff_fleet_view(self):
        from distributed_pytorch_tpu.serve import request_record
        recs = [request_record(self._req(i, handoff=0.004 + 0.002 * i,
                                         nbytes=500 * (i + 1)), "ok")
                for i in range(5)]
        agg = aggregate(recs)
        assert agg["handoff_ms_p50"] == pytest.approx(8.0)
        assert agg["handoff_ms_p99"] == pytest.approx(12.0)
        assert agg["handoff_bytes"] == 500 * (1 + 2 + 3 + 4 + 5)
        assert agg["prefill_ms_p50"] == pytest.approx(20.0)
        # monolithic records have no handoff timeline -> no fleet keys
        mono = dict(recs[0])
        for k in ("prefill_ms", "handoff_ms", "decode_ms",
                  "handoff_bytes"):
            mono.pop(k)
        agg2 = aggregate([mono])
        assert "handoff_ms_p50" not in agg2

    def test_engine_metrics_flow_to_logger(self, tmp_path):
        """Live engine: serve_request events carry the decomposition +
        handoff bytes; every span is nonnegative and the timeline is
        ordered (handoff_recv precedes the first token — TPOT is
        decode-attributable by construction)."""
        model = _lm1()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(11)
        log = tmp_path / "disagg.jsonl"
        logger = MetricsLogger(path=str(log))
        eng = _disagg(model, params, metrics=logger, log_every=2)
        with eng:
            hs = [eng.submit(rng.integers(0, 61, (9,)).astype(np.int32),
                             SamplingParams(max_new_tokens=6),
                             rng=jax.random.PRNGKey(i))
                  for i in range(3)]
            for h in hs:
                h.result(timeout=120)
        logger.close()
        rows = [json.loads(ln) for ln in log.read_text().splitlines()]
        reqs = [r for r in rows if r.get("event") == "serve_request"]
        assert len(reqs) == 3
        for r in reqs:
            for k in ("queue_ms", "prefill_ms", "handoff_ms",
                      "decode_ms"):
                assert r[k] is not None and r[k] >= 0, (k, r)
            assert r["handoff_bytes"] > 0
            assert (r["queue_ms"] + r["prefill_ms"] + r["handoff_ms"]
                    + r["decode_ms"]) == pytest.approx(r["ttft_ms"],
                                                       rel=1e-6)
        for h in hs:
            req = h._request
            assert req.handoff_recv_t <= req.first_token_t
        agg = aggregate([h.metrics for h in hs])
        assert agg["handoff_bytes"] == sum(
            h.metrics["handoff_bytes"] for h in hs)
        assert agg["handoff_ms_p50"] is not None


# ---------------------------------------------------------------------------
# cross-process transport (separate prefill/decode OS processes)
# ---------------------------------------------------------------------------


def _xproc_worker(rank, world, q):
    """Rank 0 = prefill side, rank 1 = decode side, over the native
    host group. Rank 0 sends one good frame then is hard-KILLED by the
    DPX_FAULT grammar entering its second send; rank 1 round-trips the
    first frame and observes the death as a typed, attributed failure
    within the comm deadline."""
    import numpy as np
    import distributed_pytorch_tpu as dist
    from distributed_pytorch_tpu.runtime import context
    from distributed_pytorch_tpu.serve.disagg import (HostCommTransport,
                                                      decode_frame,
                                                      encode_frame)
    from distributed_pytorch_tpu.serve.disagg.transport import \
        TransportSevered

    dist.init_process_group(rank, world)
    try:
        comm = context.get_host_comm()
        t = HostCommTransport(comm, src=0)
        rng = np.random.default_rng(0)
        ks = [rng.standard_normal((2, 2, 4, 4)).astype(np.float32)]
        vs = [rng.standard_normal((2, 2, 4, 4)).astype(np.float32)]
        logits = rng.standard_normal((16,)).astype(np.float32)
        if rank == 0:
            frame, kv = encode_frame(9, 7, logits, ks, vs, 8)
            t.send(frame, kv)
            # the 2nd send never happens: kill@op=handoff_send,call=2
            # fires in the hook — a real mid-handoff process death
            t.send(frame, kv)
            q.put((rank, "unreachable"))
        else:
            fr = decode_frame(t.recv())
            ok = (fr.request_id == 9 and fr.length == 7
                  and np.array_equal(fr.logits, logits))
            try:
                t.recv()
                q.put((rank, "no-error"))
            except TransportSevered as e:
                q.put((rank, ("severed", ok,
                              type(e.__cause__).__name__)))
    finally:
        dist.cleanup()


def test_hostcomm_transport_kill_prefill_process():
    """The cross-process leg: frames move between REAL OS processes
    over HostComm, and a prefill process hard-killed mid-handoff
    (kill@op=handoff_send — exit 43, indistinguishable from OOM)
    surfaces on the decode side as a typed severed transport blamed on
    a dead peer, within one comm deadline."""
    import multiprocessing as mp

    from distributed_pytorch_tpu.runtime.multiprocess import \
        launch_multiprocess

    faults.install("kill@op=handoff_send,call=2,rank=0")
    q = mp.get_context("spawn").Queue()
    with pytest.raises(RuntimeError):
        # rank 0's injected death propagates as the launcher's typed
        # child-failure report (exit code 43)
        launch_multiprocess(_xproc_worker, 2, q)
    got = {}
    while not q.empty():
        rank, payload = q.get()
        got[rank] = payload
    assert 0 not in got          # rank 0 died before reporting
    kind, first_ok, cause = got[1]
    assert kind == "severed" and first_ok
    assert cause in ("CommPeerDied", "CommTimeout")
