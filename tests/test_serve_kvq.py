"""Quantized-at-rest paged KV pool (``serve/pages/`` ``kv_dtype``).

What must hold (ISSUE 16 / docs/serving.md "Quantized resident pool"):

- the jnp in-program page codec and the numpy host/wire codec are
  BIT-identical — one block grid (``comm/wire.py``'s QUANT_BLOCK over
  the flat page) shared by pool, kernel and handoff frame;
- the quality contract: per-element KV error <= scale/2 (every element
  quantized exactly once, from exact f32, on page completion), cold
  first tokens exact, one-step logit deltas bounded, bounded token
  divergence on a mixed cold/shared stream;
- the exact default: ``kv_dtype="f32"`` is bit-identical to the
  pre-existing pool — zero behavior change unless opted in;
- the ONE-decode-program discipline survives quantization;
- ``extract``/``adopt`` work at all three widths (stale tails zeroed,
  sub-page tails exact), and the matched-width handoff pass-through
  (``extract_quantized``/``encode_frame_quantized``/``decode_frame(
  keep_bits)``/``adopt_quantized``) moves the pool's resident bits
  byte-identically with no dequant→requant double hop;
- ``PagedSlotPool.admit`` prefills a tail longer than every bucket in
  chunks (it was refused until PR 31), every page taken before a program
  runs and all given back, refcounts as before, by a release half-way.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_pytorch_tpu import models
from distributed_pytorch_tpu.comm import wire
from distributed_pytorch_tpu.ops.quant import (dequantize_page_blocks,
                                               pack_page_nibbles,
                                               page_block_map,
                                               quantize_page_blocks,
                                               unpack_page_nibbles)
from distributed_pytorch_tpu.serve import (EngineConfig, InferenceEngine,
                                           SamplingParams)
from distributed_pytorch_tpu.serve.disagg import frames
from distributed_pytorch_tpu.serve.pages import PagedSlotPool
from distributed_pytorch_tpu.serve.pages.quant import (dequantize_page_np,
                                                       pack_pages_np,
                                                       quantize_page_np,
                                                       resolve_kv_bits,
                                                       unpack_pages_np)

MAX_LEN = 64
L = 8
BUCKETS = (8, 16, 32)


def _lm(**kw):
    kw.setdefault("vocab", 61)
    kw.setdefault("dim", 32)
    kw.setdefault("n_layers", 2)
    kw.setdefault("n_heads", 4)
    kw.setdefault("n_kv_heads", 2)
    kw.setdefault("pos", "rope")
    kw.setdefault("max_seq", 128)
    return models.TransformerLM(**kw)


def _pool(model, kv_dtype, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("page_len", L)
    kw.setdefault("n_pages", 32)
    return PagedSlotPool(model, kw.pop("n_slots"), MAX_LEN,
                         kv_dtype=kv_dtype, **kw)


def _greedy_run(model, params, pool, prompt, steps):
    """Admit + ``steps`` greedy decodes on slot 0; returns (tokens,
    first logits, per-step logits)."""
    logits, _, _ = pool.admit(params, prompt, 0, BUCKETS)
    first = np.asarray(logits)[0].copy()
    toks = [int(np.argmax(first))]
    active = np.zeros(pool.n_slots, bool)
    active[0] = True
    cur = np.zeros(pool.n_slots, np.int32)
    step_logits = []
    for _ in range(steps):
        pool.ensure_decode_capacity(0)
        cur[0] = toks[-1]
        tok, lg = pool.decode(params, cur, active)
        lg = np.asarray(lg)[0].copy()
        step_logits.append(lg)
        # the program's own token is the argmax of the logits beside it
        assert int(np.asarray(tok)[0]) == int(np.argmax(lg))
        toks.append(int(np.argmax(lg)))
    return toks, first, step_logits


# ---------------------------------------------------------------------------
# the one block codec: jnp in-program face == numpy host/wire face
# ---------------------------------------------------------------------------


class TestPageCodec:
    @pytest.mark.parametrize("bits", [8, 4])
    def test_jnp_codec_bit_identical_to_wire(self, bits):
        """``quantize_page_blocks`` (traced, page-shaped, zero-padded
        to the block grid) must agree BIT-for-bit with
        ``wire.quantize_blocks`` on the unpadded flat page — the
        property that makes the matched-width handoff pass-through
        byte-identical."""
        rng = np.random.default_rng(0)
        # (Hkv, L, Dh) pages: generic, zero-block, and integer-snap
        pages = [rng.standard_normal((4, 8, 34)).astype(np.float32),
                 np.zeros((4, 8, 34), np.float32),
                 rng.integers(-5, 6, (4, 8, 34)).astype(np.float32)]
        for page in pages:
            qj, sj = quantize_page_blocks(jnp.asarray(page), bits)
            qn, sn = wire.quantize_blocks(page.ravel(), bits=bits)
            nb = wire.num_blocks(page.size)
            assert np.array_equal(np.asarray(qj).ravel(), qn)
            assert np.array_equal(np.asarray(sj), sn[:nb])
            # and both dequant faces agree with each other
            bmap = page_block_map(4, 8, 34)
            dj = np.asarray(dequantize_page_blocks(qj, sj, bmap))
            dn = wire.dequantize_blocks(qn, sn).reshape(page.shape)
            assert np.array_equal(dj, dn)

    def test_nibble_pack_both_faces_byte_identical(self):
        rng = np.random.default_rng(1)
        q = rng.integers(-7, 8, (4, 8, 34)).astype(np.int8)
        pj = np.asarray(pack_page_nibbles(jnp.asarray(q)))
        pn = pack_pages_np(q)
        assert np.array_equal(pj, pn)
        assert np.array_equal(pn.ravel(),
                              wire.pack_nibbles(q.ravel()))
        uj = np.asarray(unpack_page_nibbles(jnp.asarray(pn)))
        un = unpack_pages_np(pn)
        assert np.array_equal(uj, q) and np.array_equal(un, q)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_per_element_error_bound_half_scale(self, bits):
        """The contract the deferred-tail design buys: every resident
        element is within scale/2 of its exact value (one rounding,
        from exact f32 — never re-rounded)."""
        rng = np.random.default_rng(2)
        page = rng.standard_normal((2, 8, 16)).astype(np.float32) * 3.0
        q, scales = quantize_page_np(page, bits)
        deq = dequantize_page_np(q, scales)
        per_elem_scale = scales[
            np.arange(page.size) // wire.QUANT_BLOCK].reshape(page.shape)
        assert np.all(np.abs(deq - page) <= per_elem_scale / 2 + 1e-7)

    def test_resolve_kv_bits(self):
        assert resolve_kv_bits("f32") is None
        assert resolve_kv_bits("q8") == 8
        assert resolve_kv_bits("q4") == 4
        with pytest.raises(ValueError, match="kv_dtype"):
            resolve_kv_bits("int8")

    def test_q4_odd_head_dim_rejected(self):
        model = _lm(dim=36, n_heads=4, n_kv_heads=2)   # Dh = 9, odd
        with pytest.raises(ValueError, match="even"):
            _pool(model, "q4")


# ---------------------------------------------------------------------------
# quality contract vs the exact pool
# ---------------------------------------------------------------------------


class TestQuantPoolQuality:
    def test_f32_mode_bit_identical_and_q8_bounded(self):
        """One admit + greedy decode run per width. ``f32`` must be
        bit-identical to the default pool (zero behavior change);
        ``q8`` must keep first logits EXACT (cold prefill attends
        in-register f32), one-step logit deltas under the ceiling, and
        the ONE-decode-program discipline."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        prompt = np.random.default_rng(0).integers(
            0, 61, 21).astype(np.int32)
        base = _greedy_run(model, params, _pool(model, "f32"), prompt, 12)
        ref = _greedy_run(model, params,
                          PagedSlotPool(model, 2, MAX_LEN, page_len=L,
                                        n_pages=32), prompt, 12)
        assert base[0] == ref[0]
        assert np.array_equal(base[1], ref[1])
        for a, b in zip(base[2], ref[2]):
            assert np.array_equal(a, b)
        for kv_dtype in ("q8", "q4"):
            pool = _pool(model, kv_dtype)
            toks, first, steps = _greedy_run(model, params, pool,
                                             prompt, 12)
            # cold admission: the whole prompt is computed in-register
            # (no quantized prefix pages to read) — token 0 exact
            assert np.array_equal(first, base[1]), kv_dtype
            assert pool.compiles.decode == 1, kv_dtype
            if kv_dtype == "q8":
                # one-step logit delta ceiling on the smoke model
                deltas = [float(np.abs(a - b).max())
                          for a, b in zip(steps, base[2])]
                assert max(deltas) <= 0.05, deltas
                div = np.mean([a != b for a, b in zip(toks, base[0])])
                assert div <= 0.25, (toks, base[0])

    def test_engine_q8_mixed_stream_quality(self):
        """Engine-level mixed cold/shared population: q8 vs f32 token
        divergence bounded, cold first tokens exact, decode stays one
        program, and the capacity gauges tell the ~4x story."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)
        shared = rng.integers(0, 61, 16).astype(np.int32)
        prompts = [np.concatenate(
            [shared, rng.integers(0, 61, 5 + i).astype(np.int32)])
            for i in range(3)] + [rng.integers(0, 61, 11).astype(np.int32)]
        sp = SamplingParams(max_new_tokens=8, temperature=0.0)

        def run(kv_dtype):
            eng = InferenceEngine(model, params, EngineConfig(
                paged=True, n_slots=3, max_len=MAX_LEN, page_len=L,
                kv_dtype=kv_dtype))
            with eng:
                hs = [eng.submit(p, sp) for p in prompts]
                outs = [h.result(timeout=120) for h in hs]
            return outs, eng.stats()

        o_f, st_f = run("f32")
        o_q, st_q = run("q8")
        assert st_q["decode_compiles"] == 1
        assert o_q[0][0] == o_f[0][0]          # cold request, token 0
        assert o_q[3][0] == o_f[3][0]          # fully cold prompt
        div = np.mean([a != b for x, y in zip(o_f, o_q)
                       for a, b in zip(x, y)])
        assert div <= 0.25
        pf, pq = st_f["pages"], st_q["pages"]
        assert pq["kv_dtype"] == "q8" and pq["kv_bits"] == 8
        assert pf["kv_bits"] == 32
        ratio = (pf["bytes_per_resident_token"]
                 / pq["bytes_per_resident_token"])
        assert ratio >= 3.5

    @pytest.mark.parametrize("kv_dtype", ["q8", "q4"])
    def test_engine_quant_mixed_settings_batch_equals_alone(self, kv_dtype):
        """A quantized pool's decode program ends in the same greedy
        tail, and its rows join the same batched samplers: greedy rows
        and rows of two sampling settings side by side get, each, the
        tokens the same engine gives the request alone (a quantized
        stream is not generate()'s, but it is its own: no row's token
        depends on who decodes beside it)."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, 61, 5 + 3 * i).astype(np.int32)
                   for i in range(4)]
        sps = [SamplingParams(max_new_tokens=12, **kw) for kw in (
            {}, dict(temperature=0.7, top_k=8),
            dict(temperature=0.9, top_p=0.9), {})]
        keys = [jax.random.PRNGKey(40 + i) for i in range(4)]
        eng = InferenceEngine(model, params, EngineConfig(
            paged=True, n_slots=4, max_len=MAX_LEN, page_len=L,
            kv_dtype=kv_dtype, prefix_share=False))
        with eng:
            hs = [eng.submit(p, sp, rng=k)
                  for p, sp, k in zip(prompts, sps, keys)]
            together = [h.result(timeout=120) for h in hs]
            s1 = eng.stats()
            alone = [eng.submit(p, sp, rng=k).result(timeout=120)
                     for p, sp, k in zip(prompts, sps, keys)]
            s2 = eng.stats()
        for i in range(4):
            np.testing.assert_array_equal(together[i], alone[i],
                                          err_msg=f"request {i}")
        assert s2["decode_compiles"] == 1, s2
        assert s2["sample_compiles"] == 3 + 2, s2
        # alone: 11 decode iterations a request, a sampler in two of four
        assert s2["decode_fetches"] - s1["decode_fetches"] == 4 * 11
        assert s2["sample_dispatches"] - s1["sample_dispatches"] == 2 * 11

    @pytest.mark.parametrize("s", [13, 16])   # sub-page tail / aligned
    def test_resident_kv_error_within_half_scale(self, s):
        """Pool-level per-element bound: on a cold prefill (where the
        hidden states feeding the pool are exact — offset-0 admission
        computes everything in-register, never reading quantized
        prefix), the quantized pool's extracted KV is within scale/2 of
        the exact pool's, elementwise — the quantize-once discipline
        measured end-to-end. Decode-written positions are deliberately
        excluded: once attention reads quantized history the hidden
        states themselves drift, so the per-element bound vs an f32
        pool only holds for prefill-covered positions (the end-to-end
        decode quality is gated by the logit/token ceilings above)."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        prompt = np.random.default_rng(3).integers(
            0, 61, s).astype(np.int32)
        pf = _pool(model, "f32")
        pq = _pool(model, "q8")
        pf.admit(params, prompt, 0, BUCKETS)
        pq.admit(params, prompt, 0, BUCKETS)
        length, ksf, vsf = pf.extract(0)
        length_q, ksq, vsq = pq.extract(0)
        assert length == length_q == s
        for i in range(model.n_layers):
            for exact, got, scales in (
                    (ksf[i], ksq[i], np.asarray(pq.state[i].k.scales)),
                    (vsf[i], vsq[i], np.asarray(pq.state[i].v.scales))):
                row = pq.owned[0]
                per_page = scales[np.asarray(row)]      # (P, nb)
                bound = per_page[
                    :, np.arange(exact[0].size) // wire.QUANT_BLOCK
                ].reshape(exact.shape) / 2
                assert np.all(np.abs(got - exact) <= bound + 1e-6)
                # and the bound is tight enough to matter: the last
                # page's scales are ones only when it never completed
                assert np.any(np.abs(got - exact) > 0)


# ---------------------------------------------------------------------------
# extract / adopt / handoff pass-through
# ---------------------------------------------------------------------------


class TestExtractAdopt:
    @pytest.mark.parametrize("kv_dtype", ["f32", "q8", "q4"])
    def test_extract_zeroes_stale_tail(self, kv_dtype):
        """A released slot's buffers keep the old occupant's values; a
        re-admission with a SHORTER sub-page tail must not ship them:
        positions past ``length`` in the extracted last page are
        zeroed at every width."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(4)
        pool = _pool(model, kv_dtype, prefix_share=False)
        pool.admit(params, rng.integers(0, 61, 15).astype(np.int32),
                   0, BUCKETS)
        pool.release(0)
        # shorter prompt: 11 = one full page + 3-token tail; stale
        # positions 3..7 of the old occupant's tail must extract as 0
        pool.admit(params, rng.integers(0, 61, 11).astype(np.int32),
                   0, BUCKETS)
        length, ks, vs = pool.extract(0)
        assert length == 11
        for arr in ks + vs:
            assert arr.shape[0] == 2
            assert np.all(arr[-1, :, 3:, :] == 0.0)
            assert np.any(arr[-1, :, :3, :] != 0.0)

    @pytest.mark.parametrize("kv_dtype", ["f32", "q8", "q4"])
    @pytest.mark.parametrize("s", [11, 16])   # sub-page tail / aligned
    def test_adopt_round_trip(self, kv_dtype, s):
        """extract → adopt into a second pool → extract again must be
        value-stable at every width, and the adopted slot must keep
        decoding. f32 is bit-identical. For quantized pools the requant
        of already-dequantized pages reproduces the same q codes, but
        the scale pays a double rounding (``fl(fl(levels·s)/levels)``
        can land one ulp off ``s``), so the extracted values agree to
        one ulp of the scale, not bit-for-bit."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        prompt = np.random.default_rng(5).integers(
            0, 61, s).astype(np.int32)
        src = _pool(model, kv_dtype, prefix_share=False)
        dst = _pool(model, kv_dtype, prefix_share=False)
        logits, _, _ = src.admit(params, prompt, 0, BUCKETS)
        length, ks, vs = src.extract(0)
        dst.adopt(1, length, ks, vs)
        length2, ks2, vs2 = dst.extract(1)
        assert length2 == length
        for a, b in zip(ks + vs, ks2 + vs2):
            if kv_dtype == "f32":
                assert np.array_equal(a, b)
            else:
                # same q everywhere, scale within one ulp → relative
                # error bounded by one f32 ulp; exact zeros stay zeros
                assert np.allclose(a, b, rtol=2.5e-7, atol=0.0)
                assert np.array_equal(a == 0.0, b == 0.0)
        # the adopted stream decodes: logits must match the source
        # pool's next step exactly (same resident values in both pools)
        tok = int(np.argmax(np.asarray(logits)[0]))
        for pool, slot in ((src, 0), (dst, 1)):
            pool.ensure_decode_capacity(slot)
        active_s = np.zeros(2, bool)
        active_s[0] = True
        active_d = np.zeros(2, bool)
        active_d[1] = True
        cur_s = np.zeros(2, np.int32)
        cur_d = np.zeros(2, np.int32)
        cur_s[0] = tok
        cur_d[1] = tok
        lg_s = np.asarray(src.decode(params, cur_s, active_s)[1])[0]
        lg_d = np.asarray(dst.decode(params, cur_d, active_d)[1])[1]
        if kv_dtype == "f32":
            assert np.array_equal(lg_s, lg_d)
        else:
            # the sub-page tail pays ONE extra rounding at the handoff
            # boundary (exact f32 → quantized frame → dequantized
            # tail); full pages are bit-identical
            assert np.abs(lg_s - lg_d).max() <= 0.05

    @pytest.mark.parametrize("kv_dtype", ["q8", "q4"])
    @pytest.mark.parametrize("s", [11, 16])
    def test_matched_width_passthrough_bit_identical(self, kv_dtype, s):
        """The no-double-hop contract: a quantized pool's resident bits
        cross the frame VERBATIM when pool and wire widths match — and
        the frame carries the same q codes the dequant→requant trip it
        replaces would produce (one shared block codec; the requant
        scale can sit one ulp off the resident scale — double rounding
        — which is exactly the drift the pass-through eliminates)."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        bits = resolve_kv_bits(kv_dtype)
        prompt = np.random.default_rng(6).integers(
            0, 61, s).astype(np.int32)
        src = _pool(model, kv_dtype, prefix_share=False)
        logits, _, _ = src.admit(params, prompt, 0, BUCKETS)
        lg = np.asarray(logits)[0]
        length, kqs, vqs = src.extract_quantized(0)
        frame_q, nq = frames.encode_frame_quantized(
            7, length, lg, kqs, vqs, bits)
        # same layout and q codes as requantizing the dequantized
        # extraction; scales agree to one ulp
        _, ks, vs = src.extract(0)
        frame_f, nf = frames.encode_frame(7, length, lg, ks, vs, bits)
        assert nq == nf and len(frame_q) == len(frame_f)
        fr_rq = frames.decode_frame(frame_f, keep_bits=bits)
        fr_pt = frames.decode_frame(frame_q, keep_bits=bits)
        for (qa, sa), (qb, sb) in zip(fr_pt.ks + fr_pt.vs,
                                      fr_rq.ks + fr_rq.vs):
            assert np.array_equal(qa, qb)
            assert np.all(np.abs(sa.view(np.int32)
                                 - sb.view(np.int32)) <= 1)
        # decode with keep_bits: pages stay quantized, CRCs checked
        fr = frames.decode_frame(frame_q, keep_bits=bits)
        assert fr.quantized and fr.bits == bits
        for (qa, sa), (qb, sb) in zip(fr.ks + fr.vs, kqs + vqs):
            assert np.array_equal(qa, qb)
            assert np.array_equal(sa, sb)
        # adopt_quantized installs the sender's exact resident bits
        dst = _pool(model, kv_dtype, prefix_share=False)
        dst.adopt_quantized(0, fr.length, fr.ks, fr.vs)
        _, kqs2, vqs2 = dst.extract_quantized(0)
        for (qa, sa), (qb, sb) in zip(kqs + vqs, kqs2 + vqs2):
            assert np.array_equal(qa, qb)
            assert np.array_equal(sa, sb)
        # a mismatched keep_bits dequantizes as before
        fr_f = frames.decode_frame(frame_q, keep_bits=None)
        assert not fr_f.quantized
        assert fr_f.ks[0].dtype == np.float32

    def test_adopt_quantized_requires_quant_pool(self):
        model = _lm()
        pool = _pool(model, "f32")
        with pytest.raises(ValueError, match="quantized pool"):
            pool.extract_quantized(0)
        with pytest.raises(ValueError, match="quantized pool"):
            pool.adopt_quantized(0, 8, [], [])


# ---------------------------------------------------------------------------
# admission rejection + config plumbing
# ---------------------------------------------------------------------------


class TestAdmissionAndConfig:
    def test_prompt_longer_than_every_bucket_is_admitted_in_chunks(self):
        """A tail longer than every bucket used to be refused
        (``tail_too_long``); it is prefilled in chunks of the largest
        bucket's whole pages now, through the one program, with the
        logits of the whole-prompt admission. ``begin`` takes every page
        before a program runs; a slot released half-way gives them all
        back."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        pool = _pool(model, "f32")
        free_before = pool.pool.free_pages
        prompt = (np.arange(21, dtype=np.int32) * 7) % 61
        pool.begin(prompt, 0, (8,))
        assert pool.pool.free_pages == free_before - 3
        assert pool.chunk(params, 0).logits is None      # 8 of 21 tokens
        assert int(pool.lengths[0]) == 0 and len(pool.index) == 0
        pool.release(0)                                  # half prefilled
        assert pool.pool.free_pages == free_before
        assert pool.owned[0] == [] and pool.prefilling == {}
        logits, n_hit, offset = pool.admit(params, prompt, 0, (8,))
        assert (n_hit, offset) == (0, 0)
        assert int(pool.lengths[0]) == 21
        assert pool.compiles.prefill == {8: 1}           # 3 chunks, 1 program
        whole = _pool(model, "f32")
        ref, _, _ = whole.admit(params, prompt, 0, BUCKETS)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        with pytest.raises(ValueError, match="less than one page"):
            pool.admit(params, prompt, 1, (4,))          # page_len is 8
        assert pool.owned[1] == []

    def test_long_tail_after_prefix_hit_keeps_refcounts(self):
        """The dangerous variant: matched prefix pages are read by every
        chunk and written by none, and a tail given up half-way leaves
        their refcounts as they were before ``begin``."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        pool = _pool(model, "f32")
        shared = np.arange(16, dtype=np.int32)
        pool.admit(params, np.concatenate(
            [shared, np.arange(3, dtype=np.int32) + 40]), 0, BUCKETS)
        pool.release(0)
        refs_before = list(pool.pool.refcount)
        hit_pages = [pid for pid, on in enumerate(pool.pool.indexed) if on]
        hit_k = np.asarray(pool.state[0].k.pages)[hit_pages].copy()
        long_tail = np.concatenate(
            [shared, np.arange(9, dtype=np.int32) + 50])
        assert pool.begin(long_tail, 1, (8,)) == (2, 16)  # tail 9 > 8
        first = pool.chunk(params, 1)
        assert (first.offset, first.tokens, first.logits) == (16, 8, None)
        pool.release(1)
        assert list(pool.pool.refcount) == refs_before
        logits, n_hit, offset = pool.admit(params, long_tail, 1, (8,))
        assert (n_hit, offset) == (2, 16)
        np.testing.assert_array_equal(
            np.asarray(pool.state[0].k.pages)[hit_pages], hit_k)
        cold = _pool(model, "f32", prefix_share=False)
        ref, _, _ = cold.admit(params, long_tail, 0, BUCKETS)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        pool.release(1)
        assert list(pool.pool.refcount) == refs_before

    def test_explicit_kv_dtype_needs_no_other_word(self):
        """The engine has one pool, so ``kv_dtype`` alone says how its
        pages are kept."""
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        eng = InferenceEngine(model, params,
                              EngineConfig(kv_dtype="q8", max_len=MAX_LEN))
        assert eng.pool.kv_dtype == "q8" and eng.pool.quant_bits == 8
        eng = InferenceEngine(model, params,
                              EngineConfig(kv_dtype="f32", max_len=MAX_LEN))
        assert eng.pool.kv_dtype == "f32" and eng.pool.quant_bits is None

    def test_env_default_drives_paged_pool(self, monkeypatch):
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        monkeypatch.setenv("DPX_SERVE_KV_DTYPE", "q8")
        eng = InferenceEngine(model, params, EngineConfig(
            paged=True, n_slots=2, max_len=MAX_LEN, page_len=L))
        assert eng.pool.kv_dtype == "q8"
        assert eng.pool.quant_bits == 8
        # every engine of the process: there is no pool it does not size
        eng2 = InferenceEngine(model, params,
                               EngineConfig(max_len=MAX_LEN))
        assert eng2.pool.kv_dtype == "q8"
        # and an explicit width wins over it
        eng3 = InferenceEngine(model, params, EngineConfig(
            max_len=MAX_LEN, kv_dtype="f32"))
        assert eng3.pool.quant_bits is None

    def test_unknown_kv_dtype_raises(self):
        model = _lm()
        with pytest.raises(ValueError, match="kv_dtype"):
            _pool(model, "fp8")
