"""The compute path (docs/compute.md): page-blockwise decode attention,
bf16 mixed precision, and named remat policies.

Contracts pinned here:

- the blockwise decode kernel is value-equivalent to the dense
  full-width softmax it replaces, for contiguous rows AND paged
  pools (GQA, ragged widths, inactive-row write-reselect included);
- dead blocks past every resident length are NEVER touched — proven by
  NaN-poisoning them (a single gathered element would poison the
  output) and by the ``resident_blocks`` trip-count formula;
- a fully-masked visited block contributes exact zeros (the finite
  ``_MASK`` sentinel + explicit probability zeroing — the NaN hazard
  ``-inf`` masking would reintroduce);
- softmax statistics stay float32 under bf16 inputs in both
  ``dense_attention`` and the blockwise kernel (the f32-stats
  contract the mixed-precision mode relies on);
- long-pool/short-request serving stays bit-identical to
  ``generate()`` with ONE decode compile — the kernel change is
  invisible at the token contract;
- ``mixed_precision="bf16"`` tracks the f32 loss trajectory within an
  asserted bound on BOTH front doors, keeps the master f32, and hands
  f32 gradients to the wire;
- remat policies are gradient-equivalent and typed-validated.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_pytorch_tpu as dist
from distributed_pytorch_tpu import models, optim
from distributed_pytorch_tpu.models.generate import (decode_step_slots_paged,
                                                     make_generate_fn)
from distributed_pytorch_tpu.models.transformer import (REMAT_POLICIES,
                                                        resolve_remat)
from distributed_pytorch_tpu.nn.attention import dense_attention
from distributed_pytorch_tpu.nn.paged import ExactSide, KVPages
from distributed_pytorch_tpu.ops.decode_attention import (
    DECODE_BLOCK, blockwise_decode_attention, paged_decode_attention,
    resident_blocks)
from distributed_pytorch_tpu.ops.losses import cross_entropy
from distributed_pytorch_tpu.parallel import make_train_step, mp_cast_params
from distributed_pytorch_tpu.parallel.data_parallel import MP_POLICIES
from distributed_pytorch_tpu.serve import (EngineConfig, InferenceEngine,
                                           SamplingParams)

SCALE = 0.125  # 1/sqrt(64); tests use Dh in {8, 64} with explicit scale


def _dense_ref(hq, k, v, idx, scale):
    """The dense decode softmax the kernels replace (the exact
    pre-blockwise math of a decode step)."""
    b, h, _, dh = hq.shape
    hkv = k.shape[1]
    hq_g = hq.reshape(b, hkv, h // hkv, 1, dh)
    logits = jnp.einsum("bngqd,bnkd->bngqk", hq_g, k).astype(
        jnp.float32) * scale
    mask = jnp.arange(k.shape[2])[None, :] <= idx[:, None]
    logits = jnp.where(mask[:, None, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bngqk,bnkd->bngqd", probs, v).reshape(b, h, 1, dh)


def _rand(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _stores(kp, vp):
    """Exact K/V page stores, one a layer, over the given pool arrays."""
    return [KVPages(ExactSide(k), ExactSide(v)) for k, v in zip(kp, vp)]



class TestBlockwiseKernel:
    def test_matches_dense_reference_gqa_ragged(self):
        """Contiguous cache, GQA (H=4 over Hkv=2), width NOT a block
        multiple: blockwise == dense within f32 merge tolerance."""
        rng = np.random.default_rng(0)
        b, h, hkv, w, dh, blk = 3, 4, 2, 41, 8, 16
        hq = _rand(rng, (b, h, 1, dh))
        k = _rand(rng, (b, hkv, w, dh))
        v = _rand(rng, (b, hkv, w, dh))
        idx = jnp.asarray([0, 7, 40], jnp.int32)
        scale = 1.0 / math.sqrt(dh)
        out = blockwise_decode_attention(hq, k, v, idx, scale=scale,
                                         block_len=blk)
        np.testing.assert_allclose(out, _dense_ref(hq, k, v, idx, scale),
                                   rtol=2e-6, atol=2e-6)

    def test_dead_blocks_never_touched(self):
        """NaN-poison every position past the resident blocks: one
        gathered element would poison the output, so bit-equality with
        the clean run IS the visits-only-resident-blocks claim — and
        the trip count matches ceil((max_len+1)/block)."""
        rng = np.random.default_rng(1)
        b, hkv, w, dh, blk = 2, 2, 64, 8, 16
        hq = _rand(rng, (b, 2 * hkv, 1, dh))
        k = _rand(rng, (b, hkv, w, dh))
        v = _rand(rng, (b, hkv, w, dh))
        idx = jnp.asarray([3, 21], jnp.int32)
        nb = int(resident_blocks(idx, blk, w // blk))
        assert nb == int(max(idx)) // blk + 1 == 2
        clean = blockwise_decode_attention(hq, k, v, idx, scale=SCALE,
                                           block_len=blk)
        k_p = k.at[:, :, nb * blk:, :].set(jnp.nan)
        v_p = v.at[:, :, nb * blk:, :].set(jnp.nan)
        poisoned = blockwise_decode_attention(hq, k_p, v_p, idx,
                                              scale=SCALE, block_len=blk)
        assert bool(jnp.all(jnp.isfinite(poisoned)))
        np.testing.assert_array_equal(np.asarray(clean),
                                      np.asarray(poisoned))

    def test_fully_masked_visited_block_contributes_zero(self):
        """A short row co-resident with a long one sees whole visited
        blocks fully masked; with -inf masking the online merge would
        emit NaN (exp(0)=1 ghosts or -inf - -inf). The finite-sentinel
        fix keeps the short row exactly equal to its dense softmax."""
        rng = np.random.default_rng(2)
        b, hkv, w, dh, blk = 2, 1, 48, 8, 16
        hq = _rand(rng, (b, hkv, 1, dh))
        k = _rand(rng, (b, hkv, w, dh))
        v = _rand(rng, (b, hkv, w, dh))
        idx = jnp.asarray([2, 47], jnp.int32)   # row 0: blocks 1,2 dead
        out = blockwise_decode_attention(hq, k, v, idx, scale=SCALE,
                                         block_len=blk)
        assert bool(jnp.all(jnp.isfinite(out)))
        np.testing.assert_allclose(out, _dense_ref(hq, k, v, idx, SCALE),
                                   rtol=2e-6, atol=2e-6)

    def test_paged_matches_dense_gather_incl_inactive(self):
        """Paged kernel == gather-the-whole-table dense reference, with
        the write-position re-select giving INACTIVE rows (whose pool
        scatter was dropped) their own key — a write-mask's exact value
        semantics."""
        rng = np.random.default_rng(3)
        b, h, hkv, dh, pl, p, n_pages = 3, 4, 2, 8, 8, 6, 13
        hq = _rand(rng, (b, h, 1, dh))
        kp = _rand(rng, (n_pages, hkv, pl, dh))
        vp = _rand(rng, (n_pages, hkv, pl, dh))
        tables = jnp.asarray(rng.integers(0, n_pages, (b, p)), jnp.int32)
        nk = _rand(rng, (b, hkv, 1, dh))
        nv = _rand(rng, (b, hkv, 1, dh))
        idx = jnp.asarray([1, 14, 39], jnp.int32)
        out = paged_decode_attention(hq, kp, vp, tables, idx, nk, nv,
                                     scale=SCALE, page_len=pl)
        # dense reference: gather the full table, re-select at idx
        g = kp[tables].transpose(0, 2, 1, 3, 4).reshape(b, hkv, p * pl, dh)
        gv = vp[tables].transpose(0, 2, 1, 3, 4).reshape(b, hkv, p * pl, dh)
        wm = (jnp.arange(p * pl)[None, :] == idx[:, None])[:, None, :, None]
        ref = _dense_ref(hq, jnp.where(wm, nk, g), jnp.where(wm, nv, gv),
                         idx, SCALE)
        np.testing.assert_allclose(out, ref, rtol=2e-6, atol=2e-6)

    def test_paged_dead_pages_never_gathered(self):
        """Pages only reachable past the resident blocks are NaN-
        poisoned; the paged scan must not read them."""
        rng = np.random.default_rng(4)
        b, hkv, dh, pl, p, n_pages = 2, 2, 8, 8, 6, 8
        hq = _rand(rng, (b, 2 * hkv, 1, dh))
        kp = _rand(rng, (n_pages, hkv, pl, dh))
        vp = _rand(rng, (n_pages, hkv, pl, dh))
        # rows use pages 0..3; pages 4.. are dead-tail table entries
        tables = jnp.asarray([[0, 1, 4, 5, 6, 7],
                              [2, 3, 4, 5, 6, 7]], jnp.int32)
        idx = jnp.asarray([5, 12], jnp.int32)   # max 12 -> 2 pages
        nk = _rand(rng, (b, hkv, 1, dh))
        nv = _rand(rng, (b, hkv, 1, dh))
        assert int(resident_blocks(idx, pl, p)) == 2
        clean = paged_decode_attention(hq, kp, vp, tables, idx, nk, nv,
                                       scale=SCALE, page_len=pl)
        kp_p = kp.at[4:].set(jnp.nan)
        vp_p = vp.at[4:].set(jnp.nan)
        poisoned = paged_decode_attention(hq, kp_p, vp_p, tables, idx,
                                          nk, nv, scale=SCALE, page_len=pl)
        assert bool(jnp.all(jnp.isfinite(poisoned)))
        np.testing.assert_array_equal(np.asarray(clean),
                                      np.asarray(poisoned))

    def test_resident_blocks_formula(self):
        assert int(resident_blocks(jnp.asarray([0], jnp.int32), 16, 8)) == 1
        assert int(resident_blocks(jnp.asarray([15], jnp.int32), 16, 8)) == 1
        assert int(resident_blocks(jnp.asarray([16], jnp.int32), 16, 8)) == 2
        # clamped at the table width however long the lengths claim
        assert int(resident_blocks(jnp.asarray([999], jnp.int32), 16, 8)) == 8


class TestF32StatsContract:
    """bf16 compute must not degrade softmax accumulation — the
    mixed-precision guard of docs/compute.md."""

    def test_dense_attention_f32_stats_under_bf16(self):
        """512 identical keys: a bf16 normalizer (8 mantissa bits)
        cannot even represent the running sum past 256 (256 + 1 == 256
        in bf16), so a bf16-stats softmax would visibly lose mass. The
        f32-stats contract keeps the result at the f32 reference."""
        s, dh = 512, 64
        q = jnp.ones((1, 1, 1, dh), jnp.bfloat16)
        k = jnp.ones((1, 1, s, dh), jnp.bfloat16)
        v = jnp.ones((1, 1, s, dh), jnp.bfloat16)
        out = dense_attention(q, k, v, causal=False)
        assert out.dtype == jnp.bfloat16
        # uniform probs over identical unit values -> exactly 1.0
        np.testing.assert_allclose(np.asarray(out, np.float32), 1.0,
                                   rtol=1e-2)
        # the probabilities themselves are formed in f32: softmax over
        # equal logits is exactly uniform, so the sum is exactly s/s
        probs = jax.nn.softmax(jnp.zeros((s,), jnp.float32))
        assert float(jnp.sum(probs)) == pytest.approx(1.0, abs=1e-6)

    def test_blockwise_f32_stats_under_bf16(self):
        s, dh, blk = 512, 64, 128
        q = jnp.ones((1, 1, 1, dh), jnp.bfloat16)
        k = jnp.ones((1, 1, s, dh), jnp.bfloat16)
        v = jnp.ones((1, 1, s, dh), jnp.bfloat16)
        out = blockwise_decode_attention(
            q, k, v, jnp.asarray([s - 1], jnp.int32),
            scale=1.0 / math.sqrt(dh), block_len=blk)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32), 1.0,
                                   rtol=1e-2)

    def test_dense_fully_masked_row_nan_contract_unchanged(self):
        """Causal with s_q > s_k leaves whole rows with no visible key;
        dense softmax yields NaN there BY DESIGN and the flash kernel
        matches it — pin that the decode-path NaN fix did not leak into
        the training kernels' contract."""
        q = jnp.ones((1, 1, 3, 8))
        k = jnp.ones((1, 1, 1, 8))
        out = dense_attention(q, k, k, causal=True)
        # rows 0,1 sit above the shifted diagonal (off = 1-3 = -2)
        assert bool(jnp.all(jnp.isnan(out[0, 0, 0])))
        assert bool(jnp.all(jnp.isfinite(out[0, 0, 2])))


class TestDecodePathIntegration:
    def test_decode_step_slots_blockwise_equals_dense_path(self):
        """The kernel swap is invisible at the decode-step contract:
        same written caches (bit-exact) and logits within f32 merge
        tolerance of the dense path."""
        model = models.TransformerLM(vocab=61, dim=32, n_layers=2,
                                     n_heads=4, n_kv_heads=2, pos="rope",
                                     max_seq=512)
        params = model.init(jax.random.PRNGKey(0))
        b, per_row, page = 3, 20, 16     # rows of 320 positions
        dh = model.dim // model.n_heads
        rng = np.random.default_rng(5)
        state = [KVPages(ExactSide(_rand(rng, (b * per_row, 2, page, dh))),
                         ExactSide(_rand(rng, (b * per_row, 2, page, dh))))
                 for _ in range(2)]
        tables = jnp.asarray(rng.permutation(b * per_row).reshape(
            b, per_row), jnp.int32)
        lengths = jnp.asarray([0, 130, 300], jnp.int32)
        tokens = jnp.asarray([1, 2, 3], jnp.int32)
        step = lambda blockwise: decode_step_slots_paged(
            model, params, state, tables, lengths, tokens,
            jnp.ones((b,), bool), page_len=page, blockwise=blockwise)
        (lo_b, st_b), (lo_d, st_d) = step(True), step(False)
        ks_b, ks_d = ([st.k.rows(tables) for st in sts]
                      for sts in (st_b, st_d))
        vs_b, vs_d = ([st.v.rows(tables) for st in sts]
                      for sts in (st_b, st_d))
        # layer 0's written K/V precede any attention, so they are
        # bit-identical; deeper layers' writes inherit the f32 merge-
        # order difference of the previous layer's attention output
        np.testing.assert_array_equal(np.asarray(ks_b[0]),
                                      np.asarray(ks_d[0]))
        np.testing.assert_array_equal(np.asarray(vs_b[0]),
                                      np.asarray(vs_d[0]))
        for a, c in zip(ks_b[1:] + vs_b[1:], ks_d[1:] + vs_d[1:]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(lo_b, lo_d, rtol=2e-5, atol=2e-5)

    def test_long_pool_short_requests_bit_identical_one_compile(self):
        """A slot pool sized for 320-position requests serving short
        ones: token streams bit-identical to generate(), ONE decode
        compile — the O(resident) kernel is invisible at the serving
        contract."""
        model = models.TransformerLM(vocab=61, dim=32, n_layers=1,
                                     n_heads=4, n_kv_heads=2, pos="rope",
                                     max_seq=512)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, 61, (s,)).astype(np.int32)
                   for s in (3, 7, 5)]
        sp = SamplingParams(max_new_tokens=6)
        keys = [jax.random.PRNGKey(10 + i) for i in range(3)]
        eng = InferenceEngine(model, params,
                              EngineConfig(n_slots=3, max_len=320))
        with eng:
            outs = [eng.submit(p, sp, rng=k).result(timeout=120)
                    for p, k in zip(prompts, keys)]
        assert eng.pool.compiles.decode == 1
        # retirement releases the slot LENGTH too (the pool's release):
        # a frozen long length would keep max(lengths) — the blockwise
        # trip count — paying for requests that no longer exist
        assert int(jnp.max(eng.pool.lengths)) == 0
        for p, k, out in zip(prompts, keys, outs):
            fn = make_generate_fn(model, sp.max_new_tokens, max_len=320)
            ref = np.asarray(jax.jit(fn)(params, jnp.asarray(p[None]),
                                         k))[0]
            np.testing.assert_array_equal(out, ref)

    def test_paged_long_pool_short_requests_one_compile(self):
        """Paged engine whose tables span 16 pages/slot serving ~2-page
        requests: streams == generate(), ONE paged decode compile."""
        model = models.TransformerLM(vocab=61, dim=32, n_layers=1,
                                     n_heads=4, n_kv_heads=2, pos="rope",
                                     max_seq=256)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 61, (s,)).astype(np.int32)
                   for s in (5, 9)]
        sp = SamplingParams(max_new_tokens=5)
        keys = [jax.random.PRNGKey(20 + i) for i in range(2)]
        eng = InferenceEngine(model, params,
                              EngineConfig(n_slots=2, max_len=128,
                                           paged=True, page_len=8))
        with eng:
            outs = [eng.submit(p, sp, rng=k).result(timeout=120)
                    for p, k in zip(prompts, keys)]
        assert eng.pool.compiles.decode == 1
        for p, k, out in zip(prompts, keys, outs):
            fn = make_generate_fn(model, sp.max_new_tokens, max_len=128)
            ref = np.asarray(jax.jit(fn)(params, jnp.asarray(p[None]),
                                         k))[0]
            np.testing.assert_array_equal(out, ref)

    def test_paged_decode_visits_only_resident_pages(self):
        """The synthetic long-pool/short-request case at the decode-op
        level: NaN-poison every pool page the two requests don't own;
        decode_step_slots_paged must produce finite logits identical to
        the clean pool — the scan visited only ceil(len/page_len)
        blocks of each table."""
        model = models.TransformerLM(vocab=61, dim=32, n_layers=1,
                                     n_heads=4, n_kv_heads=2, pos="rope",
                                     max_seq=256)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(8)
        pl, n_pages, p_per = 8, 32, 12
        dh = model.dim // model.n_heads
        kp = [_rand(rng, (n_pages, 2, pl, dh))]
        vp = [_rand(rng, (n_pages, 2, pl, dh))]
        # slot 0 owns pages 0,1; slot 1 owns 2,3 — tails point at junk
        tables = jnp.asarray(
            [[0, 1] + list(range(10, 20)),
             [2, 3] + list(range(20, 30))], jnp.int32)
        lengths = jnp.asarray([9, 14], jnp.int32)   # 2 pages resident
        tokens = jnp.asarray([1, 2], jnp.int32)
        active = jnp.asarray([True, True])
        nb = int(resident_blocks(lengths, pl, p_per))
        assert nb == 2 == math.ceil((int(max(lengths)) + 1) / pl)
        lo, _ = decode_step_slots_paged(model, params, _stores(kp, vp),
                                        tables, lengths, tokens, active,
                                        page_len=pl)
        poisoned_k = [kp[0].at[4:].set(jnp.nan)]
        poisoned_v = [vp[0].at[4:].set(jnp.nan)]
        lo_p, _ = decode_step_slots_paged(
            model, params, _stores(poisoned_k, poisoned_v), tables, lengths,
            tokens, active, page_len=pl)
        assert bool(jnp.all(jnp.isfinite(lo_p)))
        np.testing.assert_array_equal(np.asarray(lo), np.asarray(lo_p))


# ---------------------------------------------------------------------------
# mixed precision
# ---------------------------------------------------------------------------


def _lm_loss(model):
    def loss_fn(p, toks):
        logits = model.apply(p, toks[:, :-1]).astype(jnp.float32)
        return cross_entropy(logits, toks[:, 1:]), {}
    return loss_fn


def _mp_trajectories(mp, *, world=1, steps=8, backend=None):
    if world > 1 or backend:
        dist.init_process_group(0, world, backend=backend)
    try:
        model = models.TransformerLM(vocab=64, dim=32, n_layers=2,
                                     n_heads=2, max_seq=32)
        params = model.init(jax.random.PRNGKey(0))
        opt = optim.adamw(1e-2)
        step = make_train_step(_lm_loss(model), opt, donate=False,
                               mixed_precision=mp)
        toks = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (4 * max(world, 1), 17), 0, 64,
            dtype=jnp.int32))
        batch = dist.shard_batch(toks) if world > 1 else jnp.asarray(toks)
        p, st = params, opt.init(params)
        losses = []
        for _ in range(steps):
            out = step(p, st, batch)
            p, st = out.params, out.opt_state
            losses.append(float(np.asarray(out.loss).mean()))
        return losses, p
    finally:
        if world > 1 or backend:
            dist.cleanup()


class TestMixedPrecision:
    def test_bf16_tracks_f32_spmd_front_door(self):
        """The asserted loss-trajectory bound, mesh front door (world
        4): bf16 compute with the f32 master stays within 2% relative
        of the f32 step at every one of 8 steps."""
        f32, _ = _mp_trajectories("off", world=4)
        bf16, p = _mp_trajectories("bf16", world=4)
        rel = np.abs(np.array(f32) - np.array(bf16)) / np.abs(f32)
        assert rel.max() < 0.02, (f32, bf16)
        # the master the optimizer updates stays f32
        assert all(l.dtype == jnp.float32
                   for l in jax.tree_util.tree_leaves(p)
                   if jnp.issubdtype(l.dtype, jnp.floating))

    def test_bf16_tracks_f32_host_front_door(self, monkeypatch):
        """Same bound through the host front door (native process
        group, world 1 — the numpy flat-bucket step path)."""
        from distributed_pytorch_tpu.runtime.launcher import find_free_port
        monkeypatch.setenv("DPX_MASTER_PORT", str(find_free_port()))
        f32, _ = _mp_trajectories("off", backend="host")
        monkeypatch.setenv("DPX_MASTER_PORT", str(find_free_port()))
        bf16, _ = _mp_trajectories("bf16", backend="host")
        rel = np.abs(np.array(f32) - np.array(bf16)) / np.abs(f32)
        assert rel.max() < 0.02, (f32, bf16)

    def test_gradients_reach_the_wire_in_f32(self):
        """The cast is linear, so grads come back in the MASTER's dtype
        — the quantized wire and the sharded update see f32 trees."""
        model = models.TransformerLM(vocab=32, dim=16, n_layers=1,
                                     n_heads=2, max_seq=16)
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, 32,
                                  dtype=jnp.int32)
        loss_fn = _lm_loss(model)

        def mp_loss(p, b):
            return loss_fn(mp_cast_params(p), b)

        grads = jax.grad(lambda p: mp_loss(p, toks)[0])(params)
        assert all(g.dtype == jnp.float32
                   for g in jax.tree_util.tree_leaves(grads))

    def test_mp_cast_rule(self):
        tree = {"w": jnp.ones((2,), jnp.float32),
                "i": jnp.ones((2,), jnp.int32),
                "b": jnp.ones((2,), jnp.bfloat16)}
        out = mp_cast_params(tree)
        assert out["w"].dtype == jnp.bfloat16
        assert out["i"].dtype == jnp.int32
        assert out["b"].dtype == jnp.bfloat16

    def test_typed_rejection_and_env_default(self, monkeypatch):
        model = models.DummyModel(in_dim=1, hidden_dim=4, n_classes=2)

        def loss_fn(p, b):
            return jnp.float32(0.0), {}

        with pytest.raises(ValueError, match="mixed_precision"):
            make_train_step(loss_fn, optim.adamw(1e-3),
                            mixed_precision="fp8")
        assert set(MP_POLICIES) == {"off", "bf16"}
        # env default: DPX_MP_POLICY drives the None case (typed knob)
        monkeypatch.setenv("DPX_MP_POLICY", "bogus")
        with pytest.raises(ValueError, match="mixed_precision"):
            make_train_step(loss_fn, optim.adamw(1e-3))
        monkeypatch.setenv("DPX_MP_POLICY", "bf16")
        make_train_step(loss_fn, optim.adamw(1e-3))   # resolves + wraps


# ---------------------------------------------------------------------------
# remat policies
# ---------------------------------------------------------------------------


class TestRematPolicies:
    def test_gradient_equivalence_across_policies(self):
        """Remat changes WHEN activations exist, never the math: every
        policy's gradients match the no-remat gradients."""
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 64,
                                  dtype=jnp.int32)
        flat = {}
        for pol in REMAT_POLICIES:
            model = models.TransformerLM(vocab=64, dim=32, n_layers=2,
                                         n_heads=2, max_seq=32, remat=pol)
            params = model.init(jax.random.PRNGKey(0))
            g = jax.grad(lambda p: cross_entropy(
                model.apply(p, toks[:, :-1]).astype(jnp.float32),
                toks[:, 1:]))(params)
            flat[pol] = np.concatenate(
                [np.ravel(l) for l in jax.tree_util.tree_leaves(g)])
        for pol in ("full", "dots_saveable"):
            np.testing.assert_allclose(flat[pol], flat["none"],
                                       rtol=1e-5, atol=1e-6)

    def test_resolution_bools_env_and_rejection(self, monkeypatch):
        assert resolve_remat(False) == "none"
        assert resolve_remat(True) == "full"
        assert resolve_remat("dots_saveable") == "dots_saveable"
        monkeypatch.setenv("DPX_REMAT", "full")
        assert resolve_remat(None) == "full"
        monkeypatch.delenv("DPX_REMAT")
        assert resolve_remat(None) == "none"
        with pytest.raises(ValueError, match="remat"):
            resolve_remat("everything")
        m = models.TransformerLM(vocab=8, dim=8, n_layers=1, n_heads=1,
                                 max_seq=8, remat="full")
        assert m.remat is True and m.remat_policy == "full"


# ---------------------------------------------------------------------------
# flash crossover knob
# ---------------------------------------------------------------------------


class TestFlashMinSeqKnob:
    def test_env_drives_dispatch(self, monkeypatch):
        """DPX_FLASH_MIN_SEQ is read at attn_fn BUILD time: above the
        threshold the pallas kernel runs, below it the dense einsum —
        observed by making the kernel path unmistakable."""
        # the module, not the same-named function ops/__init__ re-exports
        # (import ... as would resolve the package ATTRIBUTE, which the
        # __init__ from-import shadowed with the function)
        import importlib
        fa = importlib.import_module(
            "distributed_pytorch_tpu.ops.flash_attention")

        calls = []
        real = fa.flash_attention

        def spy(*a, **kw):
            calls.append(1)
            return real(*a, **kw)

        monkeypatch.setattr(fa, "flash_attention", spy)
        q = jnp.asarray(np.random.default_rng(0).standard_normal(
            (1, 2, 32, 8)), jnp.float32)
        monkeypatch.setenv("DPX_FLASH_MIN_SEQ", "64")
        fa.make_flash_attn_fn()(q, q, q, causal=True)
        assert not calls                       # 32 < 64 -> dense
        monkeypatch.setenv("DPX_FLASH_MIN_SEQ", "16")
        fa.make_flash_attn_fn()(q, q, q, causal=True)
        assert calls                           # 32 >= 16 -> kernel
