"""Flash-attention pallas kernel vs the dense reference implementation.

Values and gradients must match ``nn.attention.dense_attention`` (the
straightforward softmax(qk)v einsum) — causal and non-causal, block-aligned
and ragged sequence lengths, float32 and bfloat16. Runs in interpret mode
on the CPU test mesh; the same kernels compile on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.nn.attention import dense_attention
from distributed_pytorch_tpu.ops import flash_attention, make_flash_attn_fn


def _qkv(key, b=2, h=2, s_q=64, s_k=64, d=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, s_q, d), dtype)
    k = jax.random.normal(kk, (b, h, s_k, d), dtype)
    v = jax.random.normal(kv, (b, h, s_k, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s_q,s_k,bq,bk", [
    (64, 64, 16, 16),     # block-aligned
    (50, 50, 16, 16),     # ragged: pad+mask path
    (32, 64, 16, 16),     # cross lengths (causal frontier offset)
])
def test_forward_matches_dense(causal, s_q, s_k, bq, bk):
    q, k, v = _qkv(jax.random.PRNGKey(0), s_q=s_q, s_k=s_k)
    want = dense_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s_q,s_k,d", [
    (64, 64, 16), (50, 50, 16),
    (64, 64, 192),   # latent attention's expanded head: nope 128 + rope 64
])
def test_grads_match_dense(causal, s_q, s_k, d):
    q, k, v = _qkv(jax.random.PRNGKey(1), s_q=s_q, s_k=s_k, d=d)
    if d == 192:     # values 128 wide, padded to the keys' width
        v = v.at[..., 128:].set(0.0)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=16, block_k=16) ** 2)

    np.testing.assert_allclose(
        flash_attention(q, k, v, causal=causal, block_q=16, block_k=16),
        dense_attention(q, k, v, causal=causal), atol=2e-5, rtol=2e-5)
    want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("s_q,s_k,bq,bk", [
    (64, 32, 16, 16),    # whole q-tiles above the diagonal (body skipped)
    (40, 24, 16, 16),    # ragged + partially-masked tiles
    (64, 16, 64, 16),    # fully-masked rows inside an executed tile
])
def test_causal_sq_gt_sk_nan_rows_match_dense(s_q, s_k, bq, bk):
    """Causal with s_q > s_k: query rows above the shifted diagonal attend
    to nothing. Dense softmax over an all--inf row is NaN; the kernel must
    emit NaN for exactly those rows rather than a mean of masked-out v rows
    (regression: the _finish guard used to handle only the never-executed
    l==0 case)."""
    q, k, v = _qkv(jax.random.PRNGKey(6), s_q=s_q, s_k=s_k)
    want = np.asarray(dense_attention(q, k, v, causal=True))
    got = np.asarray(flash_attention(q, k, v, causal=True,
                                     block_q=bq, block_k=bk))
    nan_rows = np.isnan(want).all(axis=-1)
    assert nan_rows.any(), "case must exercise fully-masked rows"
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want))
    np.testing.assert_allclose(got[~nan_rows], want[~nan_rows],
                               atol=2e-5, rtol=2e-5)


def test_bfloat16_close():
    q, k, v = _qkv(jax.random.PRNGKey(2), dtype=jnp.bfloat16)
    want = dense_attention(q, k, v, causal=True).astype(jnp.float32)
    got = flash_attention(q, k, v, causal=True, block_q=16,
                          block_k=16).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2, rtol=2e-2)


def test_jit_and_scale_arg():
    q, k, v = _qkv(jax.random.PRNGKey(3))
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, scale=0.5,
                                                block_q=32, block_k=32))
    want = dense_attention(q, k, v, scale=0.5)
    np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_mha_with_flash_attn_fn():
    """A model built with make_flash_attn_fn matches the dense-core model."""
    from distributed_pytorch_tpu.nn.attention import MultiHeadAttention

    mha_dense = MultiHeadAttention(32, 4, causal=True)
    mha_flash = MultiHeadAttention(32, 4, causal=True,
                                   attn_fn=make_flash_attn_fn(16, 16, min_seq_flash=None))
    params = mha_dense.init(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 48, 32))
    want = mha_dense.apply(params, x)
    got = mha_flash.apply(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_min_seq_crossover_dispatch(monkeypatch):
    """Below min_seq_flash keys the attn_fn must run the dense einsum;
    at/above it, the kernel. Verified by
    counting kernel entries, and the two paths must agree numerically."""
    import importlib
    fa = importlib.import_module(
        "distributed_pytorch_tpu.ops.flash_attention")

    calls = {"kernel": 0}
    real = fa.flash_attention

    def counting(*a, **kw):
        calls["kernel"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(fa, "flash_attention", counting)
    attn_fn = fa.make_flash_attn_fn(16, 16, min_seq_flash=64)

    q, k, v = _qkv(jax.random.PRNGKey(11), s_q=32, s_k=32)
    short = attn_fn(q, k, v, causal=True)
    assert calls["kernel"] == 0  # dense path took it
    np.testing.assert_allclose(
        np.asarray(short), np.asarray(dense_attention(q, k, v, causal=True)),
        atol=2e-5, rtol=2e-5)

    q, k, v = _qkv(jax.random.PRNGKey(12), s_q=64, s_k=64)
    long = attn_fn(q, k, v, causal=True)
    assert calls["kernel"] == 1  # kernel took it
    np.testing.assert_allclose(
        np.asarray(long), np.asarray(dense_attention(q, k, v, causal=True)),
        atol=2e-5, rtol=2e-5)

    # None disables the fallback entirely
    always = fa.make_flash_attn_fn(16, 16, min_seq_flash=None)
    q, k, v = _qkv(jax.random.PRNGKey(13), s_q=32, s_k=32)
    always(q, k, v, causal=True)
    assert calls["kernel"] == 2


@pytest.mark.parametrize("s_q,s_k,window,bq,bk", [
    (64, 64, 16, 16, 16),   # window spans exactly one tile
    (50, 50, 7, 16, 16),    # ragged length, window not tile-aligned
    (64, 64, 1, 16, 16),    # degenerate: attend to self only
    (48, 48, 100, 16, 16),  # window larger than sequence == plain causal
    (32, 64, 8, 16, 16),    # cross lengths: off > 0 shifts the band
    (24, 48, 5, 8, 8),      # cross lengths, ragged, small blocks
])
@pytest.mark.slow
def test_sliding_window_matches_dense(s_q, s_k, window, bq, bk):
    """Causal sliding-window attention: values AND grads match the dense
    masked reference (the lower-edge tile skip must agree with the mask
    in both backward kernels too, including the cross-length offset that
    shifts the whole band when s_q != s_k)."""
    q, k, v = _qkv(jax.random.PRNGKey(7), s_q=s_q, s_k=s_k)
    want = dense_attention(q, k, v, causal=True, window=window)
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)

    def lf(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       window=window, block_q=bq,
                                       block_k=bk) ** 2)

    def ld(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True,
                                       window=window) ** 2)

    g = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    w = jax.grad(ld, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, w, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name}")


def test_window_requires_causal():
    q, k, v = _qkv(jax.random.PRNGKey(8))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="causal"):
        dense_attention(q, k, v, causal=False, window=8)


def _tpu_lowering(fn, *args):
    """StableHLO text of ``fn`` lowered for the TPU from this CPU host."""
    return fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("shape", [
    (8, 12, 12, 1024, 64, None),     # the flagship's attention
    (2, 12, 12, 4096, 64, None),
    (4, 8, 2, 2048, 128, None),      # GQA, head_dim 128
    (2, 12, 12, 4096, 64, 512),      # sliding window
    (1, 32, 32, 8192, 192, None),    # latent attention expanded, 8k
])
def test_tpu_lowering_is_three_mosaic_calls(shape):
    """What the chip compiles is the Mosaic kernel, not the interpreter:
    forward + backward lower to exactly three ``tpu_custom_call``s (fwd,
    dK/dV, dQ); the interpreted build this CPU suite runs holds none."""
    b, h, h_kv, s, d, window = shape
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, h_kv, s, d), jnp.bfloat16)

    def grad_fn(interpret):
        return jax.jit(jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, window=window,
                interpret=interpret).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))

    assert _tpu_lowering(grad_fn(False), q, kv, kv).count(
        "tpu_custom_call") == 3
    assert "tpu_custom_call" not in _tpu_lowering(grad_fn(True), q, kv, kv)


def test_tpu_lowering_under_gspmd_is_a_shard_map_island(group8):
    """XLA cannot partition a Mosaic call ("wrap the call in a
    shard_map"), so on mesh-sharded operands the kernel must lower as an
    island by itself — the front door's ZeRO/tp spec points and
    FROM_INPUTS run it inside a GSPMD program."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    import distributed_pytorch_tpu as dist

    sh = NamedSharding(dist.get_mesh(), P("dp"))
    q = jax.device_put(jnp.ones((8, 4, 256, 64), jnp.bfloat16), sh)
    fn = jax.jit(jax.grad(
        lambda q: flash_attention(q, q, q, causal=True,
                                  interpret=False)
        .astype(jnp.float32).sum()))
    assert _tpu_lowering(fn, q).count("tpu_custom_call") == 3


def test_interpret_only_where_cpu_was_asked_for():
    """interpret=None: interpreted because THIS suite selected the cpu
    platform; a CPU that JAX fell back to (no platform selected, chip
    missing) must raise instead of grinding through the interpreter."""
    import importlib
    fa = importlib.import_module(
        "distributed_pytorch_tpu.ops.flash_attention")

    assert jax.config.jax_platforms == "cpu"
    assert fa._interpret_default(None) is True
    assert fa._interpret_default(False) is False
    jax.config.update("jax_platforms", None)
    try:
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            fa._interpret_default(None)
    finally:
        jax.config.update("jax_platforms", "cpu")


# ---------------------------------------------------------------------------
# heads wider than one lane group, values narrower than the keys (PR 36)
# ---------------------------------------------------------------------------


def _wide_qkv(key, s_q, s_k, h, h_kv, d_v, d=192):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (1, h, s_q, d), jnp.float32),
            jax.random.normal(kk, (1, h_kv, s_k, d), jnp.float32),
            jax.random.normal(kv, (1, h_kv, s_k, d_v), jnp.float32))


@pytest.mark.parametrize("s_q,s_k,h,h_kv,d_v,bq,bk,causal,window", [
    (2000, 2000, 2, 2, 128, None, None, True, None),  # default tiles, ragged:
    #   forward 1024 x 1024, backward 512 x 512
    (3000, 3000, 2, 1, 128, 256, 1024, True, None),   # grouped, bq < bk
    (2048, 2048, 1, 1, 192, 512, 256, True, None),    # whole tiles, bq > bk
    (1000, 2000, 2, 2, 128, 512, 256, True, None),    # s_q != s_k
    (1024, 3000, 2, 1, 192, 256, 1024, True, None),
    (2000, 2000, 2, 1, 128, 256, 1024, False, None),  # no frontier to clamp
    (1024, 1024, 2, 2, 192, None, None, False, None),
    (2000, 2000, 2, 2, 128, 256, 256, True, 300),     # the band's lower edge
])
def test_wide_heads_match_dense(s_q, s_k, h, h_kv, d_v, bq, bk, causal,
                                window):
    """Keys 192 wide with values 128 or 192: output, and the gradients of
    q, k and v, against the dense einsum. The output and dV are as wide as
    the values."""
    q, k, v = _wide_qkv(jax.random.PRNGKey(s_q + d_v), s_q, s_k, h, h_kv,
                        d_v)
    # a cotangent that is not a function of the output, so each gradient
    # is one vjp of the kernel and nothing else
    w = jax.random.normal(jax.random.PRNGKey(9), (1, h, s_q, d_v))

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) * w)

    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, window=window, block_q=bq, block_k=bk)
    dense = lambda q, k, v: dense_attention(q, k, v, causal=causal,
                                            window=window)
    got = flash(q, k, v)
    assert got.shape == (1, h, s_q, d_v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense(q, k, v)),
                               atol=1e-4, rtol=1e-4)
    gq, gk, gv = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    wq, wk, wv = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    assert gv.shape == v.shape
    for g, want, name in ((gq, wq, "q"), (gk, wk, "k"), (gv, wv, "v")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   atol=2e-4, rtol=2e-3,
                                   err_msg=f"d{name} mismatch")


def test_narrow_values_equal_the_padded_form():
    """Values 128 wide against the same values padded with zeros to the
    keys' 192 and the result cut back, which is what callers did before
    the kernel took them narrow: the columns dropped were zeros."""
    q, k, v = _wide_qkv(jax.random.PRNGKey(3), 600, 600, 2, 1, 128)
    vp = jnp.pad(v, ((0, 0),) * 3 + ((0, 64),))
    narrow = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             block_q=256, block_k=256)
    padded = lambda q, k, vp: narrow(q, k, vp)[..., :128]
    np.testing.assert_allclose(np.asarray(narrow(q, k, v)),
                               np.asarray(padded(q, k, vp)),
                               atol=1e-6, rtol=1e-6)
    g = jax.grad(lambda *a: jnp.sum(narrow(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    gp = jax.grad(lambda *a: jnp.sum(padded(*a) ** 2), argnums=(0, 1, 2))(
        q, k, vp)
    for a, b in zip(g, (gp[0], gp[1], gp[2][..., :128])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def _specs_of_a_call(monkeypatch, q, k, v, **kw):
    """The (grid, in_specs) of the three ``pallas_call``s that forward +
    backward of one call build, in the order built: fwd, dK/dV, dQ."""
    import importlib
    fa = importlib.import_module(
        "distributed_pytorch_tpu.ops.flash_attention")
    seen = []
    real = fa.pl.pallas_call

    def recording(kernel, **call_kw):
        seen.append((call_kw["grid"], call_kw["in_specs"]))
        return real(kernel, **call_kw)

    monkeypatch.setattr(fa.pl, "pallas_call", recording)
    jax.clear_caches()
    from distributed_pytorch_tpu.ops import flash_attention_with_lse
    jax.eval_shape(jax.grad(
        lambda q, k, v: flash_attention_with_lse(q, k, v, **kw)[0].sum(),
        argnums=(0, 1, 2)), q, k, v)
    monkeypatch.undo()
    jax.clear_caches()
    assert len(seen) == 3
    return fa, seen


@pytest.mark.parametrize("s_q,s_k,bq,bk,window,diag_offset", [
    (2048, 2048, 256, 256, None, 0),
    (2000, 2000, 256, 512, None, 0),    # ragged, bq < bk
    (3000, 3000, 1024, 256, None, 0),   # bq > bk
    (1000, 2000, 256, 256, None, 0),    # the diagonal shifted right
    (2000, 1000, 256, 256, None, 0),    # q tiles that see nothing
    (2048, 2048, 256, 256, 300, 0),     # a band: skipped tiles on both sides
    (2000, 3000, 512, 256, 700, 0),
    (1024, 1024, 256, 256, 1500, 1024),  # a windowed ring hop's kv block
])
def test_index_maps_name_the_nearest_tile_the_frontier_admits(
        monkeypatch, s_q, s_k, bq, bk, window, diag_offset):
    """The wide path's causal index maps, read off the ``pallas_call``s
    themselves, at every grid step: the grid's own index wherever
    ``_frontier_ok`` runs the body, and on a skipped step the nearest tile
    it admits for that outer index (past the diagonal the LAST visible
    one), so the pipeline is handed the block it holds and copies nothing.
    Operands on the other grid axis keep the grid's index."""
    q, k, v = (jax.ShapeDtypeStruct(s, jnp.float32) for s in
               ((1, 1, s_q, 192), (1, 1, s_k, 192), (1, 1, s_k, 128)))
    fa, calls = _specs_of_a_call(monkeypatch, q, k, v, causal=True,
                                 block_q=bq, block_k=bk, window=window,
                                 diag_offset=diag_offset)
    n_q, n_k = -(-s_q // bq), -(-s_k // bk)
    ok = np.array([[bool(fa._frontier_ok(
        iq, ik, block_q=bq, block_k=bk, q_len=s_q, k_len=s_k,
        window=window, diag_offset=diag_offset))
        for ik in range(n_k)] for iq in range(n_q)])
    assert not ok.all() and ok.any()

    def nearest(visible, i):
        """i if visible, else the closest visible index (None: any)."""
        idx = np.flatnonzero(visible)
        if idx.size == 0:
            return None
        return int(idx[np.argmin(np.abs(idx - i))])

    def named(spec, *step):
        return tuple(int(x) for x in spec.index_map(*step))

    (g_f, s_f), (g_kv, s_kv), (g_q, s_q_) = calls
    assert g_f == g_q == (1, n_q, n_k) and g_kv == (1, n_k, n_q)
    for iq in range(n_q):
        for ik in range(n_k):
            want_k = nearest(ok[iq], ik)
            want_q = nearest(ok[:, ik], iq)
            # forward (q k v) and dQ (q k v dO lse delta): K and V held
            for specs in (s_f, s_q_):
                for spec in specs[1:3]:
                    got = named(spec, 0, iq, ik)
                    assert 0 <= got[1] < n_k
                    assert want_k is None or got == (0, want_k, 0), \
                        (iq, ik, got, want_k)
                for spec in (specs[0],) + tuple(specs[3:]):
                    assert named(spec, 0, iq, ik) == (0, iq, 0)
            # dK/dV (q k v dO lse delta): the q side held
            for j, spec in enumerate(s_kv):
                got = named(spec, 0, ik, iq)
                if j in (1, 2):
                    assert got == (0, ik, 0)
                else:
                    assert 0 <= got[1] < n_q
                    assert want_q is None or got == (0, want_q, 0), \
                        (iq, ik, got, want_q)


def test_head_widths_up_to_128_keep_the_plain_index_maps(monkeypatch):
    """A call at head 64 or 128 with values as wide builds what it built
    before PR 36: index maps that name the grid's own indices, and no
    ``vmem_limit_bytes``."""
    for d in (64, 128):
        q = jax.ShapeDtypeStruct((1, 1, 1024, d), jnp.float32)
        _, calls = _specs_of_a_call(monkeypatch, q, q, q, causal=True,
                                    block_q=256, block_k=256)
        (_, s_f), (_, s_kv), (_, s_q_) = calls
        for specs in (s_f, s_q_):
            assert tuple(specs[1].index_map(0, 0, 3)) == (0, 3, 0)
        assert tuple(s_kv[0].index_map(0, 3, 0)) == (0, 0, 0)

    def lowered(d, d_v):
        q = jax.ShapeDtypeStruct((1, 2, 2048, d), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((1, 2, 2048, d_v), jnp.bfloat16)
        return _tpu_lowering(jax.jit(jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True,
                interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))), q, q, v)

    # ``vmem_limit_bytes`` reaches the custom call as a scoped memory size
    assert "scoped_memory_configs" not in lowered(128, 128)
    assert lowered(192, 128).count("scoped_memory_configs") == 3


# (d, s_q, s_k, window, forward tiles, backward tiles) as _block_sizes
# returned them at the commit before PR 36
_TILES_BEFORE_PR36 = [
    (64, 1000, 1000, None, (1024, 1024), (512, 512)),
    (64, 1000, 1000, 128, (1024, 128), (512, 128)),
    (64, 1000, 1000, 600, (1024, 640), (512, 512)),
    (64, 1024, 4096, None, (1024, 1024), (512, 512)),
    (64, 1024, 4096, 128, (1024, 128), (512, 128)),
    (64, 8192, 8192, None, (1024, 1024), (512, 512)),
    (64, 8192, 8192, 600, (1024, 640), (512, 512)),
    (128, 512, 512, None, (512, 512), (256, 256)),
    (128, 1000, 1000, None, (512, 512), (256, 256)),
    (128, 1000, 1000, 128, (512, 128), (256, 128)),
    (128, 1024, 4096, 600, (512, 512), (256, 256)),
    (128, 8192, 8192, None, (512, 512), (256, 256)),
    (128, 8192, 8192, 128, (512, 128), (256, 128)),
]


@pytest.mark.parametrize("d,s_q,s_k,window,fwd,bwd", _TILES_BEFORE_PR36)
def test_tiles_at_head_widths_up_to_128_are_what_they_were(d, s_q, s_k,
                                                           window, fwd, bwd):
    from distributed_pytorch_tpu.ops.flash_attention import _block_sizes
    assert _block_sizes(s_q, s_k, None, None, d=d, window=window) == fwd
    assert _block_sizes(s_q, s_k, None, None, d=d, bwd=True,
                        window=window) == bwd
    # explicit tiles are the caller's, clamped to the sequence
    assert _block_sizes(s_q, s_k, 64, 4096, d=d, bwd=True,
                        window=window) == (64, min(4096, s_k))
