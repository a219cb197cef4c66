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
