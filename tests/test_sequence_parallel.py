"""Ring attention (sequence parallelism): exactness vs dense attention,
causal correctness, and the full dp x tp x sp mesh-composed training step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import distributed_pytorch_tpu as dist
from distributed_pytorch_tpu import models, optim
from distributed_pytorch_tpu.nn.attention import dense_attention
from distributed_pytorch_tpu.ops.losses import cross_entropy_per_example
from distributed_pytorch_tpu.parallel.sequence import ring_attention
from distributed_pytorch_tpu.parallel.spmd import (make_gspmd_ring_attn_fn,
                                                   make_spmd_train_step,
                                                   shard_batch_spec)
from distributed_pytorch_tpu.parallel.tensor import (
    replicated_specs, shard_params, transformer_lm_param_specs)
from distributed_pytorch_tpu.runtime import context


@pytest.fixture
def sp_mesh8():
    mesh = context.init_mesh(sp=8)
    yield mesh
    dist.cleanup()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h_kv", [4, 2, 1])
def test_ring_attention_matches_dense(sp_mesh8, causal, h_kv):
    """Ring attention over 8 sequence shards == dense attention, exactly
    — including GQA kv heads (h_kv < h) via the grouped block update.
    h_kv=2 is the true grouped case that pins the contiguous
    query-group convention (MQA h_kv=1 cannot — every mapping is
    equivalent there)."""
    rng = np.random.default_rng(0)
    b, h, s, d = 2, 4, 32, 8
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h_kv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h_kv, s, d)), jnp.float32)

    want = dense_attention(q, k, v, causal=causal)

    spec = P(None, None, "sp", None)
    f = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp",
                                       causal=causal),
        mesh=sp_mesh8,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    got = jax.jit(f)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_gspmd_ring_attn_island(sp_mesh8):
    """The shard_map island composes inside a jitted GSPMD program."""
    attn = make_gspmd_ring_attn_fn(sp_mesh8)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 2, 16, 4)), jnp.float32)
    got = jax.jit(lambda q: attn(q, q, q, causal=True))(q)
    want = dense_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def _lm_loss(model):
    def loss_fn(p, batch):
        x, y = batch
        logits = model.apply(p, x)
        per_tok = cross_entropy_per_example(logits, y)
        return per_tok.mean(), {}
    return loss_fn


@pytest.mark.slow
def test_dp_tp_sp_mesh_train_step():
    """Full composition: batch over dp=2, heads/mlp over tp=2, sequence
    over sp=2 — one jitted train step, loss matches the single-device
    run of the same model/batch."""
    mesh = context.init_mesh(dp=2, tp=2, sp=2)
    try:
        model = models.TransformerLM(
            vocab=32, dim=16, n_layers=2, n_heads=2, max_seq=8,
            attn_fn=make_gspmd_ring_attn_fn(mesh))
        ref_model = models.TransformerLM(
            vocab=32, dim=16, n_layers=2, n_heads=2, max_seq=8)

        params0 = ref_model.init(jax.random.PRNGKey(0))
        specs = transformer_lm_param_specs(model)
        params = shard_params(params0, specs, mesh)
        opt = optim.adamw(1e-3)
        opt_state = opt.init(params)

        rng = np.random.default_rng(0)
        toks = rng.integers(0, 32, (4, 8)).astype(np.int32)
        batch = shard_batch_spec((toks, toks), mesh, P("dp", "sp"))

        step = make_spmd_train_step(_lm_loss(model), opt, donate=False)
        out = step(params, opt_state, batch)

        # single-device reference: same params, same batch
        ref_loss, _ = _lm_loss(ref_model)(params0, (jnp.asarray(toks),
                                                    jnp.asarray(toks)))
        np.testing.assert_allclose(float(out.loss), float(ref_loss),
                                   rtol=2e-5)
        # params stay sharded per spec after the update
        qkv_w = out.params["blocks"][0]["attn"]["qkv"]["w"]
        assert qkv_w.sharding.spec == P(None, "tp")

        # and training actually progresses under the full mesh
        losses = [float(out.loss)]
        for _ in range(3):
            out = step(out.params, out.opt_state, batch)
            losses.append(float(out.loss))
        assert losses[-1] < losses[0]
    finally:
        dist.cleanup()


def test_init_mesh_validation():
    with pytest.raises(ValueError):
        context.init_mesh(dp=3, tp=2)  # 6 != 8 devices


# ---------------------------------------------------------------------------
# ring FLASH attention (pallas core per ring hop)
# ---------------------------------------------------------------------------

from distributed_pytorch_tpu.ops import flash_attention_with_lse  # noqa: E402
from distributed_pytorch_tpu.parallel.sequence import (  # noqa: E402
    ring_flash_attention)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_with_lse_values_and_lse(causal):
    """The lse output equals dense logsumexp of the scaled logits."""
    rng = np.random.default_rng(3)
    b, h, s, d = 2, 2, 32, 8
    q, k, v = (jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
               for _ in range(3))
    o, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                      block_q=16, block_k=16)
    want_o = dense_attention(q, k, v, causal=causal)
    logits = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        mask = np.tril(np.ones((s, s), bool))
        logits = np.where(mask, logits, -np.inf)
    want_lse = np.log(np.exp(logits - logits.max(-1, keepdims=True))
                      .sum(-1)) + logits.max(-1)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), want_lse,
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_with_lse_grads_include_lse_cotangent(causal):
    """Gradients when the LSE participates in the loss: checks the
    g_lse -> delta adjustment in the backward kernels against autodiff
    through a dense implementation."""
    rng = np.random.default_rng(4)
    b, h, s, d = 1, 2, 24, 8
    q, k, v = (jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
               for _ in range(3))

    def loss_flash(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                          block_q=8, block_k=8)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    def loss_dense(q, k, v):
        logits = (jnp.einsum("bhqd,bhkd->bhqk", q, k)
                  .astype(jnp.float32)) / jnp.sqrt(jnp.float32(d))
        if causal:
            m = jnp.tril(jnp.ones((s, s), bool))
            logits = jnp.where(m, logits, -jnp.inf)
        lse = jax.nn.logsumexp(logits, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd",
                       jnp.exp(logits - lse[..., None]), v)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    w = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g, w, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_dense(sp_mesh8, causal):
    """Ring flash attention over 8 sequence shards == dense attention."""
    rng = np.random.default_rng(5)
    b, h, s, d = 2, 2, 64, 8
    q, k, v = (jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
               for _ in range(3))
    want = dense_attention(q, k, v, causal=causal)
    spec = P(None, None, "sp", None)
    f = jax.shard_map(
        lambda q, k, v: ring_flash_attention(q, k, v, axis_name="sp",
                                             causal=causal, block_q=8,
                                             block_k=8),
        mesh=sp_mesh8, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    got = jax.jit(f)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_grads_match_dense(sp_mesh8, causal):
    """jax.grad through the unrolled ring (reverse ppermutes + the flash
    lse backward) == grads of dense attention."""
    rng = np.random.default_rng(6)
    b, h, s, d = 1, 2, 32, 8
    q, k, v = (jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
               for _ in range(3))
    spec = P(None, None, "sp", None)

    ring = jax.shard_map(
        lambda q, k, v: ring_flash_attention(q, k, v, axis_name="sp",
                                             causal=causal, block_q=4,
                                             block_k=4),
        mesh=sp_mesh8, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    g = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    w = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g, w, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name}")


def test_dp_tp_sp_tied_embeddings_parity():
    """Tied embeddings under tensor parallelism: the tok table takes the
    vocab sharding (P('tp', None) — the transposed head sharding), and
    the mesh loss matches the single-device run exactly."""
    mesh = context.init_mesh(dp=2, tp=2, sp=2)
    try:
        kw = dict(vocab=32, dim=16, n_layers=2, n_heads=2, max_seq=8,
                  tie_embeddings=True)
        model = models.TransformerLM(
            attn_fn=make_gspmd_ring_attn_fn(mesh), **kw)
        ref_model = models.TransformerLM(**kw)
        params0 = ref_model.init(jax.random.PRNGKey(0))
        assert "head" not in params0
        params = shard_params(params0, transformer_lm_param_specs(model),
                              mesh)
        assert params["tok"]["emb"].sharding.spec == P("tp", None)
        opt = optim.adamw(1e-3)

        toks = np.random.default_rng(0).integers(0, 32, (4, 8)) \
            .astype(np.int32)
        step = make_spmd_train_step(_lm_loss(model), opt, donate=False)
        batch = shard_batch_spec((toks, toks), mesh, P("dp", "sp"))
        out = step(params, opt.init(params), batch)
        ref_loss, _ = _lm_loss(ref_model)(params0, (jnp.asarray(toks),
                                                    jnp.asarray(toks)))
        np.testing.assert_allclose(float(out.loss), float(ref_loss),
                                   rtol=2e-5)
    finally:
        dist.cleanup()


# ---------------------------------------------------------------------------
# striped (load-balanced) causal ring
# ---------------------------------------------------------------------------


def test_stripe_tokens_layout_and_roundtrip():
    """Shard r of the striped layout holds original positions
    {r, r+n, ...} in order; unstripe inverts exactly."""
    from distributed_pytorch_tpu.parallel import (stripe_tokens,
                                                  unstripe_tokens)
    x = jnp.arange(16)
    st = stripe_tokens(x, 4, axis=0)
    np.testing.assert_array_equal(
        np.asarray(st),
        [0, 4, 8, 12, 1, 5, 9, 13, 2, 6, 10, 14, 3, 7, 11, 15])
    np.testing.assert_array_equal(
        np.asarray(unstripe_tokens(st, 4, axis=0)), np.arange(16))
    x2 = jnp.arange(2 * 16 * 3).reshape(2, 16, 3)
    rt = unstripe_tokens(stripe_tokens(x2, 8, axis=1), 8, axis=1)
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(x2))
    with pytest.raises(ValueError):
        stripe_tokens(jnp.arange(10), 4, axis=0)


def test_striped_ring_matches_dense(sp_mesh8):
    """Striped causal ring == dense causal attention on the unstriped
    sequence (every hop a triangular kernel — balance must be layout,
    not math), including GQA kv heads."""
    from distributed_pytorch_tpu.parallel import stripe_tokens, unstripe_tokens
    from distributed_pytorch_tpu.parallel.spmd import (
        make_gspmd_striped_ring_attn_fn)

    rng = np.random.default_rng(1)
    n, (b, h, s, d) = 8, (2, 4, 64, 8)
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h // 2, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h // 2, s, d)), jnp.float32)
    want = dense_attention(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1),
                           causal=True)

    attn = make_gspmd_striped_ring_attn_fn(sp_mesh8, block_q=4, block_k=4)
    qs, ks, vs = (stripe_tokens(t, n, axis=2) for t in (q, k, v))
    got = unstripe_tokens(
        jax.jit(lambda a, b_, c: attn(a, b_, c, causal=True))(qs, ks, vs),
        n, axis=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

    with pytest.raises(ValueError):
        attn(qs, ks, vs, causal=False)  # striped ring is causal-only


def test_striped_ring_grads_match_dense(sp_mesh8):
    from distributed_pytorch_tpu.parallel import stripe_tokens, unstripe_tokens
    from distributed_pytorch_tpu.parallel.spmd import (
        make_gspmd_striped_ring_attn_fn)

    rng = np.random.default_rng(2)
    n, (b, h, s, d) = 8, (1, 2, 32, 8)
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    attn = make_gspmd_striped_ring_attn_fn(sp_mesh8, block_q=4, block_k=4)

    def loss_striped(q, k, v):
        qs, ks, vs = (stripe_tokens(t, n, axis=2) for t in (q, k, v))
        o = unstripe_tokens(attn(qs, ks, vs, causal=True), n, axis=2)
        return jnp.sum(o ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    gs = jax.jit(jax.grad(loss_striped, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gs, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.slow
def test_striped_lm_training_loss_matches_contiguous():
    """Full LM path in striped layout (tokens+targets+positions striped
    once at the data level, striped ring attention inside) reproduces
    the contiguous dense-attention loss — the data-level contract of
    stripe_tokens."""
    from distributed_pytorch_tpu.parallel import stripe_tokens
    from distributed_pytorch_tpu.parallel.spmd import (
        make_gspmd_striped_ring_attn_fn)

    mesh = context.init_mesh(dp=2, sp=4)
    try:
        n, seq = 4, 32
        kw = dict(vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
                  pos="rope", max_seq=seq)
        m_striped = models.TransformerLM(
            attn_fn=make_gspmd_striped_ring_attn_fn(mesh, block_q=4,
                                                    block_k=4), **kw)
        m_plain = models.TransformerLM(**kw)
        params = m_plain.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        toks = rng.integers(0, 64, (4, seq + 1)).astype(np.int32)
        x, y = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])

        oracle = float(cross_entropy_per_example(
            m_plain.apply(params, x), y).mean())

        pos_st = stripe_tokens(jnp.arange(seq), n, axis=0)
        x_st = stripe_tokens(x, n, axis=1)
        y_st = stripe_tokens(y, n, axis=1)
        logits = jax.jit(
            lambda p, t: m_striped.apply(p, t, positions=pos_st))(params,
                                                                  x_st)
        loss = float(cross_entropy_per_example(logits, y_st).mean())
        np.testing.assert_allclose(loss, oracle, rtol=5e-4, atol=5e-4)
    finally:
        dist.cleanup()


# ---------------------------------------------------------------------------
# Ulysses (all-to-all) sequence parallelism
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(sp_mesh8, causal):
    """All-to-all SP == dense attention: heads<->sequence reshard around
    a full-sequence kernel must be pure transport."""
    from distributed_pytorch_tpu.parallel.spmd import make_gspmd_ring_attn_fn

    rng = np.random.default_rng(4)
    b, h, s, d = 2, 8, 64, 16
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    attn = make_gspmd_ring_attn_fn(sp_mesh8, core="ulysses",
                                   block_q=8, block_k=8)
    got = jax.jit(lambda a, b_, c: attn(a, b_, c, causal=causal))(q, k, v)
    want = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

    with pytest.raises(ValueError):  # kv heads must divide the axis
        attn(q, k[:, :4], v[:, :4], causal=causal)


def test_ulysses_gqa_and_grads():
    """GQA (kv heads divisible by sp but < q heads) + gradient parity on
    a 4-shard axis."""
    from distributed_pytorch_tpu.parallel.spmd import make_gspmd_ring_attn_fn

    mesh = context.init_mesh(dp=2, sp=4)
    try:
        rng = np.random.default_rng(5)
        b, h, h_kv, s, d = 2, 8, 4, 32, 8
        q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, h_kv, s, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, h_kv, s, d)), jnp.float32)
        attn = make_gspmd_ring_attn_fn(mesh, core="ulysses",
                                       block_q=8, block_k=8)

        def loss_u(q, k, v):
            return jnp.sum(attn(q, k, v, causal=True) ** 2)

        def loss_d(q, k, v):
            return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

        np.testing.assert_allclose(
            np.asarray(jax.jit(lambda a, b_, c: attn(a, b_, c,
                                                     causal=True))(q, k, v)),
            np.asarray(dense_attention(q, k, v, causal=True)),
            rtol=2e-4, atol=2e-4)
        gu = jax.jit(jax.grad(loss_u, argnums=(0, 1, 2)))(q, k, v)
        gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gu, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=5e-4, atol=5e-4)
    finally:
        dist.cleanup()


@pytest.mark.slow
def test_striped_moe_lm_matches_contiguous():
    """The striped data-level contract composes with the MoE LM: striped
    tokens/targets/positions + striped ring attention reproduce the
    contiguous dense-attention loss (capacity generous enough that the
    token-choice router drops nothing — drops are layout-order-dependent,
    see stripe_tokens docstring)."""
    from distributed_pytorch_tpu.parallel import stripe_tokens
    from distributed_pytorch_tpu.parallel.spmd import (
        make_gspmd_striped_ring_attn_fn)

    mesh = context.init_mesh(dp=2, sp=4)
    try:
        n, seq = 4, 32
        kw = dict(vocab=64, dim=32, n_layers=2, n_heads=4, n_experts=4,
                  capacity_factor=4.0, pos="rope", max_seq=seq)
        m_striped = models.MoETransformerLM(
            attn_fn=make_gspmd_striped_ring_attn_fn(mesh, block_q=4,
                                                    block_k=4), **kw)
        m_plain = models.MoETransformerLM(**kw)
        params = m_plain.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(6)
        toks = rng.integers(0, 64, (4, seq + 1)).astype(np.int32)
        x, y = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])

        logits_o, aux_o = m_plain.apply(params, x)
        oracle = float(cross_entropy_per_example(logits_o, y).mean()
                       + 0.01 * aux_o)

        pos_st = stripe_tokens(jnp.arange(seq), n, axis=0)
        x_st = stripe_tokens(x, n, axis=1)
        y_st = stripe_tokens(y, n, axis=1)
        logits, aux = jax.jit(
            lambda p, t: m_striped.apply(p, t, positions=pos_st))(params,
                                                                  x_st)
        loss = float(cross_entropy_per_example(logits, y_st).mean()
                     + 0.01 * aux)
        np.testing.assert_allclose(loss, oracle, rtol=5e-4, atol=5e-4)
    finally:
        dist.cleanup()


# ---------------------------------------------------------------------------
# sliding-window ring attention (banded hops, static far-hop skip)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [4, 12, 200])
@pytest.mark.parametrize("core", ["flash", "ulysses"])
def test_windowed_sp_matches_dense(sp_mesh8, window, core):
    """Sliding-window attention across sequence shards == the dense
    windowed oracle, for windows inside one shard, spanning shards, and
    wider than the whole sequence."""
    rng = np.random.default_rng(7)
    b, h, s, d = 2, 8, 64, 16  # 8 tokens per shard
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    attn = make_gspmd_ring_attn_fn(sp_mesh8, core=core, window=window,
                                   block_q=4, block_k=4)
    got = jax.jit(lambda a, b_, c: attn(a, b_, c, causal=True))(q, k, v)
    want = dense_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_windowed_ring_skips_far_hops_statically(sp_mesh8):
    """The O(S*window) claim: with window <= S_local only 2 of the 8
    hops run, so the traced program contains 2 ppermute pairs instead of
    7 — the skip is in the compiled program, not a runtime branch."""
    from distributed_pytorch_tpu.parallel.sequence import (
        ring_flash_attention)
    b, h, s_loc, d = 1, 2, 8, 8

    def island(window):
        spec = P(None, None, "sp", None)
        return jax.shard_map(
            lambda q, k, v: ring_flash_attention(
                q, k, v, axis_name="sp", causal=True, window=window,
                block_q=4, block_k=4),
            mesh=sp_mesh8, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False)

    x = jnp.zeros((1, 2, 64, 8), jnp.float32)
    narrow = str(jax.make_jaxpr(
        lambda q: island(8)(q, q, q))(x)).count("ppermute")
    full = str(jax.make_jaxpr(
        lambda q: island(None)(q, q, q))(x)).count("ppermute")
    assert narrow < full, (narrow, full)
    assert narrow <= 2 * 2  # hops 0..1 -> at most 2 k/v shift pairs


def test_windowed_ring_grads_match_dense(sp_mesh8):
    rng = np.random.default_rng(8)
    b, h, s, d = 1, 2, 64, 8
    W = 12  # spans shard boundaries
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    attn = make_gspmd_ring_attn_fn(sp_mesh8, core="flash", window=W,
                                   block_q=4, block_k=4)

    def lf(q, k, v):
        return jnp.sum(attn(q, k, v, causal=True) ** 2)

    def ld(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True,
                                       window=W) ** 2)

    gf = jax.jit(jax.grad(lf, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(ld, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)


def test_window_rejected_for_dense_and_striped_cores(sp_mesh8):
    with pytest.raises(ValueError):
        make_gspmd_ring_attn_fn(sp_mesh8, core="dense", window=8)
    with pytest.raises(ValueError):
        make_gspmd_ring_attn_fn(sp_mesh8, core="striped", window=8)
