"""Model-zoo tests: shapes, trainability on the 8-device mesh, and the
stateful (BatchNorm) + scan-fused training paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_pytorch_tpu as dist
from distributed_pytorch_tpu import models, optim
from distributed_pytorch_tpu.ops.losses import (cross_entropy,
                                                cross_entropy_per_example)
from distributed_pytorch_tpu.parallel import (make_scan_train_steps,
                                              make_stateful_train_step,
                                              make_train_step, stack_state)


def test_transformer_lm_shapes():
    model = models.TransformerLM(vocab=64, dim=32, n_layers=2, n_heads=4,
                                 max_seq=16)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 16, 64)


def test_transformer_causality():
    """Changing a future token must not change past logits."""
    model = models.TransformerLM(vocab=64, dim=32, n_layers=2, n_heads=4,
                                 max_seq=8)
    params = model.init(jax.random.PRNGKey(0))
    a = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    b = a.at[0, 6].set(9)
    la = model.apply(params, a)
    lb = model.apply(params, b)
    np.testing.assert_allclose(np.asarray(la[0, :6]), np.asarray(lb[0, :6]),
                               rtol=1e-5)
    assert not np.allclose(np.asarray(la[0, 6:]), np.asarray(lb[0, 6:]))


def test_transformer_dp_training(group8):
    model = models.TransformerLM(vocab=32, dim=32, n_layers=1, n_heads=2,
                                 max_seq=8)
    params = dist.replicate(model.init(jax.random.PRNGKey(0)))
    opt = optim.adamw(1e-3)
    opt_state = dist.replicate(opt.init(params))

    def loss_fn(p, batch):
        x, y = batch
        logits = model.apply(p, x)
        per_tok = cross_entropy_per_example(logits, y)
        return per_tok.mean(), {"per_tok": per_tok.mean(axis=-1)}

    step = make_train_step(loss_fn, opt)
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(5):
        x = rng.integers(0, 32, (16, 8)).astype(np.int32)
        batch = dist.shard_batch((x[:, :], x[:, :]))
        params, opt_state, loss, _ = step(params, opt_state, batch)
        losses.append(float(np.asarray(loss).mean()))
    assert losses[-1] < losses[0]


def test_resnet18_shapes_and_state():
    model = models.ResNet18(n_classes=10, small_input=True)
    params, state = model.init(jax.random.PRNGKey(0))
    x = jnp.ones((2, 32, 32, 3))
    logits, new_state = model.apply(params, x, state=state, train=True)
    assert logits.shape == (2, 10)
    # running stats must move in train mode
    assert not np.allclose(np.asarray(new_state["bn_stem"]["mean"]),
                           np.asarray(state["bn_stem"]["mean"]))
    # eval mode: state passes through unchanged
    _, eval_state = model.apply(params, x, state=new_state, train=False)
    np.testing.assert_array_equal(np.asarray(eval_state["bn_stem"]["mean"]),
                                  np.asarray(new_state["bn_stem"]["mean"]))


@pytest.mark.slow
def test_resnet18_stateful_dp_training(group8):
    model = models.ResNet18(n_classes=4, small_input=True)
    params, state0 = model.init(jax.random.PRNGKey(0))
    params = dist.replicate(params)
    state = stack_state(state0)  # per-rank BN stats, stacked layout
    opt = optim.adamw(1e-3)
    opt_state = dist.replicate(opt.init(params))

    def loss_fn(p, s, batch):
        x, y = batch
        logits, ns = model.apply(p, x, state=s, train=True)
        per_ex = cross_entropy_per_example(logits, y)
        return per_ex.mean(), (ns, {"correct": jnp.argmax(logits, -1) == y})

    step = make_stateful_train_step(loss_fn, opt)
    rng = np.random.default_rng(0)
    # fixed batch: loss must fall as the model fits it
    x = rng.random((16, 8, 8, 3), dtype=np.float32)
    y = rng.integers(0, 4, (16,)).astype(np.int32)
    losses = []
    for _ in range(4):
        out = step(params, state, opt_state, dist.shard_batch((x, y)))
        params, state, opt_state = out.params, out.state, out.opt_state
        losses.append(float(np.asarray(out.loss).mean()))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    # BN state is per-rank: leading axis = world
    leaf = jax.tree_util.tree_leaves(state)[0]
    assert leaf.shape[0] == 8


def test_scan_fused_steps_match_per_step(group8):
    """n scan-fused steps must produce the same params as n individual
    steps (the fast path is numerically the same program)."""
    model = models.DummyModel(in_dim=1, hidden_dim=8, n_classes=4)
    p0 = dist.replicate(model.init(jax.random.PRNGKey(0)))
    opt = optim.adamw(1e-2)
    o0 = dist.replicate(opt.init(p0))

    def loss_fn(p, batch):
        x, y = batch
        logits = model.apply(p, x)
        return cross_entropy(logits, y), {}

    rng = np.random.default_rng(0)
    xs = rng.random((4, 16, 1), dtype=np.float32)
    ys = rng.integers(0, 4, (4, 16)).astype(np.int32)

    step = make_train_step(loss_fn, opt, donate=False)
    p, o = p0, o0
    for t in range(4):
        p, o, _, _ = step(p, o, dist.shard_batch((xs[t], ys[t])))

    run = make_scan_train_steps(loss_fn, opt, n_steps=4, donate=False)
    p2, o2, losses = run(p0, o0, (jnp.asarray(xs), jnp.asarray(ys)))
    assert losses.shape == (4, 8)
    np.testing.assert_allclose(np.asarray(p["lin1"]["w"]),
                               np.asarray(p2["lin1"]["w"]), rtol=1e-5)


@pytest.mark.slow
def test_transformer_remat_same_values_and_grads():
    """remat=True must be numerically invisible (same logits, same grads)
    and actually install the checkpoint primitive. (The HBM saving shows
    on TPU; XLA-CPU's buffer assignment reports identical temp peaks, so
    here the mechanism is pinned via the jaxpr and the peak is only
    required not to regress.)"""
    from distributed_pytorch_tpu.ops.losses import cross_entropy
    from distributed_pytorch_tpu.utils import profiler

    # big enough that per-block activations dominate the temp buffers
    # (at toy sizes checkpoint bookkeeping outweighs the savings)
    kw = dict(vocab=64, dim=128, n_layers=6, n_heads=4, max_seq=128)
    m0 = models.TransformerLM(**kw)
    m1 = models.TransformerLM(remat=True, **kw)
    params = m0.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.arange(8 * 128).reshape(8, 128) % 64, jnp.int32)

    np.testing.assert_allclose(np.asarray(m0.apply(params, toks)),
                               np.asarray(m1.apply(params, toks)),
                               rtol=1e-6, atol=1e-6)

    def loss(m):
        def f(p):
            return cross_entropy(m.apply(p, toks[:, :-1]), toks[:, 1:])
        return f

    g0 = jax.grad(loss(m0))(params)
    g1 = jax.grad(loss(m1))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    jaxpr0 = str(jax.make_jaxpr(jax.grad(loss(m0)))(params))
    jaxpr1 = str(jax.make_jaxpr(jax.grad(loss(m1)))(params))
    assert "remat" not in jaxpr0
    assert "remat" in jaxpr1

    mem0 = profiler.compiled_memory(jax.grad(loss(m0)), params)
    mem1 = profiler.compiled_memory(jax.grad(loss(m1)), params)
    if mem0.get("temp_size_bytes") and mem1.get("temp_size_bytes"):
        assert mem1["temp_size_bytes"] <= mem0["temp_size_bytes"]


class TestSyncBatchNorm:
    def test_sync_bn_matches_full_batch_stats(self, group8):
        """SyncBN inside an 8-way shard_map == local BN on the gathered
        global batch: same outputs, same (replica-identical) running
        stats."""
        from jax.sharding import PartitionSpec as P

        from distributed_pytorch_tpu.nn.conv import BatchNorm2d
        from distributed_pytorch_tpu.runtime import context

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((16, 4, 4, 3)) * 3 + 1,
                        jnp.float32)
        bn_sync = BatchNorm2d(3, axis_name="dp")
        bn_local = BatchNorm2d(3)
        params = bn_sync.init(jax.random.PRNGKey(0))
        state = bn_sync.init_state()

        want_y, want_state = bn_local.apply(params, x, state=state,
                                            train=True)

        mesh = context.get_mesh()

        def island(x):
            y, ns = bn_sync.apply(params, x, state=state, train=True)
            return y, ns["mean"], ns["var"]

        y, nm, nv = jax.jit(jax.shard_map(
            island, mesh=mesh,
            in_specs=P("dp"), out_specs=(P("dp"), P("dp"), P("dp")),
            check_vma=False))(x)
        # outputs equal the full-batch normalization
        np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                                   rtol=2e-4, atol=2e-5)
        # every shard's running stats equal the full-batch update
        nm = np.asarray(nm).reshape(8, -1)
        nv = np.asarray(nv).reshape(8, -1)
        for r in range(8):
            np.testing.assert_allclose(nm[r], np.asarray(want_state["mean"]),
                                       rtol=2e-4, atol=2e-5)
            np.testing.assert_allclose(nv[r], np.asarray(want_state["var"]),
                                       rtol=2e-3, atol=2e-4)

    def test_sync_bn_degrades_outside_shard_map(self):
        """axis_name set but no axis bound (world-1 / plain jit): local
        statistics, no error — the 0/1/N contract."""
        from distributed_pytorch_tpu.nn.conv import BatchNorm2d

        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((4, 2, 2, 3)), jnp.float32)
        bn_sync = BatchNorm2d(3, axis_name="dp")
        bn_local = BatchNorm2d(3)
        params = bn_sync.init(jax.random.PRNGKey(0))
        y_sync, _ = jax.jit(lambda x: bn_sync.apply(params, x,
                                                    train=True))(x)
        y_local, _ = bn_local.apply(params, x, train=True)
        np.testing.assert_allclose(np.asarray(y_sync),
                                   np.asarray(y_local),
                                   rtol=2e-5, atol=2e-6)

    @pytest.mark.slow
    def test_resnet_sync_bn_trains(self, group8):
        """ResNet18(sync_bn=True) trains under the stateful DP step."""
        from distributed_pytorch_tpu import optim
        from distributed_pytorch_tpu.ops.losses import cross_entropy
        from distributed_pytorch_tpu.parallel import (
            make_stateful_train_step, stack_state)
        import distributed_pytorch_tpu as dist

        model = models.ResNet18(n_classes=4, small_input=True,
                                sync_bn=True)
        params, state = model.init(jax.random.PRNGKey(0))
        opt = optim.adamw(1e-3)
        opt_state = opt.init(params)

        def loss_fn(p, st, batch):
            x, y = batch
            logits, ns = model.apply(p, x, state=st, train=True)
            return cross_entropy(logits, y), (ns, {})

        step = make_stateful_train_step(loss_fn, opt, donate=False)
        rng = np.random.default_rng(0)
        x = dist.shard_batch(
            rng.standard_normal((16, 8, 8, 3)).astype(np.float32))
        y = dist.shard_batch(rng.integers(0, 4, 16).astype(np.int32))
        params_r = dist.replicate(params)
        opt_r = dist.replicate(opt_state)
        state_s = stack_state(state)
        losses = []
        out = step(params_r, state_s, opt_r, (x, y))
        losses.append(float(jnp.mean(out.loss)))
        for _ in range(4):
            out = step(out.params, out.state, out.opt_state, (x, y))
            losses.append(float(jnp.mean(out.loss)))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]


class TestFusedLinearCrossEntropy:
    """fused_linear_cross_entropy streams the vocab projection chunkwise;
    it must match the materialize-then-CE path in value and gradients."""

    def _setup(self, n=37, d=16, v=53, seed=0):
        from distributed_pytorch_tpu.ops.losses import \
            fused_linear_cross_entropy
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
        h = jax.random.normal(k1, (n, d), jnp.float32)
        w = jax.random.normal(k2, (d, v), jnp.float32) * 0.1
        y = jax.random.randint(k3, (n,), 0, v, jnp.int32)
        return fused_linear_cross_entropy, h, w, y

    def test_value_matches_unfused(self):
        fused, h, w, y = self._setup()
        ref = cross_entropy(h @ w, y)
        # chunk 8 does not divide 37 -> exercises the padding path
        got = fused(h, w, y, chunk_rows=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6)

    def test_single_chunk_and_batched_shapes(self):
        fused, h, w, y = self._setup(n=24)
        ref = cross_entropy(h @ w, y)
        np.testing.assert_allclose(
            np.asarray(fused(h, w, y, chunk_rows=1024)),
            np.asarray(ref), rtol=1e-6)
        # (B, S, d) hidden + (B, S) labels flatten internally
        np.testing.assert_allclose(
            np.asarray(fused(h.reshape(4, 6, -1), w, y.reshape(4, 6),
                             chunk_rows=7)),
            np.asarray(ref), rtol=1e-6)

    def test_grads_match_unfused(self):
        fused, h, w, y = self._setup()

        gh_ref, gw_ref = jax.grad(
            lambda h_, w_: cross_entropy(h_ @ w_, y), argnums=(0, 1))(h, w)
        gh, gw = jax.grad(
            lambda h_, w_: fused(h_, w_, y, chunk_rows=8),
            argnums=(0, 1))(h, w)
        np.testing.assert_allclose(np.asarray(gh), np.asarray(gh_ref),
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                                   rtol=1e-5, atol=1e-7)

    def test_lm_training_with_fused_head(self):
        """End-to-end: TransformerLM return_hidden + fused CE trains, and
        the loss equals the standard logits path."""
        from distributed_pytorch_tpu.ops.losses import \
            fused_linear_cross_entropy
        model = models.TransformerLM(vocab=64, dim=32, n_layers=2, n_heads=4,
                                     max_seq=16)
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 64,
                                  jnp.int32)

        def loss_fused(p, t):
            hid = model.apply(p, t[:, :-1], return_hidden=True)
            return fused_linear_cross_entropy(hid, p["head"]["w"], t[:, 1:],
                                              chunk_rows=8), {}

        def loss_ref(p, t):
            return cross_entropy(model.apply(p, t[:, :-1]), t[:, 1:]), {}

        lf, _ = loss_fused(params, toks)
        lr, _ = loss_ref(params, toks)
        np.testing.assert_allclose(np.asarray(lf), np.asarray(lr), rtol=1e-6)

        opt = optim.adamw(1e-3)
        step = make_train_step(loss_fused, opt, donate=False)
        out = step(params, opt.init(params), toks)
        l0 = float(out.loss.mean())
        for _ in range(5):
            out = step(out.params, out.opt_state, toks)
        assert float(out.loss.mean()) < l0


class TestTiedEmbeddings:
    """tie_embeddings: the vocab projection reuses the token table
    transposed — no head parameter, logits = h @ emb.T."""

    def _model(self, **kw):
        return models.TransformerLM(vocab=61, dim=32, n_layers=2, n_heads=4,
                                    max_seq=32, tie_embeddings=True, **kw)

    def test_no_head_param_and_logits_use_emb(self):
        model = self._model()
        params = model.init(jax.random.PRNGKey(0))
        assert "head" not in params
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 61)
        hid = model.apply(params, toks, return_hidden=True)
        logits = model.apply(params, toks)
        want = np.asarray(hid) @ np.asarray(params["tok"]["emb"]).T
        np.testing.assert_allclose(np.asarray(logits), want, atol=1e-5)

    def test_trains_and_gradient_flows_through_both_uses(self):
        from distributed_pytorch_tpu.parallel import make_train_step
        model = self._model()
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 61)

        def loss_fn(p, t):
            return cross_entropy(model.apply(p, t[:, :-1]), t[:, 1:]), {}

        opt = optim.adamw(1e-3)
        step = make_train_step(loss_fn, opt, donate=False)
        out = step(params, opt.init(params), toks)
        l0 = float(out.loss.mean())
        for _ in range(5):
            out = step(out.params, out.opt_state, toks)
        assert float(out.loss.mean()) < l0

    @pytest.mark.slow
    def test_cached_decode_matches_full_forward(self):
        from distributed_pytorch_tpu.models.generate import make_generate_fn
        model = self._model(n_kv_heads=2, pos="rope")
        params = model.init(jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, 61)
        out = np.asarray(make_generate_fn(model, 5)(
            params, prompt, jax.random.PRNGKey(2)))
        toks = np.asarray(prompt)
        want = []
        for _ in range(5):
            logits = model.apply(params, jnp.asarray(toks))
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
            want.append(nxt)
            toks = np.concatenate([toks, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(out, np.stack(want, axis=1))

    def test_fused_ce_uses_head_weight(self):
        from distributed_pytorch_tpu.ops.losses import \
            fused_linear_cross_entropy
        model = self._model()
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(3), (2, 9), 0, 61)
        hid = model.apply(params, toks[:, :-1], return_hidden=True)
        fused = fused_linear_cross_entropy(hid, model.head_weight(params),
                                           toks[:, 1:], chunk_rows=8)
        ref = cross_entropy(model.apply(params, toks[:, :-1]), toks[:, 1:])
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                                   rtol=1e-6)

    def test_param_count_saving(self):
        tied = self._model().init(jax.random.PRNGKey(0))
        untied = models.TransformerLM(vocab=61, dim=32, n_layers=2,
                                      n_heads=4, max_seq=32).init(
                                          jax.random.PRNGKey(0))
        n = lambda p: sum(int(np.prod(l.shape))
                          for l in jax.tree_util.tree_leaves(p))
        assert n(untied) - n(tied) == 61 * 32


class TestVocabParallelCE:
    def test_matches_gathered_loss_and_grads(self):
        """Megatron-style vocab-parallel CE: the tp island (local
        projection slice + scalar-per-token collectives) equals the
        gathered softmax-CE in value AND gradients — the (B,S,V) logits
        never exist on any device."""
        from distributed_pytorch_tpu.ops import make_vocab_parallel_ce_fn
        from distributed_pytorch_tpu.runtime import context

        mesh = context.init_mesh(dp=2, tp=4)
        try:
            rng = np.random.default_rng(0)
            B, S, D, V = 4, 6, 16, 32
            h = jnp.asarray(rng.standard_normal((B, S, D)), jnp.float32)
            w = jnp.asarray(rng.standard_normal((D, V)) * 0.2,
                            jnp.float32)
            y = jnp.asarray(rng.integers(0, V, (B, S)).astype(np.int32))
            fn = make_vocab_parallel_ce_fn(mesh)

            got = jax.jit(fn)(h, w, y)
            want = cross_entropy_per_example(jnp.matmul(h, w), y)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)

            gv = jax.jit(jax.grad(
                lambda h, w: jnp.mean(fn(h, w, y)),
                argnums=(0, 1)))(h, w)
            gd = jax.grad(
                lambda h, w: jnp.mean(cross_entropy_per_example(
                    jnp.matmul(h, w), y)), argnums=(0, 1))(h, w)
            for a, b in zip(gv, gd):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=5e-5, atol=5e-5)
        finally:
            dist.cleanup()

    def test_unowned_labels_surface_as_nan(self):
        """A label no tp shard owns (ignore-index padding like -100)
        must surface as NaN like the gathered path — not silent finite
        garbage that corrupts training."""
        from distributed_pytorch_tpu.ops import make_vocab_parallel_ce_fn
        from distributed_pytorch_tpu.runtime import context

        mesh = context.init_mesh(dp=2, tp=4)
        try:
            rng = np.random.default_rng(1)
            h = jnp.asarray(rng.standard_normal((2, 4, 8)), jnp.float32)
            w = jnp.asarray(rng.standard_normal((8, 16)) * 0.3,
                            jnp.float32)
            y = jnp.asarray(rng.integers(0, 16, (2, 4)).astype(np.int32))
            y = y.at[0, 0].set(-100).at[1, 3].set(16)
            out = np.asarray(jax.jit(make_vocab_parallel_ce_fn(mesh))(
                h, w, y))
            assert np.isnan(out[0, 0]) and np.isnan(out[1, 3])
            mask = np.ones_like(out, bool)
            mask[0, 0] = mask[1, 3] = False
            assert np.isfinite(out[mask]).all()
        finally:
            dist.cleanup()
