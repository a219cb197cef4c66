"""The spec-driven front-door suite (ISSUE 13).

ONE parametrized matrix over ``(mesh, spec, wire, weight_update)``
replaces the per-front-door duplicate matrices that accumulated since
PR 7 (``test_sharded_optim.py``'s SPMD/host twins and
``test_adaptive_collectives.py``'s SPMD q4/adaptive pair): every point
is built through the same ``parallel.front_door.make_step`` spec
resolution and held to the same oracle — the exact replicated-mean
trajectory — plus the two front-door contracts the refactor exists
for:

* **compile counters**: one program per (mesh, spec, width) point,
  asserted via trace-time counters, never trusted;
* **donation + reshard-free handoff**: params/opt state donated with
  out == in shardings (XLA ``memory_analysis`` alias/peak bytes as
  evidence), and the train -> eval -> serve-admit chain moving zero
  bytes between pjit programs (``verify_handoff`` + pinned eval/admit
  shardings), at world 1 and on a virtual mesh of 4 (the CI
  ``front-door-contract`` step).

The builder-cache regression (a kwargs combo missing the cache and
silently dropping donation) is pinned by TestBuilderCache.
"""

import multiprocessing as mp
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import distributed_pytorch_tpu as dist  # noqa: E402
from distributed_pytorch_tpu import models, optim  # noqa: E402
from distributed_pytorch_tpu.ops.losses import cross_entropy  # noqa: E402
from distributed_pytorch_tpu.parallel import (  # noqa: E402
    FROM_INPUTS, HandoffMismatch, StepSpecs, front_door, handoff_shardings,
    make_train_step, make_step, shard_layouts, verify_handoff)
from distributed_pytorch_tpu.runtime import context  # noqa: E402
from distributed_pytorch_tpu.runtime.multiprocess import (  # noqa: E402
    launch_multiprocess)


@pytest.fixture(autouse=True)
def _fresh_cache():
    front_door.cache_clear()
    yield
    front_door.cache_clear()


def _setup(hidden=32, in_dim=1, seed=0):
    model = models.DummyModel(in_dim=in_dim, hidden_dim=hidden,
                              n_classes=4)
    params = model.init(jax.random.PRNGKey(seed))
    opt = optim.adamw(1e-3)

    def loss_fn(p, batch):
        x, y = batch
        return cross_entropy(model.apply(p, x), y), {}

    return model, params, opt, loss_fn


def _batch(in_dim=1, n=16):
    rng = np.random.default_rng(3)
    x = dist.shard_batch(rng.random((n, in_dim)).astype(np.float32))
    y = dist.shard_batch((np.arange(n) % 4).astype(np.int32))
    return (x, y)


def _run(step, params, opt_state, batch, steps=5):
    losses = []
    p, s = params, opt_state
    for _ in range(steps):
        out = step(p, s, batch)
        p, s = out.params, out.opt_state
        losses.append(float(np.asarray(out.loss).mean()))
    return p, losses


# ---------------------------------------------------------------------------
# builder cache + donation (the satellite-4 regression class)
# ---------------------------------------------------------------------------


class TestBuilderCache:
    def test_same_config_returns_cached_step_no_retrace(self, group8):
        model, params, opt, loss_fn = _setup()
        batch = _batch()
        a = make_step(loss_fn, opt)
        b = make_step(loss_fn, opt)
        assert a is b, "identical config must hit the builder cache"
        st = opt.init(params)
        out = a(params, st, batch)
        out = b(out.params, out.opt_state, batch)
        # the cached step is ONE program, traced once — a silent
        # re-trace (the old per-call-rebuild behavior) would bump this
        assert a.compiles == 1, a.trace_counts

    def test_donate_is_part_of_the_cache_key(self, group8):
        """The regression this suite pins: re-entering the builder with
        a different kwargs combo must NOT hand back a program built
        under other flags — donation in particular. Keyed on the full
        config tuple; proven by XLA's own aliasing accounting."""
        model, params, opt, loss_fn = _setup()
        batch = _batch()
        don = make_step(loss_fn, opt, donate=True)
        cop = make_step(loss_fn, opt, donate=False)
        assert don is not cop
        assert don.donated and not cop.donated
        st = opt.init(params)
        ma_d = don.memory_analysis(params, st, batch)
        ma_c = cop.memory_analysis(params, st, batch)
        assert ma_d["alias"] > 0, "donated build must alias in->out"
        assert ma_c["alias"] == 0, "copy build must not alias"
        assert ma_d["peak_bytes"] < ma_c["peak_bytes"]
        # and a third spelling of the same donate=True config still
        # hits the first build
        assert make_step(loss_fn, opt, donate=True) is don

    def test_wire_mp_and_specs_are_keyed(self, group8):
        model, params, opt, loss_fn = _setup()
        a = make_step(loss_fn, opt, donate=False)
        assert make_step(loss_fn, opt, wire="quant",
                         donate=False) is not a
        assert make_step(loss_fn, opt, mixed_precision="bf16",
                         donate=False) is not a
        assert make_step(loss_fn, opt, specs=FROM_INPUTS,
                         donate=False) is not a

    def test_donated_input_is_consumed(self, group8):
        """Donation is real, not a flag: the donated params buffer is
        deleted after the step (reuse would read clobbered memory)."""
        model, params, opt, loss_fn = _setup()
        batch = _batch()
        step = make_step(loss_fn, opt, donate=True)
        p = jax.device_put(params, context.replicated_sharding())
        st = opt.init(p)
        leaf_before = jax.tree_util.tree_leaves(p)[0]
        out = step(p, st, batch)
        assert leaf_before.is_deleted()
        # out == in shardings: the returned params carry exactly the
        # sharding the step pins on its inputs
        verify_handoff(out.params, handoff_shardings(step))

    def test_dpx_donate_env_default(self, group8, monkeypatch):
        model, params, opt, loss_fn = _setup()
        monkeypatch.setenv("DPX_DONATE", "0")
        off = make_step(loss_fn, opt)
        assert not off.donated
        monkeypatch.delenv("DPX_DONATE")
        on = make_step(loss_fn, opt)
        assert on.donated and on is not off


# ---------------------------------------------------------------------------
# the spec-driven matrix (mesh door) — one suite, every spec point
# ---------------------------------------------------------------------------

#: (name, wire, weight_update, rtol) — the dp points of the matrix.
DP_POINTS = [
    ("mean-replicated", "mean", "replicated", 1e-6),
    ("quant-replicated", "quant", "replicated", 5e-2),
    ("q4-replicated", "q4", "replicated", 2e-1),
    ("adaptive-replicated", "adaptive", "replicated", 5e-2),
    ("mean-sharded", "mean", "sharded", 1e-4),
    ("quant-sharded", "quant", "sharded", 5e-2),
]


class TestSpecMatrix:
    """Every (spec, wire, weight_update) point tracks the exact
    replicated oracle and compiles exactly one program per width."""

    def _oracle(self, loss_fn, opt, params, batch):
        step = make_step(loss_fn, opt, donate=False)
        _, losses = _run(step, params, opt.init(params), batch)
        return losses

    @pytest.mark.parametrize("name,wire,wu,rtol",
                             DP_POINTS, ids=[p[0] for p in DP_POINTS])
    def test_dp_point_tracks_oracle(self, group8, name, wire, wu, rtol):
        model, params, opt, loss_fn = _setup()
        batch = _batch()
        oracle = self._oracle(loss_fn, opt, params, batch)
        step = make_step(loss_fn, opt, wire=wire, weight_update=wu,
                         donate=False)
        st = (step.init_opt_state(params) if wu == "sharded"
              else opt.init(params))
        _, losses = _run(step, params, st, batch)
        np.testing.assert_allclose(losses, oracle, rtol=rtol, atol=rtol)
        # ONE program per (mesh, spec, width) point: adaptive owns one
        # per width it actually ran, every other point exactly one
        assert all(n == 1 for n in step.trace_counts.values()), \
            step.trace_counts
        if wire == "adaptive":
            assert step.width_chooser is not None
            assert set(step.width_chooser.widths) <= {4, 8}
            assert len(step.trace_counts) <= 2
        else:
            assert step.compiles == 1

    def test_adaptive_converges_to_q4_and_keeps_programs_bounded(
            self, group8):
        """Gaussian gradients drop to q4 after the hysteresis — and the
        width flip compiles exactly one more program, not one per
        step (the bounded-variants discipline)."""
        model, params, opt, loss_fn = _setup()
        batch = _batch()
        step = make_step(loss_fn, opt, wire="adaptive", donate=False)
        _, _ = _run(step, params, opt.init(params), batch, steps=6)
        widths = step.width_chooser.widths
        assert widths[:2] == [8, 8]       # starts safe, hysteresis 2
        assert all(n == 1 for n in step.trace_counts.values())

    @pytest.mark.parametrize("rung", ["zero3", "zero1", "zero2"])
    def test_constraint_ladder_tracks_oracle(self, group8, rung):
        """The fsdp ladder as front-door spec points, resolved through
        the shard_layouts/opt_state_specs contract. Loss is the global
        scalar (GSPMD view) — equal to the stacked oracle's mean."""
        model, params, opt, loss_fn = _setup(hidden=64, in_dim=8)
        batch = _batch(in_dim=8)
        oracle = self._oracle(loss_fn, opt, params, batch)
        opt_state = opt.init(params)
        p_specs, o_specs, axes = shard_layouts(
            params, opt_state, n_shards=8, min_size=64)
        assert axes == {"dp": 8}
        from distributed_pytorch_tpu.parallel.tensor import \
            replicated_specs
        if rung == "zero3":
            specs = StepSpecs(params=p_specs)
        elif rung == "zero2":
            specs = StepSpecs(params=replicated_specs(params),
                              opt=p_specs, grads=p_specs)
        else:
            specs = StepSpecs(params=replicated_specs(params),
                              opt=p_specs,
                              grads=replicated_specs(params))
        step = make_step(loss_fn, opt, mesh=context.get_mesh(),
                         specs=specs, donate=False)
        _, losses = _run(step, params, opt_state, batch)
        np.testing.assert_allclose(losses, oracle, rtol=2e-5, atol=1e-6)
        assert step.compiles == 1, step.trace_counts
        # the ladder's memory claim is XLA-visible: the sharded-state
        # rungs pin the opt state to 1/8 leaves (spec P('dp') on the
        # big leaves), and out shardings == in shardings
        assert step.out_shardings["opt"] == step.in_shardings["opt"]
        assert step.out_shardings["params"] == step.in_shardings["params"]

    def test_sharded_state_specs_flow_to_ckpt_contract(self, group8):
        """weight_update='sharded' through the front door keeps the
        checkpoint-facing exports (state_specs/init_opt_state)."""
        model, params, opt, loss_fn = _setup()
        step = make_step(loss_fn, opt, weight_update="sharded",
                         donate=False)
        st = step.init_opt_state(params)
        specs = step.state_specs(st)
        assert specs.master == P("dp")
        assert specs.inner.mu == P("dp")
        assert specs.inner.step == P()


# ---------------------------------------------------------------------------
# the host door points of the same matrix (per-rank processes, world 2)
# ---------------------------------------------------------------------------


def _host_matrix_worker(rank, world, q, wire, wu, steps):
    """One (wire, weight_update) point on the host door: the reference
    DDP workload stepped through the SAME make_step spec resolution;
    reports the loss trajectory, a bitwise param digest (ranks must
    never drift), and per-op CommStats bytes (the wire accounting)."""
    import hashlib

    import jax as _jax
    import numpy as _np

    import distributed_pytorch_tpu as _dist
    from distributed_pytorch_tpu import models as _models
    from distributed_pytorch_tpu import optim as _optim
    from distributed_pytorch_tpu.ops.losses import cross_entropy as _ce
    from distributed_pytorch_tpu.parallel import make_step as _mk
    from distributed_pytorch_tpu.runtime import context as _ctx

    _dist.init_process_group(rank, world)
    try:
        model = _models.DummyModel(in_dim=1, hidden_dim=32, n_classes=4)
        params = model.init(_jax.random.PRNGKey(0))
        opt = _optim.adamw(1e-2)

        def loss_fn(p, batch):
            x, y = batch
            return _ce(model.apply(p, x), y), {}

        rng = _np.random.default_rng(0)
        x = rng.random((16, 1), dtype=_np.float32)
        y = rng.integers(0, 4, (16,)).astype(_np.int32)
        lo = rank * (16 // world)
        hi = lo + 16 // world
        step = _mk(loss_fn, opt, wire=wire, weight_update=wu)
        st = (step.init_opt_state(params)
              if hasattr(step, "init_opt_state")
              and wu == "sharded" else opt.init(params))
        losses = []
        for _ in range(steps):
            out = step(params, st, (x[lo:hi], y[lo:hi]))
            params, st = out.params, out.opt_state
            losses.append(float(_np.asarray(out.loss)[0]))
        digest = hashlib.sha256(b"".join(
            _np.ascontiguousarray(_np.asarray(l, _np.float32)).tobytes()
            for l in _jax.tree_util.tree_leaves(params))).hexdigest()
        comm = _ctx.get_host_comm()
        stats = {k: int(v["bytes"])
                 for k, v in comm.stats.summary().items()}
        widths = (step.width_chooser.widths
                  if getattr(step, "width_chooser", None) else None)
        q.put((rank, digest, losses, stats, widths))
    finally:
        _dist.cleanup()


_host_cache = {}


def _run_host_point(wire, wu, world=2, steps=4):
    key = (wire, wu, world, steps)
    if key in _host_cache:       # the replicated baseline is shared
        return _host_cache[key]
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    launch_multiprocess(_host_matrix_worker, world, q, wire, wu, steps)
    res = {}
    while len(res) < world:
        rank, digest, losses, stats, widths = q.get(timeout=120)
        res[rank] = (digest, losses, stats, widths)
    # ranks never drift apart, at any spec point
    assert len({v[0] for v in res.values()}) == 1, (wire, wu)
    _host_cache[key] = res[0]
    return res[0]


class TestHostMatrix:
    def test_sharded_mean_tracks_replicated(self):
        rep = _run_host_point("mean", "replicated")
        sh = _run_host_point("mean", "sharded")
        np.testing.assert_allclose(sh[1], rep[1], rtol=1e-5, atol=1e-6)

    def test_adaptive_replicated_tracks_and_agrees_on_widths(self):
        rep = _run_host_point("mean", "replicated")
        ad = _run_host_point("adaptive", "replicated")
        np.testing.assert_allclose(ad[1], rep[1], rtol=5e-2, atol=5e-2)
        # hysteresis: starts at q8; the chooser state is rank-agreed
        # (digest equality above pins the params; widths recorded)
        assert ad[3] is not None and ad[3][:2] == [8, 8]
        assert set(ad[3]) <= {4, 8}

    @pytest.mark.slow
    def test_sharded_quant_tracks_and_books_leg_bytes(self):
        """Quant wire + sharded update on the host door: trajectory
        tracks, and CommStats recorded the reduce_scatter/allgather
        legs at exactly the wire.py accounting (bytes-on-wire asserted,
        not narrated). Slow tier: the leg byte accounting is also
        asserted process-free by test_sharded_optim.TestWireLegSpecs
        and end to end by the CI bench smoke."""
        from distributed_pytorch_tpu.comm import wire

        rep = _run_host_point("mean", "replicated")
        shq = _run_host_point("quant", "sharded")
        np.testing.assert_allclose(shq[1], rep[1], rtol=5e-2, atol=5e-2)
        stats = shq[2]
        assert "reduce_scatter" in stats and "allgather" in stats
        # DummyModel flat bucket at world 2: 4 leaves x 1 block each
        n_padded = 4 * wire.QUANT_BLOCK
        leg = wire.quant_leg_wire_bytes(n_padded, 2) // 2
        assert stats["reduce_scatter"] == 4 * leg  # 4 steps
        assert stats["allgather"] == 4 * leg


# ---------------------------------------------------------------------------
# the train -> eval -> serve-admit handoff chain (world 1 + mesh 4)
# ---------------------------------------------------------------------------


class TestHandoffChain:
    def _lm_setup(self):
        model = models.TransformerLM(vocab=64, dim=32, n_layers=2,
                                     n_heads=2, pos="rope", max_seq=64)
        params = model.init(jax.random.PRNGKey(0))
        opt = optim.adamw(1e-3)

        def loss_fn(p, batch):
            tokens = batch
            logits = model.apply(p, tokens[:, :-1])
            return cross_entropy(
                logits.reshape(-1, 64), tokens[:, 1:].reshape(-1)), {}

        return model, params, opt, loss_fn

    def _chain(self, world):
        """Train -> eval -> serve-admit with zero resharding, asserted
        at every joint by verify_handoff + compile counters."""
        from distributed_pytorch_tpu.serve import (EngineConfig,
                                                   InferenceEngine,
                                                   SamplingParams)

        if world > 1:
            dist.init_process_group(rank=0, world_size=world)
        try:
            model, params, opt, loss_fn = self._lm_setup()
            rng = np.random.default_rng(0)
            tokens = dist.shard_batch(
                rng.integers(0, 64, (8, 17)).astype(np.int32))
            step = make_train_step(loss_fn, opt)   # donation default ON
            st = opt.init(params)
            out = step(params, st, tokens)
            out = step(out.params, out.opt_state, tokens)
            assert step.compiles == 1, step.trace_counts
            p_sh = handoff_shardings(step)
            # train -> eval: pinned in_shardings, zero copies
            verify_handoff(out.params, p_sh)
            ev = front_door.make_eval_step(
                lambda p, b: model.apply(p, b).argmax(-1), like=step)
            pred = ev(out.params, tokens)
            pred = ev(out.params, tokens)
            assert np.asarray(pred).shape == (8, 17)
            assert ev.trace_counts["n"] == 1
            # eval -> serve admit: the engine pins the SAME shardings
            # and must accept the step's params verbatim (no copy:
            # verify_handoff returns the identical tree)
            eng = InferenceEngine(
                model, out.params,
                EngineConfig(n_slots=2, max_len=64, param_shardings=p_sh))
            assert jax.tree_util.tree_leaves(eng.params)[0] is \
                jax.tree_util.tree_leaves(out.params)[0]
            with eng:
                toks = eng.submit(
                    rng.integers(0, 64, (5,)).astype(np.int32),
                    SamplingParams(max_new_tokens=4),
                    rng=jax.random.PRNGKey(7)).result(timeout=120)
            assert len(toks) == 4
            assert eng.pool.compiles.decode == 1
            # a tree that does NOT carry the pinned shardings is
            # rejected typed instead of silently resharded
            host_params = jax.tree_util.tree_map(np.asarray, out.params)
            if p_sh is not None:
                with pytest.raises(HandoffMismatch):
                    InferenceEngine(model, host_params,
                                    EngineConfig(n_slots=2, max_len=64,
                                                 param_shardings=p_sh))
                from distributed_pytorch_tpu.models.generate import \
                    make_generate_fn
                gen = make_generate_fn(model, 2, param_shardings=p_sh)
                with pytest.raises(HandoffMismatch):
                    gen(host_params,
                        jnp.asarray(rng.integers(0, 64, (1, 4))),
                        jax.random.PRNGKey(0))
        finally:
            if world > 1:
                dist.cleanup()

    def test_chain_world1(self):
        self._chain(1)

    def test_chain_mesh4(self):
        self._chain(4)

    def test_eval_pins_tree_shardings_from_constrained_step(self,
                                                            group8):
        """The constraint-ladder consumer half: a ZeRO-3 step's params
        out-shardings are a TREE; make_eval_step(like=) must pin that
        tree verbatim (a replicated fallback would make pjit silently
        all-gather the sharded weights on entry — the review repro)."""
        from jax.sharding import NamedSharding

        model, params, opt, loss_fn = _setup(hidden=64, in_dim=8)
        batch = _batch(in_dim=8)
        opt_state = opt.init(params)
        p_specs, _, _ = shard_layouts(params, opt_state, n_shards=8,
                                      min_size=64)
        step = make_step(loss_fn, opt, mesh=context.get_mesh(),
                         specs=StepSpecs(params=p_specs), donate=False)
        out = step(params, opt_state, batch)
        pinned = handoff_shardings(step)
        assert not isinstance(pinned, NamedSharding)   # a TREE
        ev = front_door.make_eval_step(
            lambda p, b: model.apply(p, b[0]).argmax(-1), like=step)
        assert ev.in_shardings["params"] is pinned
        # the step's own output feeds it with zero resharding
        verify_handoff(out.params, pinned)
        pred = ev(out.params, batch)
        pred = ev(out.params, batch)
        assert np.asarray(pred).shape == (16,)
        assert ev.trace_counts["n"] == 1

    def test_verify_handoff_surface(self, group8):
        model, params, opt, loss_fn = _setup()
        step = make_step(loss_fn, opt, donate=False)
        sh = handoff_shardings(step)
        assert sh is not None
        with pytest.raises(HandoffMismatch, match="handoff"):
            verify_handoff(params, sh)     # uncommitted host tree
        placed = jax.device_put(params, sh)
        assert verify_handoff(placed, sh) is placed   # zero-copy

    def test_out_equals_in_shardings_every_engine(self, group8):
        """The pjit-to-pjit precondition, asserted on the declared
        contract for the dp and sharded engines (the constraint ladder
        is covered in TestSpecMatrix)."""
        model, params, opt, loss_fn = _setup()
        for kw in ({}, {"weight_update": "sharded"}):
            step = make_step(loss_fn, opt, donate=False, **kw)
            if kw:
                step.init_opt_state(params)
                st = step.init_opt_state(params)
                step(params, st, _batch())   # sharded pins lazily
            assert step.in_shardings["params"] == \
                step.out_shardings["params"]
            assert step.in_shardings["opt"] == step.out_shardings["opt"]


# ---------------------------------------------------------------------------
# leaves outside the optimizer (docs/front_door.md)
# ---------------------------------------------------------------------------


def _buffered():
    """A model with one weight and one counter beside it: the loss reads
    both, the rule adds what the loss's second output says."""
    from distributed_pytorch_tpu.parallel import Buffers

    params = {"w": jnp.asarray([1.0, -2.0, 3.0]),
              "seen": jnp.asarray([0.25], jnp.float32)}

    def loss_fn(p, batch):
        pred = batch @ p["w"] + p["seen"][0]
        return jnp.mean(jnp.square(pred)), {
            "rows": jnp.float32(batch.shape[0]), "seen_dtype_is_f32":
            jnp.float32(p["seen"].dtype == jnp.float32)}

    buffers = Buffers(
        mask=lambda p: {"w": False, "seen": True},
        update=lambda p, m: {"w": p["w"] * 0.0,       # dropped: no buffer
                             "seen": p["seen"] + m["rows"]})
    return params, loss_fn, buffers


class TestBuffers:
    def test_rule_moves_the_buffer_and_the_optimizer_never_sees_it(self):
        params, loss_fn, buffers = _buffered()
        opt = optim.adamw(1e-2, weight_decay=0.5)
        state = opt.init(buffers.trainable(params))
        assert state.mu == {"w": state.mu["w"], "seen": None}
        step = make_train_step(loss_fn, opt, donate=False, buffers=buffers,
                               mixed_precision="bf16")
        batch = jnp.asarray(np.random.default_rng(0).random((4, 3)),
                            jnp.float32)
        plain = make_train_step(
            lambda p, b: loss_fn({**p, "seen": params["seen"]}, b), opt,
            donate=False, mixed_precision="bf16")
        want = plain({"w": params["w"]}, opt.init({"w": params["w"]}), batch)
        out = step(params, state, batch)
        # the weight took exactly the optimizer's step; the rule's own
        # change to it was dropped
        np.testing.assert_array_equal(out.params["w"], want.params["w"])
        # the buffer moved by the rule alone: no decay, no moment, and it
        # reached the loss uncast under the bf16 working copy
        np.testing.assert_array_equal(out.params["seen"], [4.25])
        assert out.opt_state.mu["seen"] is None
        assert float(out.metrics["seen_dtype_is_f32"]) == 1.0
        out = step(out.params, out.opt_state, batch)
        np.testing.assert_array_equal(out.params["seen"], [8.25])

    def test_empty_mask_lowers_to_the_step_without_buffers(self):
        """With no leaf outside the optimizer the program is the one
        built without the keyword, text for text."""
        from distributed_pytorch_tpu.parallel import Buffers

        _, params, opt, loss_fn = _setup()
        batch = (jnp.ones((8, 1)), jnp.zeros((8,), jnp.int32))
        state = opt.init(params)
        empty = Buffers(
            mask=lambda p: jax.tree_util.tree_map(lambda _: False, p),
            update=lambda p, m: p)
        texts = [make_train_step(loss_fn, opt, donate=True,
                                 mixed_precision="bf16", buffers=b)
                 .lower(params, state, batch).as_text()
                 for b in (None, empty)]
        assert texts[0] == texts[1]

    def test_runs_where_the_loss_is_the_whole_batchs(self, group8):
        params, loss_fn, buffers = _buffered()
        opt = optim.adamw(1e-2)
        step = make_step(loss_fn, opt, specs=FROM_INPUTS, donate=False,
                         buffers=buffers)
        out = step(params, opt.init(buffers.trainable(params)),
                   jnp.ones((8, 3)))
        np.testing.assert_array_equal(out.params["seen"], [8.25])

    @pytest.mark.parametrize("kw,where", [
        (dict(), "dp island"),
        (dict(weight_update="sharded"), "sharded"),
        (dict(specs=StepSpecs(params={"w": P(), "seen": P()})), "ladder")])
    def test_per_shard_engines_refuse_by_name(self, group8, kw, where):
        params, loss_fn, buffers = _buffered()
        with pytest.raises(ValueError, match="buffers= is not carried by"):
            make_step(loss_fn, optim.adamw(1e-2), buffers=buffers, **kw)
