"""optim/sharded — cross-replica sharded weight update (ZeRO-1) on the
quantized ring (ISSUE 7): flat layout geometry, bit-exact per-slice
optimizer math vs the replicated step, the native reduce-scatter/
all-gather leg parity against the numpy wire spec, byte accounting +
error-feedback residual bounds (the PR 1 acceptance pattern), both
front doors end to end (SPMD mesh + host TCP ring), chaos kill
mid-reduce-scatter with typed op attribution, and the sharded-optimizer
checkpoint written at dp=4 restoring bit-exact at dp=2."""

import multiprocessing as mp
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import distributed_pytorch_tpu as dist  # noqa: E402
from distributed_pytorch_tpu import models, optim  # noqa: E402
from distributed_pytorch_tpu.comm import primitives as prim  # noqa: E402
from distributed_pytorch_tpu.comm import wire  # noqa: E402
from distributed_pytorch_tpu.ops.losses import cross_entropy  # noqa: E402
from distributed_pytorch_tpu.optim.sharded import (  # noqa: E402
    ShardedOptState, build_layout, lcm_pad_multiple, shard_optimizer)
from distributed_pytorch_tpu.optim.sharded import (  # noqa: E402
    make_sharded_train_step)
from distributed_pytorch_tpu.parallel import make_train_step  # noqa: E402
from distributed_pytorch_tpu.runtime import faults  # noqa: E402
from distributed_pytorch_tpu.runtime.multiprocess import (  # noqa: E402
    launch_multiprocess)
from distributed_pytorch_tpu.runtime.watchdog import WorkerFailure  # noqa: E402

BLOCK = wire.QUANT_BLOCK


def _params():
    """A small mixed-shape/mixed-size param tree (every leaf smaller
    than one quant block, so per-leaf padding is actually exercised)."""
    rng = np.random.default_rng(0)
    return {
        "emb": {"w": jnp.asarray(rng.standard_normal((16, 8)),
                                 jnp.float32)},
        "ln": {"scale": jnp.asarray(np.ones(8), jnp.float32),
               "bias": jnp.asarray(np.zeros(8), jnp.float32)},
        "head": {"w": jnp.asarray(rng.standard_normal((8, 4)) * 0.1,
                                  jnp.float32)},
    }


def _grads_like(tree, seed=1, scale=1e-2):
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.standard_normal(np.shape(p)) * scale,
                              jnp.float32), tree)


# ---------------------------------------------------------------------------
# flat layout geometry
# ---------------------------------------------------------------------------


class TestFlatLayout:
    def test_roundtrip_and_block_alignment(self):
        params = _params()
        lay = build_layout(params, 4)
        # every leaf starts on a block edge; total pads to world*block
        for off in lay.offsets:
            assert off % BLOCK == 0
        assert lay.n_padded % (4 * BLOCK) == 0
        assert lay.seg % BLOCK == 0
        flat = lay.flatten_np(params)
        back = lay.unflatten_jnp(jnp.asarray(flat))
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # jnp flatten agrees with the numpy flatten bit for bit
        np.testing.assert_array_equal(
            np.asarray(lay.flatten_jnp(params)), flat)

    def test_equal_grid_matches_ring_grid(self):
        """The equal-segment grid the SPMD psum_scatter needs IS the
        block grid the native ring computes (the tail pad makes block
        counts divide evenly), so both front doors share one ownership
        map."""
        lay = build_layout(_params(), 4)
        for rank in range(4):
            lo, cnt = wire.ring_owned_span(lay.n_padded, 4, rank)
            slo, shi = lay.span(lay.ring_segment(rank))
            assert (lo, lo + cnt) == (slo, shi)

    def test_scalar_and_python_leaves_roundtrip(self):
        """Bare Python scalars and 0-d leaves survive the flat layout
        (dtype extraction must not assume .dtype exists)."""
        tree = {"w": jnp.ones((4, 4), jnp.float32), "t": 0.5,
                "s": jnp.asarray(2.0, jnp.float32)}
        lay = build_layout(tree, 2)
        back = lay.unflatten_jnp(jnp.asarray(lay.flatten_np(tree)))
        assert float(back["t"]) == 0.5
        assert float(back["s"]) == 2.0
        np.testing.assert_array_equal(np.asarray(back["w"]),
                                      np.ones((4, 4), np.float32))

    def test_pad_multiple_makes_layouts_portable(self):
        params = _params()
        pm = lcm_pad_multiple([4, 2])
        l4 = build_layout(params, 4, pad_multiple=pm)
        l2 = build_layout(params, 2, pad_multiple=pm)
        assert l4.n_padded == l2.n_padded
        assert l4.offsets == l2.offsets
        with pytest.raises(ValueError, match="multiple"):
            build_layout(params, 4, pad_multiple=2 * BLOCK)

    def test_state_specs_shard_flat_vectors_only(self):
        params = _params()
        lay = build_layout(params, 4)
        opt = optim.adamw(1e-3)
        state = shard_optimizer(opt, lay).init_global(params)
        specs = lay.state_specs(state)
        flat_specs = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        leaves = jax.tree_util.tree_leaves(state)
        assert len(flat_specs) == len(leaves)
        for leaf, spec in zip(leaves, flat_specs):
            if np.ndim(leaf) == 1 and leaf.shape[0] == lay.n_padded:
                assert spec == P("dp")
            elif np.ndim(leaf) == 0:
                assert spec == P()


# ---------------------------------------------------------------------------
# acceptance: bit-exact per-leaf step on the owned slice (f32 AdamW)
# ---------------------------------------------------------------------------


class TestSlicedStepBitExact:
    @pytest.mark.parametrize("make_opt", [
        lambda: optim.adamw(1e-3),
        lambda: optim.sgd(1e-2, momentum=0.9),
    ], ids=["adamw", "sgd_momentum"])
    def test_sharded_update_equals_replicated_slice(self, make_opt):
        """Given the same mean gradients, the sharded optimizer's step
        on each owned slice is BIT-IDENTICAL to the replicated
        optimizer's step on the whole tree, sliced — the ISSUE 7
        numerical-equivalence acceptance criterion, over 3 steps."""
        world = 4
        params = _params()
        lay = build_layout(params, world)
        opt = make_opt()
        sharded = shard_optimizer(opt, lay)

        rep_params = params
        rep_state = opt.init(params)
        flat0 = lay.flatten_np(params)
        sl_states = [
            sharded.init_flat(jnp.asarray(
                flat0[lay.span(lay.ring_segment(r))[0]:
                      lay.span(lay.ring_segment(r))[1]]))
            for r in range(world)]

        for step_i in range(3):
            grads = _grads_like(params, seed=10 + step_i)
            rep_params, rep_state = jax.jit(opt.update)(
                grads, rep_state, rep_params)
            flat_g = lay.flatten_np(grads)
            flat_new = np.zeros_like(flat_g)
            for r in range(world):
                lo, hi = lay.span(lay.ring_segment(r))
                new_master, sl_states[r] = jax.jit(
                    sharded.update_flat)(jnp.asarray(flat_g[lo:hi]),
                                         sl_states[r])
                flat_new[lo:hi] = np.asarray(new_master)
            sh_params = lay.unflatten_jnp(jnp.asarray(flat_new))
            for a, b in zip(jax.tree_util.tree_leaves(rep_params),
                            jax.tree_util.tree_leaves(sh_params)):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b))

    def test_shard_optimizer_rejects_non_optimizer(self):
        lay = build_layout(_params(), 2)
        with pytest.raises(TypeError, match="Optimizer"):
            shard_optimizer(lambda g, s, p: (p, s), lay)

    def test_adafactor_rejected_as_non_elementwise(self):
        """Silent corruption becomes a typed error: adafactor's
        factored moments cannot be updated on a flat slice — detected
        by state type at init (bare and composed)."""
        params = _params()
        lay = build_layout(params, 2)
        for opt in (optim.adafactor(1e-3),
                    optim.with_schedule(lambda lr: optim.adafactor(lr),
                                        optim.constant(1e-3))):
            with pytest.raises(TypeError, match="ELEMENTWISE"):
                shard_optimizer(opt, lay).init_global(params)


# ---------------------------------------------------------------------------
# wire: the standalone legs vs the executable spec + byte accounting
# ---------------------------------------------------------------------------


class TestWireLegSpecs:
    def _ranks(self, world, n, seed=0):
        rng = np.random.default_rng(seed)
        return [(rng.standard_normal(n) * 2).astype(np.float32)
                for _ in range(world)]

    def test_legs_compose_to_the_allreduce_bit_exactly(self):
        """reduce-scatter sim + all-gather sim == simulate_quant_ring,
        bit for bit — which is itself pinned bit-identical to the
        native dpx_allreduce_q8, so the standalone native legs share
        the same oracle."""
        for world in (2, 4, 8):
            xs = self._ranks(world, 3 * BLOCK + 123, seed=world)
            ref, ref_bytes = wire.simulate_quant_ring(xs)
            bufs, b1 = wire.simulate_quant_reduce_scatter(xs)
            outs, b2 = wire.simulate_quant_allgather(bufs)
            assert b1 + b2 == ref_bytes
            for r in range(world):
                np.testing.assert_array_equal(outs[r],
                                              ref[r].ravel())

    def test_reduce_scatter_owned_span_holds_the_sum(self):
        world, n = 4, 2 * BLOCK * 4 + 77
        xs = self._ranks(world, n, seed=3)
        bufs, _ = wire.simulate_quant_reduce_scatter(xs)
        exact = np.sum(np.stack(xs), axis=0, dtype=np.float64)
        for r in range(world):
            lo, cnt = wire.ring_owned_span(n, world, r)
            got = bufs[r][lo:lo + cnt]
            want = exact[lo:lo + cnt]
            err = np.abs(got - want).max() / (np.abs(want).max() + 1e-12)
            assert err <= 2.5e-2, (r, err)

    def test_allgather_bit_identical_across_ranks(self):
        world, n = 4, 3 * BLOCK * 4
        bufs = self._ranks(world, n, seed=5)
        outs, _ = wire.simulate_quant_allgather(bufs)
        for r in range(1, world):
            np.testing.assert_array_equal(outs[r], outs[0])

    def test_leg_byte_accounting_and_ratio(self):
        """ISSUE 7 acceptance: each leg is half the quant allreduce;
        the sharded update's two quantized legs move >= 3.5x fewer
        bytes than the f32 replicated ring's allreduce."""
        n = 1 << 20
        for world in (2, 4, 8):
            leg = wire.quant_leg_wire_bytes(n, world)
            assert 2 * leg == wire.quant_ring_allreduce_wire_bytes(
                n, world)
            ratio = wire.ring_allreduce_wire_bytes(n, world) / (2 * leg)
            assert ratio >= 3.5, (world, ratio)
        assert wire.quant_leg_wire_bytes(n, 1) == 0

    def test_sim_bytes_match_accounting(self):
        world, n = 4, 5 * BLOCK + 9
        xs = self._ranks(world, n)
        _, rs_bytes = wire.simulate_quant_reduce_scatter(xs)
        assert rs_bytes == wire.quant_leg_wire_bytes(n, world)


# ---------------------------------------------------------------------------
# error feedback: the gather-leg residual (PR 1 acceptance pattern)
# ---------------------------------------------------------------------------


class TestParamResidual:
    def test_master_to_grid_gap_bounded_and_not_compounding(self):
        """The sharded state's exact master vs the broadcast int8-grid
        params: the gap stays within HALF a quantization step per block
        on EVERY step (it re-derives from the fresh master instead of
        accumulating) — the error-feedback property of the gather leg."""
        params = _params()
        lay = build_layout(params, 1)
        opt = optim.adamw(1e-3)
        sharded = shard_optimizer(opt, lay)
        state = sharded.init_global(params)
        upd = jax.jit(sharded.update_flat)
        g = jnp.asarray(lay.flatten_np(_grads_like(params, seed=2)))
        for step_i in range(50):
            new_master, state = upd(g, state)
            master = np.asarray(new_master)
            q, s = wire.quantize_blocks(master)
            working = wire.dequantize_blocks(q, s)
            for b in range(s.size):
                blk = slice(b * BLOCK, (b + 1) * BLOCK)
                gap = np.abs(working[blk] - master[blk]).max()
                assert gap <= s[b] / 2 + 1e-7, (step_i, b, gap)

    def test_grad_leg_reuses_pr1_error_feedback(self):
        """The host engine's scatter leg carries the PR 1
        ErrorFeedback residual: time-averaged transmitted gradients
        converge to the true gradient (re-asserted here over the
        sharded bucket layout, with the per-leaf padding in place)."""
        from distributed_pytorch_tpu.ops.quant import ErrorFeedback
        params = _params()
        lay = build_layout(params, 4)
        g = lay.flatten_np(_grads_like(params, seed=3, scale=1e-3))
        ef = ErrorFeedback()
        outs = [ef.compensate(g) for _ in range(64)]
        single = np.abs(outs[0] - g).max()
        averaged = np.abs(np.mean(outs, axis=0) - g).max()
        assert averaged < single / 10
        q, s = wire.quantize_blocks(g)
        assert np.abs(ef.residual).max() <= s.max()


# ---------------------------------------------------------------------------
# SPMD front door (8-device virtual mesh)
# ---------------------------------------------------------------------------


class TestSpmdSharded:
    """The SPMD sharded-vs-replicated trajectory matrix moved to the
    spec-driven suite (tests/test_front_door.py::TestSpecMatrix — the
    ISSUE 13 collapse); this class keeps only what is NOT a per-front-
    door duplicate: the checkpoint-facing spec exports and validation."""

    def _setup(self):
        model = models.DummyModel(in_dim=1, hidden_dim=32, n_classes=4)
        params = model.init(jax.random.PRNGKey(0))
        opt = optim.adamw(1e-3)

        def loss_fn(p, batch):
            x, y = batch
            return cross_entropy(model.apply(p, x), y), {}

        x = dist.shard_batch(np.arange(16, dtype=np.float32)[:, None])
        y = dist.shard_batch((np.arange(16) % 4).astype(np.int32))
        return params, opt, loss_fn, (x, y)

    def test_init_opt_state_is_sharded_state(self, group8):
        params, opt, loss_fn, batch = self._setup()
        step = make_train_step(loss_fn, opt, donate=False,
                               weight_update="sharded")
        assert isinstance(step.init_opt_state(params), ShardedOptState)

    def test_state_specs_exported_for_ckpt(self, group8):
        params, opt, loss_fn, batch = self._setup()
        step = make_train_step(loss_fn, opt, donate=False,
                               weight_update="sharded")
        state = step.init_opt_state(params)
        specs = step.state_specs(state)
        assert specs.master == P("dp")
        assert specs.inner.mu == P("dp")
        assert specs.inner.step == P()

    def test_weight_update_validated_and_env_default(self, group8,
                                                     monkeypatch):
        params, opt, loss_fn, batch = self._setup()
        with pytest.raises(ValueError, match="weight_update"):
            make_train_step(loss_fn, opt, weight_update="zero9")
        monkeypatch.setenv("DPX_WEIGHT_UPDATE", "sharded")
        step = make_train_step(loss_fn, opt, donate=False)
        assert hasattr(step, "init_opt_state")

    def test_world1_same_state_structure(self):
        """At world==1 the sharded step runs unsharded but keeps the
        global flat state structure — checkpoints stay portable."""
        model = models.DummyModel(in_dim=1, hidden_dim=32, n_classes=4)
        params = model.init(jax.random.PRNGKey(0))
        opt = optim.adamw(1e-3)

        def loss_fn(p, batch):
            x, y = batch
            return cross_entropy(model.apply(p, x), y), {}

        step = make_train_step(loss_fn, opt, donate=False,
                               weight_update="sharded")
        state = step.init_opt_state(params)
        assert isinstance(state, ShardedOptState)
        x = np.arange(8, dtype=np.float32)[:, None]
        y = (np.arange(8) % 4).astype(np.int32)
        out = step(params, state, (x, y))
        assert np.isfinite(float(out.loss.mean()))


class TestQuantizedLegPrimitives:
    def test_quantized_reduce_scatter_sums(self, group8):
        mesh = dist.get_mesh()
        n = 8 * 2 * BLOCK
        xs = np.stack([(np.random.default_rng(r).standard_normal(n))
                       .astype(np.float32) for r in range(8)])

        def island(x):
            return prim.quantized_reduce_scatter(x[0], "dp")[None]

        f = jax.shard_map(island, mesh=mesh, in_specs=(P("dp"),),
                          out_specs=P("dp"), check_vma=False)
        out = np.asarray(jax.jit(f)(jnp.asarray(xs))).ravel()
        exact = xs.sum(axis=0, dtype=np.float64)
        err = np.abs(out - exact).max() / np.abs(exact).max()
        assert err <= 1e-2, err

    def test_quantized_all_gather_bit_identical(self, group8):
        mesh = dist.get_mesh()
        chunk = 2 * BLOCK
        xs = np.stack([(np.random.default_rng(r).standard_normal(chunk))
                       .astype(np.float32) for r in range(8)])

        def island(x):
            return prim.quantized_all_gather(x[0], "dp")[None]

        f = jax.shard_map(island, mesh=mesh, in_specs=(P("dp"),),
                          out_specs=P("dp"), check_vma=False)
        out = np.asarray(jax.jit(f)(jnp.asarray(xs)))
        # every device decoded the same bytes — replicated values
        # rebuilt from sharded updates cannot drift
        for r in range(1, 8):
            np.testing.assert_array_equal(out[r], out[0])
        # within one quantization step of the exact concatenation
        # (NOT asserted bit-equal to the numpy codec: XLA lowers the
        # /127 to a reciprocal multiply, a 1-ulp scale difference)
        flat = xs.ravel()
        _, s = wire.quantize_blocks(flat)
        per_elem = np.repeat(s, BLOCK)[:flat.size]
        assert np.all(np.abs(out[0] - flat) <= per_elem / 2 + 1e-6)

    def test_divisibility_validated(self, group8):
        mesh = dist.get_mesh()
        bad = np.zeros((8, 10), np.float32)
        for fn in (prim.quantized_reduce_scatter,
                   prim.quantized_all_gather):
            island = lambda x: fn(x[0], "dp")[None]  # noqa: B023
            f = jax.shard_map(island, mesh=mesh, in_specs=(P("dp"),),
                              out_specs=P("dp"), check_vma=False)
            with pytest.raises(ValueError, match="divisible"):
                f(jnp.asarray(bad))


# ---------------------------------------------------------------------------
# host front door (native TCP ring, spawned processes)
# ---------------------------------------------------------------------------


class TestHostSharded:
    """The world-2 host sharded/quant trajectory + CommStats twins
    moved to the spec-driven suite (tests/test_front_door.py::
    TestHostMatrix — the ISSUE 13 collapse). What stays is the native
    leg bit-parity against the numpy wire spec, which no other door
    exercises."""

    @pytest.mark.slow
    def test_world4_sharded_native_legs_match_numpy_spec(self):
        """Native dpx_reduce_scatter_q8 / dpx_allgather_q8 vs the wire
        spec sims: owned spans and gathered buffers bit-identical."""
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        n = 70000
        launch_multiprocess(_native_leg_worker, 4, q, n)
        res = {}
        while len(res) < 4:
            rank, rs_hex, ag_hex = q.get(timeout=120)
            res[rank] = (rs_hex, ag_hex)
        import hashlib
        xs = [(np.random.default_rng(100 + r).standard_normal(n) * 2)
              .astype(np.float32) for r in range(4)]
        bufs, _ = wire.simulate_quant_reduce_scatter(xs)
        outs, _ = wire.simulate_quant_allgather(bufs)
        for r in range(4):
            lo, cnt = wire.ring_owned_span(n, 4, r)
            want_rs = hashlib.sha256(
                np.ascontiguousarray(bufs[r][lo:lo + cnt]).tobytes()
            ).hexdigest()
            want_ag = hashlib.sha256(
                np.ascontiguousarray(outs[r]).tobytes()).hexdigest()
            assert res[r] == (want_rs, want_ag), r


def _native_leg_worker(rank, world, q, n):
    import hashlib

    import numpy as _np

    import distributed_pytorch_tpu as _dist
    from distributed_pytorch_tpu.comm import wire as _wire
    from distributed_pytorch_tpu.runtime import context as _ctx

    _dist.init_process_group(rank, world)
    try:
        comm = _ctx.get_host_comm()
        x = (_np.random.default_rng(100 + rank).standard_normal(n) * 2
             ).astype(_np.float32)
        buf = x.copy()
        comm.reduce_scatter_q8(buf)
        lo, cnt = _wire.ring_owned_span(n, world, rank)
        rs_hex = hashlib.sha256(
            _np.ascontiguousarray(buf[lo:lo + cnt]).tobytes()).hexdigest()
        # feed the SAME post-reduce-scatter buffer to the gather leg —
        # exactly the sharded update's dataflow (sans the local step)
        comm.allgather_q8(buf)
        ag_hex = hashlib.sha256(
            _np.ascontiguousarray(buf).tobytes()).hexdigest()
        q.put((rank, rs_hex, ag_hex))
    finally:
        _dist.cleanup()


# ---------------------------------------------------------------------------
# chaos: kill mid-reduce-scatter (DPX_FAULT grammar, typed attribution)
# ---------------------------------------------------------------------------

CHAOS_TIMEOUT_MS = 2000


def _report_and_reraise(q, rank, fn):
    from distributed_pytorch_tpu.runtime.native import CommError
    t0 = time.monotonic()
    try:
        fn()
    except CommError as e:
        q.put((rank, type(e).__name__, e.op, e.peer,
               time.monotonic() - t0))
        q.close()
        q.join_thread()
        raise
    q.put((rank, None, None, None, time.monotonic() - t0))


def _sharded_chaos_worker(rank, world, q):
    """Two clean sharded-update comm cycles, then rank 2 is killed
    entering its third reduce_scatter (mid-leg for everyone else)."""
    import numpy as _np

    import distributed_pytorch_tpu as _dist
    from distributed_pytorch_tpu.runtime import context as _ctx

    _dist.init_process_group(rank, world)
    comm = _ctx.get_host_comm()
    buf = _np.ones(8 * 1024, _np.float32)
    for _ in range(2):
        comm.reduce_scatter_q8(buf.copy())
        comm.allgather_q8(buf.copy())
    _report_and_reraise(
        q, rank, lambda: comm.reduce_scatter_q8(buf.copy()))


def test_chaos_kill_mid_reduce_scatter_world4(monkeypatch):
    """ISSUE 7 satellite: the reduce_scatter/allgather ops are live in
    the DPX_FAULT grammar — a rank killed mid-reduce-scatter in a world
    of 4 surfaces as typed CommErrors on every survivor, attributed to
    op "reduce_scatter", within the deadline bound (no hang)."""
    assert "reduce_scatter" in faults.COMM_OPS
    assert "allgather" in faults.COMM_OPS
    (spec,) = faults.parse_fault_spec("kill@op=reduce_scatter,call=3,rank=2")
    assert spec.action == "kill" and spec.op == "reduce_scatter"

    monkeypatch.setenv(faults.FAULT_ENV,
                       "kill@op=reduce_scatter,call=3,rank=2")
    monkeypatch.setenv("DPX_COMM_TIMEOUT_MS", str(CHAOS_TIMEOUT_MS))
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    result = {}

    def run():
        try:
            launch_multiprocess(_sharded_chaos_worker, 4, q)
        except BaseException as e:  # noqa: BLE001
            result["exc"] = e

    t = threading.Thread(target=run, name="test-sharded-chaos",
                         daemon=True)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive(), "chaos run hung: deadline guard failed"
    assert isinstance(result.get("exc"), WorkerFailure)
    failure = result["exc"]
    assert failure.rank == 2
    assert failure.op == "reduce_scatter"
    assert failure.exitcode == faults.KILL_EXIT_CODE

    reports = {}
    while len(reports) < 3:
        rank, kind, op, peer, elapsed = q.get(timeout=10)
        reports[rank] = (kind, op, peer, elapsed)
    assert set(reports) == {0, 1, 3}
    for rank, (kind, op, peer, elapsed) in reports.items():
        assert kind in ("CommPeerDied", "CommTimeout"), (rank, kind)
        assert op == "reduce_scatter"
        assert elapsed < 2 * CHAOS_TIMEOUT_MS / 1000.0, (rank, elapsed)


# ---------------------------------------------------------------------------
# ckpt: sharded-optimizer checkpoint written at dp=4 restores at dp=2
# ---------------------------------------------------------------------------


class TestShardedOptCkptReshard:
    CUT, TOTAL = 2, 4

    def _setup(self, world):
        dist.init_process_group(rank=0, world_size=world)
        model = models.DummyModel(in_dim=1, hidden_dim=32, n_classes=4)
        params = model.init(jax.random.PRNGKey(0))
        opt = optim.adamw(1e-2)

        def loss_fn(p, batch):
            x, y = batch
            return cross_entropy(model.apply(p, x), y), {}

        step = make_sharded_train_step(
            loss_fn, opt, donate=False,
            pad_multiple=lcm_pad_multiple([4, 2]))
        return params, step

    def _batches(self):
        rng = np.random.default_rng(7)
        return [(rng.random((8, 1), dtype=np.float32),
                 rng.integers(0, 4, (8,)).astype(np.int32))
                for _ in range(self.TOTAL)]

    def _shard_batch(self, b):
        return tuple(dist.shard_batch(v) for v in b)

    def test_dp4_ckpt_restores_bit_exact_at_dp2(self, tmp_path):
        from distributed_pytorch_tpu.ckpt import CheckpointManager
        from distributed_pytorch_tpu.parallel.tensor import (
            replicated_specs, shard_params)

        # uninterrupted dp=4 reference trajectory
        params, step = self._setup(4)
        st = step.init_opt_state(params)
        ref_losses, p, s = [], params, st
        for b in self._batches():
            out = step(p, s, self._shard_batch(b))
            p, s = out.params, out.opt_state
            ref_losses.append(float(out.loss.mean()))
        dist.cleanup()

        # dp=4 run, checkpointing the sharded state at step CUT
        params, step = self._setup(4)
        st = step.init_opt_state(params)
        p, s = params, st
        mgr = CheckpointManager(
            str(tmp_path), sharded=True,
            param_specs=replicated_specs(params),
            opt_specs=step.state_specs(st), axis_sizes={"dp": 4})
        for i, b in enumerate(self._batches()[:self.CUT]):
            out = step(p, s, self._shard_batch(b))
            p, s = out.params, out.opt_state
            mgr.save(i + 1, p, s, force=(i + 1 == self.CUT))
        mgr.wait()
        saved_state = jax.tree_util.tree_map(np.asarray, s)
        dist.cleanup()

        # restore at dp=2: same global flat length (lcm pad_multiple),
        # so the resharding reader re-slices the moments for free
        from distributed_pytorch_tpu.utils.checkpoint import (
            restore_checkpoint)
        params2, step2 = self._setup(2)
        template = step2.init_opt_state(params2)
        ck = restore_checkpoint(str(tmp_path), like_params=params2,
                                like_opt_state=template)
        assert ck.step == self.CUT
        # bit-exact: the dp=2 restore holds exactly the dp=4 moments
        for a, b in zip(jax.tree_util.tree_leaves(saved_state),
                        jax.tree_util.tree_leaves(ck.opt_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and the run continues loss-correctly on the shrunk world
        from distributed_pytorch_tpu.runtime import context
        p2 = ck.params
        s2 = shard_params(ck.opt_state, step2.state_specs(template),
                          context.get_mesh())
        for i, b in enumerate(self._batches()[self.CUT:]):
            out = step2(p2, s2, self._shard_batch(b))
            p2, s2 = out.params, out.opt_state
            np.testing.assert_allclose(
                float(out.loss.mean()), ref_losses[self.CUT + i],
                rtol=1e-4, atol=1e-5)
        dist.cleanup()
