"""Quantized collective layer (comm/wire.py + dpx_allreduce_q8 +
quantized_pmean): wire-format codec invariants, the executable ring spec
(cross-rank determinism, error bounds, byte accounting — the issue-1
acceptance criteria), error-feedback residual behavior, and the
reference-exact full-width contracts staying untouched.

The numpy ring simulation IS the native schedule (bit-for-bit — the
slow multiprocess test in test_host_backend.py pins that), so the fast
tests here exercise the real wire numerics without spawning processes.
"""

import numpy as np
import pytest

import distributed_pytorch_tpu as dist
from distributed_pytorch_tpu.comm import primitives as prim
from distributed_pytorch_tpu.comm import wire
from distributed_pytorch_tpu.ops.quant import (ErrorFeedback,
                                               dequantize_grad_blocks,
                                               quantize_grad_blocks)

MIB_ELEMS = 262144  # 1 MiB of f32 — the acceptance-criterion bucket size


def _ranks(world, n, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal(n) * scale).astype(np.float32)
            for _ in range(world)]


class TestBlockCodec:
    def test_roundtrip_error_within_one_step(self):
        x = (np.random.default_rng(0).standard_normal(8192) * 3
             ).astype(np.float32)
        q, s = wire.quantize_blocks(x)
        back = wire.dequantize_blocks(q, s)
        # per-block error <= scale/2 = amax/254
        for b in range(s.size):
            blk = slice(b * wire.QUANT_BLOCK, (b + 1) * wire.QUANT_BLOCK)
            assert np.abs(back[blk] - x[blk]).max() <= s[b] / 2 + 1e-7

    def test_small_integer_payloads_exact(self):
        """The integer-exact snap: |v| <= 127 integers round-trip
        bit-exactly (scale 1) — counters and tallies survive the wire."""
        x = np.random.default_rng(1).integers(
            -127, 128, 4096).astype(np.float32)
        q, s = wire.quantize_blocks(x)
        assert np.array_equal(s, np.ones_like(s))
        assert np.array_equal(wire.dequantize_blocks(q, s), x)

    def test_zeros_exact(self):
        q, s = wire.quantize_blocks(np.zeros(3000, np.float32))
        assert np.array_equal(wire.dequantize_blocks(q, s),
                              np.zeros(3000, np.float32))

    def test_numpy_jnp_codec_parity(self):
        """ops/quant.py's jnp quantizer (the SPMD wire) and comm/wire.py's
        numpy quantizer (the host wire) produce identical grids."""
        x = (np.random.default_rng(2).standard_normal(4 * wire.QUANT_BLOCK)
             * 2.5).astype(np.float32)
        qn, sn = wire.quantize_blocks(x)
        qj, sj = quantize_grad_blocks(x.reshape(4, wire.QUANT_BLOCK))
        assert np.array_equal(qn.reshape(4, -1), np.asarray(qj))
        assert np.array_equal(sn, np.asarray(sj).ravel())
        back_j = np.asarray(dequantize_grad_blocks(qj, sj)).ravel()
        assert np.array_equal(back_j, wire.dequantize_blocks(qn, sn))

    def test_ragged_tail(self):
        x = (np.random.default_rng(3).standard_normal(wire.QUANT_BLOCK + 77)
             ).astype(np.float32)
        q, s = wire.quantize_blocks(x)
        assert q.size == x.size and s.size == 2
        assert np.abs(wire.dequantize_blocks(q, s) - x).max() <= s.max()


class TestQuantRing:
    """The executable spec of dpx_allreduce_q8 (bit-identical to it)."""

    def test_acceptance_bytes_and_error_1mib(self):
        """ISSUE-1 acceptance: on a >= 1 MiB N(0,1) gradient bucket the
        quantized all_reduce moves >= 3.5x fewer payload bytes than the
        f32 ring, with max relative error <= 1e-2."""
        world = 2
        xs = _ranks(world, MIB_ELEMS)
        res, qbytes = wire.simulate_quant_ring(xs)
        f32bytes = wire.ring_allreduce_wire_bytes(MIB_ELEMS, world)
        assert f32bytes / qbytes >= 3.5
        assert qbytes == wire.quant_ring_allreduce_wire_bytes(
            MIB_ELEMS, world)
        exact = np.sum(np.stack(xs), axis=0, dtype=np.float64)
        err = np.abs(res[0] - exact).max() / np.abs(exact).max()
        assert err <= 1e-2, err

    def test_byte_reduction_all_worlds(self):
        for world in (2, 4, 8):
            ratio = (wire.ring_allreduce_wire_bytes(MIB_ELEMS, world)
                     / wire.quant_ring_allreduce_wire_bytes(
                         MIB_ELEMS, world))
            assert ratio >= 3.5, (world, ratio)

    def test_cross_rank_determinism(self):
        """Every rank decodes the same forwarded bytes: results are
        BIT-identical on all ranks (ranks cannot drift apart)."""
        for world in (2, 4, 8):
            res, _ = wire.simulate_quant_ring(
                _ranks(world, 3 * wire.QUANT_BLOCK + 123, seed=world))
            for r in range(1, world):
                assert np.array_equal(res[r], res[0]), (world, r)

    def test_error_grows_at_most_one_step_per_hop(self):
        """Lossy accumulation is bounded: the reduce-scatter leg
        requantizes partials once per hop, so larger worlds pay more —
        but never more than ~one quantization step of the running
        partial per traversed hop (documented bound; w=8 measured
        ~1.6e-2 on N(0,1), vs 6e-3 at w=2)."""
        for world, bound in ((2, 1e-2), (4, 1.5e-2), (8, 2.5e-2)):
            xs = _ranks(world, MIB_ELEMS // 2, seed=7)
            res, _ = wire.simulate_quant_ring(xs)
            exact = np.sum(np.stack(xs), axis=0, dtype=np.float64)
            err = np.abs(res[0] - exact).max() / np.abs(exact).max()
            assert err <= bound, (world, err)

    def test_integer_payloads_survive_the_ring(self):
        """Small-magnitude integer payloads stay integer-exact END TO
        END: every partial sum of integers is again a small integer, so
        every hop takes the snap path."""
        world = 4
        rng = np.random.default_rng(5)
        xs = [rng.integers(-10, 11, 5000).astype(np.float32)
              for _ in range(world)]
        res, _ = wire.simulate_quant_ring(xs)
        exact = np.sum(np.stack(xs), axis=0).astype(np.float32)
        assert np.array_equal(res[0], exact)

    def test_ragged_and_tiny_sizes(self):
        for n in (1, 7, wire.QUANT_BLOCK - 1, wire.QUANT_BLOCK + 1, 5000):
            res, _ = wire.simulate_quant_ring(_ranks(4, n, seed=n))
            assert res[0].size == n


class TestErrorFeedback:
    def test_residual_corrects_bias_over_steps(self):
        """Reducing the SAME gradient repeatedly with EF: the time-average
        of what crossed the wire converges to the true gradient (the
        single-shot quantization bias cancels)."""
        ef = ErrorFeedback()
        g = (np.random.default_rng(0).standard_normal(4096) * 1e-2
             ).astype(np.float32)
        outs = [ef.compensate(g) for _ in range(64)]
        single = np.abs(outs[0] - g).max()
        averaged = np.abs(np.mean(outs, axis=0) - g).max()
        assert averaged < single / 10
        # residual stays bounded by one quantization step
        q, s = wire.quantize_blocks(g)
        assert np.abs(ef.residual).max() <= s.max()

    def test_compensated_value_is_on_wire_grid(self):
        """compensate() returns the int8-grid value, so the first ring
        hop retransmits it exactly (re-quantization is idempotent)."""
        ef = ErrorFeedback()
        g = (np.random.default_rng(1).standard_normal(2048) * 3
             ).astype(np.float32)
        grid = ef.compensate(g)
        q, s = wire.quantize_blocks(grid)
        assert np.array_equal(wire.dequantize_blocks(q, s), grid)

    def test_tiny_gradients_recovered(self):
        """A gradient far below its block-mate's scale quantizes to zero
        on step 1 but MUST eventually transmit via the residual."""
        ef = ErrorFeedback()
        g = np.zeros(wire.QUANT_BLOCK, np.float32)
        g[0] = 100.0   # sets the block scale
        g[1] = 0.11    # far below scale/2 ~ 0.39: rounds to zero
        sent = np.sum([ef.compensate(g)[1] for _ in range(40)])
        assert sent > 0.0  # residual accumulated until it crossed a step


class TestSpmdQuantPath:
    """grad_reduce="quant" on the 8-device SPMD mesh (quantized_pmean)."""

    def test_quantized_pmean_error_within_1e2_w8(self, group8):
        """The SPMD quantized reduce (two quantizations total) meets the
        1e-2 acceptance bound at world=8."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P


        mesh = dist.get_mesh()
        xs = np.stack(_ranks(8, 65536, seed=9))

        def island(x):
            return prim.quantized_pmean(x[0], "dp")[None]

        f = jax.shard_map(island, mesh=mesh, in_specs=(P("dp"),),
                          out_specs=P("dp"), check_vma=False)
        out = np.asarray(jax.jit(f)(jnp.asarray(xs)))
        exact = xs.mean(axis=0)
        err = np.abs(out[0] - exact).max() / np.abs(exact).max()
        assert err <= 1e-2, err

    def test_grad_reduce_quant_trains(self, group8):
        """make_train_step(grad_reduce="quant") — the issue-1 opt-in
        mode — tracks the exact-reduce step on the reference workload."""
        import jax
        from distributed_pytorch_tpu import models, optim
        from distributed_pytorch_tpu.ops.losses import cross_entropy
        from distributed_pytorch_tpu.parallel import make_train_step

        model = models.DummyModel(in_dim=1, hidden_dim=32, n_classes=4)
        params = model.init(jax.random.PRNGKey(0))
        opt = optim.adamw(1e-3)

        def loss_fn(p, batch):
            x, y = batch
            return cross_entropy(model.apply(p, x), y), {}

        x = dist.shard_batch(np.arange(16, dtype=np.float32)[:, None])
        y = dist.shard_batch((np.arange(16) % 4).astype(np.int32))
        step_q = make_train_step(loss_fn, opt, donate=False,
                                 grad_reduce="quant")
        step_e = make_train_step(loss_fn, opt, donate=False)
        pq = pe = params
        sq, se = opt.init(params), opt.init(params)
        for _ in range(5):
            oq = step_q(pq, sq, (x, y))
            oe = step_e(pe, se, (x, y))
            pq, sq, pe, se = oq.params, oq.opt_state, oe.params, oe.opt_state
        np.testing.assert_allclose(float(oq.loss.mean()),
                                   float(oe.loss.mean()),
                                   rtol=5e-3, atol=5e-3)


class TestExactContractsUntouched:
    """The reference-exact full-width contracts never quantize."""

    def test_wire_flag_validated(self, group8):
        with pytest.raises(ValueError, match="wire"):
            dist.all_reduce(np.zeros((8, 3), np.float32), wire="fp4")

    def test_reduce_and_gather_have_no_wire_param(self):
        """Rooted ops (reduce's untouched-non-root, gather's
        zeros-on-non-primary) stay reference-exact: the quantized wire is
        not even plumbed to them."""
        import inspect
        from distributed_pytorch_tpu.comm import collectives, host_backend
        for fn in (collectives.reduce, collectives.gather,
                   host_backend.reduce, host_backend.gather):
            assert "wire" not in inspect.signature(fn).parameters

    def test_integer_all_reduce_stays_exact_under_quant_wire(self, group8):
        """wire="quant" on the SPMD front door is a no-op hint: results
        stay exact (XLA moves exact bytes over ICI)."""
        import jax.numpy as jnp
        x = jnp.stack([jnp.full((3,), float(r + 1)) for r in range(8)])
        out = dist.all_reduce(x, op="sum", wire="quant")
        np.testing.assert_allclose(np.asarray(out), 36.0)


class TestByteAccounting:
    def test_quant_wire_bytes_formula(self):
        for n in (1, 1000, wire.QUANT_BLOCK, MIB_ELEMS + 13):
            nb = wire.num_blocks(n)
            assert wire.quant_wire_bytes(n) == n + 4 * nb

    def test_segment_grid_covers_everything_once(self):
        for n in (5000, MIB_ELEMS + 777):
            for world in (2, 4, 8):
                segs = wire.segment_blocks(n, world)
                assert sum(c for _, c in segs) == wire.num_blocks(n)
                starts = [s for s, _ in segs]
                assert starts == sorted(starts)

    def test_quantized_pmean_wire_bytes(self):
        assert prim.quantized_pmean_wire_bytes(MIB_ELEMS, 1) == 0
        b = prim.quantized_pmean_wire_bytes(MIB_ELEMS, 8)
        # ~4x fewer than an equivalent exact f32 exchange of both legs
        f32 = 2 * MIB_ELEMS * 4 * 7  # two legs, 7/8 of the bucket each
        assert f32 / b > 3.5
