"""Native host process group: true multi-process collectives (SURVEY.md §4
'multi-process CPU tests') — ring allreduce, rooted reduce/gather (incl.
the zeros-on-non-primary gather contract), broadcast, barrier ordering,
and spawn error propagation (the join=True contract)."""

import multiprocessing as mp
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_pytorch_tpu.runtime.multiprocess import launch_multiprocess

WORLD = 4


def _collectives_worker(rank, world, q):
    """Runs in a spawned process: exercises every collective through the
    public API (init_process_group routes to the native group via
    DPX_BACKEND=host set by the launcher)."""
    import numpy as np
    import distributed_pytorch_tpu as dist

    dist.init_process_group(rank, world)
    try:
        assert dist.get_rank() == rank
        assert dist.get_world_size() == world
        assert dist.is_primary() == (rank == 0)
        assert dist.get_backend() == "host"

        # all_reduce sum + avg (ring)
        x = np.full((5,), float(rank + 1), np.float32)
        s = dist.all_reduce(x.copy(), op="sum")
        a = dist.all_reduce(x.copy(), op="avg")

        # big buffer: crosses socket-buffer sizes (deadlock regression)
        big = np.full((300_000,), float(rank + 1), np.float32)
        bigsum = dist.all_reduce(big, op="sum")

        # rooted reduce: only rank 0 must hold the sum
        r = dist.reduce(np.full((3,), float(rank + 1), np.float32))

        # rooted gather: zeros on non-primary (reference wart, exact)
        g = dist.gather(np.full((2,), float(rank), np.float32))

        # all_gather: every rank sees the stacked values
        ag = dist.all_gather(np.full((2,), float(rank), np.float32))

        # max all_reduce (SPMD-parity extension)
        mx = dist.all_reduce(np.full((2,), float(rank), np.float32), op="max")

        # integer reduce must preserve dtype exactly
        ir = dist.reduce(np.full((2,), rank + 1, np.int64))

        # broadcast from rank 2
        b = dist.broadcast(np.full((4,), float(rank), np.float32), src=2)

        # sync_params from rank 0
        p = dist.sync_params([np.full((2,), float(rank), np.float32)])[0]

        dist.barrier()
        dist.wait_for_everyone()

        q.put((rank, {
            "sum": s.tolist(), "avg": a.tolist(),
            "bigsum0": float(bigsum[0]), "bigsum_last": float(bigsum[-1]),
            "reduce": r.tolist(),
            "gather": [t.tolist() for t in g],
            "all_gather": np.asarray(ag).tolist(),
            "max": mx.tolist(),
            "int_reduce": ir.tolist(), "int_reduce_dtype": str(ir.dtype),
            "bcast": b.tolist(), "sync": p.tolist(),
        }))
    finally:
        dist.cleanup()


@pytest.mark.slow
def test_native_collectives_multiprocess():
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    launch_multiprocess(_collectives_worker, WORLD, q)
    results = {}
    while len(results) < WORLD:
        rank, data = q.get(timeout=60)
        results[rank] = data

    expect_sum = float(sum(range(1, WORLD + 1)))
    for rank in range(WORLD):
        d = results[rank]
        assert d["sum"] == [expect_sum] * 5
        assert d["avg"] == [expect_sum / WORLD] * 5
        assert d["bigsum0"] == expect_sum and d["bigsum_last"] == expect_sum
        assert d["bcast"] == [2.0] * 4          # src rank 2's value
        assert d["sync"] == [0.0, 0.0]           # rank 0's value
        assert d["all_gather"] == [[float(r)] * 2 for r in range(WORLD)]
        assert d["max"] == [float(WORLD - 1)] * 2
        assert d["int_reduce_dtype"] == "int64"
        if rank == 0:
            assert d["int_reduce"] == [int(expect_sum)] * 2
        else:
            assert d["int_reduce"] == [rank + 1] * 2
        if rank == 0:
            assert d["reduce"] == [expect_sum] * 3
            assert d["gather"] == [[float(r)] * 2 for r in range(WORLD)]
        else:
            # non-root reduce buffer unchanged; gather list all zeros
            assert d["reduce"] == [float(rank + 1)] * 3
            assert d["gather"] == [[0.0, 0.0] for _ in range(WORLD)]


def _quant_ring_worker(rank, world, q, n):
    """Native quantized ring (dpx_allreduce_q8) through the public API:
    result digests prove cross-rank bit-determinism and bit-parity with
    the numpy executable spec (comm/wire.py:simulate_quant_ring); comm
    stats prove the wire moved ~4x fewer bytes."""
    import hashlib

    import numpy as np
    import distributed_pytorch_tpu as dist
    from distributed_pytorch_tpu.comm import collectives
    from distributed_pytorch_tpu.runtime import context

    dist.init_process_group(rank, world)
    comm = context.get_host_comm()
    try:
        x = (np.random.default_rng(rank).standard_normal(n) * 2
             ).astype(np.float32)
        out = collectives.all_reduce(x, op="sum", wire="quant")
        # sync_params over the quantized wire: bit-identical everywhere
        p = collectives.sync_params(
            [np.random.default_rng(100 + rank).standard_normal(2048)
             .astype(np.float32)], wire="quant")[0]
        q.put((rank,
               hashlib.sha256(np.ascontiguousarray(out).tobytes())
               .hexdigest(),
               hashlib.sha256(np.ascontiguousarray(p).tobytes())
               .hexdigest(),
               comm.stats.summary().get("allreduce_q8", {}).get("bytes")))
    finally:
        dist.cleanup()


@pytest.mark.slow
def test_native_quant_ring_determinism_and_parity():
    import hashlib

    from distributed_pytorch_tpu.comm import wire

    n = 70000  # ragged: not a block or world multiple
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    launch_multiprocess(_quant_ring_worker, WORLD, q, n)
    res = {}
    while len(res) < WORLD:
        rank, d, pd, qbytes = q.get(timeout=60)
        res[rank] = (d, pd, qbytes)
    # bit-identical across ranks (allreduce AND quant param sync)
    assert len({v[0] for v in res.values()}) == 1
    assert len({v[1] for v in res.values()}) == 1
    # bit-identical to the numpy executable spec
    xs = [(np.random.default_rng(r).standard_normal(n) * 2
           ).astype(np.float32) for r in range(WORLD)]
    sim, sim_bytes = wire.simulate_quant_ring(xs)
    assert (hashlib.sha256(sim[0].tobytes()).hexdigest()
            == res[0][0])
    # recorded wire bytes match the accounting (per-rank share)
    assert res[0][2] == sim_bytes // WORLD


def _failing_worker(rank, world):
    import distributed_pytorch_tpu as dist
    dist.init_process_group(rank, world)
    try:
        if rank == 1:
            raise RuntimeError("boom on rank 1")
        dist.barrier()  # others would wait; rank 1 dies first
    finally:
        dist.cleanup()


def test_spawn_propagates_child_failure():
    """join=True contract (reference distributed.py:51-52): a failing
    child surfaces in the parent as an exception naming the rank."""
    with pytest.raises(RuntimeError, match="rank 1"):
        launch_multiprocess(_failing_worker, 2)


def _invalid_op_worker(rank, world):
    import numpy as np
    import distributed_pytorch_tpu as dist
    dist.init_process_group(rank, world)
    try:
        try:
            dist.all_reduce(np.ones(2, np.float32), op="product")
        except ValueError:
            return  # expected — reference distributed.py:131
        raise AssertionError("invalid op did not raise")
    finally:
        dist.cleanup()


def test_invalid_op_raises_in_host_mode():
    launch_multiprocess(_invalid_op_worker, 2)


def _ddp_worker(rank, world, q):
    """Fixed global batch split across ranks; host-mode DDP step (native
    bucketed grad allreduce). Reports the loss trajectory."""
    import jax
    import numpy as np
    import distributed_pytorch_tpu as dist
    from distributed_pytorch_tpu import models, optim
    from distributed_pytorch_tpu.ops.losses import cross_entropy_per_example
    from distributed_pytorch_tpu.parallel import make_train_step

    if world > 1:
        dist.init_process_group(rank, world)
    try:
        model = models.DummyModel(in_dim=1, hidden_dim=8, n_classes=4)
        params = model.init(jax.random.PRNGKey(0))
        opt = optim.adamw(1e-2)
        opt_state = opt.init(params)

        def loss_fn(p, batch):
            x, y = batch
            logits = model.apply(p, x)
            return cross_entropy_per_example(logits, y).mean(), {}

        step = make_train_step(loss_fn, opt)
        rng = np.random.default_rng(0)
        losses = []
        for _ in range(4):
            x = rng.random((8, 1), dtype=np.float32)
            y = rng.integers(0, 4, (8,)).astype(np.int32)
            lo = rank * (8 // max(world, 1))
            hi = lo + (8 // max(world, 1))
            out = step(params, opt_state, (x[lo:hi], y[lo:hi]))
            params, opt_state = out.params, out.opt_state
            # global mean loss = avg of per-rank means (equal shards)
            l = dist.all_reduce(
                np.asarray(out.loss, np.float32), op="avg") \
                if world > 1 else np.asarray(out.loss)
            losses.append(float(np.asarray(l).reshape(-1)[0]))
        q.put((rank, losses))
    finally:
        dist.cleanup()


@pytest.mark.slow
def test_host_ddp_loss_parity_vs_single_process():
    """2-process native-DDP training reproduces the single-process loss
    trajectory on the same global batches (BASELINE loss-curve parity,
    host front door)."""
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    launch_multiprocess(_ddp_worker, 1, q)
    _, ref = q.get(timeout=60)

    q2 = ctx.Queue()
    launch_multiprocess(_ddp_worker, 2, q2)
    results = {}
    while len(results) < 2:
        rank, losses = q2.get(timeout=60)
        results[rank] = losses

    np.testing.assert_allclose(results[0], results[1], rtol=1e-6)
    np.testing.assert_allclose(ref, results[0], rtol=2e-5, atol=1e-6)


def _env_reporter(rank, world, out_dir):
    import json
    import os
    with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
        json.dump({"JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
                   "TPU_VISIBLE_DEVICES":
                       os.environ.get("TPU_VISIBLE_DEVICES")}, f)


class TestPerRankDeviceAssignment:
    def test_default_children_are_cpu(self, tmp_path):
        import json

        from distributed_pytorch_tpu.runtime import launch_multiprocess

        launch_multiprocess(_env_reporter, 2, str(tmp_path))
        for r in range(2):
            with open(tmp_path / f"rank{r}.json") as f:
                env = json.load(f)
            # JAX_PLATFORMS=cpu is what keeps children off the chip;
            # TPU_VISIBLE_DEVICES is deliberately left alone (ambient)
            assert env["JAX_PLATFORMS"] == "cpu"

    def test_accel_optin_assigns_chip_per_rank(self, tmp_path, monkeypatch):
        """DPX_MULTIPROC_ACCEL=tpu: rank r's child owns chip r (the
        torch one-process-per-device model; reference rank->device
        mapping, distributed.py:88-91). The CPU suite asserts the env
        the children get; the execution ran on a four-chip host
        (runtime/multiprocess.py)."""
        import json

        from distributed_pytorch_tpu.runtime import launch_multiprocess
        from distributed_pytorch_tpu.runtime.multiprocess import (
            MULTIPROC_ACCEL_ENV)

        monkeypatch.setenv(MULTIPROC_ACCEL_ENV, "tpu")
        launch_multiprocess(_env_reporter, 2, str(tmp_path))
        for r in range(2):
            with open(tmp_path / f"rank{r}.json") as f:
                env = json.load(f)
            assert env["JAX_PLATFORMS"] == "tpu"
            assert env["TPU_VISIBLE_DEVICES"] == str(r)


    def test_unknown_accel_value_raises(self, monkeypatch):
        from distributed_pytorch_tpu.runtime import launch_multiprocess
        from distributed_pytorch_tpu.runtime.multiprocess import (
            MULTIPROC_ACCEL_ENV)

        monkeypatch.setenv(MULTIPROC_ACCEL_ENV, "gpu")
        with pytest.raises(ValueError, match="not supported"):
            launch_multiprocess(_env_reporter, 2, "/tmp")
