"""Collective semantics on the 8-device virtual mesh (SURVEY.md §4
'multi-process CPU tests'): sum/avg all-reduce, rooted reduce/gather value
placement, broadcast, barrier — the contracts of reference
distributed.py:119-187."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_pytorch_tpu as dist


def stacked(world, shape=(3,)):
    """Per-rank values: rank r holds r+1 everywhere."""
    return jnp.stack([jnp.full(shape, float(r + 1)) for r in range(world)])


def test_all_reduce_sum(group8):
    x = stacked(8)
    out = dist.all_reduce(x, op="sum")
    expect = sum(range(1, 9))
    assert out.shape == x.shape
    np.testing.assert_allclose(np.asarray(out), expect)


def test_all_reduce_avg(group8):
    x = stacked(8)
    out = dist.all_reduce(x, op="avg")
    np.testing.assert_allclose(np.asarray(out), sum(range(1, 9)) / 8)


def test_all_reduce_invalid_op(group8):
    with pytest.raises(ValueError):
        dist.all_reduce(stacked(8), op="product")


def test_reduce_sum_primary_view(group8):
    out = dist.reduce(stacked(8))
    assert out.shape == (3,)
    np.testing.assert_allclose(np.asarray(out), sum(range(1, 9)))


def test_gather_rank_order(group8):
    out = dist.gather(stacked(8))
    assert isinstance(out, list) and len(out) == 8
    for r, t in enumerate(out):
        np.testing.assert_allclose(np.asarray(t), r + 1)


def test_gather_shape_mismatch_raises(group8):
    with pytest.raises(ValueError):
        dist.gather(jnp.zeros((5, 3)))  # leading axis != world


def test_broadcast_src(group8):
    out = dist.broadcast(stacked(8), src=3)
    np.testing.assert_allclose(np.asarray(out), 4.0)


def test_all_gather(group8):
    x = stacked(8)
    out = dist.all_gather(x)
    assert out.shape == (8, 3)


def test_all_gather_shape_mismatch_raises(group8):
    """all_gather must validate the stacked layout at world>1 like gather
    does — a silent passthrough would hand callers a wrongly-shaped array."""
    with pytest.raises(ValueError):
        dist.all_gather(jnp.zeros((5, 3)))  # leading axis != world
    with pytest.raises(ValueError):
        dist.all_gather(jnp.float32(1.0))   # scalar can't be stacked


def test_barrier_runs(group8):
    dist.barrier()
    dist.wait_for_everyone()


def test_collectives_on_sharded_arrays(group8):
    """The helpers must work on arrays actually sharded over the mesh (the
    real runtime layout), not just host arrays."""
    x = dist.shard_batch(np.arange(16.0).reshape(8, 2))
    out = dist.all_reduce(x, op="sum")
    np.testing.assert_allclose(np.asarray(out)[0], np.asarray(out)[7])
    red = dist.reduce(x)
    np.testing.assert_allclose(np.asarray(red),
                               np.arange(16.0).reshape(8, 2).sum(0))


def test_in_step_primitives_under_shard_map(group8):
    """psum/all_gather/ppermute wrappers lower correctly inside shard_map."""
    from jax.sharding import PartitionSpec as P
    from distributed_pytorch_tpu.comm import primitives as prim

    mesh = dist.get_mesh()

    def body(x):
        s = prim.psum(x, "dp")
        g = prim.all_gather(x, "dp", axis=0, tiled=True)
        shifted = prim.ring_shift(x, "dp", shift=1)
        idx = prim.axis_index("dp")
        return s, g, shifted, idx[None]

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(P("dp"),),
                      out_specs=(P(), P("dp"), P("dp"), P("dp")),
                      check_vma=False)
    x = jnp.arange(8.0).reshape(8, 1)
    s, g, shifted, idx = jax.jit(f)(x)
    np.testing.assert_allclose(np.asarray(s), 28.0)
    np.testing.assert_allclose(np.asarray(g).reshape(8, 8)[0],
                               np.asarray(g).reshape(8, 8)[7])
    # ring shift moves rank r's block to rank (r+1)
    np.testing.assert_allclose(np.asarray(shifted).ravel(),
                               np.roll(np.arange(8.0), 1))
    np.testing.assert_array_equal(np.asarray(idx).ravel(), np.arange(8))


def test_line_shift_under_shard_map(group8):
    """line_shift: no wraparound, zero fill at the unfed end — the
    pipeline stage transport (activations +1, gradients -1)."""
    from jax.sharding import PartitionSpec as P
    from distributed_pytorch_tpu.comm import primitives as prim

    mesh = dist.get_mesh()

    def body(x):
        return (prim.line_shift(x, "dp", 1),
                prim.line_shift(x, "dp", -1),
                prim.line_shift(x, "dp", 0),
                prim.line_shift(x, "dp", 8))

    f = jax.shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                      out_specs=(P("dp"),) * 4, check_vma=False)
    x = jnp.arange(8.0).reshape(8, 1)
    fwd, bwd, ident, over = jax.jit(f)(x)
    # +1: rank r receives rank r-1's block; rank 0 gets zeros
    np.testing.assert_allclose(np.asarray(fwd).ravel(),
                               [0, 0, 1, 2, 3, 4, 5, 6])
    # -1: rank r receives rank r+1's block; rank 7 gets zeros
    np.testing.assert_allclose(np.asarray(bwd).ravel(),
                               [1, 2, 3, 4, 5, 6, 7, 0])
    np.testing.assert_allclose(np.asarray(ident).ravel(), np.arange(8.0))
    # shift >= axis size: nobody sends, everyone zero-filled
    np.testing.assert_allclose(np.asarray(over).ravel(), np.zeros(8))


def test_quantized_pmean_error_bound_and_agreement(group8):
    """int8-compressed mean: every device gets the SAME result, within
    one quantization step per wire leg of the exact mean; zeros exact;
    odd (non-divisible) sizes padded correctly."""
    from jax.sharding import PartitionSpec as P
    from distributed_pytorch_tpu.comm import primitives as prim

    mesh = dist.get_mesh()
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((8, 13, 7)).astype(np.float32) * 3.0

    def island(x):
        return prim.quantized_pmean(x[0], "dp")[None]

    f = jax.shard_map(island, mesh=mesh, in_specs=(P("dp"),),
                      out_specs=P("dp"), check_vma=False)
    out = np.asarray(jax.jit(f)(jnp.asarray(xs)))
    exact = xs.mean(0)
    for i in range(1, 8):
        np.testing.assert_array_equal(out[i], out[0])
    err = np.abs(out[0] - exact).max()
    bound = np.abs(xs).max() / 254 + np.abs(exact).max() / 254
    assert err <= bound * 1.05, (err, bound)
    assert np.asarray(jax.jit(f)(jnp.zeros((8, 4, 4)))).max() == 0.0
