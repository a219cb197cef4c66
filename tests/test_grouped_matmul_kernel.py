"""The grouped expert matmul kernel (``ops/grouped_matmul_kernel.py``) in
interpret mode against ``jax.lax.ragged_dot`` in float32: what the walk
visits and what it leaves alone, every row of the result, and the rule
that sends a call to it (``parallel/moe.py::_kernel_interpret``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.ops import grouped_matmul_kernel as gk
from distributed_pytorch_tpu.parallel import moe


def _operands(g, r, k, n, dtype=jnp.float32, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kx, (r, k), dtype),
            jax.random.normal(kw, (g, k, n), dtype) * k ** -0.5)


def _want(xs, w, sizes):
    """``ragged_dot`` in float32, the rows past the last group zeros."""
    out = jax.lax.ragged_dot(xs.astype(jnp.float32), w.astype(jnp.float32),
                             sizes, preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)
    grouped = jnp.arange(xs.shape[0]) < jnp.sum(sizes)
    return jnp.where(grouped[:, None], out, 0.0)


# (rows, tile, sizes): what the walk has to get right
CASES = {
    "empty groups between full ones": (64, 16, [16, 0, 0, 32, 0, 16]),
    "all rows in one group": (64, 16, [0, 0, 64, 0]),
    "a group over two row tiles": (64, 16, [10, 20, 30, 4]),
    "a group over three row tiles": (64, 16, [6, 40, 0, 18]),
    "rows past the last group": (64, 16, [5, 0, 9, 3]),
    "a whole tile past the last group": (64, 16, [3, 2, 0, 1]),
    "no group has a row": (48, 16, [0, 0, 0]),
    "rows not a multiple of the tile": (70, 16, [3, 40, 0, 20]),
    "fewer rows than the default tile": (40, None, [1, 2, 0, 0, 3, 0, 0, 1]),
    "the first group empty": (64, 32, [0, 33, 31]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_every_row_agrees_with_ragged_dot(case):
    rows, tm, sizes = CASES[case]
    sizes = jnp.asarray(sizes, jnp.int32)
    xs, w = _operands(len(sizes), rows, 128, 256)
    got = gk.grouped_matmul(xs, w, sizes, tm=tm, interpret=True)
    assert got.dtype == jnp.float32 and got.shape == (rows, 256)
    np.testing.assert_allclose(got, _want(xs, w, sizes), atol=2e-5)
    # the tail is zeros to the bit, not small numbers
    assert not np.any(np.asarray(got[int(jnp.sum(sizes)):]))


# the four serving shapes (PERF.md Findings, PR 38) cut to test size:
# the groups, and rows a group, of the real call; K and N its proportions
SERVING = {
    "sdar block step": (128, 16, 256, 128),
    "sdar prefill chunk": (128, 64, 256, 128),
    "xing4 decode step": (64, 4, 384, 128),
    "xing4 prefill chunk": (64, 64, 384, 128),
}


@pytest.mark.parametrize("down", [False, True])
@pytest.mark.parametrize("shape", list(SERVING))
def test_serving_shapes_in_bfloat16(shape, down):
    g, a_group, k, n = SERVING[shape]
    if down:
        k, n = n, k
    rows = g * a_group
    rng = np.random.default_rng(g + a_group)
    # a routing, not a partition: some experts hot, some untouched, and
    # idle rows past the last group
    sizes = rng.multinomial(rows - rows // 8,
                            rng.dirichlet(np.full((g,), 0.3)))
    sizes = jnp.asarray(sizes, jnp.int32)
    xs, w = _operands(g, rows, k, n, jnp.bfloat16)
    got = gk.grouped_matmul(xs, w, sizes, interpret=True)
    want = _want(xs, w, sizes)
    # bf16 x bf16 products are exact in float32: only the order of the
    # accumulation differs
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)


def test_the_walk_visits_touched_groups_only():
    """``visits``: one visit a (row tile, group) that shares a row, none
    for a group of no rows, the tail's visits name the last touched
    group's weights (nothing new to copy), steps past the end repeat the
    last visit."""
    offsets, group, slab, tile, n = (np.asarray(a) for a in gk.visits(
        jnp.asarray([10, 0, 30, 5], jnp.int32), 64, 16))
    assert offsets.tolist() == [0, 10, 10, 40, 45, 64]
    assert n.tolist() == [7]
    assert group.tolist() == [0, 2, 2, 2, 3, 4, 4, 4]    # 4 = the tail
    assert slab.tolist() == [0, 2, 2, 2, 3, 3, 3, 3]
    assert tile.tolist() == [0, 0, 1, 2, 2, 2, 3, 3]
    assert 1 not in group


def test_the_walk_is_bounded_by_tiles_plus_groups():
    """The grid's static bound holds at the worst routing: every group
    touched and every boundary inside a tile."""
    g, rows, tm = 16, 16 * 9, 16
    _, group, _, tile, n = gk.visits(jnp.full((g,), 9, jnp.int32), rows, tm)
    assert int(n[0]) <= rows // tm + g == group.shape[0]
    pairs = set(zip(np.asarray(group)[:int(n[0])].tolist(),
                    np.asarray(tile)[:int(n[0])].tolist()))
    assert len(pairs) == int(n[0])                       # no visit twice


def test_one_trace_serves_every_call_site_of_a_shape():
    """The kernel sits behind ``jax.jit``: a program's call sites of one
    shape trace it once (a Pallas call is traced and lowered anew
    wherever it is not: PERF.md Findings, PR 36)."""
    xs, w = _operands(4, 32, 128, 128, seed=3)
    sizes = jnp.asarray([8, 8, 8, 8], jnp.int32)
    gk.grouped_matmul(xs, w, sizes, tm=16, interpret=True)
    before = gk.grouped_matmul._cache_size()

    @jax.jit
    def program(xs, w, sizes):
        return sum(gk.grouped_matmul(xs + i, w, sizes, tm=16,
                                     interpret=True) for i in range(3))

    program(xs, w, sizes)
    assert gk.grouped_matmul._cache_size() == before


@pytest.mark.parametrize("why,xs,w,dtype,takes", [
    ("a block step", (2048, 2048), (128, 2048, 768), jnp.bfloat16, True),
    ("four rows a group", (256, 3584), (64, 3584, 1024), jnp.bfloat16, True),
    ("4096 rows a group", (65536, 2048), (16, 2048, 768), jnp.bfloat16,
     True),
    ("a width that is not whole lanes", (64, 96), (8, 96, 128),
     jnp.bfloat16, False),
    ("the largest slab inside the VMEM budget", (1024, 6144),
     (16, 6144, 2048), jnp.bfloat16, True),
    ("a slab over the VMEM budget", (64, 8192), (8, 8192, 2048),
     jnp.bfloat16, False),
    ("float32 operands", (2048, 2048), (128, 2048, 768), jnp.float32,
     False),
])
def test_the_rule_reads_static_shapes(monkeypatch, why, xs, w, dtype, takes):
    xs, w = jax.ShapeDtypeStruct(xs, dtype), jax.ShapeDtypeStruct(w, dtype)
    assert gk.kernel_fits(xs, w) is takes, why
    assert moe._kernel_interpret(xs, w) is None         # a CPU: never
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe._kernel_interpret(xs, w) is (False if takes else None), why
