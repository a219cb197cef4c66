"""The grouped expert matmul as ONE Pallas TPU kernel a call — the TPU
form of ``jax.lax.ragged_dot(xs, w, sizes, preferred_element_type=
float32)`` for a program that takes no gradient of it
(``parallel/moe.py::grouped_matmul``).

A serving program's expert layer is a stream of weights with a few rows
held: 4-16 rows an expert in a decode or block step, 32-64 in a prefill
chunk, against 3-7 MB of weights an expert. XLA's ``ragged-dot`` runs
that at a third to three fifths of what the bytes allow (PERF.md,
Findings, PR 38: the sweep's table). This kernel keeps the arithmetic
and changes what is copied:

- **Visits, not experts.** The rows arrive sorted by group. The work is
  a list of (row tile, group) VISITS, computed on the device from
  ``sizes`` (:func:`visits`) and scalar-prefetched: every row tile that
  holds a row of the group, for every group that has a row. A group of
  no rows gets no visit, so an untouched expert's weights are never
  copied. The grid is the list's static bound (row tiles + groups);
  steps past the list's end do nothing and name the last visit's blocks,
  so they copy nothing either.
- **One slab a group, read once.** A visit's weight block is ``w[g]``
  whole (contiguous in HBM; the block's index changes only with the
  group, and the pipeline copies a block only when its index changes),
  double buffered by the pipeline: the next group's slab flies under
  this visit's product. bf16 x bf16 into float32 on the MXU, float32
  out: ``ragged_dot``'s arithmetic, no other precision.
- **Rows past the last group are zeros.** They are one more group, the
  TAIL, whose visits write zeros and copy no weights: a row tile's first
  visit zeroes the tile, every visit writes only its group's rows
  (masked by the prefetched offsets), so a tile that straddles groups is
  assembled in VMEM and written to HBM once.

A form that took the gate and the up weights in one walk and wrote
``silu(x Wg) * (x Wu)`` was timed beside two calls and XLA's fusion: 2 %
faster at a block step's shape, 5 % at a chunk's (PERF.md, Findings,
PR 38). It is not here: the expert layer's code is one for the programs
that differentiate it, which keep ``ragged_dot``.

Tested in interpret mode on the CPU against ``ragged_dot``
(``tests/test_grouped_matmul_kernel.py``); timed beside ``ragged_dot``
and megablox's ``gmm`` by ``benchmarks/grouped_matmul_sweep.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ROW_TILE", "grouped_matmul", "kernel_fits", "row_tile_for",
           "visits"]

#: Rows of one visit's product. 128 is the MXU's own height on a v5e: a
#: visit of fewer rows costs the same pass over the weights.
ROW_TILE = 128

#: Most bytes of one group's weights: the block is the whole slab, double
#: buffered, and a v5e core has 128 MiB of VMEM for it, the rows, the
#: result and the compiler's own. 24 MiB is the largest expert matrix
#: among the served configurations (6144 x 2048 in bfloat16; the call
#: then states 57 MiB and compiles for a described v5e).
_SLAB_BYTES = 24 << 20


def kernel_fits(xs, w) -> bool:
    """Whether the kernel takes these operands: bfloat16 rows and
    weights (``ragged_dot``'s arithmetic for them is the MXU's one bf16
    pass, which is the kernel's), ``K`` and ``N`` whole lanes, and one
    group's slab inside its VMEM budget."""
    _, k, n = w.shape
    return (xs.dtype == w.dtype == jnp.bfloat16 and k % 128 == 0
            and n % 128 == 0 and k * n * w.dtype.itemsize <= _SLAB_BYTES)


def row_tile_for(rows: int) -> int:
    """The row tile of a call of ``rows`` rows: ``ROW_TILE``, or all the
    rows (rounded up to bfloat16's sublane tile) where they are fewer."""
    return min(ROW_TILE, -(-rows // 16) * 16)


def visits(sizes, rows: int, tm: int):
    """The walk of one call, from ``sizes`` (G,) on the device.

    Returns ``(offsets, group, slab, tile, n)``: ``offsets`` (G + 2,)
    the first row of each group, of the tail (the rows past the last
    group) and ``rows``; for each of the ``cdiv(rows, tm) + G`` grid
    steps the ``group`` it works for (``G`` = the tail), the weight
    ``slab`` it names (the last touched group's for the tail: nothing new
    to copy) and its row ``tile``; ``n`` (1,) the visits that are real.
    Steps past ``n`` repeat the last visit's ``slab`` and ``tile``."""
    g = sizes.shape[0]
    n_tiles = -(-rows // tm)
    sizes = sizes.astype(jnp.int32)
    grouped = jnp.minimum(jnp.sum(sizes), rows)
    ext = jnp.concatenate([sizes, (rows - grouped)[None]])
    ends = jnp.cumsum(ext)
    starts = ends - ext
    first = starts // tm
    n_of = jnp.where(ext > 0, (ends - 1) // tm - first + 1, 0)
    v_end = jnp.cumsum(n_of)
    n = v_end[-1]
    v = jnp.minimum(jnp.arange(n_tiles + g, dtype=jnp.int32), n - 1)
    # the group whose visits hold step v: how many groups end at or
    # before it (one fused compare and sum; a binary search is a loop)
    group = jnp.sum(v[:, None] >= v_end[None, :], axis=1, dtype=jnp.int32)
    group = jnp.minimum(group, g)
    tile = first[group] + v - (v_end - n_of)[group]
    last_touched = jnp.max(jnp.where(sizes > 0, jnp.arange(g), 0))
    slab = jnp.minimum(group, last_touched)
    offsets = jnp.concatenate([starts, ends[-1:]]).astype(jnp.int32)
    return (offsets, group, slab.astype(jnp.int32), tile.astype(jnp.int32),
            n.reshape(1).astype(jnp.int32))


def _kernel(offsets_ref, group_ref, slab_ref, tile_ref, n_ref,  # SMEM
            x_ref, w_ref, o_ref, *, n_groups: int, tm: int):
    del slab_ref
    v = pl.program_id(0)
    tile = tile_ref[v]
    group = group_ref[v]
    real = v < n_ref[0]

    @pl.when(jnp.logical_or(v == 0,
                            tile != tile_ref[jnp.maximum(v - 1, 0)]))
    def _first_visit_of_the_tile():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(jnp.logical_and(real, group < n_groups))
    def _product():
        y = jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        row = tile * tm + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        mine = jnp.logical_and(row >= offsets_ref[group],
                               row < offsets_ref[group + 1])
        o_ref[...] = jnp.where(mine, y, o_ref[...])


def _vmem_limit(tm: int, k: int, n: int, itemsize: int) -> int:
    """An upper bound on what the call holds in VMEM, counted from its
    shapes: the weight slab, the rows and the float32 result double
    buffered, the product, and room for the compiler's own."""
    blocks = 2 * (k * n * itemsize + tm * k * itemsize + tm * n * 4)
    return int(blocks + 2 * tm * n * 4 + (8 << 20))


# jitted, so that a program's call sites (three a layer) trace and lower
# the kernel once per distinct shape (PR 36 did the same for the wide
# flash calls; XLA's inliner gives every call site back its name stack)
@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def grouped_matmul(xs, w, sizes, *, tm=None, interpret=False):
    """``xs`` (R, K) sorted into groups of ``sizes`` (G,), each group
    through its own ``w[g]`` (G, K, N) -> (R, N) float32; rows past the
    last group come back zero. ``sizes`` is data: one compiled kernel
    serves every routing. ``tm``: the row tile, :func:`row_tile_for`'s
    unless a test or the sweep names one."""
    rows, k = xs.shape
    g, _, n = w.shape
    tm = row_tile_for(rows) if tm is None else tm
    n_tiles = -(-rows // tm)

    def row_map(v, offsets, group, slab, tile, n_real):
        return (tile[v], 0)

    def slab_map(v, offsets, group, slab, tile, n_real):
        return (slab[v], 0, 0)

    return pl.pallas_call(
        functools.partial(_kernel, n_groups=g, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_tiles + g,),
            in_specs=[pl.BlockSpec((tm, k), row_map),
                      pl.BlockSpec((None, k, n), slab_map)],
            out_specs=pl.BlockSpec((tm, n), row_map)),
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(tm, k, n, w.dtype.itemsize)),
        interpret=interpret,
        name="grouped_matmul",
    )(*visits(sizes, rows, tm), xs, w)
