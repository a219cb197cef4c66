"""Paged decode attention as ONE Pallas TPU kernel a layer — the TPU form
of ``ops/decode_attention.paged_decode_attention`` for an exact K/V pool.

The ``lax.fori_loop`` it stands in for makes one trip a PAGE for ALL
slots, gathers every slot's page into a block that is written to HBM and
read back, and runs until the LONGEST row is done: at ``page_len`` 16 and
64 slots that is a dozen small fusions and 128 eight-kilobyte gathers a
trip, 18 us of fixed cost for 1.3 us of bytes. This kernel keeps the
arithmetic and changes who pays for what:

- **Pages are read in place.** ``k_pages`` / ``v_pages`` stay in HBM
  (``memory_space=ANY``); the tables, the lengths and the order of the
  rows are scalar-prefetched into SMEM; a row's pages are copied straight
  from the pool into VMEM by ``pltpu.make_async_copy``, one copy a page
  (a page ``(Hkv, page_len, Dh)`` is whole sublane tiles, so one copy
  brings every KV head), double buffered: the next block's copies fly
  under this block's products, and a row's last block starts the next
  row's first.
- **Many pages a trip.** A KV block is ``block_pages`` pages (``KV_BLOCK``
  positions): scores ``(g, Dh) x (Dh, block)``, the online-softmax merge
  of ``decode_attention._merge_block`` with float32 ``m``, ``l``, ``acc``
  in VMEM scratch, ``p`` cast to the pool's dtype for ``p @ v`` with
  float32 accumulation.
- **Each row walks its own length.** The grid is over the slots, ACTIVE
  ROWS FIRST (``order``, made outside: a grid step costs the same
  whether it works or skips, so the grid is not over (slot, block)); row
  ``b`` makes ``idx[b] // block + 1`` trips, read from SMEM, not the
  batch's maximum. A step past the last active row writes zeros and
  copies nothing: the engine discards an inactive row's logits.

Numerics are the module's contract (``ops/decode_attention.py``): float32
statistics and accumulator, ``_MASK`` and not ``-inf``, probabilities of
masked positions exact zeros. One more, which many pages a trip makes
necessary: a page past the row's length is never copied (its table entry
may name any page, or none), so its VMEM rows hold whatever the buffer
held, and the dead tail of the row's last page may hold anything too (the
contract tests poison both with NaN, and ``0 x NaN`` is NaN). A NaN key
row only reaches its own score column, which the mask replaces; value
rows past the row's length are selected to zero before ``p @ v``.

``decode_paged`` writes the step's K/V into the pool before it attends,
so an active row reads its own key from the pool: there is no
``new_k`` / ``new_v`` re-select here (the loop has it for inactive rows).

Tested in interpret mode on the CPU against the loop and the dense
gather (``tests/test_paged_attention_kernel.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _LANES, _MASK

__all__ = ["KV_BLOCK", "block_pages_for", "kernel_fits", "paged_attention"]

#: Positions of one KV block (one trip of a row's loop): ``KV_BLOCK //
#: page_len`` pages, each its own copy.
KV_BLOCK = 512
#: Most VMEM the two double-buffered K and V blocks may take together;
#: a pool with many KV heads gets fewer pages a trip.
_BUFFER_BYTES = 4 << 20


def _sublanes(dtype) -> int:
    """Rows of the dtype's native (sublane, 128) tile: 8 for float32,
    16 for bfloat16."""
    return 32 // jnp.dtype(dtype).itemsize


def kernel_fits(k_pages, v_pages, page_len: int) -> bool:
    """Whether this pool's layout is one the kernel copies page by page:
    a float32 or bfloat16 ``(n_pages, Hkv, page_len, Dh)`` K and V of one
    shape, ``Dh`` whole lanes, ``page_len`` whole sublane tiles (a page
    of every head then lands tile-aligned in the VMEM block)."""
    return (v_pages is not None and k_pages.shape == v_pages.shape
            and k_pages.dtype == v_pages.dtype
            and k_pages.dtype in (jnp.float32, jnp.bfloat16)
            and k_pages.shape[2] == page_len
            and k_pages.shape[3] % _LANES == 0
            and page_len % _sublanes(k_pages.dtype) == 0)


def block_pages_for(k_pages, pages_per_row: int) -> int:
    """Pages of one KV block: ``KV_BLOCK`` positions, fewer where the
    table is shorter or the four VMEM blocks would pass their budget."""
    _, hkv, page_len, dh = k_pages.shape
    page_bytes = hkv * page_len * dh * k_pages.dtype.itemsize
    return max(1, min(pages_per_row, KV_BLOCK // page_len,
                      _BUFFER_BYTES // (4 * page_bytes)))


def _kernel(order_ref, n_active_ref, idx_ref, tables_ref,   # SMEM
            q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, m_scr, l_scr, acc_scr,
            *, scale, page_len, block_pages, pages_per_row):
    i = pl.program_id(0)
    n_active = n_active_ref[0]
    block = block_pages * page_len

    def pages(row, j, slot, start: bool):
        """Start, or wait for, the copies of block ``j`` of ``row`` into
        buffer ``slot``: the pages that hold a live position, and no
        other (a dead entry of the table is never read). A loop and not
        ``block_pages`` unrolled predicates, three times over: a decode
        program holds this kernel once a layer."""
        first = j * block_pages
        n_live = jnp.minimum(idx_ref[row] // page_len - first + 1,
                             block_pages)

        def page(p, carry):
            pid = tables_ref[row * pages_per_row + first + p]
            at = pl.ds(pl.multiple_of(p * page_len, page_len), page_len)
            for hbm, buf, s in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                copy = pltpu.make_async_copy(
                    hbm.at[pid], buf.at[slot, :, at], sems.at[s, slot])
                if start:
                    copy.start()
                else:
                    # dpxlint: disable=DPX003 a DMA semaphore wait inside the Mosaic kernel, not a host call that could block the runtime
                    copy.wait()
            return carry

        jax.lax.fori_loop(0, n_live, page, 0)

    @pl.when(i >= n_active)
    def _skip():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_active)
    def _row():
        row = order_ref[i]
        idx = idx_ref[row]
        n_blocks = idx // block + 1

        @pl.when(i == 0)
        def _first():
            slot_ref[0] = 0
            pages(row, 0, 0, True)

        base = slot_ref[0]
        m_scr[...] = jnp.full_like(m_scr, _MASK)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        q = q_ref[0]                                    # (Hkv, g, Dh)

        def body(j, carry):
            slot = (base + j) % 2

            @pl.when(j + 1 < n_blocks)
            def _():
                pages(row, j + 1, 1 - slot, True)

            @pl.when(jnp.logical_and(j + 1 == n_blocks, i + 1 < n_active))
            def _():
                pages(order_ref[jnp.minimum(i + 1, pl.num_programs(0) - 1)],
                      0, 1 - slot, True)

            pages(row, j, slot, False)
            k = k_buf[slot]                             # (Hkv, block, Dh)
            v = v_buf[slot]
            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale
            pos = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            valid = pos <= idx
            s = jnp.where(valid, s, _MASK)              # (Hkv, g, block)
            m_old = m_scr[:, :, :1]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            # exact zeros where masked: while m_new is still the sentinel
            # exp(_MASK - m_new) would be exp(0) = 1
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_old - m_new)
            l_new = alpha * l_scr[:, :, :1] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            # rows past the length were not copied, or are a page's dead
            # tail: 0 x NaN is NaN, so they leave the product as zeros
            v_pos = j * block + jax.lax.broadcasted_iota(jnp.int32,
                                                         v.shape, 1)
            v = jnp.where(v_pos <= idx, v, jnp.zeros_like(v))
            acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
            return carry

        jax.lax.fori_loop(0, n_blocks, body, 0)
        slot_ref[0] = (base + n_blocks) % 2
        l = l_scr[:, :, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


# jitted, so that a decode program's 30 layers trace and lower the kernel
# once (4.2 -> 1.1 s of every process's set-up for StarCoder2, PR 29)
@functools.partial(jax.jit, static_argnames=("scale", "page_len",
                                             "block_pages", "interpret"))
def paged_attention(hq, k_pages, v_pages, tables, idx, active, *, scale,
                    page_len: int, block_pages=None, interpret=False):
    """One decode token a row over its resident pages.

    hq: (B, H, 1, Dh); k_pages / v_pages: (n_pages[+1], Hkv, page_len,
    Dh), a pool :func:`kernel_fits` accepts; tables: (B, P) int32;
    idx: (B,) int32, position ``idx[b]`` being the row's newest (already
    in the pool); active: (B,) bool. Returns (B, H, 1, Dh) in the pool's
    dtype: zeros for an inactive row. ``tables`` / ``idx`` / ``active``
    are data: one compiled kernel serves every mix."""
    b, h, _, dh = hq.shape
    hkv = k_pages.shape[1]
    g = h // hkv
    pages_per_row = tables.shape[1]
    if block_pages is None:
        block_pages = block_pages_for(k_pages, pages_per_row)
    block = block_pages * page_len
    # the query group padded to whole sublane tiles (12 -> 16): zero rows
    # score 0 everywhere, stay finite, and are sliced away
    gp = -(-g // _sublanes(k_pages.dtype)) * _sublanes(k_pages.dtype)
    q = hq.reshape(b, hkv, g, dh).astype(k_pages.dtype)
    if gp != g:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    active = jnp.asarray(active, jnp.bool_)
    # active rows first, in slot order: step i < n_active works on row
    # order[i] and knows the row after it
    order = jnp.argsort(jnp.logical_not(active), stable=True) \
        .astype(jnp.int32)
    n_active = jnp.sum(active, dtype=jnp.int32).reshape(1)

    def row_map(i, order_ref, *_):
        return (order_ref[i], 0, 0, 0)

    row_spec = pl.BlockSpec((1, hkv, gp, dh), row_map)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, page_len=page_len,
                          block_pages=block_pages,
                          pages_per_row=pages_per_row),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b,),
            in_specs=[row_spec,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((2, hkv, block, dh), k_pages.dtype),
                pltpu.VMEM((2, hkv, block, dh), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((hkv, gp, _LANES), jnp.float32),
                pltpu.VMEM((hkv, gp, _LANES), jnp.float32),
                pltpu.VMEM((hkv, gp, dh), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, dh), v_pages.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(order, n_active, idx.astype(jnp.int32),
      tables.astype(jnp.int32).reshape(-1), q, k_pages, v_pages)
    return out[:, :, :g].reshape(b, h, 1, dh)
