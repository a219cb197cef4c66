"""Page-blockwise decode attention — the single-token attention kernel
shared by ``generate()`` and every serving engine.

The dense decode path pays O(cache width) per token regardless of how
many positions are actually resident: a slot pool sized for 4096-token
requests charges a 32-token request the full 4096-wide softmax every
step. This module replaces that with the online-softmax block merge the
flash kernel already uses (``ops/flash_attention.py`` —
``lse = logaddexp(lse1, lse2)``, partials rescaled by ``exp(m_old -
m_new)``), run as a ``lax.fori_loop`` over KV *blocks* whose trip count
is the TRACED number of resident blocks:

    n_blocks = max(lengths) // block_len + 1          (<= total blocks)

One compiled program serves every request mix (the loop bound is data,
not shape), and per-token attention cost scales with the blocks that
actually hold keys — dead pages past every slot's length are never
gathered, never multiplied, never even touched (the contract tests
poison them with NaN to prove it).

Numerics contract (the mixed-precision guard, docs/compute.md):

- softmax statistics (running max ``m``, normalizer ``l``) and the
  output accumulator are **float32** regardless of cache dtype — the
  same f32-stats rule the flash kernel and ``nn.attention
  .dense_attention`` follow, so bf16 caches cannot silently degrade
  softmax accumulation;
- masked logits use a large-negative finite sentinel (``_MASK``), not
  ``-inf``: a visited block that is fully masked for a short row would
  otherwise poison the merge with ``-inf - -inf = NaN`` (and the
  ``exp(0) = 1`` rescue of an all-`_MASK` block is closed by masking
  the probabilities to exact zeros);
- the p@v matmul runs with the probabilities cast to the cache dtype
  and ``preferred_element_type=float32`` (the FlashAttention-2 recipe:
  bf16 on the MXU's native path, f32 accumulation).

**On a TPU an exact K/V page pool takes one Mosaic kernel a layer**
(``ops/paged_attention_kernel.py``) in place of the paged loop: each
row's resident pages copied from the pool in place, many pages a trip,
each row walking its own length, rows that hold nothing skipped.
:func:`paged_decode_attention`, the entry of an exact K/V store
(``nn/paged.py`` ``KVPages.attend``), picks it from what it can see in
its input (:func:`_kernel_interpret`: a TPU backend, one device,
whole-lane heads, whole-tile pages, the rows' ``active`` mask);
everything else — the CPU, head size 64, and the stores of another
format (the latent pool, the quantized pools), which hand
:func:`paged_loop_attention` their own page loaders — runs the one loop
below, which is also the reference the kernel is tested against. The
contract above holds in both.

Every decode front door routes here (``models/generate.py``:
``decode_step``, ``decode_step_slots_paged``), so ``generate()``,
``serve/pages/``, and both the monolithic and disaggregated engines
share one kernel. ``generate()``'s sliding-window rolling cache keeps
the dense path, as a window layer's ring does: its width IS the window,
so every entry is potentially resident and there is nothing to skip.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import paged_attention_kernel
from .flash_attention import _MASK

__all__ = ["DECODE_BLOCK", "blockwise_decode_attention",
           "dense_decode_attention", "kernel_traces",
           "paged_decode_attention", "paged_loop_attention",
           "resident_blocks"]

#: Default block length for CONTIGUOUS caches (``decode_step``);
#: paged pools use their ``page_len``. 128 =
#: one VPU lane width per gather on TPU, and small enough that a short
#: resident prefix in a long pool skips most of the width.
DECODE_BLOCK = 128


def resident_blocks(lengths, block_len: int, total_blocks: int):
    """Traced number of leading blocks holding any resident position.

    ``lengths`` are the CURRENT write positions (position ``lengths[b]``
    is being written this step, so ``lengths[b] + 1`` positions are
    live). The ONE definition of the loop bound — the kernels and the
    contract tests (`tests/test_compute_path.py`) both call it, so
    "the scan visits only ceil(len/block) blocks" is asserted against
    the same formula the kernel executes."""
    lengths = jnp.asarray(lengths)
    return jnp.minimum(jnp.max(lengths) // block_len + 1, total_blocks)


@jax.named_scope("decode_attention")
def dense_decode_attention(hq, k, v, pos_mask, *, scale):
    """The dense full-width decode softmax — the REFERENCE the
    blockwise kernel is contract-tested against, and the baseline the
    decode bench arm times. One definition for every ``blockwise=False``
    branch (decode_step / decode_step_slots_paged), the sliding-window
    rolling cache and a window layer's ring, whose width IS the window.

    hq: (B, H, 1, Dh); k, v: (B, Hkv, W, Dh); pos_mask: (B, W) or
    (1, W) bool — True where the position is visible. The grouped
    einsum reads GQA kv zero-copy; softmax stats are f32 with probs
    cast back to ``v.dtype`` (the f32-stats contract); a row with NO
    visible position yields NaN, matching dense_attention/flash."""
    b, h, _, dh = hq.shape
    hkv = k.shape[1]
    hq_g = hq.reshape(b, hkv, h // hkv, 1, dh)
    logits = jnp.einsum("bngqd,bnkd->bngqk", hq_g, k).astype(
        jnp.float32) * scale                         # (B,Hkv,g,1,W)
    logits = jnp.where(pos_mask[:, None, None, None, :], logits,
                       -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bngqk,bnkd->bngqd", probs, v) \
        .reshape(b, h, 1, dh)


def _merge_block(carry, s, v_blk, valid):
    """One online-softmax merge step, f32 stats.

    carry = (m, l, acc): running max (B, Hkv, g, 1), normalizer
    (B, Hkv, g, 1), output accumulator (B, Hkv, g, 1, Dh) — all f32.
    s: (B, Hkv, g, 1, L) f32 logits with masked entries ALREADY at
    ``_MASK``; valid: (B, 1, 1, 1, L) bool; v_blk: (B, Hkv, L, Dh).
    """
    m, l, acc = carry
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # exp(_MASK - m_new) underflows to 0 once any real logit has been
    # seen, but while m_new is still the _MASK sentinel (every visited
    # position masked so far) it would be exp(0) = 1 — mask explicitly
    # so fully-masked blocks contribute exact zeros, never NaN.
    p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1)
    # p@v in the cache dtype with f32 accumulation (flash recipe)
    pv = jax.lax.dot_general(
        p.astype(v_blk.dtype), v_blk,
        (((4,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32)       # (B, Hkv, g, 1, Dh)
    acc_new = alpha[..., None] * acc + pv
    return m_new, l_new, acc_new


def _finish(m, l, acc, out_dtype):
    # l == 0 cannot happen for a live decode row (position 0 is always
    # <= idx and block 0 is always visited), but a zero normalizer must
    # divide safely rather than emit inf — belt to the _MASK braces.
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).astype(out_dtype)


@jax.named_scope("decode_attention")
def blockwise_decode_attention(hq, k, v, idx, *, scale,
                               block_len: Optional[int] = None):
    """Single-token attention over a CONTIGUOUS cache, blockwise.

    hq: (B, H, 1, Dh) this step's queries; k, v: (B, Hkv, W, Dh) cache
    rows (Hkv divides H — GQA reads grouped); idx: (B,) int32 current
    positions (the mask exposes positions ``<= idx[b]``, matching the
    dense decode's ``pos_mask``). Returns o (B, H, 1, Dh) in v.dtype.

    Value-identical (up to f32 summation order) to

        softmax(where(pos <= idx, q k^T * scale, -inf)) @ v

    but only ``resident_blocks(idx, block_len, ...)`` leading blocks of
    the width are ever read — cost scales with occupancy, not capacity.
    """
    block_len = block_len or DECODE_BLOCK
    b, h, _, dh = hq.shape
    hkv, width = k.shape[1], k.shape[2]
    g = h // hkv
    hq_g = hq.reshape(b, hkv, g, 1, dh)
    total = -(-width // block_len)
    nb = resident_blocks(idx, block_len, total)

    def body(j, carry):
        # ragged tail: clip the gather indices into range; the position
        # mask kills the duplicated tail entries (pos >= width is never
        # <= idx because idx < width by the cache-capacity contract)
        pos = j * block_len + jnp.arange(block_len)
        span = jnp.clip(pos, 0, width - 1)
        k_blk = jnp.take(k, span, axis=2)
        v_blk = jnp.take(v, span, axis=2)
        valid = ((pos[None, :] <= idx[:, None])
                 & (pos[None, :] < width))            # (B, L)
        s = jax.lax.dot_general(
            hq_g.astype(k_blk.dtype), k_blk,
            (((4,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32) * scale
        valid5 = valid[:, None, None, None, :]
        s = jnp.where(valid5, s, _MASK)
        return _merge_block(carry, s, v_blk, valid5)

    carry = (jnp.full((b, hkv, g, 1), _MASK, jnp.float32),
             jnp.zeros((b, hkv, g, 1), jnp.float32),
             jnp.zeros((b, hkv, g, 1, dh), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, nb, body, carry)
    return _finish(m, l, acc, v.dtype).reshape(b, h, 1, dh)


#: How many times :func:`paged_decode_attention` took the kernel, counted
#: where it is traced: a pool reads it around the trace of its decode
#: program (``decode_attention_kernel_layers``).
_kernel_traces = 0


def kernel_traces() -> int:
    return _kernel_traces


def _kernel_interpret(interpret: Optional[bool]) -> Optional[bool]:
    """The rule's one look at the backend: ``False`` (compile the
    kernel) on a TPU, ``None`` (no kernel: the loop) anywhere else.
    ``interpret`` is a test's own answer, as in
    ``flash_attention._interpret_default``: the Pallas interpreter is
    never a default, a CPU run takes the loop."""
    if interpret is not None:
        return interpret
    return False if jax.default_backend() == "tpu" else None


def _on_one_device(x) -> bool:
    """GSPMD cannot partition a Mosaic call (``flash_attention
    ._mesh_island``): a program traced over a mesh of several devices
    keeps the loop, which it partitions like any other JAX code."""
    mesh = jax.typeof(x).sharding.mesh
    return bool(mesh.empty or mesh.size == 1 or mesh.manual_axes)


@jax.named_scope("decode_attention")
def paged_decode_attention(hq, k_pages, v_pages, tables, idx, new_k,
                           new_v, *, scale, page_len: int, active=None,
                           interpret: Optional[bool] = None,
                           zero_dead: bool = False):
    """Single-token attention over an exact paged K/V pool.

    hq: (B, H, 1, Dh); k_pages/v_pages: (n_pages[+1], Hkv, page_len,
    Dh) pool buffers (an out-of-range table id reads garbage a masked
    position never exposes); tables: (B, P) int32 page ids; idx: (B,)
    int32 positions; new_k/new_v: (B, Hkv, 1, Dh) — THIS step's K/V
    (see :func:`paged_loop_attention`).

    **The kernel** (``ops/paged_attention_kernel.py``). ``active`` (B,)
    bool says which rows wrote this step's K/V into the pool. Given it,
    a pool that ``paged_attention_kernel.kernel_fits``, on one TPU
    (``interpret=True``: a test's interpreter), is read by one Mosaic
    kernel instead of the loop: each active row over its own pages, the
    inactive rows zeros (their logits are discarded; the loop gives them
    ``new_k`` / ``new_v`` instead). Decided here, at trace time, from
    the arguments alone. Everything else takes the loop, each page a
    plain ``take``; so does a store of another format
    (``nn/paged.py``), which hands the loop its own loaders.

    ``zero_dead``: the loop's value rows past a row's ``idx`` read as
    zeros, as the kernel's do. For a caller whose table entries past a
    row's length name pages that may hold anything (a sparse layer's
    unchosen pages, ``nn.paged.SelectedPages``): the loop visits them
    where another row is longer, and ``0 x NaN`` is NaN.
    """
    if (active is not None
            and paged_attention_kernel.kernel_fits(k_pages, v_pages,
                                                   page_len)
            and _on_one_device(hq)):
        mode = _kernel_interpret(interpret)
        if mode is not None:
            global _kernel_traces
            _kernel_traces += 1
            return paged_attention_kernel.paged_attention(
                hq, k_pages, v_pages, tables, idx, active, scale=scale,
                page_len=page_len, interpret=mode)
    load_v = lambda pids, j: jnp.take(v_pages, pids, axis=0)
    if zero_dead:
        def load_v(pids, j):
            live = j * page_len + jnp.arange(page_len)[None, :] <= idx[:, None]
            return jnp.where(live[:, None, :, None],
                             jnp.take(v_pages, pids, axis=0), 0)
    return _paged_loop(
        hq, lambda pids, j: jnp.take(k_pages, pids, axis=0), load_v,
        tables, idx, new_k, new_v, scale=scale, page_len=page_len,
        out_dtype=v_pages.dtype)


def _paged_loop(hq, load_k, load_v, tables, idx, new_k, new_v, *, scale,
                page_len: int, out_dtype, value_width=None):
    """The paged loop, one page per step, ONE body for every format of
    resident page: what differs is how page j of every row is loaded.

    ``load_k(pids, j)`` / ``load_v(pids, j)`` give the (B, Hkv,
    page_len, Dh) block of the pages ``pids`` (B,) = ``tables[:, j]``:
    a ``take`` for an exact pool; a take, unpack, dequantise and the
    overlay of the row's exact tail page for a quantized one. With
    ``load_v`` None the entries are both key and value (the latent
    pool, ``nn/latent.py``): every query head scores against the one
    shared key head, the values are the first ``value_width`` of the
    same block, ``new_v`` is None and the result is (B, H, 1,
    ``value_width``).

    new_k/new_v (B, Hkv, 1, Dh) are re-selected at position ``idx[b]``
    so rows whose pool write was dropped (inactive slots) still see
    their own key, value-identical to a write-mask select over the
    row (and after a quantized side's tail overlay: the write
    mask must still win for them).

    Visits only ``resident_blocks(idx, page_len, P)`` pages: the page
    gather itself is inside the loop, so a long pool serving short
    requests neither reads nor multiplies its dead pages.
    """
    b, h, _, dh = hq.shape
    hkv = new_k.shape[1]
    g = h // hkv
    hq_g = hq.reshape(b, hkv, g, 1, dh)
    total = tables.shape[1]
    nb = resident_blocks(idx, page_len, total)
    nk_g = new_k.reshape(b, hkv, 1, dh)
    nv_g = None if load_v is None else new_v.reshape(b, hkv, 1, dh)

    def body(j, carry):
        pos = j * page_len + jnp.arange(page_len)
        with jax.named_scope("page_gather"):
            pids = jax.lax.dynamic_index_in_dim(tables, j, axis=1,
                                                keepdims=False)  # (B,)
            k_blk = load_k(pids, j)                  # (B, Hkv, L, Dh)
            v_blk = None if load_v is None else load_v(pids, j)
        wm = (pos[None, :] == idx[:, None])[:, None, :, None]
        k_blk = jnp.where(wm, nk_g.astype(k_blk.dtype), k_blk)
        if load_v is None:
            v_blk = k_blk[..., :value_width]
        else:
            v_blk = jnp.where(wm, nv_g.astype(v_blk.dtype), v_blk)
        valid = (pos[None, :] <= idx[:, None])
        s = jax.lax.dot_general(
            hq_g.astype(k_blk.dtype), k_blk,
            (((4,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32) * scale
        valid5 = valid[:, None, None, None, :]
        s = jnp.where(valid5, s, _MASK)
        return _merge_block(carry, s, v_blk, valid5)

    dv = value_width if load_v is None else dh
    carry = (jnp.full((b, hkv, g, 1), _MASK, jnp.float32),
             jnp.zeros((b, hkv, g, 1), jnp.float32),
             jnp.zeros((b, hkv, g, 1, dv), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, nb, body, carry)
    return _finish(m, l, acc, out_dtype).reshape(b, h, 1, dv)


#: The loop for a store that loads its own pages, under the scope the
#: trace's readers key on.
paged_loop_attention = jax.named_scope("decode_attention")(_paged_loop)
