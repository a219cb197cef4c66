"""Flash attention as a Pallas TPU kernel (forward + backward).

The hot op of the Transformer rung (BASELINE.json ladder). The reference
repo has no attention at all (its model is two Linear layers, reference
``min_DDP.py:44-48``) — this kernel exists because our framework carries
full transformer workloads; it is designed for the TPU memory hierarchy
rather than translated from any CUDA kernel:

- Blockwise online-softmax (FlashAttention-2 schedule): O(S) memory
  instead of the O(S^2) probability matrix of ``nn.attention.dense_attention``.
- Q/K/V tiles staged through VMEM by the pallas grid pipeline; the
  (block_q, block_k) logits tile lives only in registers/VMEM.
- All matmuls hit the MXU with ``preferred_element_type=float32``;
  softmax statistics are kept in float32 even for bfloat16 inputs.
- The TPU grid executes the last axis sequentially (annotated
  "arbitrary"), which is what makes the scratch-accumulator pattern
  (m/l/acc carried across k-blocks) correct without atomics; the
  batch*head and outer block axes are annotated "parallel" so Mosaic can
  megacore-partition them.

Backward follows FlashAttention-2: recompute p = exp(qk - lse) blockwise;
one kernel accumulates dK/dV over q-blocks, a second accumulates dQ over
k-blocks. Residuals are (q, k, v, o, lse) — no S^2 tensor is ever saved.

Heads wider than one lane group of 128, or values narrower than the keys
(``_wide``), take tiles from the sweep at that width, index maps that hand
the pipeline no new block on a grid step the causal mask skips, and a
``jax.jit`` around the calls so that a step's layers share one traced and
lowered kernel; every other call builds the program it always built.

Numerics are validated against ``dense_attention`` (values and grads) in
``tests/test_flash_attention.py`` using interpret mode on CPU.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..runtime.context import DATA_AXIS, TENSOR_AXIS

# Large-negative mask value instead of -inf: -inf - (-inf) = NaN would
# poison the online-softmax rescaling for fully-masked tiles.
_MASK = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128   # VPU lane width; m/l scratch replicates across lanes.
_STATS = 8     # trailing dim of row-stat arrays (lse, delta): the smallest
# width Mosaic's tiling accepts as a full trailing dimension, so stats cost
# 8 floats/row in HBM instead of a lane-replicated 128.

_PARALLEL = ("parallel", "parallel", "arbitrary")  # grid = (bh, outer, inner)


def _interpret_default(interpret):
    """Compiled on a TPU; interpreted only where the CPU platform was
    ASKED for (``JAX_PLATFORMS=cpu`` / ``jax_platforms``). JAX falls
    back to the CPU with a warning when it expected a chip and found
    none — interpreting there would grind a full-size model through the
    Pallas interpreter and report it as a run, so that case raises."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if (jax.config.jax_platforms or "").split(",")[0] != "cpu":
        raise RuntimeError(
            f"flash attention found backend {backend!r} but no platform "
            "was selected: the TPU was expected and is missing. Set "
            "JAX_PLATFORMS=cpu to run the kernel in interpret mode on "
            "purpose.")
    return True


def _ceil128(s):
    return -(-s // 128) * 128


def _wide(d, d_v):
    """Whether a call takes the path of heads wider than one lane group
    (``d > 128``) or of values narrower than the keys: tiles from the
    sweep at that width, K/V (dK/dV: q/dO) index maps that name no new
    block on a grid step the causal frontier skips, and output, dO, dV
    and the accumulator at the values' own width. Every other call builds
    the three ``pallas_call``s with the tiles, index maps, kernel bodies
    and compiler parameters they had before PR 36: its lowered program is
    that one, text for text (CHANGES.md, PR 36; a Pallas call costs a
    tenth of a second to trace and lower on every run, and a step may
    hold 80)."""
    return d > _LANES or d_v != d


def _block_sizes(s_q, s_k, block_q, block_k, d=64, bwd=False, window=None):
    """Resolve tile sizes, of the forward kernel or (``bwd``) of the two
    backward kernels. Explicit ints behave as before (clamped to the
    sequence); ``None`` picks the default for the chip.

    Large tiles, because the grid is short at moderate seq and each grid
    step has a fixed cost. Head widths up to 128 take CAPS: 1024 wide in
    forward at d <= 64 and 512 at 128; the backward kernels 512 and 256
    -- their three (bq, bk) f32 tiles (p, dp, ds) triple the VMEM bill.
    Every one compiles on the v5e, forward and both backward kernels, at
    the flagship shape (b8 h12 s1024 d64), seq 4096, d=128, GQA and
    ``window=`` (chip_smoke.py, PR 21); they are what they were before
    PR 36 (``tests/test_flash_attention.py`` holds the table), and what
    the sweep reads at GPT-2 XL's shape is ROADMAP Speed 6's.

    Head widths over 128 (latent attention expanded: keys nope 128 + rope
    64 = 192, values 128) take what the v5e measured, kernel by kernel
    (``benchmarks/flash_block_sweep.py --shape 1,32,<s>,192,128`` at s
    1024, 2048, 4096 and 8192; the table is in PERF.md, Findings, PR 36).
    FORWARD: 1024 x 1024 at every length (s 8192: 7.3 ms a call against
    26.0 at the 256 x 256 every such shape had before; s 1024: one tile a
    head, 0.17 ms against 0.29 at 512 x 512 and 0.47 at 256 x 256, though
    it computes the masked quarter of the square). BACKWARD, dK/dV and
    dQ alike: SQUARE TILES OF HALF THE SHORTER SEQUENCE, AT LEAST 256 AND
    AT MOST 1024 (s 8192: 11.6 and 11.4 ms at 1024 x 1024 against 21.1
    and 16.5; s 1024: 512 x 512 beats 1024 x 1024 by 7 and 4 %; s 2048:
    the two within 3 %). A grid step at 256 x 256 cost as much as its
    work, and tiles of 2048 either way gain nothing more. A (rows, 192)
    bf16 block is laid out over two lane groups of 128 and costs the
    VMEM of one 256 wide; each ``pallas_call`` of that path states a
    bound on what it holds (``_vmem_limit``).

    With sliding-window attention the k cap clamps near the window width
    instead -- a k tile much wider than the band would compute
    mostly-masked logits and degrade the O(S*window) cost toward
    O(S*block_k)."""
    if d > _LANES:
        half = max(min(s_q, s_k) // 2, 1)
        cap = min(1024, max(256, 1 << (half.bit_length() - 1))) if bwd \
            else 1024
    elif bwd:
        cap = 512 if d <= 64 else 256
    else:
        cap = 1024 if d <= 64 else 512
    cap_k = min(cap, max(128, _ceil128(window))) if window is not None \
        else cap
    bq = min(cap, _ceil128(s_q)) if block_q is None \
        else max(min(block_q, s_q), 1)
    bk = min(cap_k, _ceil128(s_k)) if block_k is None \
        else max(min(block_k, s_k), 1)
    return bq, bk


def _vmem_limit(kernel, bq, bk, d, d_v, itemsize):
    """``vmem_limit_bytes`` of one kernel of the wide path: an UPPER
    bound counted from its shapes, a quarter on top, and never under the
    16 MiB the compiler scopes by default. Counted as if held at once:
    operand and result blocks double-buffered by the pipeline (a block's
    trailing dimension laid out over whole lane groups of 128), the
    float32 scratch accumulators, and every (bq, bk) tile the body names
    (forward: scores and probabilities in float32 and the probabilities
    again in the operands' type; backward: p, dp, ds and two such casts).
    Mosaic holds less than that: at 1024 x 1024, keys 192 and values 128,
    the three kernels compile for a DESCRIBED v5e from 8, 8 and 9 MiB
    (fwd, dK/dV, dQ; bisected on the CPU container, PR 36) where this
    says 19, 30 and 29. On the chip the parent's kernels at 1024 x 1024
    with the values padded to 192 and no limit stated ran out of VMEM in
    the backward (PR 36, chip call 1), so the bound is stated; it also
    lets explicit tiles up to 2048 x 1024 (15-17 MiB by the same
    bisection) compile."""
    def blk(rows, width, nbytes=itemsize):
        return rows * _ceil128(width) * nbytes

    stats = blk(bq, _STATS, 4)
    io = blk(bq, d) + blk(bk, d) + blk(bk, d_v) + blk(bq, d_v)
    if kernel == "fwd":
        io += stats
        scratch = 2 * blk(bq, _LANES, 4) + blk(bq, d_v, 4)
        held = 2 * 4 + itemsize
    elif kernel == "dkv":
        io += 2 * stats + blk(bk, d) + blk(bk, d_v)
        scratch = blk(bk, d, 4) + blk(bk, d_v, 4)
        held = 3 * 4 + 2 * itemsize
    else:
        io += 2 * stats + blk(bq, d)
        scratch = blk(bq, d, 4)
        held = 3 * 4 + 2 * itemsize
    need = 2 * io + scratch + held * bq * bk
    return max(16 * 2 ** 20, need + need // 4)


def _compiler_params(kernel, wide, bq, bk, d, d_v, itemsize):
    if not wide:
        return pltpu.CompilerParams(dimension_semantics=_PARALLEL)
    return pltpu.CompilerParams(
        dimension_semantics=_PARALLEL,
        vmem_limit_bytes=_vmem_limit(kernel, bq, bk, d, d_v, itemsize))


def _pad_seq(x, block, axis):
    s = x.shape[axis]
    pad = (-s) % block
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _frontier_ok(iq, ik, *, block_q, block_k, q_len, k_len, window=None,
                 diag_offset=0):
    """Whether k-tile ``ik`` intersects the causal-visible region of q-tile
    ``iq``. The ``k_len - q_len`` offset aligns the causal diagonal when
    s_q != s_k (query block i attends through absolute key position
    i + k_len - q_len); ``diag_offset`` shifts that diagonal further —
    the windowed-ring-hop contract where this kv block sits
    ``diag_offset`` positions EARLIER in the global sequence than the
    local indices suggest. With a sliding ``window`` the band has a
    LOWER edge too (row r sees cols (r+off-window, r+off]), so tiles
    entirely below it are skipped — that skip is what makes windowed
    attention O(S*window) instead of O(S^2/2). Single source of truth
    for fwd and both bwd kernels — the masks must never desynchronize or
    gradients silently break. The wide path's index maps (``_held_k``,
    ``_held_q``) ask it too: a block index is the grid's own wherever
    this says the body runs."""
    off = k_len - q_len + diag_offset
    ok = ik * block_k <= (iq + 1) * block_q - 1 + off
    if window is not None:
        # tile's last col >= the tile's first row's lowest visible col
        ok = jnp.logical_and(
            ok, ik * block_k + block_k - 1 >= iq * block_q + off - window + 1)
    return ok


def _floor_div_pos(x, n):
    """``x // n`` for a numerator clipped at 0 first (an edge that lies
    before the first tile is the first tile): truncating division is
    then the floor, without the sign repair ``//`` traces."""
    return jax.lax.div(jnp.maximum(x, 0), n)


def _held_k(iq, ik, n_k, *, block_q, block_k, q_len, k_len, window,
            diag_offset):
    """The k tile a K or V BlockSpec names at grid step (iq, ik) of the
    wide path's causal forward and dQ kernels: ``ik`` wherever
    ``_frontier_ok`` runs the body; on a step it skips, the nearest tile
    it admits for ``iq`` -- the last one past the diagonal, with a
    ``window`` the first one before the band's lower edge -- so the
    pipeline sees the block index it holds and copies nothing. The
    nearest tile is ``_frontier_ok``'s inequalities solved for ``ik``
    (``tests/test_flash_attention.py`` holds the two together step by
    step); were it ever wrong, a skipped step would copy a block nobody
    reads, and no result would change. Always inside ``[0, n_k)``, also
    where ``iq`` sees nothing."""
    off = k_len - q_len + diag_offset
    near = jnp.minimum(ik, _floor_div_pos((iq + 1) * block_q - 1 + off,
                                          block_k))
    if window is not None:
        first = _floor_div_pos(iq * block_q + off - window + 1, block_k)
        near = jnp.maximum(near, jnp.minimum(first, n_k - 1))
    ok = _frontier_ok(iq, ik, block_q=block_q, block_k=block_k, q_len=q_len,
                      k_len=k_len, window=window, diag_offset=diag_offset)
    return jnp.where(ok, ik, near)


def _held_q(ik, iq, n_q, *, block_q, block_k, q_len, k_len, window,
            diag_offset):
    """``_held_k``'s mirror for the dK/dV kernel, whose grid is (bh, ik,
    iq): the q tile that the q, dO and row-statistics BlockSpecs name.
    Causality bounds the q tiles of ``ik`` from below (the steps before
    its first visible q tile name that tile), a ``window`` from above."""
    off = k_len - q_len + diag_offset
    first = _floor_div_pos(ik * block_k - off, block_q)
    near = jnp.maximum(iq, jnp.minimum(first, n_q - 1))
    if window is not None:
        near = jnp.minimum(near, _floor_div_pos(
            ik * block_k + block_k - 2 - off + window, block_q))
    ok = _frontier_ok(iq, ik, block_q=block_q, block_k=block_k, q_len=q_len,
                      k_len=k_len, window=window, diag_offset=diag_offset)
    return jnp.where(ok, iq, near)


def _tile_mask(iq, ik, *, block_q, block_k, q_len, k_len, causal,
               mask_pad_rows, window=None, causal_offset=0,
               diag_offset=0):
    """Boolean (block_q, block_k) mask of logits to suppress: padded key
    columns, the causal future, positions below the sliding window's
    lower edge, and (in backward only, where padded q rows would
    otherwise leak into the dK/dV accumulators) padded query rows.
    In forward, padded-row outputs are sliced away on the host instead.

    ``causal_offset`` shifts the causal frontier down: offset 1 masks the
    diagonal too (strict lower-triangular). The striped sequence-parallel
    ring (parallel/sequence.py:striped_ring_flash_attention) alternates
    between offset 0 and 1 per hop — in striped token layout a rotated
    k/v block is visible either through the diagonal or strictly below
    it. ``diag_offset`` shifts the whole diagonal (causal AND window
    edges) the other way: key column j stands for global position
    j - diag_offset relative to the queries — the windowed-ring-hop
    contract (hop t's kv block sits t*S_local positions earlier, so
    ``diag_offset = t*S_local``). The tile FRONTIER (_frontier_ok)
    shares diag_offset but deliberately ignores causal_offset: it
    over-includes by at most the diagonal elements of diagonal tiles,
    which this mask then suppresses — fwd and bwd stay in lockstep."""
    rows = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    off = k_len - q_len + diag_offset
    masked = cols >= k_len
    if mask_pad_rows:
        masked = jnp.logical_or(masked, rows >= q_len)
    if causal:
        masked = jnp.logical_or(
            masked, cols > rows + off - causal_offset)
    if window is not None:
        masked = jnp.logical_or(
            masked, cols <= rows + off - window)
    return masked


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, window, block_q, block_k, n_k, q_len,
                k_len, causal_offset=0, diag_offset=0):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _MASK)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _body():
        q = q_ref[0]                                       # (bq, d)
        k = k_ref[0]                                       # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (bq, bk)
        s = jnp.where(
            _tile_mask(iq, ik, block_q=block_q, block_k=block_k,
                       q_len=q_len, k_len=k_len, causal=causal,
                       mask_pad_rows=False, window=window,
                       causal_offset=causal_offset,
                       diag_offset=diag_offset),
            _MASK, s)

        m_old = m_scr[:, :1]                               # (bq, 1)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                             # (bq, bk) f32
        alpha = jnp.exp(m_old - m_new)                     # (bq, 1)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        # The p@v matmul runs in the INPUT dtype (softmax stats stay f32,
        # accumulation stays f32 via preferred_element_type): for bf16
        # inputs this keeps the MXU on its native bf16 path (~4x the f32
        # matmul throughput on v5e) — the FlashAttention-2 mixed-precision
        # recipe. For f32 inputs nothing changes.
        acc_scr[:] = alpha * acc_scr[:] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        @pl.when(_frontier_ok(iq, ik, block_q=block_q, block_k=block_k,
                              q_len=q_len, k_len=k_len, window=window,
                              diag_offset=diag_offset))
        def _():
            _body()
    else:
        _body()

    @pl.when(ik == n_k - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # Rows whose running max never moved off the _MASK sentinel saw no
        # unmasked logit (causal with s_q > s_k puts whole rows above the
        # diagonal). Dense softmax over an all--inf row is NaN; match it —
        # otherwise such rows silently emit a mean of masked-out v rows.
        no_logit = m_scr[:, :1] == _MASK
        out = jnp.where(no_logit, jnp.float32(jnp.nan), acc_scr[:] / l_safe)
        o_ref[0] = out.astype(o_ref.dtype)
        # Row stats are written (bq, _STATS)-wide: TPU blocks need their
        # trailing dim to be 128-divisible or the full array dim, so the
        # stat arrays carry a narrow replicated trailing axis and column 0
        # is read back on the host side.
        lse_ref[0] = jnp.broadcast_to(m_scr[:, :1] + jnp.log(l_safe),
                                      lse_ref.shape[1:])


def _kv_head_group(h: int, h_kv: int):
    """Validate grouped-query head counts; return the group size g.

    GQA (g q-heads share one kv-head) costs the kernels NOTHING extra:
    the kv BlockSpec index map (:func:`_kv_index`) sends the q-head-major
    grid index to its kv block — the shared kv tile is simply read by g
    programs, never replicated in HBM."""
    if h % h_kv:
        raise ValueError(f"n_heads {h} not divisible by kv heads {h_kv}")
    return h // h_kv


def _kv_index(bh, h, h_kv, g):
    """Grid index ``bh = bi*h + hi`` -> kv block ``bi*h_kv + hi//g``.
    The ONE definition of the GQA head mapping, shared by the forward and
    both backward kernels' BlockSpecs — if fwd and bwd ever addressed kv
    differently, gradients would silently be wrong."""
    return bh // h * h_kv + bh % h // g


def _kv_map(h, h_kv, g, n_k, held, **geom):
    """The index map of a K or V block under the grid (bh, iq, ik) of the
    forward and dQ kernels: the grid's own ``ik``, or (``held``: the wide
    path under a causal mask) ``_held_k``'s."""
    if held:
        return lambda bh, iq, ik: (_kv_index(bh, h, h_kv, g),
                                   _held_k(iq, ik, n_k, **geom), 0)
    return lambda bh, iq, ik: (_kv_index(bh, h, h_kv, g), ik, 0)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               window=None, causal_offset=0, diag_offset=0):
    """(o, lse). A wide call (``_wide``) goes through ``jax.jit``: a
    step's layers make the same call again and again, and jit traces and
    lowers one function for all of them, where a bare ``pallas_call`` is
    traced and lowered anew each time (on the sandbox's CPU a repeated
    forward + backward call costs 4 ms to trace and lower against 144 bare;
    PERF.md, Findings, PR 36). XLA inlines the calls, so the compiled
    step is the same. Every other call stays bare: its program is the one
    it was."""
    impl = _flash_fwd_shared if _wide(q.shape[-1], v.shape[-1]) \
        else _flash_fwd_impl
    return impl(q, k, v, causal, scale, block_q, block_k,
                _interpret_default(interpret), window, causal_offset,
                diag_offset)


def _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret,
                    window, causal_offset, diag_offset):
    b, h, s_q, d = q.shape
    h_kv, s_k, d_v = k.shape[1], k.shape[2], v.shape[-1]
    g = _kv_head_group(h, h_kv)
    wide = _wide(d, d_v)
    bq, bk = _block_sizes(s_q, s_k, block_q, block_k, d=d, window=window)

    q3 = _pad_seq(q.reshape(b * h, s_q, d), bq, 1)
    k3 = _pad_seq(k.reshape(b * h_kv, s_k, d), bk, 1)
    v3 = _pad_seq(v.reshape(b * h_kv, s_k, d_v), bk, 1)
    sq_p, sk_p = q3.shape[1], k3.shape[1]
    n_q, n_k = sq_p // bq, sk_p // bk

    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_k=bk, n_k=n_k, q_len=s_q, k_len=s_k,
        causal_offset=causal_offset, diag_offset=diag_offset)
    kv_map = _kv_map(h, h_kv, g, n_k, wide and causal, block_q=bq,
                     block_k=bk, q_len=s_q, k_len=s_k, window=window,
                     diag_offset=diag_offset)
    o3, lse3 = pl.pallas_call(
        kern,
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d_v), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d_v), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bq, _STATS), lambda bh, iq, ik: (bh, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_p, d_v), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq_p, _STATS), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, d_v), jnp.float32),
        ],
        compiler_params=_compiler_params("fwd", wide, bq, bk, d, d_v,
                                         q.dtype.itemsize),
        interpret=interpret,
    )(q3, k3, v3)
    o = o3[:, :s_q].reshape(b, h, s_q, d_v)
    lse = lse3[:, :s_q, 0].reshape(b, h, s_q)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _recompute_p(q_ref, k_ref, lse_ref, iq, ik, *, scale, causal, window,
                 block_q, block_k, q_len, k_len, causal_offset=0,
                 diag_offset=0):
    """p = exp(qk*scale - lse) for one tile, masked to exact zeros."""
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    masked = _tile_mask(iq, ik, block_q=block_q, block_k=block_k,
                        q_len=q_len, k_len=k_len, causal=causal,
                        mask_pad_rows=True, window=window,
                        causal_offset=causal_offset,
                        diag_offset=diag_offset)
    p = jnp.exp(jnp.where(masked, _MASK, s) - lse_ref[0][:, :1])
    return jnp.where(masked, 0.0, p)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, window, block_q, block_k, n_q, q_len,
                    k_len, causal_offset=0, diag_offset=0):
    ik, iq = pl.program_id(1), pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _body():
        # Matmul operands stay in the input dtype (bf16 on the MXU's
        # native path; f32 stats/accumulators) — see _fwd_kernel._body.
        p = _recompute_p(q_ref, k_ref, lse_ref, iq, ik, scale=scale,
                         causal=causal, window=window, block_q=block_q,
                         block_k=block_k, q_len=q_len, k_len=k_len,
                         causal_offset=causal_offset,
                         diag_offset=diag_offset)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # p^T @ dO
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # dO @ v^T
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # ds^T @ q

    if causal:
        @pl.when(_frontier_ok(iq, ik, block_q=block_q, block_k=block_k,
                              q_len=q_len, k_len=k_len, window=window,
                              diag_offset=diag_offset))
        def _():
            _body()
    else:
        _body()

    @pl.when(iq == n_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr,
                   *, scale, causal, window, block_q, block_k, n_k, q_len,
                   k_len, causal_offset=0, diag_offset=0):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _body():
        p = _recompute_p(q_ref, k_ref, lse_ref, iq, ik, scale=scale,
                         causal=causal, window=window, block_q=block_q,
                         block_k=block_k, q_len=q_len, k_len=k_len,
                         causal_offset=causal_offset,
                         diag_offset=diag_offset)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # ds @ k

    if causal:
        @pl.when(_frontier_ok(iq, ik, block_q=block_q, block_k=block_k,
                              q_len=q_len, k_len=k_len, window=window,
                              diag_offset=diag_offset))
        def _():
            _body()
    else:
        _body()

    @pl.when(ik == n_k - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_operands(q, k, v, g, lse, delta, bq, bk):
    """The six operands of a backward kernel, flattened over batch*heads
    and padded to whole (bq, bk) tiles."""
    (b, h, s_q, d), (_, h_kv, s_k, d_v) = q.shape, v.shape
    q3 = _pad_seq(q.reshape(b * h, s_q, d), bq, 1)
    k3 = _pad_seq(k.reshape(b * h_kv, s_k, d), bk, 1)
    v3 = _pad_seq(v.reshape(b * h_kv, s_k, d_v), bk, 1)
    g3 = _pad_seq(g.reshape(b * h, s_q, d_v), bq, 1)
    # Row stats replicated to a narrow (BH, S, _STATS) trailing axis — see
    # the lse layout note in _fwd_kernel.
    lse2 = _pad_seq(lse.reshape(b * h, s_q), bq, 1)
    delta2 = _pad_seq(delta.reshape(b * h, s_q), bq, 1)
    lse3 = jnp.broadcast_to(lse2[..., None], lse2.shape + (_STATS,))
    delta3 = jnp.broadcast_to(delta2[..., None], lse3.shape)
    return q3, k3, v3, g3, lse3, delta3


def _bwd_dkv(ops, shape, bq, bk, causal, scale, interp, window,
             causal_offset, diag_offset):
    """dK and dV PER Q-HEAD, (B*H, Sk padded, d) and (B*H, Sk padded,
    d_v): grid programs may not reduce into a shared output block, so a
    kv group's partials are summed by the caller -- one extra (B, H, Sk,
    D) temp, only when the group is larger than 1. ``shape`` is (b, h,
    h_kv, s_q, s_k, d, d_v) of the call, ``ops`` its ``_bwd_operands``
    at (bq, bk)."""
    b, h, h_kv, s_q, s_k, d, d_v = shape
    grp = _kv_head_group(h, h_kv)
    wide = _wide(d, d_v)
    n_q, n_k = ops[0].shape[1] // bq, ops[1].shape[1] // bk
    if wide and causal:
        def row_map(bh, ik, iq):
            return bh, _held_q(ik, iq, n_q, block_q=bq, block_k=bk,
                               q_len=s_q, k_len=s_k, window=window,
                               diag_offset=diag_offset), 0
    else:
        def row_map(bh, ik, iq):
            return bh, iq, 0
    q_spec = pl.BlockSpec((1, bq, d), row_map)
    do_spec = pl.BlockSpec((1, bq, d_v), row_map)
    row_spec = pl.BlockSpec((1, bq, _STATS), row_map)

    def kv_map(bh, ik, iq):
        return _kv_index(bh, h, h_kv, grp), ik, 0

    def out_map(bh, ik, iq):
        return bh, ik, 0

    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, block_q=bq, block_k=bk, n_q=n_q,
                          q_len=s_q, k_len=s_k,
                          causal_offset=causal_offset,
                          diag_offset=diag_offset),
        grid=(b * h, n_k, n_q),
        in_specs=[q_spec, pl.BlockSpec((1, bk, d), kv_map),
                  pl.BlockSpec((1, bk, d_v), kv_map), do_spec, row_spec,
                  row_spec],
        out_specs=[pl.BlockSpec((1, bk, d), out_map),
                   pl.BlockSpec((1, bk, d_v), out_map)],
        out_shape=[jax.ShapeDtypeStruct((b * h, n_k * bk, d), ops[1].dtype),
                   jax.ShapeDtypeStruct((b * h, n_k * bk, d_v),
                                        ops[2].dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d_v), jnp.float32)],
        compiler_params=_compiler_params("dkv", wide, bq, bk, d, d_v,
                                         ops[0].dtype.itemsize),
        interpret=interp,
    )(*ops)


def _bwd_dq(ops, shape, bq, bk, causal, scale, interp, window,
            causal_offset, diag_offset):
    """dQ, (B*H, Sq padded, d); arguments as ``_bwd_dkv``'s."""
    b, h, h_kv, s_q, s_k, d, d_v = shape
    grp = _kv_head_group(h, h_kv)
    wide = _wide(d, d_v)
    n_q, n_k = ops[0].shape[1] // bq, ops[1].shape[1] // bk
    kv_map = _kv_map(h, h_kv, grp, n_k, wide and causal, block_q=bq,
                     block_k=bk, q_len=s_q, k_len=s_k, window=window,
                     diag_offset=diag_offset)

    def row_map(bh, iq, ik):
        return bh, iq, 0

    q_spec = pl.BlockSpec((1, bq, d), row_map)
    row_spec = pl.BlockSpec((1, bq, _STATS), row_map)
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, block_q=bq, block_k=bk, n_k=n_k,
                          q_len=s_q, k_len=s_k,
                          causal_offset=causal_offset,
                          diag_offset=diag_offset),
        grid=(b * h, n_q, n_k),
        in_specs=[q_spec, pl.BlockSpec((1, bk, d), kv_map),
                  pl.BlockSpec((1, bk, d_v), kv_map),
                  pl.BlockSpec((1, bq, d_v), row_map), row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, n_q * bq, d), ops[0].dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_compiler_params("dq", wide, bq, bk, d, d_v,
                                         ops[0].dtype.itemsize),
        interpret=interp,
    )(*ops)


def _flash_bwd(q, k, v, o, lse, g, causal, scale, block_q, block_k,
               interpret, g_lse=None, window=None, causal_offset=0,
               diag_offset=0):
    """(dq, dk, dv); a wide call through ``jax.jit`` as ``_flash_fwd``'s."""
    impl = _flash_bwd_shared if _wide(q.shape[-1], v.shape[-1]) \
        else _flash_bwd_impl
    return impl(q, k, v, o, lse, g, g_lse, causal, scale, block_q, block_k,
                _interpret_default(interpret), window, causal_offset,
                diag_offset)


def _flash_bwd_impl(q, k, v, o, lse, g, g_lse, causal, scale, block_q,
                    block_k, interp, window, causal_offset, diag_offset):
    (b, h, s_q, d), (_, h_kv, s_k, d_v) = q.shape, v.shape
    grp = _kv_head_group(h, h_kv)

    # delta_i = sum_d dO_i * O_i — tiny elementwise+reduce; XLA fuses it.
    # Zero cotangent elements contribute exactly zero even where O is
    # non-finite: rows with NO visible key (causal s_q > s_k, or the
    # strict causal_offset=1 mask) emit NaN output by design, and their
    # callers weight them to zero — 0 * NaN = NaN would otherwise poison
    # delta and, through ds = p * (dp - delta), the dq/dk/dv of every
    # OTHER row sharing the tile.
    gf, of = g.astype(jnp.float32), o.astype(jnp.float32)
    delta = jnp.sum(jnp.where(gf == 0.0, 0.0, gf * of), axis=-1)
    if g_lse is not None:
        # An lse cotangent folds into the same kernels: per query row,
        # ds_j = p_j (dp_j - delta + g_lse)   [dlse/ds_j = p_j], i.e. the
        # kernels run unchanged with delta' = delta - g_lse.
        delta = delta - g_lse.astype(jnp.float32)

    bq, bk = _block_sizes(s_q, s_k, block_q, block_k, d=d, bwd=True,
                          window=window)
    ops = _bwd_operands(q, k, v, g, lse, delta, bq, bk)
    rest = ((b, h, h_kv, s_q, s_k, d, d_v), bq, bk, causal, scale, interp,
            window, causal_offset, diag_offset)
    dk3, dv3 = _bwd_dkv(ops, *rest)
    dq3 = _bwd_dq(ops, *rest)

    dq = dq3[:, :s_q].reshape(b, h, s_q, d)
    dk = dk3[:, :s_k].reshape(b, h, s_k, d)
    dv = dv3[:, :s_k].reshape(b, h, s_k, d_v)
    if grp > 1:
        # sum the g per-q-head partials of each kv group (f32 to avoid
        # bf16 accumulation error across the group)
        dk = dk.reshape(b, h_kv, grp, s_k, d).astype(jnp.float32) \
               .sum(axis=2).astype(k.dtype)
        dv = dv.reshape(b, h_kv, grp, s_k, d_v).astype(jnp.float32) \
               .sum(axis=2).astype(v.dtype)
    return dq, dk, dv


_flash_fwd_shared = jax.jit(_flash_fwd_impl,
                            static_argnums=tuple(range(3, 11)))
_flash_bwd_shared = jax.jit(_flash_bwd_impl,
                            static_argnums=tuple(range(7, 15)))


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret,
               window, causal_offset, diag_offset):
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                      window=window, causal_offset=causal_offset,
                      diag_offset=diag_offset)


def _flash_lse_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                       window, causal_offset, diag_offset):
    o, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                        window=window, causal_offset=causal_offset,
                        diag_offset=diag_offset)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_vjp_bwd(causal, scale, block_q, block_k, interpret, window,
                       causal_offset, diag_offset, res, gs):
    q, k, v, o, lse = res
    g_o, g_lse = gs
    return _flash_bwd(q, k, v, o, lse, g_o, causal, scale, block_q,
                      block_k, interpret, g_lse=g_lse, window=window,
                      causal_offset=causal_offset,
                      diag_offset=diag_offset)


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def _mesh_island(kernel, q, k, v):
    """Run ``kernel(q, k, v) -> (o, lse)`` where GSPMD can place it.

    XLA's partitioner cannot split a Mosaic call ("Mosaic kernels cannot
    be automatically partitioned. Please wrap the call in a shard_map"),
    so inside a GSPMD program on more than one device — the front door's
    ZeRO/tp spec points, ``FROM_INPUTS`` — the kernel runs as a
    ``shard_map`` island: batch over ``dp`` and heads over ``tp`` where
    the mesh has those axes and they divide, everything else (and every
    other axis) replicated. The mesh is read off the operand's own type;
    one device, or a caller that is already inside a ``shard_map`` (the
    stacked-dp engine, the ring-attention islands), calls the kernel as
    it is."""
    mesh = jax.typeof(q).sharding.mesh
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return kernel(q, k, v)

    def axis(name, *dims):
        n = mesh.shape.get(name)
        return name if n and all(d % n == 0 for d in dims) else None

    spec = P(axis(DATA_AXIS, q.shape[0]),
             axis(TENSOR_AXIS, q.shape[1], k.shape[1]))
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=(spec, spec), check_vma=False)(q, k, v)


def flash_attention_with_lse(q, k, v, *, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: Optional[bool] = None,
                             window: Optional[int] = None,
                             causal_offset: int = 0,
                             diag_offset: int = 0):
    """Like :func:`flash_attention` but also returns the per-row
    log-sum-exp ``lse`` (B, H, Sq) — DIFFERENTIABLY (the lse cotangent is
    folded into the backward kernels' delta term). This is the building
    block for cross-block softmax merging: two attention partials
    ``(o1, lse1), (o2, lse2)`` over disjoint key sets combine exactly via

        lse = logaddexp(lse1, lse2)
        o   = o1 * exp(lse1 - lse) + o2 * exp(lse2 - lse)

    which is how ring flash attention (parallel/sequence.py) accumulates
    a device's queries over the rotating k/v blocks."""
    if window is not None and not causal:
        raise ValueError("window requires causal=True (sliding-window "
                         "attention is a causal-decoder pattern)")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if causal_offset and not causal:
        raise ValueError("causal_offset shifts the causal frontier and "
                         "requires causal=True")
    if causal_offset and window is not None:
        raise ValueError("causal_offset cannot combine with window: the "
                         "window lower edge is anchored to the inclusive "
                         "diagonal, so the combination would silently "
                         "shrink the band to window-1 keys")
    if causal_offset not in (0, 1):
        raise ValueError(f"causal_offset must be 0 (include diagonal) or "
                         f"1 (strict), got {causal_offset}")
    if diag_offset and not causal:
        raise ValueError("diag_offset shifts the causal/window diagonal "
                         "and requires causal=True")
    *_, dh = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    kernel = functools.partial(
        _flash_lse, causal=causal, scale=float(scale),
        block_q=int(block_q) if block_q is not None else None,
        block_k=int(block_k) if block_k is not None else None,
        interpret=interpret,
        window=int(window) if window is not None else None,
        causal_offset=int(causal_offset), diag_offset=int(diag_offset))
    return _mesh_island(kernel, q, k, v)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None):
    """Memory-efficient attention: softmax(q k^T * scale) v, blockwise.

    Drop-in for :func:`nn.attention.dense_attention` (same signature,
    same result up to float tolerance) with O(S) memory and MXU-tiled
    pallas kernels. q: (B, H, Sq, Dh); k: (B, Hkv, Sk, Dh); v: (B, Hkv,
    Sk, Dv), where Dv may differ from Dh (latent attention: keys 192,
    values 128 — the result, dO, dV and the accumulator are Dv wide; do
    NOT pad the values to the keys, a third of the PV and dV FLOPs would
    multiply zeros) and Hkv divides H — Hkv < H is grouped-query
    attention, served zero-copy by
    the kv BlockSpec index maps (do NOT repeat kv heads to H yourself;
    that materializes exactly the memory GQA removes). Sequence lengths
    need not divide the block sizes (tiles are padded+masked).
    ``block_q``/``block_k`` default to the measured-best tiling for the
    chip (large tiles — see ``_block_sizes``); pass explicit ints only to
    pin a tiling (tests, VMEM-constrained fusions).

    ``interpret=None`` compiles on a TPU and interprets where the CPU
    platform was selected explicitly (tests, sandbox runs); a CPU that
    JAX fell back to because the chip is missing raises
    (``_interpret_default``).
    """
    o, _ = flash_attention_with_lse(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret, window=window)
    # single vjp path: the unused lse output gets a zero cotangent, which
    # the backward folds away for free (delta - 0)
    return o


# The flash/dense hand-off. At short seq the kernel's grid is too short
# to amortize its fixed costs and the dense einsum — still cheap in
# memory there — is expected to win, so below this many KEYS
# make_flash_attn_fn dispatches to it. Where the crossover sits on the
# v5e is not measured (the records behind the 1024 default are gone;
# ROADMAP Speed 5 re-derives it from the kernel's own timing). The
# threshold lives in the typed env registry (DPX_FLASH_MIN_SEQ); this
# module attribute is its import-time read, kept for
# the consumers that report it (benchmarks/mfu_transformer.py).
# make_flash_attn_fn re-reads the registry at build time, so a test or
# deployment that sets the variable after import still takes effect.
from ..runtime import env as _env  # noqa: E402 — placed at its consumer

FLASH_MIN_SEQ = int(_env.get("DPX_FLASH_MIN_SEQ"))

#: Sentinel default for ``make_flash_attn_fn(min_seq_flash=...)``: "use
#: the registry value at build time" (None/0 keep meaning "always run
#: the kernel").
_MIN_SEQ_ENV = object()

# one-time flag for the dense-dispatch info log (list, so the closure in
# make_flash_attn_fn can mutate it without a global statement)
_dense_dispatch_logged = []


def make_flash_attn_fn(block_q: Optional[int] = None,
                       block_k: Optional[int] = None,
                       interpret: Optional[bool] = None,
                       window: Optional[int] = None,
                       min_seq_flash=_MIN_SEQ_ENV):
    """An ``attn_fn`` for :class:`nn.attention.MultiHeadAttention` /
    model constructors: models built with this compute attention through
    the pallas kernel instead of the dense einsum path. ``window`` bakes
    sliding-window (local) attention into the model — O(S*window)
    compute and the long-context default for causal decoders.

    Below ``min_seq_flash`` keys (default: the typed registry knob
    ``DPX_FLASH_MIN_SEQ``) the call dispatches to the dense einsum
    instead — same function, expected faster at short seq — so enabling
    flash is safe at every sequence length. Shapes are static under jit,
    so the dispatch costs nothing at runtime. Pass ``min_seq_flash=None``
    (or 0) to always run the kernel (tests, kernel benchmarking)."""
    if min_seq_flash is _MIN_SEQ_ENV:
        min_seq_flash = int(_env.get("DPX_FLASH_MIN_SEQ"))

    def attn_fn(q, k, v, *, causal=False, scale=None):
        if min_seq_flash and k.shape[-2] < min_seq_flash:
            if not _dense_dispatch_logged:
                _dense_dispatch_logged.append(True)
                logging.getLogger(__name__).info(
                    "flash attn_fn: %d keys < min_seq_flash=%d, "
                    "dispatching to dense einsum (numerics identical "
                    "— logged once)",
                    k.shape[-2], min_seq_flash)
            from ..nn.attention import dense_attention
            return dense_attention(q, k, v, causal=causal, scale=scale,
                                   window=window)
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret, window=window)

    # full-window flash computes exactly softmax(qk)v, so cached decode
    # (models/generate.py) may substitute its inline core; a sliding
    # window changes the function and must not be silently swapped —
    # decode reads .window instead and switches to the rolling
    # (O(window)-memory) cache that reproduces it exactly
    attn_fn.dense_equivalent = window is None
    attn_fn.window = window
    # the kernel and the dense einsum both take values narrower than the
    # keys (nn/latent.py ``_core`` asks)
    attn_fn.narrow_values = True
    return attn_fn
