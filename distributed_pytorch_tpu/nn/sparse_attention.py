"""Sparse attention that chooses its blocks (InfLLM-V2, MiniCPM4's):
grouped-query softmax attention in which a query reads only a CHOSEN
subset of the earlier positions, by blocks of ``block`` positions, a set
a query and a KV head.

- **Compressed keys.** Window ``j`` of KV head ``n`` is the mean of the
  keys at positions ``stride * j .. stride * j + kernel - 1``; it exists
  for a query at ``t`` once it has closed, ``stride * j + kernel - 1 <= t``.
- **Scores.** ``p_j = sum over the heads h of the group of
  softmax_j(scale * q_h . Kc_j)`` over the closed windows; block ``m``
  scores the largest ``p_j`` among the windows that overlap it (0 where
  none has closed).
- **The choice.** The first ``init_blocks`` blocks, the blocks that hold
  the last ``window`` positions, and the ``topk`` best-scoring of the
  rest (all of them where fewer exist); a tie goes to the earlier block.
- **Dense below ``dense_len``.** A request whose PROMPT is shorter than
  ``dense_len`` reads every earlier position for its whole life.

Served, a block is a page (``block == page_len``): the layer's store is
``nn.paged.SelectedPages``, K and V pages beside the slot's compressed
keys, and a decode step reads the chosen pages alone."""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..ops.flash_attention import _MASK
from .attention import MultiHeadAttention, _scopes, dense_attention
from .core import Linear, Params

#: compressed keys a trip when a prompt's chunk scores the windows its
#: context has closed (``chunk_block_scores``): scores are (Hkv, g, S,
#: WINDOW_BLOCK) float32. Measured: benchmarks/sparse_select_sweep.py
WINDOW_BLOCK = 512


class Selection(NamedTuple):
    """The sizes of the compression and of the choice (the family's
    ``sparse_config``): ``kernel`` and ``stride`` of a compressed key's
    window, ``block`` positions a block, ``topk`` blocks chosen by score
    beside ``init_blocks`` leading ones and those of the last ``window``
    positions, ``dense_len`` the prompt length below which a request
    selects nothing."""
    kernel: int = 32
    stride: int = 16
    block: int = 64
    topk: int = 64
    init_blocks: int = 1
    window: int = 2048
    dense_len: int = 8192

    def check(self):
        if (self.kernel % self.stride or self.block % self.stride
                or self.kernel - self.stride > self.block
                or min(self) < 0 or min(self[:4]) < 1):
            raise ValueError(
                f"{self}: kernel and block are whole strides, a window "
                "overlaps at most two blocks (kernel - stride <= block), "
                "and no size is negative")
        return self

    def closed(self, n):
        """The windows that have closed once ``n`` positions are there
        (0 or less: none)."""
        return (n - self.kernel) // self.stride + 1


def window_probs(q, ck, t, sel: Selection, scale):
    """What each compressed key is worth to a query's group: q (..., g,
    Dh) the group's queries at position ``t`` (...), ck (..., W, Dh) the
    KV head's compressed keys (leading axes broadcast). -> p (..., W)
    float32: the sum over the group of each head's softmax over the
    windows that have closed at ``t``, exact zeros elsewhere (and
    everywhere where none has)."""
    s = jnp.einsum("...gd,...wd->...gw", q, ck.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    n_closed = (t - (sel.kernel - 1)) // sel.stride + 1
    closed = (jnp.arange(ck.shape[-2]) < n_closed[..., None])[..., None, :]
    s = jnp.where(closed, s, _MASK)
    e = jnp.where(closed, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    total = jnp.sum(e, -1, keepdims=True)
    return jnp.sum(e / jnp.where(total == 0.0, 1.0, total), axis=-2)


def block_scores(p, sel: Selection, n_blocks: int):
    """What each block is worth to a query, from its windows' worth ``p``
    (..., W), ``W >= n_blocks * block / stride``: the largest among the
    windows that overlap the block. -> (..., n_blocks) float32."""
    per, extra = sel.block // sel.stride, sel.kernel // sel.stride - 1
    pb = p[..., :n_blocks * per].reshape(p.shape[:-1] + (n_blocks, per))
    score = jnp.max(pb, axis=-1)
    for e in range(1, extra + 1):
        # the windows that start in the block before and end in this one
        before = jnp.pad(pb[..., :-1, per - e],
                         [(0, 0)] * (pb.ndim - 2) + [(1, 0)])
        score = jnp.maximum(score, before)
    return score


def choose_scored(score, t, sel: Selection):
    """The blocks a query at ``t`` (...) reads, from its blocks' scores
    (..., n_blocks). -> (chosen, exists), (..., n_blocks) bool each:
    ``exists`` the blocks that hold a position ``<= t``."""
    n_blocks = score.shape[-1]
    m = jnp.arange(n_blocks)
    t = t[..., None]
    exists = m <= t // sel.block
    forced = exists & ((m < sel.init_blocks)
                       | (m >= (t - sel.window + 1) // sel.block))
    rest = exists & ~forced
    top, at = jax.lax.top_k(jnp.where(rest, score, -jnp.inf),
                            min(sel.topk, n_blocks))
    picked = jnp.any((at[..., None] == m) & (top[..., None] > -jnp.inf),
                     axis=-2)
    return forced | picked, exists


def choose_blocks(p, t, sel: Selection, n_blocks: int):
    """:func:`choose_scored` of :func:`block_scores`: the blocks a query at
    ``t`` (...) reads, from its windows' worth ``p`` (..., W)."""
    return choose_scored(block_scores(p, sel, n_blocks), t, sel)


def chunk_block_scores(q, ck, t, n_closed, sel: Selection, scale,
                       n_blocks: int, window_block: int):
    """:func:`block_scores` of :func:`window_probs` for the queries of a
    prompt's chunk, without the windows' worth ever formed: q (Hkv, g, S,
    Dh) at positions ``t`` (S,), ck (Hkv, W, Dh) the slot's compressed
    keys, of which the chunk's real rows have closed the first
    ``n_closed`` (a traced scalar: no query reads a window past it, so a
    pad row reads what the last real row may). The keys are walked
    ``window_block`` windows a trip (a whole number of blocks; at the
    published sizes 512 windows are 128 blocks, one tile of lanes), as
    many trips as hold a closed window, twice: a head's softmax needs its
    normaliser over every closed window before a window's worth can be
    summed over the group, so the first walk keeps the running maximum
    and sum a query and head, and the second forms ``p`` a trip, pools it
    to blocks and writes them. The statistics and probabilities are
    float32, a window that has not closed at a query's own ``t`` is worth
    an exact zero, and a block past the last trip scores 0. -> (Hkv, S,
    n_blocks) float32."""
    hkv, g, s, dh = q.shape
    per, extra = sel.block // sel.stride, sel.kernel // sel.stride - 1
    if window_block % per:
        raise ValueError(f"a trip of {window_block} windows is no whole "
                         f"number of blocks of {per}")
    wb, mb = window_block, window_block // per
    n_trips = -(-n_blocks // mb)
    ck = ck[:, :n_blocks * per].astype(q.dtype)
    ck = jnp.pad(ck, ((0, 0), (0, n_trips * wb - ck.shape[1]), (0, 0)))
    # a trip's windows by their place in the block first, then by block:
    # pooling to blocks is then a maximum over ``per`` slabs of ``mb``
    # lanes, not over neighbours in a lane
    ck = ck.reshape(hkv, n_trips, mb, per, dh).swapaxes(2, 3).reshape(
        hkv, n_trips, wb, dh)
    order = (jnp.arange(wb) % mb) * per + jnp.arange(wb) // mb
    closed_at = jnp.minimum(sel.closed(t + 1), n_closed)

    def scores(j):
        sc = jnp.einsum("ngsd,nwd->ngsw", q, ck[:, j],
                        preferred_element_type=jnp.float32) * scale
        closed = (j * wb + order)[None, :] < closed_at[:, None]
        return jnp.where(closed, sc, _MASK)

    def stats(j, carry):
        m, l = carry
        sc = scores(j)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        return m_new, l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(sc - m_new[..., None]), axis=-1)

    def pool(j, carry):
        score, tail = carry
        # exp(_MASK - m) is an exact zero; where nothing has closed m is
        # _MASK itself and ``inv`` the zero
        p = jnp.sum(jnp.exp(scores(j) - m[..., None]) * inv[..., None],
                    axis=1).reshape(hkv, s, per, mb)
        sc = jnp.max(p, axis=2)
        # the windows that start in the block before and end in this one:
        # the trip's first block takes them from the trip before
        last = p[:, :, per - extra:, :]
        if extra:
            sc = jnp.maximum(sc, jnp.max(jnp.concatenate(
                [tail[..., None], last[..., :-1]], axis=-1), axis=2))
        return (jax.lax.dynamic_update_slice_in_dim(score, sc, j * mb, 2),
                last[..., -1])

    trips = jnp.clip((n_closed + wb - 1) // wb, 0, n_trips)
    m, l = jax.lax.fori_loop(
        0, trips, stats, (jnp.full((hkv, g, s), _MASK, jnp.float32),
                          jnp.zeros((hkv, g, s), jnp.float32)))
    inv = jnp.where(closed_at > 0, 1.0 / l, 0.0)
    score, _ = jax.lax.fori_loop(
        0, trips, pool, (jnp.zeros((hkv, s, n_trips * mb), jnp.float32),
                         jnp.zeros((hkv, s, extra), jnp.float32)))
    return score[..., :n_blocks]


def compress_keys(k, sel: Selection):
    """Every window of a whole sequence: k (..., S, Dh) -> (..., W, Dh)
    float32, ``W = (S - kernel) // stride + 1`` (0 where S < kernel)."""
    s = k.shape[-2]
    n = max((s - sel.kernel) // sel.stride + 1, 0)
    at = sel.stride * jnp.arange(n)[:, None] + jnp.arange(sel.kernel)
    return jnp.mean(k.astype(jnp.float32)[..., at, :], axis=-2)


class SparseAttention(MultiHeadAttention):
    """``y = W_o(sigmoid(W_g u) * o)`` with ``o`` the attention of the
    module docstring: ``n_heads`` query heads over ``n_kv_heads`` KV heads
    of ``head_dim``, no biases; the projections, the q/k norms
    (``qk_norm``, an epsilon or None) and the rotation (``rope``: the
    published model does not) are multi-head attention's own;
    ``out_gate`` the logistic gate on the heads' outputs. ``select`` are
    the :class:`Selection` sizes; ``max_seq`` sizes a served slot's
    compressed keys; ``tail_block`` the positions a trip when a prompt's
    chunk reads the resident pages."""

    def __init__(self, dim: int, n_heads: int, *, n_kv_heads: int,
                 head_dim: int, select: Selection, max_seq: int,
                 rope: bool = False, rope_base: float = 10000.0,
                 qk_norm: Optional[float] = 1e-6, out_gate: bool = True,
                 tail_block: int = 512, dtype=jnp.float32):
        super().__init__(dim, n_heads, causal=True, n_kv_heads=n_kv_heads,
                         rope=rope, rope_base=rope_base, dtype=dtype,
                         head_dim=head_dim, bias=False, qk_norm=qk_norm,
                         tail_block=tail_block)
        self.select, self.max_seq = select.check(), max_seq
        self.gate = Linear(dim, n_heads * head_dim, bias=False, dtype=dtype) \
            if out_gate else None

    def init(self, key) -> Params:
        p = super().init(key)
        if self.gate is not None:
            p["gate"] = self.gate.init(jax.random.fold_in(key, 1))
        return p

    def project_out(self, params: Params, o, x):
        """o (B, H, S, Dh), x the layer's input (the gate's) -> (B, S, D)."""
        b, h, s, dh = o.shape
        with jax.named_scope("attn/out"):
            o = o.astype(x.dtype).transpose(0, 2, 1, 3).reshape(b, s, h * dh)
            if self.gate is not None:
                o = jax.nn.sigmoid(self.gate.apply(params["gate"], x)) * o
            return self.out.apply(params["out"], o)

    def selection_mask(self, q, k):
        """(B, Hkv, S, S) bool: the positions each query of a whole
        sequence may read, by the selection alone (the caller adds the
        causal order). q (B, H, S, Dh), k (B, Hkv, S, Dh)."""
        sel = self.select
        b, h, s, dh = q.shape
        hkv = self.n_kv_heads
        n_blocks = -(-s // sel.block)
        want = n_blocks * (sel.block // sel.stride)
        ck = compress_keys(k, sel).astype(k.dtype)
        ck = jnp.pad(ck, ((0, 0), (0, 0), (0, want - ck.shape[2]), (0, 0)))
        t = jnp.broadcast_to(jnp.arange(s), (b, hkv, s))
        qg = jnp.moveaxis(q.reshape(b, hkv, h // hkv, s, dh), 2, 3)
        p = window_probs(qg, ck[:, :, None], t, sel, 1.0 / math.sqrt(dh))
        chosen, _ = choose_blocks(p, t, sel, n_blocks)
        return jnp.repeat(chosen, sel.block, axis=-1)[..., :s]

    def apply(self, params: Params, x, *, positions=None, **_):
        """A whole sequence is a prompt: one shorter than ``dense_len``
        attends densely."""
        b, s, _ = x.shape
        q, k, v = self.project_qkv(params, x)
        q, k = self.maybe_rope(q, k, positions)
        with _scopes("attn/core", "sparse_attention"):
            if s < self.select.dense_len:
                o = dense_attention(q, k, v, causal=True)
            else:
                with jax.named_scope("select"):
                    seen = self.selection_mask(q, k) \
                        & jnp.tril(jnp.ones((s, s), bool))
                with jax.named_scope("attend"):
                    hkv, g = self.n_kv_heads, self.n_heads // self.n_kv_heads
                    sc = jnp.einsum(
                        "bngqd,bnkd->bngqk",
                        q.reshape(b, hkv, g, s, -1), k,
                        preferred_element_type=jnp.float32) \
                        / math.sqrt(self.head_dim)
                    pr = jax.nn.softmax(jnp.where(seen[:, :, None], sc,
                                                  -jnp.inf), axis=-1)
                    o = jnp.einsum("bngqk,bnkd->bngqd", pr.astype(v.dtype),
                                   v).reshape(q.shape)
        return self.project_out(params, o, x)

    # -- the paged path (nn/paged.py SelectedPages) -------------------------

    def make_pages(self, n_pages: int, n_slots: int, page_len: int, bits,
                   dtype):
        from .paged import SelectedPages, mixers_unsupported
        if bits is not None:
            raise mixers_unsupported(SelectedPages.LACKS["quantized"])
        sel = self.select
        if sel.block != page_len:
            raise ValueError(
                f"a sparse-attention layer chooses blocks of {sel.block} "
                f"positions and the pool keeps pages of {page_len}: a "
                "block has to be a page (page_len == block)")
        windows = -(-self.max_seq // page_len) * (sel.block // sel.stride)
        return SelectedPages.zeros((self.n_kv_heads, page_len, self.head_dim),
                                   n_pages, n_slots, windows, dtype)

    def decode_paged(self, params: Params, x, pages, ctx):
        """One token a row. x (B, 1, D) normed -> ((B, 1, D), the store
        written): the step's K and V into the pages, the compressed key
        of a window the step closes, then the choice and the read."""
        hq, hk, hv = self.project_qkv(params, x)
        hq, hk = self.maybe_rope(hq, hk, ctx.idx[:, None, None])
        with jax.named_scope("page_write"):
            pages = pages.write(hk, hv, ctx)
        with _scopes("decode_attention", "sparse_attention"):
            with jax.named_scope("compress"):
                pages = pages.compress(ctx, self.select)
            o = pages.attend(ctx, hq, hk, hv, 1.0 / math.sqrt(self.head_dim),
                             self.select)
        return self.project_out(params, o, x), pages

    def prefill_paged(self, params: Params, x, pages, ctx):
        """A prompt's chunk over the slot's resident pages under the
        selection. x (1, S, D) normed -> ((1, S, D), the store written)."""
        hq, hk, hv = self.project_qkv(params, x)
        hq, hk = self.maybe_rope(hq, hk, ctx.positions)
        with jax.named_scope("page_write"):
            pages = pages.write_tail(hk, hv, ctx)
        with _scopes("attn/core", "sparse_attention"):
            with jax.named_scope("compress"):
                pages = pages.compress_tail(hk, ctx, self.select)
            o = pages.attend_tail(ctx, hq, 1.0 / math.sqrt(self.head_dim),
                                  self.select, self.tail_block)
        return self.project_out(params, o, x), pages
