"""The paged path's one seam: what a step function hands every block,
and the STORE that holds a layer's resident pages.

The step functions (``models/generate.py``) own what is the same for
every layer: where this step's entries go in the pool, the positions,
the masks (the ``*Ctx`` tuples below). The format of a layer's resident
pages lives behind the store its attention module hands out
(``attn.make_pages``): the pool, the step functions and the programs
carry a list of stores, one a layer, as one opaque pytree, and only the
attention module and the store look inside one.

- :class:`KVPages` (``nn/attention.py``): a K and a V side, each
  :class:`ExactSide` (one ``(n_pages, Hkv, page_len, Dh)`` array in the
  model's dtype) or :class:`QuantSide` (int8 / packed-nibble pages,
  per-page-per-block f32 scales, per-slot exact f32 tail pages:
  docs/serving.md "Quantized resident pool"). Every operation is written
  once, for a side, and applied to K and to V.
- ``LatentPages`` (``nn/latent.py``): ONE array of ``[c | k_r]`` entries,
  ``(n_pages, 1, page_len, page_width)``; what it lacks (quantized pages,
  the speculative commit, the hand-off) it refuses by name with
  :class:`LatentPagesUnsupported`.
- :class:`WindowPages`: a sliding-window layer's K and V as ONE RING a
  slot, ``(n_slots, Hkv, ring, Dh)``, position ``p`` at ``p % ring``. No
  table addresses it and the allocator counts no page for it: what a slot
  keeps resident there is fixed by the window and the page length, not by
  the context. It stands beside :class:`KVPages` in one ``state`` list
  where a model's layers mix window and global attention
  (``TransformerLM(layer_windows=...)``); what such a model cannot do yet
  (prefix sharing, quantized pages, the speculative commit, the hand-off)
  is refused by name with :class:`MixedStoresUnsupported`.

- :class:`StatePages`: a linear-attention layer's ONE STATE a slot,
  ``(n_slots, H, Dk, Dv)`` float32, whatever the context: a step reads it
  and writes it back whole, and :meth:`StatePages.reset` zeroes a slot's
  when the slot is taken (nothing masks a state's past, as positions mask
  a ring's).
- :class:`SelectedPages`: a sparse-attention layer's K and V pages (a
  :class:`KVPages` of exact sides) and beside them the slot's COMPRESSED
  KEYS, ``(n_slots, Hkv, windows, Dh)``, the selector's own array, written
  as the windows of ``kernel`` positions fill; a decode step scores them,
  chooses pages a row and a KV head inside the program and reads those
  pages alone. What a model with either cannot do yet (prefix sharing,
  quantized pages, the speculative commit, snapshots, the hand-off) is
  refused by name with :class:`MixerStoresUnsupported`.

A store is a ``NamedTuple`` of arrays: a pytree that crosses ``jax.jit``
and is donated whole. What a store answers: ``write`` (one entry a row:
a decode step, one position of a speculative commit), ``write_tail`` (a
prompt's tail), ``commit``, ``attend`` (a decode step; the kernel-or-loop
choice of ``ops/decode_attention.py`` is made from here), ``rows`` (the
dense, dequantised rows that prefill and verify attend over),
``resident_bytes``, ``n_pages``, ``require`` and the host side of the
hand-off, ``export*`` / ``adopt*``.

**Quantize once.** A quantized side writes every entry into its slot's
exact f32 tail page; the write that fills offset ``page_len - 1``
quantizes the whole tail page, from exact values, on the wire block
grid, and scatters it into the int pool with its scales. A dropped row
(``dest == n_pages``: an idle slot, a rejected speculative position)
writes nothing anywhere, so no value is ever rounded twice."""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.decode_attention import (_MASK, _finish, _merge_block,
                                    dense_decode_attention,
                                    paged_decode_attention,
                                    paged_loop_attention)
from ..ops.quant import (dequantize_page_blocks, pack_page_nibbles,
                         page_block_map, quantize_page_blocks,
                         unpack_page_nibbles)
from .attention import write_rows
from .sparse_attention import (WINDOW_BLOCK, choose_blocks, choose_scored,
                               chunk_block_scores, window_probs)


class DecodeCtx(NamedTuple):
    """One decode step over every slot. ``dest`` (B,) is the page each
    row's new entry goes to (``n_pages`` for an inactive row: dropped),
    ``wo`` (B,) the offset inside it; ``idx`` (B,) the positions;
    ``active`` (B,) bool; ``pos_mask`` / ``write_mask`` serve the dense
    (``blockwise=False``) path; ``moe_stats`` is a list an expert layer
    appends its counts to, or None."""
    tables: Any
    idx: Any
    dest: Any
    wo: Any
    active: Any
    pos_mask: Any
    write_mask: Any
    page_len: int
    blockwise: bool = True
    moe_stats: Optional[list] = None
    #: a list a sparse-attention layer appends its (blocks chosen, blocks
    #: resident) to, summed over the active rows and KV heads; or None
    sel_stats: Optional[list] = None


class PrefillCtx(NamedTuple):
    """The tail of one prompt: ``positions`` (S,) = ``offset`` + arange,
    ``dest`` / ``dest_off`` (S,) where each tail entry goes (pad rows
    route out of bounds), ``mask`` (S, W + S) over [prefix pages | tail] of
    ``width`` W, ``row_mask`` (S,) the tail's ``true_len`` real rows,
    ``slot`` the row of the pool the prompt is admitted to; ``dense``
    (a bool scalar, or None) says that the prompt is shorter than a
    sparse-attention layer's ``dense_len``, so the request attends to
    every earlier position for its whole life."""
    table_row: Any
    positions: Any
    offset: Any
    true_len: Any
    slot: Any
    dest: Any
    dest_off: Any
    mask: Any
    row_mask: Any
    width: int
    moe_stats: Optional[list] = None
    dense: Any = None


class VerifyCtx(NamedTuple):
    """A speculative verify: every row's k + 1 candidates at
    ``positions`` (B, S) = ``idx`` + arange, attending under ``mask``
    (B, S, W + S) over [resident rows | candidates]. Reads only."""
    tables: Any
    idx: Any
    positions: Any
    mask: Any


class BlockCtx(NamedTuple):
    """One pass of block generation over every slot: each row's block of
    ``L`` positions at ``positions`` (B, L) = ``idx`` + arange, ``idx``
    (B,) the row's length (the block's first position, a multiple of
    ``L``). The block lies inside one page (``page_len`` is a multiple of
    ``L``): ``dest`` (B,) is that page (``n_pages`` for an inactive row:
    dropped), ``wo`` (B,) the block's first offset inside it."""
    tables: Any
    idx: Any
    positions: Any
    dest: Any
    wo: Any
    active: Any
    page_len: int
    moe_stats: Optional[list] = None


class LatentPagesUnsupported(NotImplementedError):
    """A serving path that has no layout for latent-attention blocks."""


def latent_unsupported(what: str) -> LatentPagesUnsupported:
    """Latent blocks keep one exact array a layer; what has not been
    carried over to that layout says so by name."""
    return LatentPagesUnsupported(
        f"{what} cannot hold latent (MLA) pages yet: a latent block "
        "keeps one array of [c | k_r] entries a layer, served only by "
        "exact pages (InferenceEngine, kv_dtype='f32')")


class BlockGenerationUnsupported(NotImplementedError):
    """A serving path that cannot serve a model that generates by blocks
    (``TransformerLM(gen_block=...)``)."""


def block_unsupported(what: str) -> BlockGenerationUnsupported:
    """Generation by blocks runs through ONE path: greedy requests on
    the exact paged pool. What has not been carried over to a block step
    says so by name."""
    return BlockGenerationUnsupported(
        f"{what} cannot serve a model that generates by blocks "
        "(TransformerLM(gen_block=...)): a block step fills positions of "
        "a block under a confidence rule, served only greedy, by "
        "InferenceEngine(kv_dtype='f32') without speculation")


class MixedStoresUnsupported(NotImplementedError):
    """A serving path that cannot hold a window layer's ring beside a
    global layer's pages (``TransformerLM(layer_windows=...)``)."""


def mixed_unsupported(what: str) -> MixedStoresUnsupported:
    """Window and global layers in one cache run through ONE path: the
    exact paged pool without prefix sharing. What has not been carried
    over to a ring says so by name."""
    return MixedStoresUnsupported(
        f"{what} cannot serve a model whose layers mix a sliding window "
        "with global attention (TransformerLM(layer_windows=...)): a "
        "window layer keeps a ring of its last entries a slot and no "
        "pages, served only by InferenceEngine(kv_dtype='f32', "
        "prefix_share=False) without speculation")


class MixerStoresUnsupported(NotImplementedError):
    """A serving path that cannot hold a linear-attention layer's state
    or a sparse-attention layer's compressed keys
    (``TransformerLM(layer_mixers=...)``)."""


def mixers_unsupported(what: str) -> MixerStoresUnsupported:
    """A model of linear- and sparse-attention layers runs through ONE
    path: the exact paged pool without prefix sharing. What has not been
    carried over to a state a slot or to compressed keys says so by
    name."""
    return MixerStoresUnsupported(
        f"{what} cannot serve a model of linear- and sparse-attention "
        "layers (TransformerLM(layer_mixers=...)): a linear layer keeps "
        "one state a slot and no pages, a sparse layer its compressed "
        "keys a slot beside its pages, served only by "
        "InferenceEngine(kv_dtype='f32', prefix_share=False) without "
        "speculation")


def table_pages(state):
    """The page count the tables address: that of the first store that
    keeps pages by table (a window layer's ring has none; where every
    layer is one, nothing reads a page id)."""
    return next((st.n_pages for st in state if st.n_pages is not None), 0)


def dense_rows(g):
    """Gathered pages as contiguous rows: (P, H, L, D) of one table row
    -> (1, H, P*L, D); (B, P, H, L, D) of a batch of them -> (B, H, P*L,
    D). Unallocated table entries may hold any valid id: the caller's
    position mask hides them."""
    if g.ndim == 4:
        return g.transpose(1, 0, 2, 3).reshape(1, g.shape[1], -1,
                                               g.shape[-1])
    b, p, h, l, d = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(b, h, p * l, d)


class ExactSide(NamedTuple):
    """K or V in the model's dtype, ``(n_pages, Hkv, page_len, Dh)``."""
    pages: Any

    def write(self, h, dest, wo, j: int = 0):
        return ExactSide(write_rows(self.pages, dest, wo, h[:, :, j, :]))

    def write_tail(self, h, ctx):
        return ExactSide(write_rows(self.pages, ctx.dest, ctx.dest_off,
                                    jnp.moveaxis(h[0], 1, 0)))

    def write_block(self, h, dest, wo):
        """Every row's block (B, Hkv, L, Dh) to offsets ``wo`` ..
        ``wo + L - 1`` of page ``dest`` (B,): one scatter of B x L rows."""
        b, hkv, l, dh = h.shape
        return ExactSide(write_rows(
            self.pages, jnp.repeat(dest, l),
            (wo[:, None] + jnp.arange(l)[None, :]).reshape(-1),
            jnp.moveaxis(h, 2, 1).reshape(b * l, hkv, dh)))

    def rows(self, tables, idx=None):
        return dense_rows(self.pages[tables])

    def resident_bytes(self) -> int:
        return self.pages.nbytes

    # -- the hand-off's host side ------------------------------------------

    def export(self, idx, slot: int, valid_last: int):
        """The pages ``idx`` names as one (P, Hkv, L, Dh) f32 numpy
        array, the last page zeroed past ``valid_last``: a reused page
        may carry a previous occupant's stale entries there, which no
        mask would attend but which would poison a quantized frame's
        scales. (np.array, not asarray: a CPU-backend transfer can alias
        read-only memory.)"""
        a = np.array(self.pages[idx], np.float32)
        a[-1, :, valid_last:, :] = 0.0
        return a

    def adopt(self, pages, idx, slot: int, valid_last: int):
        return ExactSide(self.pages.at[idx].set(
            jnp.asarray(pages, self.pages.dtype)))


class QuantSide(NamedTuple):
    """K or V block-quantized: ``q`` (n_pages, Hkv, page_len, Dh) int8,
    or (..., Dh // 2) uint8 nibble pairs at four bits; ``scales``
    (n_pages, nb) f32, 1 where never written (the codec's all-zero
    snap: such a page dequantizes to exact zeros); ``tail`` (n_slots,
    Hkv, page_len, Dh) f32, each slot's partial page, exact."""
    q: Any
    scales: Any
    tail: Any

    @property
    def bits(self) -> int:
        return 4 if self.q.dtype == jnp.uint8 else 8

    def _quantize(self, pages):
        q, scales = quantize_page_blocks(pages, self.bits)
        return (pack_page_nibbles(q) if self.bits == 4 else q), scales

    def _dequantize(self, q, scales):
        if self.bits == 4:
            q = unpack_page_nibbles(q)
        return dequantize_page_blocks(q, scales,
                                      page_block_map(*self.tail.shape[1:]))

    def write(self, h, dest, wo, j: int = 0):
        """Row b's entry into slot b's tail page; the pages this write
        completes are quantized, the one time, and scattered."""
        n_pages, page_len = self.q.shape[0], self.tail.shape[2]
        live = dest < n_pages
        tail = write_rows(
            self.tail, jnp.where(live, jnp.arange(dest.shape[0]),
                                 self.tail.shape[0]), wo, h[:, :, j, :])
        done = jnp.where(live & (wo == page_len - 1), dest, n_pages)
        q, scales = self._quantize(tail)                # (B, Hkv, L, Dh)
        return QuantSide(self.q.at[done].set(q, mode="drop"),
                         self.scales.at[done].set(scales, mode="drop"),
                         tail)

    def write_tail(self, h, ctx):
        """The tail starts at a page boundary (only full pages are
        shared), so its chunk c IS the slot's page ``offset // page_len
        + c``: a chunk that lies within ``true_len`` is complete and is
        quantized, the rest goes exact into the slot's tail page (what
        lies past ``true_len`` zeroed), where decode goes on writing."""
        q, scales = self.q, self.scales
        page_len, s = self.tail.shape[2], h.shape[2]
        last = ctx.table_row.shape[0] - 1
        for c in range(s // page_len):
            lo = c * page_len
            qc, sc = self._quantize(
                h[0, :, lo:lo + page_len, :].astype(jnp.float32))
            # incomplete chunks route out of bounds and drop; the page
            # index gather clamps harmlessly for them
            pid = jnp.where(
                lo + page_len <= ctx.true_len,
                ctx.table_row[jnp.clip(ctx.offset // page_len + c, 0, last)],
                q.shape[0])
            q = q.at[pid].set(qc, mode="drop")
            scales = scales.at[pid].set(sc, mode="drop")
        floor = (ctx.offset + ctx.true_len) // page_len * page_len \
            - ctx.offset
        r = floor + jnp.arange(page_len)
        part = jnp.where((r < ctx.true_len)[None, :, None],
                         jnp.take(h[0], jnp.clip(r, 0, s - 1), axis=1), 0.0)
        return QuantSide(q, scales, self.tail.at[ctx.slot].set(
            part.astype(jnp.float32)))

    def rows(self, tables, idx=None):
        """Dequantised rows. With ``idx`` (B,), the positions on each
        row's CURRENT page are read from its exact tail page: the pool
        row of an incomplete page was never written. (A prompt's shared
        prefix is whole pages: no ``idx``.)"""
        rows = dense_rows(self._dequantize(self.q[tables],
                                           self.scales[tables]))
        if idx is None:
            return rows
        page_len = self.tail.shape[2]
        col = jnp.arange(rows.shape[2])
        current = (col[None, :] // page_len) == (idx[:, None] // page_len)
        return jnp.where(current[:, None, :, None],
                         self.tail[:, :, col % page_len, :], rows)

    def loader(self, idx):
        """Page j of every row for the decode loop: dequantised as it is
        gathered, a row's current page overlaid from its tail."""
        tail_page = idx // self.tail.shape[2]

        def load(pids, j):
            blk = self._dequantize(jnp.take(self.q, pids, axis=0),
                                   jnp.take(self.scales, pids, axis=0))
            return jnp.where((j == tail_page)[:, None, None, None],
                             self.tail, blk)
        return load

    def resident_bytes(self) -> int:
        """Pages and scales: the tails are a slot's, not a page's."""
        return self.q.nbytes + self.scales.nbytes

    # -- the hand-off's host side ------------------------------------------

    def _tail_np(self, slot: int, valid_last: int):
        t = np.array(self.tail[slot], np.float32)
        t[:, valid_last:, :] = 0.0
        return t

    def export_quantized(self, idx, slot: int, valid_last: int):
        """The resident bits as ``(q (P, Hkv, L, Dh) int8 unpacked,
        scales (P, nb))``; the partial last page is quantized here,
        once, from its exact tail page, through the wire codec."""
        from ..serve.pages import quant as codec
        q = np.array(self.q[idx])
        q = np.ascontiguousarray(
            codec.unpack_pages_np(q) if self.bits == 4 else q, np.int8)
        scales = np.array(self.scales[idx], np.float32)
        if valid_last < self.tail.shape[2]:
            q[-1], scales[-1] = codec.quantize_page_np(
                self._tail_np(slot, valid_last), self.bits)
        return q, scales

    def export(self, idx, slot: int, valid_last: int):
        """Full pages dequantised on the host, the partial last page
        from the exact tail: it ships with no quantization error."""
        from ..serve.pages import quant as codec
        q, scales = self.export_quantized(idx, slot, self.tail.shape[2])
        a = np.stack([codec.dequantize_page_np(q[p], scales[p])
                      for p in range(q.shape[0])])
        if valid_last < self.tail.shape[2]:
            a[-1] = self._tail_np(slot, valid_last)
        return a

    def _install(self, idx, slot: int, q, scales, tail):
        from ..serve.pages import quant as codec
        if self.bits == 4:
            q = codec.pack_pages_np(q)
        return QuantSide(
            self.q.at[idx].set(jnp.asarray(q)),
            self.scales.at[idx].set(jnp.asarray(scales, jnp.float32)),
            self.tail.at[slot].set(jnp.asarray(tail)))

    def adopt(self, pages, idx, slot: int, valid_last: int):
        """Exact pages in: the full ones are quantized here (their ONE
        rounding), the partial last page goes into the slot's tail page,
        which a page-aligned length zeroes, so that a previous
        occupant's tail can never alias into this request."""
        from ..serve.pages import quant as codec
        n, page_len = pages.shape[0], self.tail.shape[2]
        partial = valid_last < page_len
        q = np.zeros(pages.shape, np.int8)
        scales = np.ones((n, self.scales.shape[1]), np.float32)
        for p in range(n - partial):
            q[p], scales[p] = codec.quantize_page_np(pages[p], self.bits)
        tail = np.array(pages[-1], np.float32) if partial \
            else np.zeros(pages.shape[1:], np.float32)
        tail[:, valid_last:, :] = 0.0
        return self._install(idx, slot, q, scales, tail)

    def adopt_quantized(self, pages, idx, slot: int, valid_last: int):
        """The sender's bits in, verbatim: no rounding here. The partial
        last page is also dequantised into the slot's tail page
        (lossless given ``q`` and ``scales``), so that decode's overlay
        and the completing quantization see what the sender's pool
        held."""
        from ..serve.pages import quant as codec
        q, scales = pages
        q = np.ascontiguousarray(q, np.int8)
        if valid_last < self.tail.shape[2]:
            tail = codec.dequantize_page_np(q[-1], np.asarray(scales[-1]))
            tail[:, valid_last:, :] = 0.0
        else:
            tail = np.zeros(q.shape[1:], np.float32)
        return self._install(idx, slot, q, scales, tail)


class KVPages(NamedTuple):
    """Multi-head attention's store: a K side and a V side of one kind."""
    k: Any
    v: Any

    @classmethod
    def zeros(cls, shape, n_pages: int, n_slots: int, bits, dtype):
        """``shape`` = (Hkv, page_len, Dh); ``bits`` None (exact, in
        ``dtype``), 8 or 4."""
        if bits is None:
            side = lambda: ExactSide(jnp.zeros((n_pages,) + shape, dtype))
        else:
            from ..comm.wire import num_blocks
            hkv, page_len, dh = shape
            if bits == 4 and dh % 2:
                raise ValueError(
                    f"kv_dtype='q4' packs two nibbles per byte along "
                    f"the head dim, which must be even (got Dh={dh})")
            store = (hkv, page_len, dh // 2) if bits == 4 else shape
            nb = num_blocks(hkv * page_len * dh)
            side = lambda: QuantSide(
                jnp.zeros((n_pages,) + store,
                          jnp.uint8 if bits == 4 else jnp.int8),
                jnp.ones((n_pages, nb), jnp.float32),
                jnp.zeros((n_slots,) + shape, jnp.float32))
        return cls(side(), side())

    @property
    def n_pages(self) -> int:
        return self.k[0].shape[0]        # a side's first array: its pages

    def _both(self, op, k, v, *args):
        return KVPages(getattr(self.k, op)(k, *args),
                       getattr(self.v, op)(v, *args))

    def write(self, hk, hv, dest, wo, j: int = 0):
        """One entry a row, position ``j`` of hk / hv (B, Hkv, S, Dh),
        to offset ``wo`` of page ``dest`` (B,); ``dest == n_pages``
        drops the row."""
        return self._both("write", hk, hv, dest, wo, j)

    def write_tail(self, hk, hv, ctx: PrefillCtx):
        """A prompt's tail, (1, Hkv, S, Dh) each."""
        return self._both("write_tail", hk, hv, ctx)

    def commit(self, steps, sk, sv):
        """The accepted prefix of a verify's scratch (B, Hkv, S, Dh):
        ``steps[j]`` = (dest, wo) of candidate j, a rejected one routed
        out of bounds, so a page only ever completes from accepted
        tokens, position by position as decode would have written
        them."""
        pages = self
        for j, (dest, wo) in enumerate(steps):
            pages = pages.write(sk, sv, dest, wo, j)
        return pages

    def rows(self, tables, idx, like_k, like_v):
        """The resident rows a prefill (``tables`` (P,), no ``idx``) or
        a verify (``tables`` (B, P)) attends over, in the dtypes of this
        step's K and V."""
        return (self.k.rows(tables, idx).astype(like_k.dtype),
                self.v.rows(tables, idx).astype(like_v.dtype))

    def attend(self, ctx: DecodeCtx, hq, hk, hv, scale):
        """A decode step's attention, this step's entries written.
        Blockwise, hk / hv are re-selected at the write position per
        block: identity for active rows (already written), and gives
        inactive rows the write-mask's exact value semantics
        (their discarded logits still see "their" key). On a TPU an
        exact store takes the kernel instead: active rows read their key
        from the pool, inactive rows are skipped
        (``ops/decode_attention.py``)."""
        if not ctx.blockwise:
            # logical rows: gather the updated pool, then re-select the
            # new key at the write position
            k, v = self.k.rows(ctx.tables, ctx.idx), \
                self.v.rows(ctx.tables, ctx.idx)
            return dense_decode_attention(
                hq, jnp.where(ctx.write_mask, hk.astype(k.dtype), k),
                jnp.where(ctx.write_mask, hv.astype(v.dtype), v),
                ctx.pos_mask, scale=scale)
        if isinstance(self.k, ExactSide):
            return paged_decode_attention(
                hq, self.k.pages, self.v.pages, ctx.tables, ctx.idx, hk, hv,
                scale=scale, page_len=ctx.page_len, active=ctx.active)
        return paged_loop_attention(
            hq, self.k.loader(ctx.idx), self.v.loader(ctx.idx), ctx.tables,
            ctx.idx, hk, hv, scale=scale, page_len=ctx.page_len,
            out_dtype=hv.dtype)

    def attend_tail(self, ctx: PrefillCtx, hq, scale, block: int,
                    chosen=None):
        """A prompt's tail over the slot's resident pages, the tail's own
        entries among them (``write_tail`` ran): queries hq (1, H, S, Dh)
        at ``ctx.positions`` over the positions ``<=`` their own, read
        ``block`` positions a trip with the online-softmax merge of
        ``ops/decode_attention.py`` (float32 statistics, ``_MASK`` and
        exact-zero probabilities). The trips follow ``ctx.offset``: as
        many as hold a position of the prompt so far, so that no
        (S, width) score array is ever formed and a short context pays
        for no long one. ``chosen`` (Hkv, S, pages) bool, a sparse layer's
        (:class:`SelectedPages`): a position is seen only in a page its
        query chose, and a page that no query chose reads as zeros,
        whatever it holds. Exact pages only. Returns (1, H, S, Dh)."""
        pages_k, pages_v = self.k.pages, self.v.pages
        _, hkv, page_len, dh = pages_k.shape
        _, h, s, _ = hq.shape
        g = h // hkv
        per = max(1, block // page_len)
        row = ctx.table_row
        if row.shape[0] % per:
            pad = per - row.shape[0] % per
            row = jnp.pad(row, (0, pad))
            if chosen is not None:
                chosen = jnp.pad(chosen, ((0, 0), (0, 0), (0, pad)))
        q = hq.reshape(1, hkv, g, s, dh).astype(pages_k.dtype)
        span = per * page_len

        def rows(pages, pids):
            return pages[pids].transpose(1, 0, 2, 3).reshape(1, hkv, span, dh)

        def body(j, carry):
            pids = jax.lax.dynamic_slice_in_dim(row, j * per, per)
            pos_k = j * span + jnp.arange(span)
            # past the prompt so far a page may be any page, or none, and
            # hold anything (0 x NaN is NaN): those rows read as zeros
            held = (pos_k < ctx.offset + ctx.true_len)[:, None]
            if chosen is not None:
                ch = jnp.repeat(jax.lax.dynamic_slice_in_dim(
                    chosen, j * per, per, axis=2), page_len, axis=2)
                held = held & jnp.any(ch, axis=1)[None, :, :, None]
            k = jnp.where(held, rows(pages_k, pids), 0)
            v = jnp.where(held, rows(pages_v, pids), 0)
            sc = jnp.einsum("bngqd,bnkd->bngqk", q, k,
                            preferred_element_type=jnp.float32) * scale
            seen = (pos_k[None, :] <= ctx.positions[:, None])[None, None,
                                                              None]
            if chosen is not None:
                seen = seen & ch[None, :, None]
            return _merge_block(carry, jnp.where(seen, sc, _MASK), v, seen)

        carry = (jnp.full((1, hkv, g, s), _MASK, jnp.float32),
                 jnp.zeros((1, hkv, g, s), jnp.float32),
                 jnp.zeros((1, hkv, g, s, dh), jnp.float32))
        trips = (ctx.offset + ctx.true_len + span - 1) // span
        m, l, acc = jax.lax.fori_loop(0, trips, body, carry)
        return _finish(m, l, acc, hq.dtype).reshape(1, h, s, dh)

    def write_block(self, hk, hv, dest, wo):
        """A pass's block a row, (B, Hkv, L, Dh) each, in place."""
        return self._both("write_block", hk, hv, dest, wo)

    def attend_block(self, ctx: BlockCtx, hq, hk, hv, scale):
        """A block pass's attention, the block written: every one of the
        ``L`` query positions of a row sees the same keys (the resident
        positions and the whole block, ``<= idx + L - 1``), so they fold
        into the query group, ``L x g`` query rows a KV head, through
        the decode step's kernel or loop (``paged_decode_attention``:
        the loop re-selects the block's LAST key for the rows whose
        write was dropped, whose result nobody reads)."""
        b, h, l, dh = hq.shape
        hkv = hk.shape[1]
        g = h // hkv
        folded = hq.reshape(b, hkv, g * l, dh).reshape(b, hkv * g * l, 1, dh)
        o = paged_decode_attention(
            folded, self.k.pages, self.v.pages, ctx.tables, ctx.idx + l - 1,
            hk[:, :, -1:], hv[:, :, -1:], scale=scale,
            page_len=ctx.page_len, active=ctx.active)
        return o.reshape(b, hkv, g, l, dh).reshape(b, h, l, dh)

    def resident_bytes(self) -> int:
        return self.k.resident_bytes() + self.v.resident_bytes()

    def require(self, op: str) -> None:
        """Every operation is here, but a block step over quantized
        pages (its in-place rewrite of a block would round an entry more
        than once) and a place beside a window layer's ring for them
        (``attend_tail`` reads exact pages)."""
        if isinstance(self.k, ExactSide):
            return
        if op == "block_step":
            raise block_unsupported("a quantized page pool "
                                    "(kv_dtype='q8'/'q4')")
        if op == "mixed":
            raise mixed_unsupported("a quantized page pool "
                                    "(kv_dtype='q8'/'q4')")

    def export(self, idx, slot: int, valid_last: int, quantized=False):
        op = "export_quantized" if quantized else "export"
        return (getattr(self.k, op)(idx, slot, valid_last),
                getattr(self.v, op)(idx, slot, valid_last))

    def adopt(self, k, v, idx, slot: int, valid_last: int, quantized=False):
        return self._both("adopt_quantized" if quantized else "adopt",
                          k, v, idx, slot, valid_last)


class WindowPages(NamedTuple):
    """A sliding-window layer's store: K and V, each ONE RING a slot,
    ``(n_slots, Hkv, ring, Dh)`` in the model's dtype. The entry of
    position ``p`` of the request in slot ``s`` lives at ``[s, :, p %
    ring]``; ``ring`` is the window rounded up to whole pages plus one
    page (the live window of a row lies across that many pages of
    ``page_len`` positions wherever its newest position falls in one), so
    what a slot keeps resident is fixed by the window and the page
    length and does not grow with the context or with ``max_len``. No
    page is allocated for it and no table addresses it.

    A slot's ring is never cleared: an entry is read only where the
    position it would hold, ``idx - (idx - r) % ring``, is one of the
    row's last ``window`` and not below 0, and every such position was
    written by the request that owns the slot now (a prefill chunk
    writes its last ``ring`` real entries, a decode step its one), so an
    earlier occupant's entries are never seen. The window itself is the
    attention module's (``MultiHeadAttention(window=...)``): it is no
    part of the pytree."""
    k: Any
    v: Any

    @classmethod
    def zeros(cls, shape, n_slots: int, window: int, page_len: int, dtype):
        """``shape`` = (Hkv, Dh)."""
        hkv, dh = shape
        ring = (-(-window // page_len) + 1) * page_len
        return cls(jnp.zeros((n_slots, hkv, ring, dh), dtype),
                   jnp.zeros((n_slots, hkv, ring, dh), dtype))

    @property
    def n_pages(self):
        return None                      # no table addresses a ring

    @property
    def ring(self) -> int:
        return self.k.shape[2]

    def write(self, hk, hv, ctx: DecodeCtx):
        """One entry a row, hk / hv (B, Hkv, 1, Dh), at ``ctx.idx %
        ring`` of row b's own ring; an inactive row writes nothing."""
        n = self.k.shape[0]
        dest = jnp.where(ctx.active, jnp.arange(n), n)
        wo = ctx.idx % self.ring
        return WindowPages(write_rows(self.k, dest, wo, hk[:, :, 0, :]),
                           write_rows(self.v, dest, wo, hv[:, :, 0, :]))

    def attend(self, ctx: DecodeCtx, hq, scale, window: int):
        """A decode step's attention, this step's entry written: row b
        over its own ring, an entry seen where the position it holds is
        one of the row's last ``window``. hq (B, H, 1, Dh) -> the
        same."""
        r = jnp.arange(self.ring)
        back = (ctx.idx[:, None] - r[None, :]) % self.ring       # (B, ring)
        seen = (back < window) & (back <= ctx.idx[:, None])
        return dense_decode_attention(hq, self.k, self.v, seen, scale=scale)

    def prior(self, ctx: PrefillCtx, window: int):
        """The ``window`` entries before a prompt's tail, positions
        ``offset - window .. offset - 1`` of slot ``ctx.slot``: (k, v)
        (1, Hkv, window, Dh). Positions below 0 read whatever the ring
        holds there: the caller's mask hides them."""
        at = (ctx.offset - window + jnp.arange(window)) % self.ring
        take = lambda side: jnp.take(jax.lax.dynamic_index_in_dim(
            side, ctx.slot, 0, keepdims=True), at, axis=2)
        return take(self.k), take(self.v)

    def write_tail(self, hk, hv, ctx: PrefillCtx):
        """A prompt's tail, (1, Hkv, S, Dh) each: its last ``ring`` real
        entries (an earlier one is seen by no position after the tail)
        into the ring of slot ``ctx.slot``."""
        s, ring, n = hk.shape[2], self.ring, self.k.shape[0]
        keep = min(s, ring)
        first = jnp.clip(ctx.true_len - keep, 0, s - keep)
        i = first + jnp.arange(keep)
        dest = jnp.where(i < ctx.true_len, ctx.slot, n)
        wo = (ctx.offset + i) % ring

        def put(side, h):
            rows = jax.lax.dynamic_slice_in_dim(h[0], first, keep, axis=1)
            return write_rows(side, dest, wo, jnp.moveaxis(rows, 1, 0))
        return WindowPages(put(self.k, hk), put(self.v, hv))

    def resident_bytes(self) -> int:
        return self.k.nbytes + self.v.nbytes

    #: what a ring has no form of yet
    LACKS = {"commit": "speculative decoding (serve/spec)",
             "export": "the disaggregated hand-off (serve/disagg)",
             "adopt": "the disaggregated hand-off (serve/disagg)",
             "block_step": "generation by blocks "
                           "(TransformerLM(gen_block=...))",
             "prefix_share": "prefix sharing (a shared page of a global "
                             "layer says nothing of a window layer's "
                             "ring)"}

    def require(self, op: str) -> None:
        if op in self.LACKS:
            raise mixed_unsupported(self.LACKS[op])


class StatePages(NamedTuple):
    """A linear-attention layer's store: ONE STATE a slot, ``(n_slots, H,
    Dk, Dv)`` float32, the running sum ``S = decay * S + k v^T`` of the
    request in the slot. It does not grow with the context and no table
    addresses it: the allocator counts no page for it. A decode step
    reads every slot's state and writes it back whole (``step``: row b is
    slot b); a prompt's chunk reads its slot's state and leaves the one
    after its last real position (``read`` / ``write``), which is how a
    prefill in chunks carries it from chunk to chunk.

    Nothing masks a state's past as positions mask a ring's: a slot's
    state is zeroed when the slot is taken (``reset``, the pool's
    ``begin``). It is float32 whatever the model's dtype: every step
    rounds it again, and in bfloat16 those roundings add up to a percent
    of a slowly decaying head's state."""
    s: Any

    @classmethod
    def zeros(cls, shape, n_slots: int):
        """``shape`` = (H, Dk, Dv)."""
        return cls(jnp.zeros((n_slots,) + shape, jnp.float32))

    @property
    def n_pages(self):
        return None                      # no table addresses a state

    def reset(self, slot):
        """The slot's state zeroed: a new request starts from nothing."""
        return StatePages(self.s.at[slot].set(0.0))

    def read(self, slot):
        """(H, Dk, Dv) of slot ``slot`` (traced)."""
        return jax.lax.dynamic_index_in_dim(self.s, slot, 0, keepdims=False)

    def write(self, slot, s):
        """The slot's state REPLACED."""
        return StatePages(jax.lax.dynamic_update_index_in_dim(
            self.s, s.astype(jnp.float32), slot, 0))

    def step(self, k, v, decay, active):
        """One recurrence step a row: ``S <- decay * S + k v^T`` where
        ``active``, k / v (B, H, D) the step's key and value, ``decay``
        (H,). Returns the store written; its ``s`` is what the step's
        queries read."""
        kv = k.astype(jnp.float32)[..., :, None] \
            * v.astype(jnp.float32)[..., None, :]
        new = decay[None, :, None, None] * self.s + kv
        return StatePages(jnp.where(active[:, None, None, None], new, self.s))

    def resident_bytes(self) -> int:
        return self.s.nbytes

    #: what a state a slot has no form of yet
    LACKS = {"commit": "speculative decoding (serve/spec: a rejected "
                       "candidate's step cannot be taken out of a state)",
             "export": "the disaggregated hand-off (serve/disagg)",
             "adopt": "the disaggregated hand-off (serve/disagg)",
             "snapshot": "a preemption snapshot (a state a slot is not "
                         "pages to park)",
             "block_step": "generation by blocks "
                           "(TransformerLM(gen_block=...))",
             "prefix_share": "prefix sharing (a shared page says nothing "
                             "of the state after the shared prefix)",
             "quantized": "a quantized page pool (kv_dtype='q8'/'q4')"}

    def require(self, op: str) -> None:
        if op in self.LACKS:
            raise mixers_unsupported(self.LACKS[op])


class SelectedPages(NamedTuple):
    """A sparse-attention layer's store: K and V pages (``kv``, a
    :class:`KVPages` of exact sides, addressed by the tables like any
    global layer's) and, beside them, the selector's own array: the
    COMPRESSED KEYS of the slot's resident context, ``ck`` ``(n_slots,
    Hkv, windows, Dh)`` in the model's dtype. Window ``j`` is the mean of
    the keys at positions ``stride * j .. stride * j + kernel - 1``,
    written when its last position is (a decode step closes one every
    ``stride`` positions, a prompt's chunk every window that ends inside
    it); it is read only where ``stride * j + kernel - 1 <=`` the
    query's position, and every such window was written by the request
    that owns the slot now, so a slot's compressed keys are never
    cleared. ``dense`` (n_slots,) bool: the slot's request has a prompt
    shorter than ``dense_len`` and attends to every earlier position for
    its whole life (written by every chunk of its prompt).

    ``sel`` in the methods is the layer's ``nn.sparse_attention.Selection``
    (sizes of the compression and of the choice): no part of the pytree.
    A block of the selection IS a page (``block == page_len``)."""
    kv: Any
    ck: Any
    dense: Any

    @classmethod
    def zeros(cls, shape, n_pages: int, n_slots: int, windows: int, dtype):
        """``shape`` = (Hkv, page_len, Dh)."""
        hkv, _, dh = shape
        return cls(KVPages.zeros(shape, n_pages, n_slots, None, dtype),
                   jnp.zeros((n_slots, hkv, windows, dh), dtype),
                   jnp.zeros((n_slots,), jnp.bool_))

    @property
    def n_pages(self) -> int:
        return self.kv.n_pages

    def _keys_at(self, pids, pos):
        """The resident keys at positions ``pos`` (..., n) of the pages
        ``pids`` (..., n) name: (..., n, Hkv, Dh), one gather of Dh-wide
        rows from the pool seen flat (``write_rows``' form)."""
        pages = self.kv.k.pages
        _, hkv, page_len, dh = pages.shape
        flat = (pids[..., None] * hkv + jnp.arange(hkv)) * page_len \
            + (pos % page_len)[..., None]
        return pages.reshape(-1, dh)[flat]

    def write(self, hk, hv, ctx: DecodeCtx):
        """One entry a row into the pages (:meth:`KVPages.write`)."""
        return self._replace(kv=self.kv.write(hk, hv, ctx.dest, ctx.wo))

    def compress(self, ctx: DecodeCtx, sel):
        """The compressed key of the window that this step's entry
        closes, for the rows whose entry closes one (every ``stride``-th
        position): the mean of the row's last ``kernel`` resident keys,
        this step's among them (``write`` ran)."""
        n = self.ck.shape[0]
        first = ctx.idx - (sel.kernel - 1)
        closes = ctx.active & (first >= 0) & (first % sel.stride == 0)
        pos = jnp.maximum(first[:, None] + jnp.arange(sel.kernel), 0)
        pids = jnp.take_along_axis(ctx.tables, pos // ctx.page_len, axis=1)
        kc = jnp.mean(self._keys_at(pids, pos).astype(jnp.float32), axis=1)
        return self._replace(ck=write_rows(
            self.ck, jnp.where(closes, jnp.arange(n), n),
            jnp.clip(first // sel.stride, 0, self.ck.shape[2] - 1), kc))

    def write_tail(self, hk, hv, ctx: PrefillCtx):
        """A prompt's tail, (1, Hkv, S, Dh) each, into the pages, and the
        slot's ``dense``."""
        return self._replace(kv=self.kv.write_tail(hk, hv, ctx),
                             dense=self.dense.at[ctx.slot].set(ctx.dense))

    def compress_tail(self, hk, ctx: PrefillCtx, sel):
        """The compressed key of every window that ends inside the real
        rows of a prompt's tail hk (1, Hkv, S, Dh); the first of them
        start in the ``kernel - stride`` resident positions before the
        tail."""
        n, hkv, windows, dh = self.ck.shape
        s, page_len = hk.shape[2], self.kv.k.pages.shape[2]
        lead, per = sel.kernel - sel.stride, sel.kernel // sel.stride
        pos = jnp.maximum(ctx.offset - lead + jnp.arange(lead), 0)
        prev = self._keys_at(ctx.table_row[pos // page_len], pos)
        k_ext = jnp.concatenate([jnp.moveaxis(prev, 0, 1), hk[0]], axis=1)
        # means of ``stride`` keys, then of ``per`` neighbours: a window
        sub = jnp.mean(k_ext.astype(jnp.float32).reshape(
            hkv, (lead + s) // sel.stride, sel.stride, dh), axis=2)
        n_win = s // sel.stride
        kc = sum(sub[:, e:e + n_win] for e in range(per)) / per
        j = ctx.offset // sel.stride - (per - 1) + jnp.arange(n_win)
        ok = (j >= 0) & (j * sel.stride + sel.kernel
                         <= ctx.offset + ctx.true_len)
        return self._replace(ck=write_rows(
            self.ck, jnp.where(ok, ctx.slot, n),
            jnp.clip(j, 0, windows - 1), jnp.moveaxis(kc, 0, 1)))

    def attend(self, ctx: DecodeCtx, hq, hk, hv, scale, sel):
        """A decode step's attention, this step's entries written: every
        row scores its slot's compressed keys, chooses pages a KV head
        (``nn.sparse_attention.choose_blocks``) and attends to those
        alone. The chosen pages of (row, KV head) become one row of a
        table over the pool seen as ``n_pages * Hkv`` pages of one head,
        the current page last, so that ``paged_decode_attention`` (the
        Mosaic kernel on a TPU, the loop elsewhere) walks them as it
        walks any row's pages; an unchosen page is never read. A
        ``dense`` row walks its own table, in a second call that skips
        every other row. hq (B, H, 1, Dh) -> the same."""
        b, h, _, dh = hq.shape
        hkv, page_len = hk.shape[1], ctx.page_len
        g, n_blocks = h // hkv, ctx.tables.shape[1]
        per = sel.block // sel.stride
        if self.ck.shape[2] < n_blocks * per:
            raise ValueError(
                f"the store keeps {self.ck.shape[2]} compressed keys a slot "
                f"and a table row addresses {n_blocks} pages of {per}: the "
                "model's max_seq is shorter than the pool's max_len")
        t = jnp.broadcast_to(ctx.idx[:, None], (b, hkv))
        with jax.named_scope("select"):
            p = window_probs(hq.reshape(b, hkv, g, dh),
                             self.ck[:, :, :n_blocks * per], t, sel, scale)
            chosen, exists = choose_blocks(p, t, sel, n_blocks)
            chosen |= self.dense[:, None, None] & exists
            width = min(n_blocks, sel.init_blocks
                        + -(-sel.window // page_len) + 1 + sel.topk)
            m = jnp.arange(n_blocks)
            # the chosen blocks first, in their order: the current one last
            blocks = jnp.sort(jnp.where(chosen, m, m + n_blocks),
                              axis=-1)[..., :width] % n_blocks
            pids = jnp.take_along_axis(
                jnp.broadcast_to(ctx.tables[:, None, :],
                                 (b, hkv, n_blocks)), blocks, axis=-1)
            count = jnp.sum(chosen, axis=-1)
            idx = jnp.maximum(count - 1, 0) * page_len \
                + (ctx.idx % page_len)[:, None]
            if ctx.sel_stats is not None:
                on = ctx.active[:, None]
                ctx.sel_stats.append(jnp.stack([
                    jnp.sum(jnp.where(on, count, 0)),
                    jnp.sum(jnp.where(on, jnp.sum(exists, axis=-1), 0))]))
        fold = lambda side: side.pages.reshape(-1, 1, page_len, dh)
        sparse = ctx.active & ~self.dense
        with jax.named_scope("attend"):
            o = paged_decode_attention(
                hq.reshape(b * hkv, g, 1, dh), fold(self.kv.k),
                fold(self.kv.v),
                (pids * hkv + jnp.arange(hkv)[None, :, None]).reshape(
                    b * hkv, width),
                idx.reshape(-1), hk.reshape(b * hkv, 1, 1, dh),
                hv.reshape(b * hkv, 1, 1, dh), scale=scale,
                page_len=page_len, active=jnp.repeat(sparse, hkv),
                zero_dead=True).reshape(b, h, 1, dh)
            if sel.dense_len > 0:
                on = ctx.active & self.dense
                o = jnp.where(self.dense[:, None, None, None],
                              paged_decode_attention(
                                  hq, self.kv.k.pages, self.kv.v.pages,
                                  ctx.tables, jnp.where(on, ctx.idx, 0), hk,
                                  hv, scale=scale, page_len=page_len,
                                  active=on, zero_dead=True), o)
        return o

    def attend_tail(self, ctx: PrefillCtx, hq, scale, sel, block: int):
        """A prompt's tail over the slot's resident pages (the tail's own
        among them: ``write_tail`` ran) UNDER THE SELECTION: every query
        scores the compressed keys that the prompt so far has closed,
        ``WINDOW_BLOCK`` of them a trip
        (``chunk_block_scores``: a short context pays for no long one),
        and chooses its blocks a KV head (``ctx.dense``: every block);
        the tail then walks the resident positions as
        :meth:`KVPages.attend_tail` does, under the chosen sets' mask.
        That is the dense walk's cost for the selection's result: what a
        query did not choose never enters its softmax. Returns (1, H, S,
        Dh)."""
        _, hkv, page_len, dh = self.kv.k.pages.shape
        _, h, s, _ = hq.shape
        n_blocks = ctx.table_row.shape[0]
        with jax.named_scope("select"):
            score = chunk_block_scores(
                hq[0].reshape(hkv, h // hkv, s, dh),
                jax.lax.dynamic_index_in_dim(self.ck, ctx.slot, 0, False),
                ctx.positions, sel.closed(ctx.offset + ctx.true_len), sel,
                scale, n_blocks, WINDOW_BLOCK)
            chosen = choose_scored(score, ctx.positions, sel)[0] | ctx.dense
        with jax.named_scope("attend"):
            return self.kv.attend_tail(ctx, hq, scale, block, chosen)

    def resident_bytes(self) -> int:
        return self.kv.resident_bytes() + self.ck.nbytes

    #: what compressed keys a slot have no form of yet
    LACKS = {**StatePages.LACKS,
             "commit": "speculative decoding (serve/spec: a verify "
                       "scores candidates without the selection)",
             "prefix_share": "prefix sharing (a shared page says nothing "
                             "of the slot's compressed keys)"}

    def require(self, op: str) -> None:
        if op in self.LACKS:
            raise mixers_unsupported(self.LACKS[op])
