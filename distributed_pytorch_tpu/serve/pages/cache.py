"""PagedSlotPool: the engine's slot pool, paged and prefix-shared.

KV memory is a block pool and each slot addresses its cache through a
page table row instead of owning a contiguous stripe. What a layer's
resident pages ARE lives behind the store its attention module hands out
(``blk.attn.make_pages``, ``nn/paged.py``): exact K and V arrays of
``(n_pages, Hkv, page_len, Dh)``, their quantized form, or latent
attention's ONE array of ``(n_pages, 1, page_len, page_width)``. The pool
holds ``state``, a list of one store a layer, hands it whole to its
programs and takes it back; it never looks inside one. The allocator,
the tables and the prefix index count pages, so they serve every format
unchanged; what a format cannot do (latent: quantized pages, the
speculative commit, the hand-off) its store refuses by name, when the
pool or the engine that needs it is built (:meth:`PagedSlotPool.require`).

**Two kinds of store in one pool.** A model that mixes sliding-window and
global layers (``TransformerLM(layer_windows=...)``) gets, in the same
``state`` list, pages for its global layers and for each window layer ONE
RING a slot (``nn.paged.WindowPages``: the window rounded up to whole
pages plus one page, whatever ``max_len``). The allocator, the tables and
``pages_in_use`` count the GLOBAL layers' pages only: a request takes
``ceil(length / page_len)`` page ids, each id one page in every global
layer, and nothing in a window layer, whose ring belongs to the slot. The
counters tell the two apart (``kv_resident_bytes_global`` /
``kv_resident_bytes_window`` in :meth:`PagedSlotPool.page_stats`). What
such a pool cannot do yet it refuses by name
(``nn.paged.MixedStoresUnsupported``) when it, or the engine that needs
it, is built: prefix sharing (a shared page of a global layer says
nothing of a window layer's ring), quantized pages, the speculative
commit, the disaggregated hand-off, generation by blocks.

**A state a slot, and chosen pages.** A model of linear- and
sparse-attention layers (``TransformerLM(layer_mixers=...)``) gets, in the
same ``state`` list, for each linear layer ONE STATE a slot
(``nn.paged.StatePages``: float32, the same bytes whatever the context,
zeroed by :meth:`PagedSlotPool.begin` when the slot is taken) and for each
sparse layer its K and V pages beside the slot's compressed keys
(``nn.paged.SelectedPages``), from which a decode step chooses, inside the
program, the pages a row and a KV head reads. The allocator counts the
sparse layers' pages; the counters tell the kinds apart
(``state_resident_bytes``, ``compressed_keys_resident_bytes``,
``sparse_blocks_chosen`` of ``sparse_blocks_resident``,
``slots_state_reset``: :meth:`PagedSlotPool.mixer_stats`). What such a
pool cannot do yet it refuses by name
(``nn.paged.MixerStoresUnsupported``): prefix sharing, quantized pages,
the speculative commit, snapshots, the hand-off, generation by blocks.

Three things fall out of the indirection:

- **prefix sharing**: full pages of a prompt are keyed in a radix index
  (:mod:`.prefix`); an admitted request reuses every resident page of
  its longest matching prefix (refcount++, ZERO prefill compute for the
  covered tokens) and only prefills the tail;
- **memory elasticity**: a retired request's private pages return to
  the free list immediately, while its indexed prompt pages stay
  RESIDENT at refcount zero until LRU eviction actually needs them;
- **typed back-pressure**: when every page has a live reader,
  allocation raises :class:`~..types.PagePoolExhausted` instead of
  corrupting anything (:mod:`.pool`).

All shapes are static and page tables, lengths, offsets and true
lengths are all TRACED, so the whole serving life is ONE jitted decode
program
(``models.generate.decode_step_slots_paged``) plus one jitted admit per
chunk-length bucket (``prefill_partial_paged``), counted by the
``CompileCounts`` the tests assert on. Slot recycling needs no clearing:
a freed slot's stale entries are never attended, because the per-row
position mask only exposes positions its CURRENT occupant wrote.

**Chunked prefill.** An admission is :meth:`PagedSlotPool.begin` (the
lookup, the refcounts, ALL the prompt's pages: everything that can fail
for want of a page, before any program runs) and then one
:meth:`PagedSlotPool.chunk` per at most ``chunk_tokens(buckets,
page_len)`` tokens, each the same admit program at the traced offset
where the last one stopped: a chunk is a tail whose prefix the earlier
chunks made resident. The engine runs one chunk an iteration between two
decode steps, so a long prompt no longer stalls the running rows for its
whole prefill; :meth:`PagedSlotPool.admit` is both halves back to back.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...nn.paged import SelectedPages, StatePages, WindowPages
from ...models.generate import (block_step_slots_paged, block_unsupported,
                                decode_step_slots_paged,
                                prefill_partial_paged,
                                spec_commit_slots_paged,
                                spec_verify_slots_paged)
from ...ops.decode_attention import kernel_traces
from ...parallel import moe
from ...runtime import faults
from ..cache import (CompileCounts, greedy_tokens, named_program,
                     upload, upload_pass)
from ..sampling import carry_blocks, fill_block, open_blocks
from .pool import PagePool
from .prefix import PrefixIndex
from .quant import resolve_kv_bits


#: the most tokens one prefill program takes. Every chunk reads all the
#: weights once, so smaller chunks cost more in total; a larger one stalls
#: the running rows for longer. Read on the chip with sparse experts whose
#: chunk of 512 is 37 ms and mostly weights (PERF.md, Findings, PR 31):
#: at 512 the prefill work grew by 60 % and the engine fell behind its
#: load, at 2048 the longest prompt's stall was the whole prefill's
PREFILL_CHUNK_TOKENS = 1024


def chunk_tokens(buckets: Tuple[int, ...], page_len: int) -> int:
    """Tokens a prefill chunk holds at most: ``PREFILL_CHUNK_TOKENS`` or
    the largest bucket, whichever is smaller, in whole pages (the admit
    program wants a page-aligned offset). Buckets above it are never
    compiled."""
    c = min(PREFILL_CHUNK_TOKENS, max(buckets)) // page_len * page_len
    if c < 1:
        raise ValueError(
            f"the largest prefill bucket ({max(buckets)}) holds less than "
            f"one page ({page_len} tokens): a prompt is prefilled in "
            "chunks of whole pages")
    return c


class _Prefill(NamedTuple):
    """A prompt between ``begin`` and its last chunk: ``done`` tokens
    are resident (the prefix hit, then each of the ``chunks`` so far, at
    most ``size`` tokens a chunk)."""
    prompt: np.ndarray
    n_hit: int
    done: int
    chunks: int
    size: int
    buckets: Tuple[int, ...]


class Chunk(NamedTuple):
    """What one :meth:`PagedSlotPool.chunk` ran: the ``index``-th chunk
    of its prompt, ``tokens`` of them at ``offset`` padded to ``bucket``;
    ``logits`` (1, vocab) of the prompt's last position when it was the
    last chunk, else None (nothing of a chunk in between is read)."""
    index: int
    offset: int
    tokens: int
    bucket: int
    logits: Optional[jnp.ndarray]


class PagedSlotPool:
    """Owns the page-pool arrays, the page tables, and the jitted paged
    programs; all allocation/refcount/eviction policy is host-side.

    ``kv_dtype`` selects the RESIDENT storage format (docs/serving.md
    "Quantized resident pool"): ``"f32"`` (default) keeps exact pages
    in the model dtype — the bit-exact contract; ``"q8"``/``"q4"``
    store block-quantized int pages plus per-page-per-block f32 scales
    (the ``comm/wire.py`` block format the handoff frame uses), with
    per-slot f32 tail pages holding each slot's partial page so every
    element is quantized exactly ONCE, on page completion, inside the
    same one decode program (``nn/paged.py`` ``QuantSide``)."""

    #: what a model that generates by blocks cannot be served through
    BLOCKS_LACK = {"commit": "speculative decoding (serve/spec)",
                   "export": "the disaggregated hand-off (serve/disagg)",
                   "adopt": "the disaggregated hand-off (serve/disagg)"}

    def __init__(self, model, n_slots: int, max_len: int, *,
                 page_len: int, n_pages: int, prefix_share: bool = True,
                 kv_dtype: str = "f32"):
        if max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {max_len}")
        self.model = model
        self.n_slots = n_slots
        self.max_len = max_len
        self.page_len = page_len
        self.n_pages = n_pages
        self.prefix_share = prefix_share
        self.kv_dtype = kv_dtype
        self.quant_bits = resolve_kv_bits(kv_dtype)
        self.pages_per_slot = -(-max_len // page_len)   # ceil
        # one store a layer, made by the layer's attention module (which
        # refuses here a format it cannot keep)
        self.state = [blk.attn.make_pages(n_pages, n_slots, page_len,
                                          self.quant_bits, model.dtype)
                      for blk in model.blocks]
        # a model that generates by blocks (TransformerLM(gen_block=L))
        # runs block_step in decode's place; a block must not straddle a
        # page, and its stores must be able to rewrite a block in place
        self.gen_block = getattr(model, "gen_block", None)
        if self.gen_block:
            self.require("block_step")
            if page_len % self.gen_block:
                raise ValueError(
                    f"page_len ({page_len}) must be a multiple of the "
                    f"model's gen_block ({self.gen_block}): a block is "
                    "written into one page")
        # window layers' rings beside global layers' pages: the tables
        # and the allocator count pages (the global layers'), a ring is
        # its slot's; what a ring has no form of is refused here by name
        kind = lambda cls: [i for i, st in enumerate(self.state)
                            if isinstance(st, cls)]
        self.window_layers = kind(WindowPages)
        if self.window_layers:
            self.require("mixed")
            if prefix_share:
                self.require("prefix_share")
        # linear-attention layers' states and sparse-attention layers'
        # compressed keys, a slot each: what they have no form of is
        # refused here by name too. ``sel_counts``: the blocks the sparse
        # layers' decode steps chose and had resident, summed on the
        # device as two (high, low 20 bits) pairs, and the decode steps,
        # read only by stats(); None without such a layer, like
        # ``moe_counts``
        self.state_layers = kind(StatePages)
        self.sparse_layers = kind(SelectedPages)
        if (self.state_layers or self.sparse_layers) and prefix_share:
            self.require("prefix_share")
        self.sel_counts = jnp.zeros((5,), jnp.int32) \
            if self.sparse_layers else None
        self.dense_len = max(
            (model.blocks[i].attn.select.dense_len
             for i in self.sparse_layers), default=0)
        self.slots_state_reset = 0
        # what the sparse layers' prompt chunks scored: the windows their
        # contexts had closed, of the windows the store keeps a slot
        # (summed over chunks and layers, here on the host)
        self.sparse_prefill_windows_scored = 0
        self.sparse_prefill_windows_kept = 0
        self._reset_fn = jax.jit(
            lambda state, slot: [st.reset(slot) if isinstance(st, StatePages)
                                 else st for st in state],
            donate_argnums=(0,))
        # what an expert layer counts in a decode step, summed on the
        # device and read only by stats(): tokens routed, experts with a
        # token, the fullest expert's tokens, decode steps. None for a
        # model without expert layers: an empty argument of the one
        # decode program, not a second program
        self.moe_layers = sum(hasattr(getattr(blk, "ffn", None), "routed")
                              for blk in model.blocks)
        self.moe_counts = jnp.zeros((4,), jnp.int32) \
            if self.moe_layers else None
        # a block generator's rows' blocks: what one block-step program
        # left (``sampling.carry_blocks``: tokens, the positions it
        # filled, the positions still masked) is what the next runs
        # over, never uploaded, and what the host reads of the pass. The
        # host hands in only the block a request opens with (block_step)
        self.blocks = jnp.zeros((n_slots, 3, self.gen_block), jnp.int32) \
            if self.gen_block else None
        # host-side state: page tables / lengths mirror the traced args
        # (tiny int32 uploads per call), policy state never leaves host.
        # They are uploaded through ``upload`` (a copy of their own):
        # dispatch is asynchronous and the host advances these arrays
        # right after a call — the program would read the NEXT step's
        # lengths
        self.tables = np.zeros((n_slots, self.pages_per_slot), np.int32)
        self.lengths = np.zeros((n_slots,), np.int32)
        self.upload_ns = 0          # cumulative, serve.cache.upload_pass
        self.owned: List[List[int]] = [[] for _ in range(n_slots)]
        # slot -> its prompt's prefill in progress (begin .. last chunk)
        self.prefilling: Dict[int, _Prefill] = {}
        self.pool = PagePool(n_pages, page_len)
        self.index = PrefixIndex(page_len)
        self.compiles = CompileCounts()
        self._admit_fns: Dict[int, callable] = {}
        # the stores are donated; the counters are not: stats() reads
        # them from another thread while a step is in flight
        self._decode_fn = jax.jit(self._decode, donate_argnums=(1,))
        self._block_fn = jax.jit(
            named_program(self._decode_block, "decode_block_step"),
            donate_argnums=(1,)) if self.gen_block else None
        # NOT donated: the pool survives a verify
        self._verify_fn = jax.jit(self._verify)
        self._commit_fn = jax.jit(self._commit, donate_argnums=(0,))
        # cumulative sharing counters (engine metrics / bench)
        self.prefix_lookups = 0
        self.prefix_hit_pages_total = 0
        self.prefill_tokens_saved_total = 0
        self.prompt_tokens_total = 0

    # -- jitted programs ---------------------------------------------------

    def _decode(self, params, state, counts, tables, lengths, tokens,
                active, sel_counts=None):
        """The ONE decode program. ``counts``: the expert layers'
        counters, ``sel_counts`` the sparse-attention layers', or None
        (an empty argument, not a second program). Counted where it is
        traced: the compile, and how many of its layers' attention, and
        of its expert layers' grouped matmuls, took a Mosaic kernel."""
        self.compiles.decode += 1          # trace-time only
        before, moe_before = kernel_traces(), moe.kernel_traces()
        per_layer = None if counts is None else []
        chosen = None if sel_counts is None else []
        logits, state = decode_step_slots_paged(
            self.model, params, state, tables, lengths, tokens, active,
            page_len=self.page_len, moe_stats=per_layer, sel_stats=chosen)
        self.compiles.decode_kernel_layers = kernel_traces() - before
        self.compiles.moe_kernel_matmuls = moe.kernel_traces() - moe_before
        counts = self._counted(counts, per_layer)
        if sel_counts is not None:
            # (high, low) pairs: the low part keeps 20 bits, so a sum of
            # under 2^30 a step never wraps an int32
            low = sel_counts[1:4:2] + jnp.sum(jnp.stack(chosen), axis=0)
            sel_counts = jnp.concatenate([jnp.stack(
                [sel_counts[0:4:2] + (low >> 20), low & ((1 << 20) - 1)],
                axis=1).reshape(4), sel_counts[4:] + 1])
        return greedy_tokens(logits), logits, state, counts, sel_counts

    @staticmethod
    def _counted(counts, per_layer):
        """The expert layers' counters after one more step."""
        if counts is None:
            return None
        c = jnp.stack(per_layer)                           # (layers, 3)
        return jnp.stack([counts[0] + jnp.sum(c[:, 0]),
                          counts[1] + jnp.sum(c[:, 1]),
                          jnp.maximum(counts[2], jnp.max(c[:, 2])),
                          counts[3] + 1])

    def _decode_block(self, params, state, counts, blocks, tables, lengths,
                      given, n_given, n_fill, active):
        """The ONE block-step program of a model that generates by
        blocks, in the decode program's place (and counted as it): the
        rows' blocks as the pass before left them, but for the rows the
        host opens one for (``sampling.open_blocks``); one pass of the
        model over every row's block; the pick (``sampling.fill_block``);
        and what the pass leaves (``sampling.carry_blocks``: an active
        row with nothing to fill ran its commit pass and leaves with a
        fresh block), all on the device: ONE array, which the host reads
        a pass later and the next pass runs over."""
        self.compiles.decode += 1          # trace-time only
        mask_id = self.model.mask_id
        tokens, masked = open_blocks(blocks, given, n_given, mask_id)
        before, moe_before = kernel_traces(), moe.kernel_traces()
        per_layer = None if counts is None else []
        logits, state = block_step_slots_paged(
            self.model, params, state, tables, lengths, tokens, active,
            page_len=self.page_len, moe_stats=per_layer)
        self.compiles.decode_kernel_layers = kernel_traces() - before
        self.compiles.moe_kernel_matmuls = moe.kernel_traces() - moe_before
        counts = self._counted(counts, per_layer)
        blocks = carry_blocks(fill_block(logits, tokens, masked, n_fill),
                              masked, active & (n_fill == 0), mask_id)
        return blocks, state, counts

    def _verify(self, params, state, tables, lengths, tokens):
        # trace-time only; one compile per draft-length bucket (the
        # candidate width s = k+1 is baked into the tokens shape)
        self.compiles.bump_verify(tokens.shape[1])
        return spec_verify_slots_paged(self.model, params, state, tables,
                                       lengths, tokens,
                                       page_len=self.page_len)

    def _commit(self, state, tables, lengths, sk, sv, commit):
        self.compiles.bump_commit(sk[0].shape[2])   # trace-time only
        return spec_commit_slots_paged(state, tables, lengths, sk, sv,
                                       commit, page_len=self.page_len)

    def _admit(self, params, state, table_row, tokens, offset, true_len,
               slot, dense=None, *, bucket: int):
        self.compiles.bump_prefill(bucket)  # trace-time only
        return prefill_partial_paged(self.model, params, state, table_row,
                                     tokens, offset, true_len, slot,
                                     page_len=self.page_len, dense=dense)

    def require(self, op: str) -> None:
        """Asked once by whoever is built on an operation of the stores
        (``"commit"``: a speculating engine; ``"export"`` / ``"adopt"``:
        the two sides of the hand-off): a store that lacks it raises by
        name now, not at the first request."""
        if self.gen_block and op in self.BLOCKS_LACK:
            raise block_unsupported(self.BLOCKS_LACK[op])
        for st in self.state:
            st.require(op)

    # -- allocation --------------------------------------------------------

    def _alloc(self, n: int) -> List[int]:
        """``n`` pages: free list first, then LRU eviction of
        refcount-zero indexed pages; all-or-nothing (a partial grab is
        rolled back before the typed exhaustion raise)."""
        faults.on_comm_op("page_admit")
        out: List[int] = []
        while len(out) < n:
            pid = self.pool.take_free()
            if pid is None:
                evicted = self.index.evict_lru(self.pool)
                if evicted is None:
                    for p in out:
                        self.pool.release_to_free(p)
                    raise self.pool.exhausted(n)
                self.pool.reclaim(evicted)
                pid = evicted
            out.append(pid)
        return out

    # -- host front ends ---------------------------------------------------

    def begin(self, prompt: np.ndarray, slot: int,
              buckets: Tuple[int, ...]) -> Tuple[int, int]:
        """The half of an admission that runs no program: radix prefix
        lookup → refcount the matched full pages → allocate ALL the
        pages the rest of ``prompt`` ((S,) np int32) needs → ``slot``'s
        table row. Returns ``(n_hit pages, offset tokens)``. Raises
        :class:`PagePoolExhausted` (pool-attributed, no slot state
        changed) when they cannot be allocated. ``lengths[slot]`` stays
        0 and nothing is indexed until the last :meth:`chunk`: a page
        must not be offered to another admission before it is written."""
        size = chunk_tokens(buckets, self.page_len)  # refuses before any page
        s = int(prompt.shape[0])
        L = self.page_len
        hits: List[int] = []
        if self.prefix_share:
            # cap at (s-1)//L: at least one real token must remain for
            # the tail prefill — the last prompt position's logits have
            # to be computed even when every full page is resident
            hits = self.index.match(prompt, (s - 1) // L, self.pool)
        self.prefix_lookups += 1
        n_hit = len(hits)
        # incref matched pages BEFORE allocating: eviction only ever
        # considers refcount-zero pages, so a matched page cannot be
        # stolen to satisfy this very request's tail
        for pid in hits:
            self.pool.incref(pid)
        try:
            fresh = self._alloc(-(-s // L) - n_hit)
        except Exception:
            for pid in hits:
                self.pool.decref(pid)
            raise
        row = hits + fresh
        self.tables[slot, :len(row)] = row
        self.tables[slot, len(row):] = 0
        self.owned[slot] = row
        self.prefilling[slot] = _Prefill(prompt, n_hit, n_hit * L, 0, size,
                                         tuple(buckets))
        if self.state_layers:
            # a linear layer's state has no positions to mask the last
            # occupant's by: the slot starts from nothing
            self.state = self._reset_fn(self.state,
                                        jnp.asarray(slot, jnp.int32))
            self.slots_state_reset += 1
        return n_hit, n_hit * L

    def chunk(self, params, slot: int) -> Chunk:
        """Prefill the next at most ``chunk_tokens`` tokens of the prompt
        :meth:`begin` gave ``slot``: one call of the admit program of the
        smallest bucket that holds them, at the traced offset where the
        last chunk stopped, attending over [the slot's resident pages |
        the chunk]. Dispatched and not waited for. The last chunk sets
        the slot's length, indexes the prompt's full pages for future
        admissions and returns the last position's logits."""
        pf = self.prefilling[slot]
        s, L = int(pf.prompt.shape[0]), self.page_len
        n = min(s - pf.done, pf.size)
        bucket = next(b for b in pf.buckets if b >= n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = pf.prompt[pf.done:pf.done + n]
        fn = self._admit_fns.get(bucket)
        if fn is None:
            fn = self._admit_fns[bucket] = jax.jit(
                named_program(self._admit, f"prefill_b{bucket}",
                              bucket=bucket), donate_argnums=(1,))
        # sparse layers: whether the whole prompt is under their dense_len
        dense = jnp.asarray(s < self.dense_len) if self.sparse_layers \
            else None
        for i in self.sparse_layers:
            self.sparse_prefill_windows_scored += max(
                0, self.model.blocks[i].attn.select.closed(pf.done + n))
            self.sparse_prefill_windows_kept += self.state[i].ck.shape[2]
        logits, self.state = fn(
            params, self.state, upload(self.tables[slot]),
            jnp.asarray(padded), jnp.asarray(pf.done, jnp.int32),
            jnp.asarray(n, jnp.int32), jnp.asarray(slot, jnp.int32), dense)
        out = Chunk(pf.chunks, pf.done, n, bucket, None)
        if pf.done + n < s:
            self.prefilling[slot] = pf._replace(done=pf.done + n,
                                                chunks=pf.chunks + 1)
            return out
        del self.prefilling[slot]
        self.lengths[slot] = s
        if self.prefix_share:
            self.index.insert(pf.prompt, s // L, self.owned[slot], self.pool)
        self.prefix_hit_pages_total += pf.n_hit
        self.prefill_tokens_saved_total += pf.n_hit * L
        self.prompt_tokens_total += s
        return out._replace(logits=logits)

    def admit(self, params, prompt: np.ndarray, slot: int,
              buckets: Tuple[int, ...]):
        """Admit ``prompt`` into ``slot`` whole: :meth:`begin`, then
        every :meth:`chunk` back to back. Returns ``(last-position
        logits (1, vocab), n_hit pages, offset tokens)``."""
        n_hit, offset = self.begin(prompt, slot, buckets)
        while True:
            logits = self.chunk(params, slot).logits
            if logits is not None:
                return logits, n_hit, offset

    def ensure_decode_capacity(self, slot: int) -> None:
        """Grow ``slot``'s page table if its next decode write crosses a
        page boundary. Raises :class:`PagePoolExhausted` (slot state
        unchanged) when no page can be supplied — the engine turns that
        into a typed per-request failure."""
        need_idx = int(self.lengths[slot]) // self.page_len
        row = self.owned[slot]
        if need_idx < len(row):
            return
        pid = self._alloc(1)[0]
        row.append(pid)
        self.tables[slot, need_idx] = pid

    def decode(self, params, tokens, active: np.ndarray,
               iteration: Optional[int] = None):
        """Advance every slot one position through the ONE jitted paged
        decode program (inactive rows neither write the pool nor
        advance). ``tokens`` on the device (a pass's output, the
        engine's) goes to the program as it is, and the pass makes three
        copies to the device (tables, lengths, mask); a host array (the
        draft model's steps, the disaggregated decode loop) is a fourth.
        They are made here, under ``serve.decode.upload``
        (``serve.cache.upload_pass``). Returns each slot's greedy token
        (n_slots,) int32 and the (n_slots, vocab) logits, both left on
        the device."""
        tables, lengths, tokens, mask = upload_pass(
            self, iteration, (self.tables, self.lengths, tokens), (active,))
        out, logits, self.state, self.moe_counts, self.sel_counts = \
            self._decode_fn(params, self.state, self.moe_counts, tables,
                            lengths, tokens, mask, self.sel_counts)
        self.lengths[np.asarray(active)] += 1
        return out, logits

    def block_step(self, params, given: np.ndarray, n_given: np.ndarray,
                   n_fill: np.ndarray, active: np.ndarray,
                   iteration: Optional[int] = None):
        """One pass of block generation for every active slot through
        the ONE jitted block-step program, over the rows' blocks where
        the pass before left them, on the device (``self.blocks``).
        ``n_fill`` (n_slots,) int32: how many masked positions each
        row's pass fills; an active row with 0 runs over its clean block
        (its commit pass): what this pass writes is the block's resident
        keys and values, the row's length advances by ``L`` here, and
        its next block opens inside the program. A row whose
        ``n_given`` (n_slots,) int32 is not -1 opens a block of the
        host's: that many tokens of ``given`` (n_slots, L) int32, then
        masked positions. All four are the caller's for this pass alone
        (nobody writes them again); with the tables and the lengths they
        are the pass's six copies to the device, made under
        ``serve.decode.upload``. Returns ``self.blocks`` as the pass
        leaves it, (n_slots, 3, L) int32 on the device: the blocks after
        the pass, the positions it filled, the positions still masked
        (``sampling.carry_blocks``). It is not donated to the next pass:
        the caller reads it with that pass already dispatched."""
        args = upload_pass(self, iteration, (self.tables, self.lengths),
                           (given, n_given, n_fill, active))
        self.blocks, self.state, self.moe_counts = self._block_fn(
            params, self.state, self.moe_counts, self.blocks, *args)
        self.lengths[active & (n_fill == 0)] += self.gen_block
        return self.blocks

    def ensure_spec_capacity(self, slot: int, n_new: int) -> None:
        """Grow ``slot``'s page table so the next ``n_new`` committed
        positions all have pages — the multi-token twin of
        :meth:`ensure_decode_capacity`, called AFTER acceptance is
        known so only accepted tokens ever demand pages (and before a
        block step, for the block's ``L`` positions). All-or-nothing
        (:meth:`_alloc`): on :class:`PagePoolExhausted` no slot state
        changed, and the engine fails ONLY that request typed."""
        if n_new <= 0:
            return
        last = int(self.lengths[slot]) + n_new - 1
        need = last // self.page_len + 1
        row = self.owned[slot]
        missing = need - len(row)
        if missing <= 0:
            return
        pids = self._alloc(missing)    # all-or-nothing; may raise
        for pid in pids:
            self.tables[slot, len(row)] = pid
            row.append(pid)

    def spec_verify(self, params, tokens: np.ndarray):
        """Score all rows' k+1 candidate tokens ((n_slots, k+1) int32)
        in one batched forward WITHOUT touching the pool — no donation:
        acceptance is decided on the host, then :meth:`spec_commit`
        writes the accepted prefix (so rejection at any point, page
        boundary included, never quantizes a partial page). Returns
        (logits (n_slots, k+1, vocab), sk, sv) with sk/sv per-layer
        exact-f32 candidate K/V scratch."""
        return self._verify_fn(params, self.state, upload(self.tables),
                               upload(self.lengths), jnp.asarray(tokens))

    def spec_commit(self, sk, sv, commit: np.ndarray) -> None:
        """Scatter each row's accepted scratch prefix (``commit``
        (n_slots,) int32, 0 = row not speculating) into its pages and
        advance the host lengths. In a quantized pool accepted
        positions land in the exact f32 tail buffers and a page
        quantizes exactly ONCE, when an accepted token completes it —
        rejected suffixes were never written anywhere, so the PR 16
        quantize-once discipline is preserved by construction."""
        self.state = self._commit_fn(
            self.state, upload(self.tables), upload(self.lengths),
            sk, sv, jnp.asarray(commit))
        self.lengths += np.asarray(commit, np.int32)

    def extract(self, slot: int, quantized: bool = False):
        """Host copies of ``slot``'s resident pages, in table order —
        the prefill side of the disaggregated KV-page handoff
        (``serve/disagg/``). Returns ``(length, ks, vs)`` where ks/vs
        are per-layer ``(P, Hkv, page_len, Dh)`` f32 numpy arrays, as
        each layer's store exports them (``nn/paged.py``): positions
        past ``length`` in the last page ZEROED, and in a quantized pool
        the full pages dequantized host-side and the partial last page
        read from the slot's exact f32 tail page, so the extracted tail
        carries ZERO quantization error."""
        row = self.owned[slot]
        length = int(self.lengths[slot])
        valid_last = length - (len(row) - 1) * self.page_len
        # gather ON DEVICE, then transfer: only the slot's pages cross
        # the host boundary, not the whole pool (which would scale each
        # handoff with pool size instead of prompt size)
        idx = jnp.asarray(np.asarray(row, np.int32))
        ks, vs = zip(*(st.export(idx, slot, valid_last, quantized)
                       for st in self.state))
        return length, list(ks), list(vs)

    def extract_quantized(self, slot: int):
        """Quantized-pool handoff WITHOUT the dequant→requant double
        hop: returns ``(length, kqs, vqs)`` where each per-layer entry
        is ``(q, scales)`` — ``q`` ``(P, Hkv, page_len, Dh)`` int8
        UNPACKED, ``scales`` ``(P, nb)`` f32 — exactly the pool's
        resident bits for full pages. The partial last page is
        quantized ONCE here, from the exact zero-padded f32 tail
        page, through the same wire block codec. A dequantizing round
        trip would reconstruct the same q codes, but its requantized
        scales pay a double rounding (one ulp of drift per hop) — this
        path ships the resident scales verbatim instead."""
        if self.quant_bits is None:
            raise ValueError("extract_quantized requires a quantized "
                             "pool (kv_dtype='q8'/'q4')")
        return self.extract(slot, quantized=True)

    def adopt(self, slot: int, length: int, ks: List, vs: List,
              quantized: bool = False) -> int:
        """Materialize a handed-off request's pages into THIS pool —
        the decode side of the disaggregated handoff. Pages come from
        the same allocation path admissions use (free list, then LRU
        eviction of refcount-zero indexed pages), so
        :class:`~..types.PagePoolExhausted` back-pressure is intact and
        nothing is changed on failure. Returns the page count adopted.
        Each layer's store installs its pages (``nn/paged.py``): a
        quantized one quantizes the full pages here (their ONE rounding
        — extract shipped exact values) and keeps the partial last page
        exact in the slot's tail page."""
        self.require("adopt")
        n = len(ks[0][0] if quantized else ks[0])
        pids = self._alloc(n)          # all-or-nothing; may raise
        self.tables[slot, :n] = pids
        self.tables[slot, n:] = 0
        self.owned[slot] = pids
        idx = jnp.asarray(np.asarray(pids, np.int32))
        valid_last = length - (n - 1) * self.page_len
        self.state = [st.adopt(ks[i], vs[i], idx, slot, valid_last,
                               quantized)
                      for i, st in enumerate(self.state)]
        self.lengths[slot] = length
        return n

    def adopt_quantized(self, slot: int, length: int, kqs, vqs) -> int:
        """Inverse of :meth:`extract_quantized`: install already-
        quantized ``(q, scales)`` pages straight into the pool — NO
        rounding happens here, the resident bits are exactly the
        sender's bits (the partial last page is additionally
        dequantized into the slot's tail page, losslessly)."""
        if self.quant_bits is None:
            raise ValueError("adopt_quantized requires a quantized "
                             "pool (kv_dtype='q8'/'q4')")
        return self.adopt(slot, length, kqs, vqs, quantized=True)

    def release(self, slot: int) -> None:
        """Drop the slot's references (retirement, failure, or engine
        drain): private pages go straight back to the free list, indexed
        pages stay resident for future prefix hits until LRU-evicted. A
        prompt still prefilling indexed nothing: its fresh pages go free
        and its hit pages back to the count they had before ``begin``."""
        for pid in self.owned[slot]:
            self.pool.decref(pid)
        self.owned[slot] = []
        self.prefilling.pop(slot, None)
        self.tables[slot, :] = 0
        self.lengths[slot] = 0

    # -- introspection -----------------------------------------------------

    def prefix_hit_rate(self) -> Optional[float]:
        """Cumulative share of prompt tokens served from resident pages
        (None before the first admission)."""
        if self.prompt_tokens_total == 0:
            return None
        return self.prefill_tokens_saved_total / self.prompt_tokens_total

    def kv_bits(self) -> int:
        """Resident bits per KV element: quant width, or the exact
        storage dtype's width in f32 mode."""
        return self.quant_bits or \
            jax.tree.leaves(self.state[0])[0].dtype.itemsize * 8

    def kv_pool_bytes(self) -> int:
        """Total resident KV footprint: everything the stores keep
        (pages, scales, tail pages, window layers' rings), all layers.
        Static for a given config — this is the denominator of the
        capacity-per-byte story."""
        return sum(a.nbytes for a in jax.tree.leaves(self.state))

    def kv_resident_bytes(self) -> Tuple[int, int]:
        """``(global, window)``: the bytes of the stores that keep pages
        by table, which grow with ``n_pages``, and of the window layers'
        rings, ``n_slots`` times a ring whatever ``max_len``. A linear
        layer's states and a sparse layer's compressed keys are neither
        (:meth:`mixer_stats` counts them)."""
        window = sum(self.state[i].resident_bytes()
                     for i in self.window_layers)
        slots = sum(self.mixer_bytes())
        return (sum(st.resident_bytes() for st in self.state) - window
                - slots, window)

    def mixer_bytes(self) -> Tuple[int, int]:
        """``(states, compressed keys)``: what the linear layers' states
        and the sparse layers' compressed keys take, ``n_slots`` times a
        slot's, whatever the contexts."""
        return (sum(self.state[i].resident_bytes()
                    for i in self.state_layers),
                sum(self.state[i].ck.nbytes for i in self.sparse_layers))

    def mixer_stats(self) -> Optional[Dict]:
        """A model of linear- and sparse-attention layers: what their
        stores keep a slot, how often a slot's state was zeroed, the
        compressed keys the sparse layers' prompt chunks scored (the
        windows each chunk's context had closed) of those the store keeps
        a slot (sums over chunks and sparse layers, counted on the host),
        and the blocks the sparse layers' decode steps chose of those they
        had resident (sums over active rows, sparse layers and KV heads:
        one device-to-host read, made here and nowhere else). None for
        any other model."""
        if not (self.state_layers or self.sparse_layers):
            return None
        states, keys = self.mixer_bytes()
        out = {"state_resident_bytes": states,
               "compressed_keys_resident_bytes": keys,
               "slots_state_reset": self.slots_state_reset,
               "state_layers": len(self.state_layers),
               "sparse_layers": len(self.sparse_layers)}
        if self.sel_counts is not None:
            out["sparse_prefill_windows_scored"] = \
                self.sparse_prefill_windows_scored
            out["sparse_prefill_windows_kept"] = \
                self.sparse_prefill_windows_kept
            c = [int(v) for v in np.asarray(self.sel_counts)]
            out["sparse_blocks_chosen"] = (c[0] << 20) + c[1]
            out["sparse_blocks_resident"] = (c[2] << 20) + c[3]
            out["sparse_decode_steps"] = c[4]
        return out

    def bytes_per_resident_token(self) -> float:
        """Pool bytes (pages + scales; tails are per-slot, not
        per-resident-page, and so is a window layer's ring) per token
        position the pool can hold. The serve_bench capacity arm gates
        on the f32/q8 ratio of this — a deterministic storage-layout
        fact, not a runtime sample."""
        return self.kv_resident_bytes()[0] \
            / float(self.n_pages * self.page_len)

    def context_stats(self) -> Tuple[int, float]:
        """``(longest, mean)`` context among the slots that hold one
        (0, 0.0 where none does): what a global layer's decode step reads
        follows these, a window layer's does not."""
        held = self.lengths[self.lengths > 0]
        if not held.size:
            return 0, 0.0
        return int(held.max()), float(held.mean())

    def moe_stats(self) -> Optional[Dict]:
        """The expert layers' counters over every decode step so far
        (one device-to-host read, made here and nowhere else), or None
        for a model without expert layers. The blocks of sorted pairs
        those steps worked on come from the same sums
        (``DroplessMoE.dispatch_blocks``)."""
        if self.moe_counts is None:
            return None
        routed, touched, fullest, steps = (
            int(v) for v in np.asarray(self.moe_counts))
        # a pass routes every slot's pairs through each expert layer
        ffn = next(blk.ffn for blk in self.model.blocks
                   if hasattr(getattr(blk, "ffn", None), "routed"))
        run, blocks = ffn.dispatch_blocks(
            self.n_slots * (self.gen_block or 1) * ffn.top_k, routed,
            calls=steps * self.moe_layers)
        return {"moe_tokens_routed": routed, "moe_experts_touched": touched,
                "moe_tokens_max_expert": fullest, "moe_decode_steps": steps,
                "moe_layers": self.moe_layers,
                "moe_kernel_matmuls": self.compiles.moe_kernel_matmuls,
                "moe_dispatch_blocks_run": int(run),
                "moe_dispatch_blocks": blocks}

    def page_stats(self) -> Dict:
        in_global, in_window = self.kv_resident_bytes()
        longest, mean = self.context_stats()
        return {"n_pages": self.n_pages,
                "page_len": self.page_len,
                "decode_attention_kernel_layers":
                    self.compiles.decode_kernel_layers,
                "kv_dtype": self.kv_dtype,
                "kv_bits": self.kv_bits(),
                "kv_pool_bytes": self.kv_pool_bytes(),
                "kv_resident_bytes_global": in_global,
                "kv_resident_bytes_window": in_window,
                "window_layers": len(self.window_layers),
                "context_tokens_max": longest,
                "context_tokens_mean": mean,
                "bytes_per_resident_token": self.bytes_per_resident_token(),
                "free_pages": self.pool.free_pages,
                "pages_in_use": self.pool.pages_in_use,
                "pool_occupancy": self.pool.occupancy(),
                "indexed_pages": len(self.index),
                "evictions": self.pool.evictions,
                "prefix_lookups": self.prefix_lookups,
                "prefix_hit_pages": self.prefix_hit_pages_total,
                "prefill_tokens_saved": self.prefill_tokens_saved_total,
                "prefix_hit_rate": self.prefix_hit_rate()}
