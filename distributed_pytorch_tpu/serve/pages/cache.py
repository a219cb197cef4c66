"""PagedSlotPool: the paged, prefix-shared drop-in for ``serve.cache.SlotPool``.

KV memory is a block pool — per layer ONE ``(n_pages, Hkv, page_len,
Dh)`` buffer for K and V — and each slot addresses its cache through a
page table row instead of owning a contiguous stripe. The BLOCK owns
that layout: the pool asks each block for its page arrays
(``blk.page_shapes(page_len)``, ``nn/paged.py``). Multi-head attention
answers with the K and V shapes above; latent attention (MLA,
``nn/latent.py``) with ONE array a layer, ``(n_pages, 1, page_len,
kv_rank + rope_dim)``, held in ``k_pages`` with ``v_pages`` empty. The
allocator, the tables and the prefix index count pages and never look
inside one, so they serve both unchanged; what copies page CONTENTS
(quantized pages, the disaggregated hand-off) refuses latent blocks by
name. Three things fall out of the indirection:

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

The one-program discipline of ``SlotPool`` is preserved exactly: page
tables, lengths, offsets and true lengths are all TRACED, so the whole
serving life is still ONE jitted decode program
(``models.generate.decode_step_slots_paged``) plus one jitted admit per
tail-length bucket (``prefill_partial_paged``), counted by the same
``CompileCounts`` the tests assert on.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...comm import wire
from ...models.generate import (decode_step_slots_paged,
                                prefill_partial_paged, refuse_latent,
                                spec_commit_slots_paged,
                                spec_verify_slots_paged)
from ...ops.decode_attention import kernel_traces
from ...runtime import faults
from ..cache import CompileCounts, greedy_tokens, named_program
from ..types import AdmissionRejected
from .pool import PagePool
from .prefix import PrefixIndex
from .quant import (dequantize_page_np, num_page_blocks, pack_pages_np,
                    page_elems, quantize_page_np, resolve_kv_bits,
                    unpack_pages_np)


class PagedSlotPool:
    """Owns the page-pool arrays, the page tables, and the jitted paged
    programs; all allocation/refcount/eviction policy is host-side.

    ``kv_dtype`` selects the RESIDENT storage format (docs/serving.md
    "Quantized resident pool"): ``"f32"`` (default) keeps exact pages
    in the model dtype — the bit-exact contract, traced programs
    unchanged; ``"q8"``/``"q4"`` store block-quantized int pages plus
    per-page-per-block f32 scales (the ``comm/wire.py`` block format
    the handoff frame uses), with per-slot f32 tail buffers holding
    each slot's partial tail page so every element is quantized exactly
    ONCE, on page completion, inside the same one decode program."""

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
        dh = model.dim // model.n_heads
        h_kv = getattr(model, "n_kv_heads", model.n_heads)
        n_layers = model.n_layers
        # the block owns its page layout: (K, V) shapes, or one latent
        layouts = [blk.page_shapes(page_len) for blk in model.blocks]
        if len({len(shapes) for shapes in layouts}) > 1:
            raise ValueError("blocks disagree on how many page arrays a "
                             "layer keeps; the pool needs one layout")
        self.latent = len(layouts[0]) == 1
        self._page_shape = layouts[0][0]
        if self.quant_bits is not None:
            refuse_latent(model, "quantized pages (kv_dtype q8/q4)")
        # what an expert layer counts in a decode step, summed on the
        # device and read only by stats(): tokens routed, experts with a
        # token, the fullest expert's tokens, decode steps
        self.moe_layers = sum(hasattr(getattr(blk, "ffn", None), "routed")
                              for blk in model.blocks)
        self.moe_counts = jnp.zeros((4,), jnp.int32) \
            if self.moe_layers else None
        if self.quant_bits is None:
            self.k_pages: List[jax.Array] = [
                jnp.zeros((n_pages,) + shapes[0], model.dtype)
                for shapes in layouts]
            self.v_pages: List[jax.Array] = [
                jnp.zeros((n_pages,) + shapes[1], model.dtype)
                for shapes in layouts if len(shapes) > 1]
            self.k_scales = self.v_scales = None
            self.k_tail = self.v_tail = None
        else:
            if self.quant_bits == 4 and dh % 2:
                raise ValueError(
                    f"kv_dtype='q4' packs two nibbles per byte along "
                    f"the head dim, which must be even (got Dh={dh})")
            store = ((n_pages, h_kv, page_len, dh // 2)
                     if self.quant_bits == 4
                     else (n_pages, h_kv, page_len, dh))
            sdt = jnp.uint8 if self.quant_bits == 4 else jnp.int8
            nb = num_page_blocks(h_kv, page_len, dh)
            self.page_blocks = nb
            self.k_pages = [jnp.zeros(store, sdt) for _ in range(n_layers)]
            self.v_pages = [jnp.zeros(store, sdt) for _ in range(n_layers)]
            # scale 1 is the codec's all-zero-block snap — a never-
            # written page dequantizes to exact zeros
            self.k_scales = [jnp.ones((n_pages, nb), jnp.float32)
                             for _ in range(n_layers)]
            self.v_scales = [jnp.ones((n_pages, nb), jnp.float32)
                             for _ in range(n_layers)]
            tshape = (n_slots, h_kv, page_len, dh)
            self.k_tail = [jnp.zeros(tshape, jnp.float32)
                           for _ in range(n_layers)]
            self.v_tail = [jnp.zeros(tshape, jnp.float32)
                           for _ in range(n_layers)]
        # host-side state: page tables / lengths mirror the traced args
        # (tiny int32 uploads per call), policy state never leaves host.
        # They are uploaded with jnp.array (a copy), never jnp.asarray:
        # on the CPU backend asarray may alias the numpy buffer, dispatch
        # is asynchronous, and the host advances these arrays right after
        # a call — the program would read the NEXT step's lengths
        self.tables = np.zeros((n_slots, self.pages_per_slot), np.int32)
        self.lengths = np.zeros((n_slots,), np.int32)
        self.owned: List[List[int]] = [[] for _ in range(n_slots)]
        self.pool = PagePool(n_pages, page_len)
        self.index = PrefixIndex(page_len)
        self.compiles = CompileCounts()
        self._admit_fns: Dict[int, callable] = {}
        if self.moe_layers:
            self._decode_fn = jax.jit(self._decode_moe,
                                      donate_argnums=(1, 2))
        elif self.quant_bits is None:
            self._decode_fn = jax.jit(self._decode, donate_argnums=(1, 2))
        else:
            self._decode_fn = jax.jit(self._decode_q,
                                      donate_argnums=(1, 2, 3, 4, 5, 6))
        # cumulative sharing counters (engine metrics / bench)
        self.prefix_lookups = 0
        self.prefix_hit_pages_total = 0
        self.prefill_tokens_saved_total = 0
        self.prompt_tokens_total = 0

    # -- jitted programs ---------------------------------------------------

    def _step(self, params, k_pages, v_pages, tables, lengths, tokens,
              active, **kw):
        """The decode step inside each of the three decode programs,
        counted where it is traced: the compile, and how many of its
        layers' attention took the Mosaic kernel."""
        self.compiles.decode += 1          # trace-time only
        before = kernel_traces()
        out = decode_step_slots_paged(
            self.model, params, k_pages, v_pages, tables, lengths, tokens,
            active, page_len=self.page_len, **kw)
        self.compiles.decode_kernel_layers = kernel_traces() - before
        return out

    def _decode(self, params, k_pages, v_pages, tables, lengths, tokens,
                active):
        logits, *pool = self._step(params, k_pages, v_pages, tables,
                                   lengths, tokens, active)
        return (greedy_tokens(logits), logits, *pool)

    def _decode_moe(self, params, k_pages, v_pages, counts, tables,
                    lengths, tokens, active):
        """The decode program of a model with expert layers: the same
        step, and the layers' counts added to ``counts`` on the device."""
        per_layer = []
        logits, *pool = self._step(params, k_pages, v_pages, tables,
                                   lengths, tokens, active,
                                   moe_stats=per_layer)
        c = jnp.stack(per_layer)                           # (layers, 3)
        counts = jnp.stack([counts[0] + jnp.sum(c[:, 0]),
                            counts[1] + jnp.sum(c[:, 1]),
                            jnp.maximum(counts[2], jnp.max(c[:, 2])),
                            counts[3] + 1])
        return (greedy_tokens(logits), logits, *pool, counts)

    def _decode_q(self, params, k_pages, v_pages, k_scales, v_scales,
                  k_tail, v_tail, tables, lengths, tokens, active):
        logits, *pool = self._step(
            params, k_pages, v_pages, tables, lengths, tokens, active,
            kv_bits=self.quant_bits, k_scales=k_scales, v_scales=v_scales,
            k_tail=k_tail, v_tail=v_tail)
        return (greedy_tokens(logits), logits, *pool)

    def _verify(self, params, k_pages, v_pages, tables, lengths,
                tokens):
        # trace-time only; one compile per draft-length bucket (the
        # candidate width s = k+1 is baked into the tokens shape)
        self.compiles.bump_verify(tokens.shape[1])
        return spec_verify_slots_paged(self.model, params, k_pages,
                                       v_pages, tables, lengths, tokens,
                                       page_len=self.page_len)

    def _verify_q(self, params, k_pages, v_pages, k_scales, v_scales,
                  k_tail, v_tail, tables, lengths, tokens):
        self.compiles.bump_verify(tokens.shape[1])  # trace-time only
        return spec_verify_slots_paged(self.model, params, k_pages,
                                       v_pages, tables, lengths, tokens,
                                       page_len=self.page_len,
                                       kv_bits=self.quant_bits,
                                       k_scales=k_scales,
                                       v_scales=v_scales,
                                       k_tail=k_tail, v_tail=v_tail)

    def _commit(self, k_pages, v_pages, tables, lengths, sk, sv,
                commit):
        self.compiles.bump_commit(sk[0].shape[2])   # trace-time only
        return spec_commit_slots_paged(k_pages, v_pages, tables,
                                       lengths, sk, sv, commit,
                                       page_len=self.page_len)

    def _commit_q(self, k_pages, v_pages, k_scales, v_scales, k_tail,
                  v_tail, tables, lengths, sk, sv, commit):
        self.compiles.bump_commit(sk[0].shape[2])   # trace-time only
        return spec_commit_slots_paged(k_pages, v_pages, tables,
                                       lengths, sk, sv, commit,
                                       page_len=self.page_len,
                                       kv_bits=self.quant_bits,
                                       k_scales=k_scales,
                                       v_scales=v_scales,
                                       k_tail=k_tail, v_tail=v_tail)

    def _admit(self, params, k_pages, v_pages, table_row, tokens,
               offset, true_len, *, bucket: int):
        self.compiles.bump_prefill(bucket)  # trace-time only
        return prefill_partial_paged(self.model, params, k_pages,
                                     v_pages, table_row, tokens, offset,
                                     true_len, page_len=self.page_len)

    def _admit_q(self, params, k_pages, v_pages, k_scales, v_scales,
                 k_tail, v_tail, table_row, tokens, offset, true_len,
                 slot, *, bucket: int):
        self.compiles.bump_prefill(bucket)  # trace-time only
        return prefill_partial_paged(self.model, params, k_pages,
                                     v_pages, table_row, tokens, offset,
                                     true_len, page_len=self.page_len,
                                     kv_bits=self.quant_bits,
                                     k_scales=k_scales,
                                     v_scales=v_scales, k_tail=k_tail,
                                     v_tail=v_tail, slot=slot)

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

    def admit(self, params, prompt: np.ndarray, slot: int,
              buckets: Tuple[int, ...]):
        """Admit ``prompt`` ((S,) np int32) into ``slot``: radix prefix
        lookup → refcount the matched full pages → allocate + prefill
        only the tail → index the prompt's full pages for future
        admissions. Returns ``(last-position logits (1, vocab), n_hit
        pages, offset tokens)``. Raises :class:`PagePoolExhausted`
        (pool-attributed, no slot state changed) when the tail cannot
        be allocated, and a typed :class:`~..types.AdmissionRejected`
        (``reason="tail_too_long"``) — BEFORE any page is refcounted
        or allocated — when the tail exceeds every prefill bucket."""
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
        offset = n_hit * L
        tail_len = s - offset
        n_fresh = -(-s // L) - n_hit
        # bucket selection BEFORE any state change: a tail longer than
        # every bucket must reject typed and attributable, not escape
        # as a bare StopIteration with pages already refcounted
        bucket = None
        for b in buckets:
            if b >= tail_len:
                bucket = b
                break
        if bucket is None:
            raise AdmissionRejected(
                f"prompt tail ({tail_len} token(s) after {n_hit} shared "
                f"page(s)) exceeds the largest prefill bucket "
                f"({max(buckets)})", reason="tail_too_long")
        # incref matched pages BEFORE allocating: eviction only ever
        # considers refcount-zero pages, so a matched page cannot be
        # stolen to satisfy this very request's tail
        for pid in hits:
            self.pool.incref(pid)
        try:
            fresh = self._alloc(n_fresh)
        except Exception:
            for pid in hits:
                self.pool.decref(pid)
            raise
        row = hits + fresh
        self.tables[slot, :len(row)] = row
        self.tables[slot, len(row):] = 0
        self.owned[slot] = row
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :tail_len] = prompt[offset:]
        fn = self._admit_fns.get(bucket)
        if fn is None:
            name = f"prefill_b{bucket}"
            if self.quant_bits is None:
                fn = jax.jit(named_program(self._admit, name, bucket=bucket),
                             donate_argnums=(1, 2))
            else:
                fn = jax.jit(named_program(self._admit_q, name,
                                           bucket=bucket),
                             donate_argnums=(1, 2, 3, 4, 5, 6))
            self._admit_fns[bucket] = fn
        if self.quant_bits is None:
            logits, self.k_pages, self.v_pages = fn(
                params, self.k_pages, self.v_pages,
                jnp.array(self.tables[slot]), jnp.asarray(padded),
                jnp.asarray(offset, jnp.int32),
                jnp.asarray(tail_len, jnp.int32))
        else:
            (logits, self.k_pages, self.v_pages, self.k_scales,
             self.v_scales, self.k_tail, self.v_tail) = fn(
                params, self.k_pages, self.v_pages, self.k_scales,
                self.v_scales, self.k_tail, self.v_tail,
                jnp.array(self.tables[slot]), jnp.asarray(padded),
                jnp.asarray(offset, jnp.int32),
                jnp.asarray(tail_len, jnp.int32),
                jnp.asarray(slot, jnp.int32))
        self.lengths[slot] = s
        if self.prefix_share:
            self.index.insert(prompt, s // L, row, self.pool)
        self.prefix_hit_pages_total += n_hit
        self.prefill_tokens_saved_total += offset
        self.prompt_tokens_total += s
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

    def decode(self, params, tokens: np.ndarray, active: np.ndarray):
        """Advance every slot one position through the ONE jitted paged
        decode program (inactive rows neither write the pool nor
        advance). Returns each slot's greedy token (n_slots,) int32 and
        the (n_slots, vocab) logits, both left on the device."""
        if self.moe_layers:
            (out, logits, self.k_pages, self.v_pages,
             self.moe_counts) = self._decode_fn(
                params, self.k_pages, self.v_pages, self.moe_counts,
                jnp.array(self.tables), jnp.array(self.lengths),
                jnp.asarray(tokens), jnp.asarray(active))
        elif self.quant_bits is None:
            out, logits, self.k_pages, self.v_pages = self._decode_fn(
                params, self.k_pages, self.v_pages,
                jnp.array(self.tables), jnp.array(self.lengths),
                jnp.asarray(tokens), jnp.asarray(active))
        else:
            (out, logits, self.k_pages, self.v_pages, self.k_scales,
             self.v_scales, self.k_tail, self.v_tail) = self._decode_fn(
                params, self.k_pages, self.v_pages, self.k_scales,
                self.v_scales, self.k_tail, self.v_tail,
                jnp.array(self.tables), jnp.array(self.lengths),
                jnp.asarray(tokens), jnp.asarray(active))
        self.lengths[np.asarray(active)] += 1
        return out, logits

    def ensure_spec_capacity(self, slot: int, n_new: int) -> None:
        """Grow ``slot``'s page table so the next ``n_new`` committed
        positions all have pages — the multi-token twin of
        :meth:`ensure_decode_capacity`, called AFTER acceptance is
        known so only accepted tokens ever demand pages. All-or-nothing
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
        fn = getattr(self, "_verify_fn", None)
        if fn is None:
            # NOTE deliberately NOT donated (the pool survives verify)
            fn = self._verify_fn = jax.jit(
                self._verify if self.quant_bits is None
                else self._verify_q)
        if self.quant_bits is None:
            return fn(params, self.k_pages, self.v_pages,
                      jnp.array(self.tables), jnp.array(self.lengths),
                      jnp.asarray(tokens))
        return fn(params, self.k_pages, self.v_pages, self.k_scales,
                  self.v_scales, self.k_tail, self.v_tail,
                  jnp.array(self.tables), jnp.array(self.lengths),
                  jnp.asarray(tokens))

    def spec_commit(self, sk, sv, commit: np.ndarray) -> None:
        """Scatter each row's accepted scratch prefix (``commit``
        (n_slots,) int32, 0 = row not speculating) into its pages and
        advance the host lengths. In a quantized pool accepted
        positions land in the exact f32 tail buffers and a page
        quantizes exactly ONCE, when an accepted token completes it —
        rejected suffixes were never written anywhere, so the PR 16
        quantize-once discipline is preserved by construction."""
        fn = getattr(self, "_commit_fn", None)
        if fn is None:
            if self.quant_bits is None:
                fn = jax.jit(self._commit, donate_argnums=(0, 1))
            else:
                fn = jax.jit(self._commit_q,
                             donate_argnums=(0, 1, 2, 3, 4, 5))
            self._commit_fn = fn
        if self.quant_bits is None:
            self.k_pages, self.v_pages = fn(
                self.k_pages, self.v_pages, jnp.array(self.tables),
                jnp.array(self.lengths), sk, sv, jnp.asarray(commit))
        else:
            (self.k_pages, self.v_pages, self.k_scales, self.v_scales,
             self.k_tail, self.v_tail) = fn(
                self.k_pages, self.v_pages, self.k_scales,
                self.v_scales, self.k_tail, self.v_tail,
                jnp.array(self.tables), jnp.array(self.lengths),
                sk, sv, jnp.asarray(commit))
        self.lengths += np.asarray(commit, np.int32)

    def extract(self, slot: int) -> Tuple[int, List[np.ndarray],
                                          List[np.ndarray]]:
        """Host copies of ``slot``'s resident pages, in table order —
        the prefill side of the disaggregated KV-page handoff
        (``serve/disagg/``). Returns ``(length, ks, vs)`` where ks/vs
        are per-layer ``(P, Hkv, page_len, Dh)`` f32 numpy arrays.
        Positions past ``length`` in the last page are ZEROED: a reused
        pool page may carry a previous occupant's stale K/V there, and
        while the decode mask would never attend it, shipping garbage
        would poison the quantized frame's per-page scales.

        In a quantized pool the full pages are dequantized host-side
        and the partial last page is read from the slot's exact f32
        tail buffer (the pool row for it was never written), so the
        extracted tail carries ZERO quantization error."""
        refuse_latent(self.model, "the disaggregated hand-off (serve/disagg)")
        row = self.owned[slot]
        length = int(self.lengths[slot])
        valid_last = length - (len(row) - 1) * self.page_len
        # gather ON DEVICE, then transfer: only the slot's pages cross
        # the host boundary, not the whole pool (which would scale each
        # handoff with pool size instead of prompt size)
        idx = jnp.asarray(np.asarray(row, np.int32))
        ks, vs = [], []
        if self.quant_bits is not None:
            for i in range(self.model.n_layers):
                kq = np.array(self.k_pages[i][idx])
                vq = np.array(self.v_pages[i][idx])
                if self.quant_bits == 4:
                    kq = unpack_pages_np(kq)
                    vq = unpack_pages_np(vq)
                ksc = np.array(self.k_scales[i][idx], np.float32)
                vsc = np.array(self.v_scales[i][idx], np.float32)
                k = np.stack([dequantize_page_np(kq[p], ksc[p])
                              for p in range(len(row))])
                v = np.stack([dequantize_page_np(vq[p], vsc[p])
                              for p in range(len(row))])
                if valid_last < self.page_len:
                    # the partial page's pool row is unwritten — its
                    # exact value lives in the slot's f32 tail buffer
                    kt = np.array(self.k_tail[i][slot], np.float32)
                    vt = np.array(self.v_tail[i][slot], np.float32)
                    kt[:, valid_last:, :] = 0.0
                    vt[:, valid_last:, :] = 0.0
                    k[-1] = kt
                    v[-1] = vt
                ks.append(k)
                vs.append(v)
            return length, ks, vs
        for i in range(self.model.n_layers):
            # np.array (not asarray): the zero-padding below mutates,
            # and a CPU-backend transfer can alias read-only memory
            k = np.array(self.k_pages[i][idx], np.float32)
            v = np.array(self.v_pages[i][idx], np.float32)
            if valid_last < self.page_len:
                k[-1, :, valid_last:, :] = 0.0
                v[-1, :, valid_last:, :] = 0.0
            ks.append(k)
            vs.append(v)
        return length, ks, vs

    def extract_quantized(self, slot: int):
        """Quantized-pool handoff WITHOUT the dequant→requant double
        hop: returns ``(length, kqs, vqs)`` where each per-layer entry
        is ``(q, scales)`` — ``q`` ``(P, Hkv, page_len, Dh)`` int8
        UNPACKED, ``scales`` ``(P, nb)`` f32 — exactly the pool's
        resident bits for full pages. The partial last page is
        quantized ONCE here, from the exact zero-padded f32 tail
        buffer, through the same wire block codec. A dequantizing round
        trip would reconstruct the same q codes, but its requantized
        scales pay a double rounding (one ulp of drift per hop) — this
        path ships the resident scales verbatim instead."""
        if self.quant_bits is None:
            raise ValueError("extract_quantized requires a quantized "
                             "pool (kv_dtype='q8'/'q4')")
        row = self.owned[slot]
        length = int(self.lengths[slot])
        valid_last = length - (len(row) - 1) * self.page_len
        idx = jnp.asarray(np.asarray(row, np.int32))
        kqs, vqs = [], []
        for i in range(self.model.n_layers):
            kq = np.array(self.k_pages[i][idx])
            vq = np.array(self.v_pages[i][idx])
            if self.quant_bits == 4:
                kq = unpack_pages_np(kq)
                vq = unpack_pages_np(vq)
            kq = np.ascontiguousarray(kq, np.int8)
            vq = np.ascontiguousarray(vq, np.int8)
            ksc = np.array(self.k_scales[i][idx], np.float32)
            vsc = np.array(self.v_scales[i][idx], np.float32)
            if valid_last < self.page_len:
                kt = np.array(self.k_tail[i][slot], np.float32)
                vt = np.array(self.v_tail[i][slot], np.float32)
                kt[:, valid_last:, :] = 0.0
                vt[:, valid_last:, :] = 0.0
                kq[-1], ksc[-1] = quantize_page_np(kt, self.quant_bits)
                vq[-1], vsc[-1] = quantize_page_np(vt, self.quant_bits)
            kqs.append((kq, ksc))
            vqs.append((vq, vsc))
        return length, kqs, vqs

    def adopt(self, slot: int, length: int, ks: List[np.ndarray],
              vs: List[np.ndarray]) -> int:
        """Materialize a handed-off request's pages into THIS pool —
        the decode side of the disaggregated handoff. Pages come from
        the same allocation path admissions use (free list, then LRU
        eviction of refcount-zero indexed pages), so
        :class:`~..types.PagePoolExhausted` back-pressure is intact and
        nothing is changed on failure. Returns the page count adopted.

        In a quantized pool: full pages are quantized here (their ONE
        rounding — extract shipped exact values), the partial last page
        goes into the slot's exact f32 tail buffer, and the tail buffer
        is defensively zeroed on page-aligned lengths so a previous
        occupant's stale tail can never alias into the new request."""
        refuse_latent(self.model, "the disaggregated hand-off (serve/disagg)")
        n = int(ks[0].shape[0])
        pids = self._alloc(n)          # all-or-nothing; may raise
        self.tables[slot, :n] = pids
        self.tables[slot, n:] = 0
        self.owned[slot] = pids
        idx = jnp.asarray(np.asarray(pids, np.int32))
        if self.quant_bits is not None:
            L = self.page_len
            nfull = length // L
            valid_last = length - (n - 1) * L
            for i in range(self.model.n_layers):
                qk = np.zeros((n,) + self._page_shape, np.int8)
                qv = np.zeros((n,) + self._page_shape, np.int8)
                sk = np.ones((n, self.page_blocks), np.float32)
                sv = np.ones((n, self.page_blocks), np.float32)
                for p in range(nfull):
                    qk[p], sk[p] = quantize_page_np(ks[i][p],
                                                    self.quant_bits)
                    qv[p], sv[p] = quantize_page_np(vs[i][p],
                                                    self.quant_bits)
                if self.quant_bits == 4:
                    qk = pack_pages_np(qk)
                    qv = pack_pages_np(qv)
                self.k_pages[i] = self.k_pages[i].at[idx].set(
                    jnp.asarray(qk))
                self.v_pages[i] = self.v_pages[i].at[idx].set(
                    jnp.asarray(qv))
                self.k_scales[i] = self.k_scales[i].at[idx].set(
                    jnp.asarray(sk))
                self.v_scales[i] = self.v_scales[i].at[idx].set(
                    jnp.asarray(sv))
                if valid_last < L:
                    kt = np.array(ks[i][-1], np.float32)
                    vt = np.array(vs[i][-1], np.float32)
                    kt[:, valid_last:, :] = 0.0
                    vt[:, valid_last:, :] = 0.0
                else:
                    kt = np.zeros(self._page_shape, np.float32)
                    vt = np.zeros(self._page_shape, np.float32)
                self.k_tail[i] = self.k_tail[i].at[slot].set(
                    jnp.asarray(kt))
                self.v_tail[i] = self.v_tail[i].at[slot].set(
                    jnp.asarray(vt))
            self.lengths[slot] = length
            return n
        for i in range(self.model.n_layers):
            self.k_pages[i] = self.k_pages[i].at[idx].set(
                jnp.asarray(ks[i], self.k_pages[i].dtype))
            self.v_pages[i] = self.v_pages[i].at[idx].set(
                jnp.asarray(vs[i], self.v_pages[i].dtype))
        self.lengths[slot] = length
        return n

    def adopt_quantized(self, slot: int, length: int, kqs, vqs) -> int:
        """Inverse of :meth:`extract_quantized`: install already-
        quantized ``(q, scales)`` pages straight into the pool — NO
        rounding happens here, the resident bits are exactly the
        sender's bits. The partial last page is additionally
        dequantized into the slot's tail buffer (lossless given
        ``q``/``scales``) so decode's in-kernel tail overlay and the
        completion re-quantization see the same values the sender's
        pool held."""
        if self.quant_bits is None:
            raise ValueError("adopt_quantized requires a quantized "
                             "pool (kv_dtype='q8'/'q4')")
        n = int(kqs[0][0].shape[0])
        pids = self._alloc(n)          # all-or-nothing; may raise
        self.tables[slot, :n] = pids
        self.tables[slot, n:] = 0
        self.owned[slot] = pids
        idx = jnp.asarray(np.asarray(pids, np.int32))
        L = self.page_len
        valid_last = length - (n - 1) * L
        for i in range(self.model.n_layers):
            kq, ksc = kqs[i]
            vq, vsc = vqs[i]
            kq = np.ascontiguousarray(kq, np.int8)
            vq = np.ascontiguousarray(vq, np.int8)
            sk = pack_pages_np(kq) if self.quant_bits == 4 else kq
            sv = pack_pages_np(vq) if self.quant_bits == 4 else vq
            self.k_pages[i] = self.k_pages[i].at[idx].set(jnp.asarray(sk))
            self.v_pages[i] = self.v_pages[i].at[idx].set(jnp.asarray(sv))
            self.k_scales[i] = self.k_scales[i].at[idx].set(
                jnp.asarray(ksc, jnp.float32))
            self.v_scales[i] = self.v_scales[i].at[idx].set(
                jnp.asarray(vsc, jnp.float32))
            if valid_last < L:
                kt = dequantize_page_np(kq[-1], np.asarray(ksc[-1]))
                vt = dequantize_page_np(vq[-1], np.asarray(vsc[-1]))
                kt[:, valid_last:, :] = 0.0
                vt[:, valid_last:, :] = 0.0
            else:
                kt = np.zeros(self._page_shape, np.float32)
                vt = np.zeros(self._page_shape, np.float32)
            self.k_tail[i] = self.k_tail[i].at[slot].set(jnp.asarray(kt))
            self.v_tail[i] = self.v_tail[i].at[slot].set(jnp.asarray(vt))
        self.lengths[slot] = length
        return n

    def release(self, slot: int) -> None:
        """Drop the slot's references (retirement, failure, or engine
        drain): private pages go straight back to the free list, indexed
        pages stay resident for future prefix hits until LRU-evicted."""
        for pid in self.owned[slot]:
            self.pool.decref(pid)
        self.owned[slot] = []
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
        if self.quant_bits is not None:
            return self.quant_bits
        return self.k_pages[0].dtype.itemsize * 8

    def kv_pool_bytes(self) -> int:
        """Total resident KV footprint: pages + scales + tail buffers,
        K and V, all layers. Static for a given config — this is the
        denominator of the capacity-per-byte story."""
        total = sum(a.nbytes for a in self.k_pages)
        total += sum(a.nbytes for a in self.v_pages)
        if self.quant_bits is not None:
            total += sum(a.nbytes for a in self.k_scales)
            total += sum(a.nbytes for a in self.v_scales)
            total += sum(a.nbytes for a in self.k_tail)
            total += sum(a.nbytes for a in self.v_tail)
        return total

    def bytes_per_resident_token(self) -> float:
        """Pool bytes (pages + scales; tails are per-slot, not
        per-resident-page) per token position the pool can hold. The
        serve_bench capacity arm gates on the f32/q8 ratio of this —
        a deterministic storage-layout fact, not a runtime sample."""
        total = sum(a.nbytes for a in self.k_pages)
        total += sum(a.nbytes for a in self.v_pages)
        if self.quant_bits is not None:
            total += sum(a.nbytes for a in self.k_scales)
            total += sum(a.nbytes for a in self.v_scales)
        return total / float(self.n_pages * self.page_len)

    def moe_stats(self) -> Optional[Dict]:
        """The expert layers' counters over every decode step so far
        (one device-to-host read, made here and nowhere else), or None
        for a model without expert layers."""
        if self.moe_counts is None:
            return None
        routed, touched, fullest, steps = (
            int(v) for v in np.asarray(self.moe_counts))
        return {"moe_tokens_routed": routed, "moe_experts_touched": touched,
                "moe_tokens_max_expert": fullest, "moe_decode_steps": steps,
                "moe_layers": self.moe_layers}

    def page_stats(self) -> Dict:
        return {"n_pages": self.n_pages,
                "page_len": self.page_len,
                "decode_attention_kernel_layers":
                    self.compiles.decode_kernel_layers,
                "kv_dtype": self.kv_dtype,
                "kv_bits": self.kv_bits(),
                "kv_pool_bytes": self.kv_pool_bytes(),
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
