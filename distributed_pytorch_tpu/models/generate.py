"""Autoregressive text generation with a KV cache — the inference path.

The reference has no inference story at all (its workload is a training
loop over an MLP, reference ``min_DDP.py``); a complete LM framework needs
one, and on TPU it must be a *compiled* loop: the whole
prefill-then-decode pipeline here is two XLA programs (one prefill, one
``lax.scan`` over decode steps), with the KV cache as a fixed-shape
carry — no per-token host round trips, no dynamic shapes.

Design notes (TPU-first):
- The cache is preallocated at ``max_len`` per layer ((B, Hkv, max, Dh)
  for K and V — Hkv = ``model.n_kv_heads``, so GQA shrinks the cache by
  the group factor); each step writes one slot with
  ``dynamic_update_slice`` and attends over the full buffer under a
  position mask. Static shapes keep XLA happy; the masked tail costs
  FLOPs but no recompilation.
- Decode attention is a (B, Hkv, g, 1, max) x (B, Hkv, max, Dh) grouped
  matmul pair — bandwidth-bound as always for single-token decoding (GQA
  cuts exactly that cache bandwidth); the cache layout keeps the
  contraction on the MXU's fast axis.
- Sampling (greedy / temperature / top-k / nucleus top-p) happens
  on-device inside the scan; the host sees only the final (B, steps)
  token block.

Works on the same ``TransformerLM`` params used for training (reads the
block submodules directly; no weight conversion).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn.attention import block_causal_mask, dense_attention
from ..nn.paged import (BlockCtx, BlockGenerationUnsupported,  # noqa: F401
                        DecodeCtx, LatentPagesUnsupported,
                        MixedStoresUnsupported, MixerStoresUnsupported,
                        PrefillCtx, VerifyCtx, block_unsupported,
                        mixed_unsupported, mixers_unsupported, table_pages)
from ..ops.decode_attention import (blockwise_decode_attention,
                                    dense_decode_attention)
from .transformer import TransformerLM

Params = Dict[str, Any]


class KVCache(NamedTuple):
    k: Any        # list-like pytree of (B, Hkv, max_len, Dh) per layer
    v: Any        # (Hkv = model.n_kv_heads: GQA shrinks the cache)
    length: jnp.ndarray   # () int32 — number of valid positions


# qkv projection / output projection / MLP all go through the block's own
# methods (nn/attention.py), so the fused-qkv layout and MLP math have one
# source of truth shared with training.


def init_cache(model: TransformerLM, batch: int, max_len: int,
               dtype=None) -> KVCache:
    dtype = dtype or model.dtype
    dh = model.dim // model.n_heads
    h_kv = getattr(model, "n_kv_heads", model.n_heads)
    shape = (batch, h_kv, max_len, dh)
    zeros = lambda: [jnp.zeros(shape, dtype) for _ in range(model.n_layers)]
    return KVCache(k=zeros(), v=zeros(), length=jnp.zeros((), jnp.int32))


def prefill(model: TransformerLM, params: Params, tokens,
            max_len: int,
            window: Optional[int] = None) -> Tuple[jnp.ndarray, KVCache]:
    """Run the prompt through the model once, filling the cache.

    tokens: (B, S) int32. Returns (last-position logits (B, vocab),
    cache with ``length = S``). With ``window`` the cache is a ROLLING
    buffer of ``window`` slots — position p lives at slot ``p % W`` —
    holding the last W prompt positions; attention inside the prefill
    already runs the model's own (windowed) attn_fn, so only the cache
    layout changes."""
    b, s = tokens.shape
    if s > max_len:
        raise ValueError(f"prompt length {s} exceeds max_len {max_len}")
    w = window
    cache = init_cache(model, b, w if w is not None else max_len)
    x = model.tok.apply(params["tok"], tokens)
    positions = jnp.arange(s)
    if getattr(model, "pos", None) is not None:
        x = x + model.pos.apply(params["pos"], positions)
    ks, vs = [], []
    for i, blk in enumerate(model.blocks):
        with jax.named_scope("blocks"):
            p = params["blocks"][i]
            hq, hk, hv = blk.attn.project_qkv(p["attn"],
                                              blk.ln1.apply(p["ln1"], x))
            # rope rotates BEFORE caching: the cache holds post-rotation keys
            hq, hk = blk.attn.maybe_rope(hq, hk, positions)
            o = blk.attn.attn_fn(hq, hk, hv, causal=True)
            x = x + blk.attn.project_out(p["attn"], o)
            x = x + blk.mlp(p, x)
            hk = hk.astype(cache.k[i].dtype)
            hv = hv.astype(cache.v[i].dtype)
            if w is not None:
                # keep the LAST min(s, w) positions, laid out so position p
                # sits at slot p % w (roll of the contiguous tail)
                keep = min(s, w)
                hk, hv = hk[:, :, -keep:], hv[:, :, -keep:]
                shift = (s - keep) % w
                ks.append(jnp.roll(_pad_to(hk, w), shift, axis=2))
                vs.append(jnp.roll(_pad_to(hv, w), shift, axis=2))
            else:
                ks.append(jax.lax.dynamic_update_slice(
                    cache.k[i], hk, (0, 0, 0, 0)))
                vs.append(jax.lax.dynamic_update_slice(
                    cache.v[i], hv, (0, 0, 0, 0)))
    x = model.ln_f.apply(params["ln_f"], x[:, -1:])
    logits = model.project_vocab(params, x)[:, 0]
    return logits, KVCache(k=ks, v=vs,
                           length=jnp.asarray(s, jnp.int32))


def _pad_to(x, w: int):
    """Zero-pad the cache axis (2) up to ``w`` slots (prompt < window)."""
    pad = w - x.shape[2]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


def decode_step(model: TransformerLM, params: Params, cache: KVCache,
                token,
                window: Optional[int] = None,
                blockwise: bool = True) -> Tuple[jnp.ndarray,
                                                 KVCache]:
    """One cached decode step. token: (B,) int32 at position
    ``cache.length``. Returns (logits (B, vocab), advanced cache).

    Attention over the cache runs page-blockwise by default
    (:func:`..ops.decode_attention.blockwise_decode_attention`): the
    online-softmax block merge visits only the blocks that hold
    resident positions, so the per-token cost scales with
    ``cache.length``, not the preallocated ``max_len``.
    ``blockwise=False`` keeps the dense full-width softmax — the
    reference implementation the blockwise kernel is tested against,
    and the baseline the decode-attention bench arm times.

    With ``window`` the cache is the rolling W-slot buffer from
    :func:`prefill`: the new position writes slot ``idx % W``
    (overwriting the token that just fell out of the window) and the
    mask reconstructs each slot's global position from the slot index —
    slot j holds ``idx - ((idx - j) mod W)``, valid iff >= 0. Exact
    sliding-window semantics in O(window) memory, independent of how
    long generation runs. (The rolling buffer's width IS the window —
    every slot is potentially resident, so it keeps the dense path.)"""
    idx = cache.length
    x = model.tok.apply(params["tok"], token[:, None])         # (B,1,D)
    if getattr(model, "pos", None) is not None:
        x = x + model.pos.apply(params["pos"], idx[None])
    scale = 1.0 / math.sqrt(model.dim // model.n_heads)
    max_len = cache.k[0].shape[2]
    if window is not None:
        slots = jnp.arange(max_len)
        slot_pos = idx - ((idx - slots) % window)
        pos_mask = slot_pos >= 0                               # (W,)
        write_at = idx % window
    else:
        pos_mask = (jnp.arange(max_len) <= idx)                # (max,)
        write_at = idx

    new_k, new_v = [], []
    for i, blk in enumerate(model.blocks):
        with jax.named_scope("blocks"):
            p = params["blocks"][i]
            hq, hk, hv = blk.attn.project_qkv(p["attn"],
                                              blk.ln1.apply(p["ln1"], x))
            hq, hk = blk.attn.maybe_rope(hq, hk, idx[None])
            k = jax.lax.dynamic_update_slice(
                cache.k[i], hk.astype(cache.k[i].dtype), (0, 0, write_at, 0))
            v = jax.lax.dynamic_update_slice(
                cache.v[i], hv.astype(cache.v[i].dtype), (0, 0, write_at, 0))
            new_k.append(k)
            new_v.append(v)
            if blockwise and window is None:
                # scalar position broadcast to a length-1 batch axis: the
                # (1, L) validity mask broadcasts over the B rows
                o = blockwise_decode_attention(hq, k, v, idx[None],
                                               scale=scale)
            else:
                o = dense_decode_attention(hq, k, v, pos_mask[None, :],
                                           scale=scale)
            x = x + blk.attn.project_out(p["attn"], o)
            x = x + blk.mlp(p, x)

    x = model.ln_f.apply(params["ln_f"], x)
    logits = model.project_vocab(params, x)[:, 0]
    return logits, KVCache(k=new_k, v=new_v, length=idx + 1)


def decode_step_slots_paged(model: TransformerLM, params: Params, state,
                            tables, lengths, tokens, active, *,
                            page_len: int, blockwise: bool = True,
                            moe_stats=None, sel_stats=None
                            ) -> Tuple[jnp.ndarray, list]:
    """One decode step over a PAGED slot pool (``serve/pages/``).

    ``state`` is a list, one page store a layer, as each block's
    attention module made it (``attn.make_pages``, ``nn/paged.py``):
    exact K and V, quantized K and V, latent attention's one array of
    ``[c | k_r]`` entries, or a window layer's ring a slot beside the
    global layers' pages (``tables`` address the pages; a ring finds its
    entries from the row and ``lengths``, whose last ``window`` are its
    live positions), a linear-attention layer's one state a slot, or a
    sparse-attention layer's pages beside its slot's compressed keys
    (row b of a state and of the compressed keys is slot b). This
    function never looks inside one: each
    layer's store goes to the block's own ``decode_paged``, which writes
    this step's entry and attends, and comes back written. Under
    hyper-connections the residual streams travel as (B, 1, streams, D).
    ``moe_stats``: a list that every expert layer appends its counts
    (3,) to; ``sel_stats``: one that every sparse-attention layer appends
    its (blocks chosen, blocks resident) to.

    The continuous-batching generalization of :func:`decode_step`: the
    rows are independent requests at different depths, so the scalar
    ``cache.length`` becomes ``lengths`` (B,) int32 and every row writes
    and masks at its own position. No row owns a contiguous stripe of
    ``max_len`` positions: the entries live in a shared block pool and
    each slot addresses its pages through ``tables`` (B, P) int32. Slots
    can therefore SHARE full pages (a refcounted common prefix is
    resident once); sharing is safe because shared pages are immutable:
    decode only ever writes each slot's private tail page.

    Per-row math is exactly :func:`decode_step`'s: the row's logical
    cache is the page gather (positions ``j`` at page
    ``tables[b, j // page_len]`` offset ``j % page_len``), the new entry
    is written at ``lengths[b]`` (into the slot's tail page;
    ``active=False`` rows are routed out of bounds and dropped, so a
    freed slot's stale table cannot be corrupted), and the position mask
    exposes ``<= lengths[b]``. XLA's fusion choices depend on the batch
    shape, so across DIFFERENT batch shapes logits agree to ~1 ulp and
    not bitwise: sampled token streams are what the serving engine
    guarantees identical (tests/test_serve.py). ``tables``/``lengths``/
    ``tokens``/``active`` are all traced: ONE compiled program serves
    every request mix and every page-table state.

    Attention runs page-blockwise by default
    (:mod:`..ops.decode_attention`): the page gather moved INSIDE the
    online-softmax block loop, whose traced trip count is the resident
    page count — per-token cost scales with ``max(lengths)``, not
    ``tables.shape[1] * page_len``, and dead pages past every slot's
    length are never even gathered. ``blockwise=False`` keeps the dense
    full-table gather + softmax (the reference the contract tests pin
    the kernel against).

    Returns ``(logits (B, vocab), new state)``; host-side page
    allocation (growing a table at page boundaries) and length
    bookkeeping belong to the caller.
    """
    idx = lengths
    n_pages = table_pages(state)
    width = tables.shape[1] * page_len
    x = model.embed(params, tokens[:, None])                  # (B,1,D)
    if getattr(model, "pos", None) is not None:
        x = x + model.pos.apply(params["pos"], idx[:, None])
    x = model.streams_in(x)
    pos_mask = jnp.arange(width)[None, :] <= idx[:, None]
    write_mask = (jnp.arange(width)[None, :]
                  == idx[:, None])[:, None, :, None]          # (B,1,W,1)
    # pool write target: the slot's page holding position idx. Inactive
    # rows are routed out of bounds (index n_pages) and dropped.
    wp = jnp.take_along_axis(tables, (idx // page_len)[:, None],
                             axis=1)[:, 0]
    wo = idx % page_len
    dest = jnp.where(active, wp, n_pages)
    ctx = DecodeCtx(tables=tables, idx=idx, dest=dest, wo=wo, active=active,
                    pos_mask=pos_mask, write_mask=write_mask,
                    page_len=page_len, blockwise=blockwise,
                    moe_stats=moe_stats, sel_stats=sel_stats)
    state = list(state)
    for i, blk in enumerate(model.blocks):
        with jax.named_scope("blocks"):
            x, state[i] = blk.decode_paged(params["blocks"][i], x, state[i],
                                           ctx)

    x = model.ln_f.apply(params["ln_f"], model.streams_out(x))
    return model.project_vocab(params, x)[:, 0], state


def refuse_blocks(model, what: str):
    """For a path that steps one token a row: a model that generates by
    blocks has no such step (``block_step_slots_paged`` is its one)."""
    if getattr(model, "gen_block", None):
        raise block_unsupported(what)


def block_step_slots_paged(model: TransformerLM, params: Params, state,
                           tables, lengths, tokens, active, *,
                           page_len: int, moe_stats=None
                           ) -> Tuple[jnp.ndarray, list]:
    """One pass of block generation over a PAGED slot pool: every row's
    block of ``L = model.gen_block`` positions, ``tokens`` (B, L) int32
    (``model.mask_id`` where a position is not filled yet), at positions
    ``lengths[b] .. lengths[b] + L - 1``, over [the row's resident pages
    | the block].

    The block's keys and values are written into the row's pages in
    place by every pass (each layer's ``block_paged``); ``lengths`` does
    not move. A row's length advances, on the host, only after the pass
    that ran over its clean block (its commit pass): until then the
    block's positions lie at or past the row's length, where no other
    pass and no other row reads them, and the next pass overwrites them.
    ``lengths`` are multiples of ``L`` and ``page_len`` is one, so a
    block lies inside one page. ``active=False`` rows are routed out of
    bounds and dropped, and left out of the experts' dispatch.

    Returns ``(float32 logits (B, L, vocab), new state)``: what is filled from
    them is the caller's (``serve/pages/cache.py`` picks on the device).
    """
    block = model.gen_block
    idx = lengths
    n_pages = state[0].n_pages
    positions = idx[:, None] + jnp.arange(block)[None, :]      # (B, L)
    x = model.streams_in(model.tok.apply(params["tok"], tokens))
    wp = jnp.take_along_axis(tables, (idx // page_len)[:, None],
                             axis=1)[:, 0]
    ctx = BlockCtx(tables=tables, idx=idx, positions=positions,
                   dest=jnp.where(active, wp, n_pages), wo=idx % page_len,
                   active=active, page_len=page_len, moe_stats=moe_stats)
    state = list(state)
    for i, blk in enumerate(model.blocks):
        with jax.named_scope("blocks"):
            x, state[i] = blk.block_paged(params["blocks"][i], x, state[i],
                                          ctx)
    x = model.ln_f.apply(params["ln_f"], model.streams_out(x))
    return model.project_vocab(params, x, jnp.float32), state


def prefill_partial_paged(model: TransformerLM, params: Params, state,
                          table_row, tokens, offset, true_len, slot=0, *,
                          page_len: int, moe_stats=None, dense=None
                          ) -> Tuple[jnp.ndarray, list]:
    """Prefill the TAIL of a prompt into pool pages, attending over a
    page-resident shared prefix (``serve/pages/``).

    ``tokens`` (1, S) is the right-padded tail — the prompt MINUS its
    ``offset`` prefix tokens whose entries are already resident in the
    pages ``table_row`` (P,) names (``offset`` is page-aligned: only
    FULL pages are ever shared, so the tail always starts at a page
    boundary; for a model that mixes window and global layers, which
    shares no prefix, it is where the prompt's earlier chunks stopped).
    ``offset``, ``true_len`` (the real tail length, >= 1) and
    ``slot`` (the pool row admitted to: a quantized store keeps the
    prompt's partial last page there) are all TRACED — one compile per
    padded tail bucket, not per prompt length, serves cold
    (``offset == 0``), partially shared, and fully shared admissions
    alike.

    Tail queries run at global positions ``offset + i`` (rope/learned
    positions included) and attend over [shared prefix pages | tail]:
    prefix keys are gathered from the pool and masked to positions
    ``< offset``; the tail is causal (block-causal for a model that
    generates by blocks, whose tails are whole blocks), which makes its
    pad columns inert: a real query position never attends a later pad
    key (the pad keys only ever contribute exact zeros to masked-softmax
    sums), so the logits at position ``true_len - 1`` pick the same
    token as an exact-length :func:`prefill` and agree with it to a few
    f32 ulps (two lengths are two XLA programs that reduce in different
    orders, so bit-identity across them is not promised). Tail entries
    are written into the slot's own pages (pad positions route out of
    bounds and drop); the shared prefix pages are never written.

    Each layer's store (``state``, see :func:`decode_step_slots_paged`)
    goes to the block's own ``prefill_paged``; the tail's pad rows are
    left out of an expert layer's dispatch. ``dense`` (a traced bool, for
    a model with sparse-attention layers): the whole prompt is shorter
    than their ``dense_len``. Returns ``(logits (1, vocab) at the last
    real position, new state)``."""
    b, s = tokens.shape
    n_pages = table_pages(state)
    width = table_row.shape[0] * page_len
    offset = jnp.asarray(offset, jnp.int32)
    true_len = jnp.asarray(true_len, jnp.int32)
    positions = offset + jnp.arange(s)
    x = model.embed(params, tokens)
    if getattr(model, "pos", None) is not None:
        x = x + model.pos.apply(params["pos"], positions)
    x = model.streams_in(x)
    if (getattr(model, "layer_windows", None) is not None
            or getattr(model, "layer_mixers", None) is not None):
        # window and global layers in one cache: a window layer attends
        # in bands over its ring's last entries and the tail, a global
        # layer over its resident pages in blocks that follow ``offset``
        # (``nn/attention.py``): no (S, W + S) array is formed. Nor is
        # one for linear- and sparse-attention layers, which read a state
        # and the pages under their own selection
        mask = None
    else:
        # attention mask over [prefix pages | tail]: prefix columns valid
        # below offset, tail columns causal (pad tail is causally inert)
        prefix_mask = jnp.broadcast_to(
            (jnp.arange(width) < offset)[None, :], (s, width))
        causal = jnp.tril(jnp.ones((s, s), dtype=bool))
        if getattr(model, "gen_block", None):
            # a model that generates by blocks: full inside a block of
            # the tail, causal over blocks (``offset`` is page-aligned and
            # a page is whole blocks, so the tail's own index gives the
            # block)
            causal = block_causal_mask(jnp.arange(s), jnp.arange(s),
                                       model.gen_block)
        mask = jnp.concatenate([prefix_mask, causal], axis=1)  # (S, W+S)
    # tail scatter destinations: position offset+i lives in the slot's
    # page (offset+i)//page_len at offset (offset+i)%page_len; pad
    # positions (i >= true_len) route out of bounds and are dropped
    dest_page = table_row[jnp.clip(positions // page_len, 0,
                                   table_row.shape[0] - 1)]
    dest_off = positions % page_len
    dest = jnp.where(jnp.arange(s) < true_len, dest_page, n_pages)
    ctx = PrefillCtx(table_row=table_row, positions=positions, offset=offset,
                     true_len=true_len, slot=jnp.asarray(slot, jnp.int32),
                     dest=dest, dest_off=dest_off, mask=mask,
                     row_mask=jnp.arange(s) < true_len, width=width,
                     moe_stats=moe_stats, dense=dense)
    state = list(state)
    for i, blk in enumerate(model.blocks):
        with jax.named_scope("blocks"):
            x, state[i] = blk.prefill_paged(params["blocks"][i], x, state[i],
                                            ctx)

    x_last = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
    x_last = model.ln_f.apply(params["ln_f"], model.streams_out(x_last))
    return model.project_vocab(params, x_last)[:, 0], state


def spec_verify_slots_paged(model: TransformerLM, params: Params, state,
                            tables, lengths, tokens, *, page_len: int
                            ) -> Tuple[jnp.ndarray, list, list]:
    """Speculative-decoding VERIFY over a paged slot pool
    (``serve/spec/``): score all k+1 candidate positions of every row
    in ONE batched forward, without writing the pool.

    ``tokens`` (B, S) int32 is per row ``[cur, d_1 .. d_k]``: the slot's
    current (last-emitted, not-yet-cached) token followed by its k draft
    proposals; S = k + 1. Row b's queries run at global positions
    ``lengths[b] + j`` and attend over [the row's pages masked to
    positions < lengths[b] | causal in-register candidate block], the
    same [resident | inline] layout as :func:`prefill_partial_paged`, so
    the position-j logits equal what j sequential
    :func:`decode_step_slots_paged` calls would produce (to the usual
    ~1-ulp batching tolerance; greedy token streams are the asserted
    contract). Over-length positions a row will never accept may clip
    into a learned position table's last row: harmless, their logits are
    never accepted.

    READ-ONLY with respect to the pool: nothing is scattered, so a
    rejected suffix needs no rewind. Acceptance is decided on the host
    and only the accepted prefix is ever written, by
    :func:`spec_commit_slots_paged`, from the returned scratch K/V.

    Each layer's store (``state``, see :func:`decode_step_slots_paged`)
    goes to the block's ``verify_paged``, which attends over the store's
    dense rows of each row's table (the verify runs once per engine
    iteration over a short candidate block, so the gather is amortized
    over k+1 scored positions; a blockwise verify kernel is future work
    — docs/serving.md). A quantized store dequantises them and reads
    each row's PARTIAL current page from its exact tail page — the pool
    row for an incomplete page was never written, exactly as in decode.

    Returns ``(logits (B, S, vocab), sk, sv)`` where sk/sv are per-layer
    (B, Hkv, S, Dh) f32 EXACT candidate K/V (position j holds the key of
    ``tokens[:, j]`` at ``lengths + j``); committing (and, on page
    completion, quantizing) accepted positions belongs to
    :func:`spec_commit_slots_paged`."""
    b, s = tokens.shape
    idx = lengths
    width = tables.shape[1] * page_len
    positions = idx[:, None] + jnp.arange(s)[None, :]          # (B, S)
    x = model.tok.apply(params["tok"], tokens)
    if getattr(model, "pos", None) is not None:
        x = x + model.pos.apply(params["pos"], positions)
    prefix_mask = jnp.broadcast_to(
        (jnp.arange(width)[None, :] < idx[:, None])[:, None, :],
        (b, s, width))
    causal = jnp.broadcast_to(
        jnp.tril(jnp.ones((s, s), dtype=bool))[None], (b, s, s))
    ctx = VerifyCtx(tables=tables, idx=idx, positions=positions,
                    mask=jnp.concatenate([prefix_mask, causal], axis=2))
    sk, sv = [], []
    for i, blk in enumerate(model.blocks):
        with jax.named_scope("blocks"):
            x, (hk, hv) = blk.verify_paged(params["blocks"][i], x, state[i],
                                           ctx)
            sk.append(hk)
            sv.append(hv)

    x = model.ln_f.apply(params["ln_f"], x)
    return model.project_vocab(params, x), sk, sv


def spec_commit_slots_paged(state, tables, lengths, sk, sv, commit, *,
                            page_len: int) -> list:
    """Write each row's accepted prefix of a verify's scratch K/V into
    its pages, through each layer's store (``commit``, ``nn/paged.py``):
    the write half :func:`spec_verify_slots_paged` deliberately does not
    do.

    ``commit`` (B,) int32 is the per-row accepted position count e (0 =
    the row took no part in this spec iteration). Position
    ``lengths[b] + j`` lands in page ``tables[b, (lengths[b] + j) //
    page_len]`` at offset ``(lengths[b] + j) % page_len``; rejected
    positions (``j >= commit[b]``) route out of bounds and drop
    (rollback by construction, no rewind), so a page can only ever
    COMPLETE from accepted tokens — which is what keeps a quantized
    store's quantize-once discipline token-for-token with the
    non-speculative decode path. Returns the new state; advancing the
    host ``lengths`` by ``commit`` is the caller's business."""
    n_pages, last = state[0].n_pages, tables.shape[1] - 1
    steps = []
    for j in range(sk[0].shape[2]):
        pos = lengths + j
        wp = jnp.take_along_axis(
            tables, jnp.clip(pos // page_len, 0, last)[:, None],
            axis=1)[:, 0]
        steps.append((jnp.where(j < commit, wp, n_pages), pos % page_len))
    return [st.commit(steps, sk[i], sv[i]) for i, st in enumerate(state)]


@jax.named_scope("sample")
def _sample(logits, rng, temperature: float, top_k: Optional[int],
            top_p: Optional[float] = None):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        vals, _ = jax.lax.top_k(logits, top_k)
        cutoff = vals[..., -1:]
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    if top_p is not None:
        # nucleus sampling: keep the smallest prefix of the
        # probability-sorted vocab whose mass reaches top_p (the token
        # that CROSSES the threshold stays — cum - p < top_p — so at
        # least one survives even for tiny top_p)
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = (cum - probs) < top_p
        # clamp: top_p == 0.0 would keep zero tokens and the -1 index
        # would WRAP to the smallest logit, silently disabling filtering
        kept = jnp.maximum(jnp.sum(keep_sorted, axis=-1, keepdims=True), 1)
        cutoff = jnp.take_along_axis(sorted_logits, kept - 1, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def generate(model: TransformerLM, params: Params, prompt, max_new: int,
             *, temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             rng=None, max_len: Optional[int] = None) -> jnp.ndarray:
    """Generate ``max_new`` tokens after ``prompt`` ((B, S) int32).

    temperature=0 is greedy; otherwise softmax sampling with optional
    top-k. Returns (B, max_new) int32. The decode loop is one
    ``lax.scan`` — jit :func:`make_generate_fn`'s product to cache the
    whole pipeline as two XLA programs."""
    return make_generate_fn(model, max_new, temperature=temperature,
                            top_k=top_k, top_p=top_p, max_len=max_len)(
        params, prompt, rng if rng is not None else jax.random.PRNGKey(0))


def layer_windows(model: TransformerLM) -> Tuple[Optional[int], ...]:
    """Each layer's sliding-window width, None where the layer sees every
    earlier position: what the model was told
    (``TransformerLM(layer_windows=...)``), else what each block's
    ``attn_fn`` advertises (``make_flash_attn_fn(window=W)``)."""
    told = getattr(model, "layer_windows", None)
    if told is not None:
        return told
    return tuple(getattr(blk.attn.attn_fn, "window", None)
                 for blk in model.blocks)


def refuse_mixed(model, what: str):
    """For a path that keeps one cache layout for every layer
    (``generate()``, the disaggregated hand-off, the verify program): a
    model told its layers' windows is served by the paged pool alone,
    whose stores differ a layer (``nn/paged.py`` ``WindowPages`` beside
    ``KVPages``)."""
    if getattr(model, "layer_windows", None) is not None:
        raise mixed_unsupported(what)


def refuse_mixers(model, what: str):
    """For a path that keeps pages alone, or one layout for every layer:
    a model of linear- and sparse-attention layers
    (``TransformerLM(layer_mixers=...)``) is served by the paged pool
    alone, which holds a state a slot and compressed keys beside pages
    (``nn/paged.py`` ``StatePages``, ``SelectedPages``)."""
    if getattr(model, "layer_mixers", None) is not None:
        raise mixers_unsupported(what)


def _model_window(model: TransformerLM) -> Optional[int]:
    """The sliding-window width a contiguous cache rolls over, or None.

    A model built with ``make_flash_attn_fn(window=W)`` advertises W on
    every block's attn_fn; a uniform W switches decode to the rolling
    O(W)-memory cache that reproduces the window exactly. A model that
    was told a width a layer (``TransformerLM(layer_windows=...)``) has
    no contiguous layout and answers None here: the paged pool serves it
    and every other path refuses it by name (:func:`refuse_mixed`).
    Widths that disagree without having been told are an error."""
    if getattr(model, "layer_windows", None) is not None:
        return None
    widths = set(layer_windows(model))
    if widths <= {None}:
        return None
    if len(widths) == 1:
        return next(iter(widths))
    raise ValueError(f"blocks disagree on attention window ({sorted(map(str, widths))}); "
                     "cached decode needs a uniform width")


def _check_attn_compatible(model: TransformerLM,
                           allow_custom_attn: bool) -> None:
    """Decode attends over the cache with an inline softmax(qk)v — exact
    for the dense core, for dense-equivalent kernels (flash attention
    marks itself ``dense_equivalent``), and for uniform sliding-window
    kernels (served by the rolling cache). Refuse behavior-changing
    custom cores (biased, ring islands) unless the caller explicitly
    opts in. A layer that was told its own window
    (``MultiHeadAttention(window=...)``) computes it itself and never
    calls the core."""
    if allow_custom_attn:
        return
    for blk in model.blocks:
        f = blk.attn.attn_fn
        if (f is dense_attention or getattr(f, "dense_equivalent", False)
                or getattr(f, "window", None) is not None
                or getattr(blk.attn, "window", None) is not None):
            continue
        raise ValueError(
            "model was built with a custom attn_fn whose semantics the "
            "cached decode path cannot reproduce; pass "
            "allow_custom_attn=True only if the core computes standard "
            "softmax(q k^T * scale) v")


def make_generate_fn(model: TransformerLM, max_new: int, *,
                     temperature: float = 0.0, top_k: Optional[int] = None,
                     top_p: Optional[float] = None,
                     max_len: Optional[int] = None,
                     allow_custom_attn: bool = False,
                     pin_weight_stream: bool = False,
                     param_shardings=None):
    """Build ``fn(params, prompt, rng) -> (B, max_new) tokens`` suitable
    for ``jax.jit`` (all shape-determining arguments are closed over).

    ``param_shardings``: the producer's params out-shardings (a train
    step's ``out_shardings["params"]`` — docs/front_door.md). When set,
    the returned fn asserts the params it receives already carry them
    (``parallel.front_door.verify_handoff``): the eval/prefill entry of
    the reshard-free pjit-to-pjit chain — a mismatch raises a typed
    ``HandoffMismatch`` instead of pjit silently copying the weights.
    The check runs on CONCRETE params — i.e. on eager calls of the
    returned fn (a tracer carries its abstract type, not a device
    placement). If you wrap fn in ``jax.jit`` yourself, run
    ``verify_handoff(params, param_shardings)`` once before the first
    call — that is exactly what ``serve.EngineConfig(param_shardings=)``
    does at engine construction, the production admit path.

    ``pin_weight_stream``: ties the params consumed by each decode step
    to the loop-varying cache counter through an optimization barrier,
    so weight-DERIVED tensors cannot be hoisted out of the scan by
    loop-invariant code motion. Matters for int8 weights
    (``ops/quant.py``): if XLA hoists the dequantized bf16 copy, every
    step streams bf16 and the bandwidth win of storing int8 evaporates;
    pinned, each step re-derives from the int8 bytes (dequant fuses into
    the consuming matmul). Costs nothing when weights are un-quantized
    except disabling that same hoisting — benchmark both
    (benchmarks/decode_tpu.py measures the pinned arm against the plain
    int8 arm to show which way XLA went).

    A model built with a uniform sliding window decodes through the
    ROLLING cache automatically: W slots, position p at slot p % W —
    exact window semantics in O(window) memory however long generation
    runs."""
    _check_attn_compatible(model, allow_custom_attn)
    refuse_blocks(model, "generate() (one token a step over a contiguous "
                         "cache)")
    refuse_mixed(model, "generate() (one contiguous cache layout for "
                        "every layer)")
    refuse_mixers(model, "generate() (one contiguous cache layout for "
                         "every layer)")
    window = _model_window(model)

    def fn(params, prompt, rng):
        if param_shardings is not None and not isinstance(
                jax.tree_util.tree_leaves(params)[0], jax.core.Tracer):
            from ..parallel.front_door import verify_handoff
            verify_handoff(params, param_shardings,
                           what="generate params")
        s = prompt.shape[1]
        limit = max_len or (s + max_new)
        if limit > model.max_seq:
            raise ValueError(
                f"cache length {limit} (prompt {s} + max_new {max_new} "
                f"or explicit max_len) exceeds the model's max_seq "
                f"({model.max_seq})")
        if window is None and s + max_new > limit:
            raise ValueError(
                f"max_len {limit} cannot hold prompt ({s}) + max_new "
                f"({max_new}) tokens — the cache would wrap and corrupt")
        if (window is not None and getattr(model, "pos", None) is not None
                and s + max_new > model.max_seq):
            # the rolling cache is unbounded but LEARNED position
            # embeddings are not: past max_seq the table gather would
            # clip and silently reuse the last row. rope/none have no
            # such ceiling.
            raise ValueError(
                f"prompt ({s}) + max_new ({max_new}) exceeds max_seq "
                f"({model.max_seq}): learned position embeddings cannot "
                "extrapolate past their table even under a sliding "
                "window (use pos='rope' for unbounded generation)")
        # never allocate more slots than positions can exist: a window
        # wider than the whole run degenerates to the plain layout size
        # with identical semantics (nothing is ever evicted). s+max_new
        # (not max_len) is the bound — an explicit small max_len must
        # not silently shrink the semantic window.
        w_eff = None if window is None else min(window, s + max_new)
        rng_first, *step_rngs = jax.random.split(rng, max_new)
        logits, cache = prefill(model, params, prompt, limit,
                                window=w_eff)
        first = _sample(logits, rng_first, temperature, top_k, top_p)

        def body(carry, step_rng):
            cache, token = carry
            p = params
            if pin_weight_stream:
                p, _ = jax.lax.optimization_barrier((params, cache.length))
            logits, cache = decode_step(model, p, cache, token,
                                        window=w_eff)
            nxt = _sample(logits, step_rng, temperature, top_k, top_p)
            return (cache, nxt), nxt

        if max_new == 1:
            return first[:, None]
        (_, _), rest = jax.lax.scan(body, (cache, first),
                                    jnp.stack(step_rngs))
        return jnp.concatenate([first[:, None], jnp.moveaxis(rest, 0, 1)],
                               axis=1)                        # (B, max_new)

    return fn
