"""Attention and transformer blocks — the LM rung of the ladder
(BASELINE.json: TransformerEncoder LM) and the substrate for long-context
sequence parallelism (ring attention lives in ``parallel/sequence.py`` and
plugs in here via the ``attn_fn`` hook).

Compute shapes are chosen for the MXU: projections are single fused
matmuls over (B*S, D); attention is batched (B, H, S, S) einsums XLA tiles
onto the systolic array. bfloat16-friendly: pass ``dtype=jnp.bfloat16`` for
activations/params while softmax runs in float32 for stability.
"""

from __future__ import annotations

import contextlib
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .core import (Dropout, LayerNorm, Linear, Module, Params, RMSNorm,
                   gelu)
from .rotary import apply_rope


def dense_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    window: Optional[int] = None, mask=None):
    """Reference attention: softmax(q k^T / sqrt(d)) v.

    q: (B, H, S, Dh); k: (B, Hkv, S, Dh), v: (B, Hkv, S, Dv) where Dv may
    differ from Dh (the result is Dv wide) and Hkv divides H —
    Hkv < H is grouped-query attention (each kv head serves H/Hkv query
    heads), computed via a grouped einsum so the kv tensors are never
    repeated in memory.

    The **f32-stats contract** (docs/compute.md, guarded by
    tests/test_compute_path.py): the softmax — max, exp, and the
    normalizing SUM — runs in float32 regardless of input dtype; only
    the resulting probabilities are cast back to ``v.dtype`` for the
    p@v matmul. Under bf16 mixed precision this is what keeps the
    normalizer from accumulating in 8 mantissa bits (at S=512 a pure
    bf16 sum of uniform probabilities drifts by several percent). The
    flash kernel and ``ops.decode_attention`` follow the same rule.
    A fully-masked ROW (causal with s_q > s_k puts whole rows above
    the diagonal) yields NaN here by definition of softmax over an
    all--inf row; the flash kernel deliberately matches that, while
    the blockwise decode path — where fully-masked BLOCKS are routine
    for short rows — masks with a finite sentinel and exact-zero
    probabilities so the merge never manufactures NaN.

    ``window`` (requires ``causal``): sliding-window attention — row i
    sees keys (i+off-window, i+off] only (off aligns cross-length
    diagonals). This is the single-device path;
    ``parallel.sequence.ring_attention`` computes the same function with
    K/V sharded around the mesh ring, and ``ops.flash_attention`` is the
    O(S)-memory kernel equivalent.

    ``mask`` (S_q, S_k) bool, True where a key is seen, is any other
    visibility (the block-causal one of a model that generates by blocks,
    :func:`block_causal_mask`); the flash kernel takes none.
    """
    b, h, s_q, dh = q.shape
    h_kv, s_k = k.shape[-3], k.shape[-2]
    if h % h_kv:
        raise ValueError(f"n_heads {h} not divisible by kv heads {h_kv}")
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    qg = q.reshape(b, h_kv, h // h_kv, s_q, dh)
    logits = jnp.einsum("bngqd,bnkd->bngqk", qg, k).astype(jnp.float32) \
        * scale
    if causal:
        if mask is not None:
            raise ValueError("give causal=True or a mask, not both")
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((s_q, s_k), dtype=bool),
                              k=s_k - s_q - window)
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bngqk,bnkd->bngqd", probs, v) \
        .reshape(b, h, s_q, v.shape[-1])


#: values narrower than the keys are taken as they are; a caller that
#: holds unequal widths (``nn.latent.LatentAttention._core``) reads this
#: off its core and pads for one that does not say so
dense_attention.narrow_values = True


def block_causal_mask(q_pos, k_pos, block: int):
    """Key ``j`` is seen from query ``i`` iff ``j // block <= i // block``:
    causal over blocks of ``block`` positions, full inside one. ``q_pos``
    (..., S_q) and ``k_pos`` (..., S_k) absolute positions -> (..., S_q,
    S_k) bool."""
    return (k_pos[..., None, :] // block) <= (q_pos[..., :, None] // block)


class MultiHeadAttention(Module):
    """Multi-head self-attention with a pluggable core.

    ``attn_fn(q, k, v, causal=...)`` defaults to :func:`dense_attention`;
    the sequence-parallel engine substitutes ring attention without
    touching this module's parameters or callers.

    ``head_dim`` is ``dim // n_heads`` unless given (32 heads of 128 at
    width 2048: the projections are then ``dim -> n_heads * head_dim``
    and back); ``bias=False`` leaves the projections' biases out;
    ``qk_norm`` (an epsilon) puts an RMSNorm over each head's
    ``head_dim`` values on q and on k, one gain vector for all heads,
    before the rotation (Qwen3's); ``gen_block`` replaces the causal
    mask by :func:`block_causal_mask` (a model that generates by blocks,
    ``models/transformer.py``), which only a core that takes ``mask=``
    can compute.

    ``window`` and ``tail_block`` are a layer of a model that mixes
    sliding-window and global layers (``TransformerLM(layer_windows=...)``).
    ``window`` (a width): query ``i`` sees keys ``i - window < j <= i``,
    and the layer's store is a ring a slot (``nn.paged.WindowPages``),
    not pages. ``tail_block`` (a number of positions): a prompt's tail
    attends over the slot's resident pages that many positions a trip
    (``nn.paged.KVPages.attend_tail``), not over the gathered row under
    a dense mask; a global layer of such a model is given it.
    """

    def __init__(self, dim: int, n_heads: int, *, causal: bool = False,
                 n_kv_heads: Optional[int] = None, rope: bool = False,
                 rope_base: float = 10000.0,
                 attn_fn: Optional[Callable] = None, dtype=jnp.float32,
                 head_dim: Optional[int] = None, bias: bool = True,
                 qk_norm: Optional[float] = None,
                 gen_block: Optional[int] = None,
                 window: Optional[int] = None,
                 tail_block: Optional[int] = None):
        if head_dim is None and dim % n_heads:
            raise ValueError(f"dim {dim} not divisible by n_heads {n_heads}")
        self.dim = dim
        self.n_heads = n_heads
        self.n_kv_heads = n_kv_heads if n_kv_heads is not None else n_heads
        if n_heads % self.n_kv_heads:
            raise ValueError(f"n_heads {n_heads} not divisible by "
                             f"n_kv_heads {self.n_kv_heads}")
        self.head_dim = head_dim if head_dim is not None else dim // n_heads
        self.causal = causal
        self.rope = rope
        self.rope_base = rope_base
        self.attn_fn = attn_fn or dense_attention
        self.gen_block = gen_block
        self.window, self.tail_block = window, tail_block
        if window is not None and (window < 1 or not causal or gen_block):
            raise ValueError("window needs a width >= 1 on a causal layer "
                             "that does not generate by blocks")
        if gen_block and self.attn_fn is not dense_attention:
            raise ValueError(
                "a model that generates by blocks attends under the "
                "block-causal mask, which this attn_fn cannot take: leave "
                "attn_fn unset (dense_attention)")
        # GQA (n_kv_heads < n_heads) shrinks the k/v projections and the
        # decode KV cache by n_heads/n_kv_heads; with the default the
        # parameter tree is identical to classic MHA.
        q_dim = n_heads * self.head_dim
        kv_dim = self.n_kv_heads * self.head_dim
        self.qkv = Linear(dim, q_dim + 2 * kv_dim, bias=bias, dtype=dtype)
        self.out = Linear(q_dim, dim, bias=bias, dtype=dtype)
        self.q_norm = self.k_norm = None
        if qk_norm is not None:
            self.q_norm = RMSNorm(self.head_dim, eps=qk_norm, dtype=dtype)
            self.k_norm = RMSNorm(self.head_dim, eps=qk_norm, dtype=dtype)

    def init(self, key) -> Params:
        k1, k2 = jax.random.split(key)
        p = {"qkv": self.qkv.init(k1), "out": self.out.init(k2)}
        if self.q_norm is not None:
            p["q_norm"] = self.q_norm.init(k1)
            p["k_norm"] = self.k_norm.init(k2)
        return p

    def project_qkv(self, params: Params, x):
        """x (B, S, D) → q (B, H, S, Dh), k, v (B, Hkv, S, Dh), via the
        fused qkv matmul. The single source of truth for the qkv memory
        layout — the cached decode path (models/generate.py) builds its
        KV cache through this method."""
        b, s, _ = x.shape
        dh, h, hkv = self.head_dim, self.n_heads, self.n_kv_heads
        with jax.named_scope("attn/qkv"):
            qkv = self.qkv.apply(params["qkv"], x)  # (B, S, (H+2Hkv)*Dh)
            q, k, v = jnp.split(qkv, [h * dh, (h + hkv) * dh], axis=-1)

            def heads(t, n):
                return t.reshape(b, s, n, dh).transpose(0, 2, 1, 3)
            q, k, v = heads(q, h), heads(k, hkv), heads(v, hkv)
            if self.q_norm is not None:
                q = self.q_norm.apply(params["q_norm"], q)
                k = self.k_norm.apply(params["k_norm"], k)
            return q, k, v

    def project_out(self, params: Params, o):
        """o (B, H, S, Dh) → output projection (B, S, D)."""
        b, h, s, dh = o.shape
        with jax.named_scope("attn/out"):
            return self.out.apply(
                params["out"], o.transpose(0, 2, 1, 3).reshape(b, s, h * dh))

    def maybe_rope(self, q, k, positions=None):
        """Rotate q/k when built with ``rope=True`` (no-op otherwise).
        ``positions`` (S,) default to arange — pass explicit ids for a
        sequence-parallel shard (global offset) or a cached decode step
        (the single slot being written). The decode path MUST rotate
        through this method before caching k: the cache stores
        post-rotation keys so decode-time q.k phases are correct."""
        if not self.rope:
            return q, k
        if positions is None:
            positions = jnp.arange(q.shape[2])
        with jax.named_scope("attn/qkv"):
            return (apply_rope(q, positions, self.rope_base),
                    apply_rope(k, positions, self.rope_base))

    def apply(self, params: Params, x, *, positions=None, **kwargs):
        q, k, v = self.project_qkv(params, x)
        q, k = self.maybe_rope(q, k, positions)
        with jax.named_scope("attn/core"):
            if self.gen_block:
                at = jnp.arange(q.shape[2]) if positions is None \
                    else positions
                o = self.attn_fn(q, k, v, mask=block_causal_mask(
                    at, at, self.gen_block))
            elif self.window is not None:
                # the layer's own width, whatever core the model was
                # given (a flash ``attn_fn`` bakes in one width for all)
                o = dense_attention(q, k, v, causal=True, window=self.window)
            else:
                o = self.attn_fn(q, k, v, causal=self.causal)
        return self.project_out(params, o)

    # -- the paged path: the module hands out its page store (nn/paged.py) --

    def make_pages(self, n_pages: int, n_slots: int, page_len: int, bits,
                   dtype):
        """A layer's store: K and V of (n_pages, Hkv, page_len, Dh),
        exact in ``dtype`` (``bits`` None) or quantized to 8 or 4 bits;
        for a layer told its window, a ring a slot (exact only)."""
        from .paged import KVPages, WindowPages, mixed_unsupported
        if self.window is not None:
            if bits is not None:
                raise mixed_unsupported("a quantized page pool "
                                        "(kv_dtype='q8'/'q4')")
            return WindowPages.zeros((self.n_kv_heads, self.head_dim),
                                     n_slots, self.window, page_len, dtype)
        return KVPages.zeros((self.n_kv_heads, page_len, self.head_dim),
                             n_pages, n_slots, bits, dtype)

    def decode_paged(self, params: Params, x, pages, ctx):
        """One token a row over the paged pool. x (B, 1, D) normed;
        returns (attention's output (B, 1, D), the store written)."""
        hq, hk, hv = self.project_qkv(params, x)
        hq, hk = self.maybe_rope(hq, hk, ctx.idx[:, None, None])
        scale = 1.0 / math.sqrt(self.head_dim)
        if self.window is not None:
            with jax.named_scope("page_write"):
                pages = pages.write(hk, hv, ctx)
            with _scopes("decode_attention", "window_attention"):
                o = pages.attend(ctx, hq, scale, self.window)
            return self.project_out(params, o), pages
        with jax.named_scope("page_write"):
            pages = pages.write(hk, hv, ctx.dest, ctx.wo)
        # a global layer beside window layers reads under a name of its
        # own; every other model's programs keep the names they had
        with _scopes(*(("decode_attention", "global_attention")
                       if self.tail_block else ())):
            o = pages.attend(ctx, hq, hk, hv, scale)
        return self.project_out(params, o), pages

    def prefill_paged(self, params: Params, x, pages, ctx):
        """The tail of one prompt over [shared prefix pages | tail].
        x (1, S, D) normed; returns (output (1, S, D), the store
        written). Prefix keys come from the pool; the tail's are inline:
        its pages were just written, but the in-register tail avoids a
        second gather, keeps the math that of a right-padded prefill
        ([real | pad]), and is exact where the pool is quantized,
        so a cold prompt sees no quantization at admission."""
        hq, hk, hv = self.project_qkv(params, x)
        hq, hk = self.maybe_rope(hq, hk, ctx.positions)
        scale = 1.0 / math.sqrt(self.head_dim)
        if self.window is not None:
            # the ring is read before the tail goes into it: the tail's
            # last entries may land on the entries before it
            pk, pv = pages.prior(ctx, self.window)
            with jax.named_scope("page_write"):
                pages = pages.write_tail(hk, hv, ctx)
            with _scopes("attn/core", "window_attention"):
                o = banded_window_attention(
                    hq, jnp.concatenate([pk.astype(hk.dtype), hk], axis=2),
                    jnp.concatenate([pv.astype(hv.dtype), hv], axis=2),
                    ctx.offset, self.window, scale)
            return self.project_out(params, o), pages
        with jax.named_scope("page_write"):
            pages = pages.write_tail(hk, hv, ctx)
        if self.tail_block:
            with _scopes("attn/core", "global_attention"):
                o = pages.attend_tail(ctx, hq, scale, self.tail_block)
            return self.project_out(params, o), pages
        pref_k, pref_v = pages.rows(ctx.table_row, None, hk, hv)
        return self.project_out(params, prefix_tail_attention(
            hq, hk, hv, pref_k, pref_v, ctx.mask,
            1.0 / math.sqrt(self.head_dim))), pages

    def block_paged(self, params: Params, x, pages, ctx):
        """One pass over every row's block of ``L`` positions (x (B, L,
        D) normed, ``nn.paged.BlockCtx``): the block's keys and values
        are written into the row's pages IN PLACE, every pass (positions
        at or past a row's length are read by no other row, so a noisy
        pass's entries are overwritten by the next and the commit
        pass's stay), and the block attends over [resident | block]. All
        ``L`` positions see the same keys, so they fold into the query
        group. Returns (output (B, L, D), the store written)."""
        hq, hk, hv = self.project_qkv(params, x)
        hq, hk = self.maybe_rope(hq, hk, ctx.positions[:, None, :])
        with jax.named_scope("page_write"):
            pages = pages.write_block(hk, hv, ctx.dest, ctx.wo)
        o = pages.attend_block(ctx, hq, hk, hv,
                               1.0 / math.sqrt(self.head_dim))
        return self.project_out(params, o), pages

    def verify_paged(self, params: Params, x, pages, ctx):
        """Every row's k + 1 candidates (x (B, S, D) normed) over [its
        resident rows | the candidates], the store only read: a dense
        page gather, amortised over the k + 1 scored positions. Returns
        (output (B, S, D), this layer's exact f32 candidate (K, V), for
        ``pages.commit``)."""
        hq, hk, hv = self.project_qkv(params, x)
        hq, hk = self.maybe_rope(hq, hk, ctx.positions[:, None, :])
        gk, gv = pages.rows(ctx.tables, ctx.idx, hk, hv)
        o = prefix_tail_attention(hq, hk, hv, gk, gv, ctx.mask,
                                  1.0 / math.sqrt(self.head_dim))
        return self.project_out(params, o), (hk.astype(jnp.float32),
                                             hv.astype(jnp.float32))


@contextlib.contextmanager
def _scopes(*names):
    """``jax.named_scope`` of each name, the first outermost."""
    with contextlib.ExitStack() as stack:
        for name in names:
            stack.enter_context(jax.named_scope(name))
        yield


def write_rows(pool, dest, wo, rows):
    """``pool.at[dest, :, wo].set(rows, mode="drop")`` for a decode
    step's (B, Hkv, Dh) rows, written as a scatter of Dh-wide rows into
    the pool seen as (n_pages * Hkv * page_len, Dh). The values are the
    same; the form is the one XLA's TPU compiler updates in place. For
    the (page, :, offset) form it moves the whole donated pool to a
    layout with the head axis next to Dh and back, every layer, every
    step (two copies of 64 MB a pool: PERF.md, Findings, PR 29). A
    dropped row's ``dest`` is ``n_pages``: its flat index lies past the
    end for every head."""
    n_pages, hkv, page_len, dh = pool.shape
    flat = (dest[:, None] * hkv + jnp.arange(hkv)[None, :]) * page_len \
        + wo[:, None]                                      # (B, Hkv)
    return pool.reshape(-1, dh).at[flat.reshape(-1)].set(
        rows.reshape(-1, dh).astype(pool.dtype), mode="drop") \
        .reshape(pool.shape)


def prefix_tail_attention(hq, hk, hv, pref_k, pref_v, mask, scale):
    """Grouped-query attention of a tail's queries (B, H, S, Dh) over
    [prefix (B, Hkv, W, Dh) | tail (B, Hkv, S, Dh)] under ``mask``
    (S, W + S), or (B, S, W + S) a row; float32 statistics. Returns
    (B, H, S, Dh)."""
    s = hq.shape[2]
    k_all = jnp.concatenate([pref_k, hk], axis=2)   # (1,Hkv,W+S,Dh)
    v_all = jnp.concatenate([pref_v, hv], axis=2)
    bq, hh, _, dd = hq.shape
    hkv = k_all.shape[1]
    hq_g = hq.reshape(bq, hkv, hh // hkv, s, dd)
    logits = jnp.einsum("bngqd,bnkd->bngqk", hq_g, k_all).astype(
        jnp.float32) * scale                     # (1,Hkv,g,S,W+S)
    mask = mask[None, None, None, :, :] if mask.ndim == 2 \
        else mask[:, None, None, :, :]
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_all.dtype)
    return jnp.einsum("bngqk,bnkd->bngqd", probs, v_all) \
        .reshape(bq, hh, s, dd)


def banded_window_attention(hq, k_all, v_all, offset, window: int, scale):
    """A prompt's tail under a sliding window, in bands: queries hq
    (1, H, S, Dh), query ``i`` at position ``offset + i``, over ``k_all``
    / ``v_all`` (1, Hkv, window + S, Dh) = [the ``window`` entries before
    the tail | the tail], column ``c`` at position ``offset - window +
    c``. Query ``i`` sees the ``window`` columns ``i < c <= i + window``
    that hold a position >= 0, so a block of ``window`` queries needs two
    blocks of ``window`` columns: scores are (H, S, 2 * window), not
    (H, S, window + S). Float32 statistics; a tail whose length is no
    multiple of the window is padded to one (a padded key lies after
    every real query). Returns (1, H, S, Dh)."""
    _, h, s, dh = hq.shape
    hkv, w = k_all.shape[1], window
    g, nb = h // hkv, -(-s // w)
    pad = nb * w - s
    if pad:
        hq = jnp.pad(hq, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_all = jnp.pad(k_all, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_all = jnp.pad(v_all, ((0, 0), (0, 0), (0, pad), (0, 0)))
    q = hq.reshape(hkv, g, nb, w, dh)

    def bands(t):
        blocks = t.reshape(hkv, nb + 1, w, t.shape[-1])
        return jnp.concatenate([blocks[:, :-1], blocks[:, 1:]], axis=2)
    k, v = bands(k_all), bands(v_all)                 # (Hkv, nb, 2w, Dh)
    sc = jnp.einsum("ngbqd,nbkd->ngbqk", q, k,
                    preferred_element_type=jnp.float32) * scale
    r, c = jnp.arange(w)[:, None], jnp.arange(2 * w)[None, :]
    held = (offset - w + jnp.arange(nb)[:, None] * w + c) >= 0    # (nb, 2w)
    seen = ((c > r) & (c <= r + w))[None] & held[:, None, :]
    sc = jnp.where(seen[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    o = jnp.einsum("ngbqk,nbkd->ngbqd", p, v)
    return o.reshape(1, h, nb * w, dh)[:, :, :s]


class TransformerBlock(Module):
    """Pre-norm block: x + MHA(LN(x)); x + MLP(LN(x)), GELU MLP."""

    def __init__(self, dim: int, n_heads: int, mlp_ratio: int = 4, *,
                 causal: bool = False, dropout: float = 0.0,
                 n_kv_heads: Optional[int] = None, rope: bool = False,
                 rope_base: float = 10000.0,
                 attn_fn: Optional[Callable] = None, dtype=jnp.float32):
        self.ln1 = LayerNorm(dim, dtype=dtype)
        self.attn = MultiHeadAttention(dim, n_heads, causal=causal,
                                       n_kv_heads=n_kv_heads, rope=rope,
                                       rope_base=rope_base,
                                       attn_fn=attn_fn, dtype=dtype)
        self.ln2 = LayerNorm(dim, dtype=dtype)
        self.fc1 = Linear(dim, mlp_ratio * dim, dtype=dtype)
        self.fc2 = Linear(mlp_ratio * dim, dim, dtype=dtype)
        self.drop = Dropout(dropout)

    def init(self, key) -> Params:
        ks = jax.random.split(key, 4)
        return {"ln1": self.ln1.init(ks[0]), "attn": self.attn.init(ks[1]),
                "ln2": self.ln2.init(ks[2]),
                "fc1": self.fc1.init(ks[3]),
                "fc2": self.fc2.init(jax.random.fold_in(ks[3], 1))}

    def mlp(self, params: Params, x):
        """LN → fc1 → GELU → fc2 (no residual/dropout). Shared by apply
        and the cached decode path (models/generate.py)."""
        h = self.ln2.apply(params["ln2"], x)
        with jax.named_scope("mlp"):
            return self.fc2.apply(params["fc2"],
                                  gelu(self.fc1.apply(params["fc1"], h)))

    def _with_pages(self, step, params: Params, x, pages, ctx):
        a, out = step(params["attn"], self.ln1.apply(params["ln1"], x),
                      pages, ctx)
        x = x + a
        return x + self.mlp(params, x), out

    def decode_paged(self, params: Params, x, pages, ctx):
        """x (B, 1, D), this layer's page store -> (x, the store)."""
        return self._with_pages(self.attn.decode_paged, params, x, pages, ctx)

    def prefill_paged(self, params: Params, x, pages, ctx):
        """x (1, S, D): the padded tail of one prompt."""
        return self._with_pages(self.attn.prefill_paged, params, x, pages, ctx)

    def verify_paged(self, params: Params, x, pages, ctx):
        """x (B, S, D): a verify's candidates -> (x, their (K, V))."""
        return self._with_pages(self.attn.verify_paged, params, x, pages, ctx)

    def apply(self, params: Params, x, *, rng=None, train: bool = False,
              positions=None, **_):
        r1, r2 = (jax.random.split(rng) if rng is not None else (None, None))
        h = self.attn.apply(params["attn"], self.ln1.apply(params["ln1"], x),
                            positions=positions)
        x = x + self.drop.apply({}, h, rng=r1, train=train)
        return x + self.drop.apply({}, self.mlp(params, x), rng=r2,
                                   train=train)
