"""Multi-head latent attention (MLA, DeepSeek-V2/V3): queries and
keys/values both pass through a low-rank latent, a small rotary part of
the key is shared by all heads, and the cache holds one entry a token,
``[c | k_r]`` (the normalised key/value latent and the rotated key part),
whatever the number of heads (stored padded to whole lanes of 128).

Two forms of the same function. *Expanded* (training, prefill): the
latent is widened through ``kv_b`` into per-head keys and values, and the
attention core is the ordinary one over heads of ``nope + rope`` with
values of ``v`` (padded to the key width for the pluggable core).
*Absorbed* (decode over pages): ``q_n . k_n = (q_n W_k^T) . c`` and
``P v = (P c) W_v``, so the scores are taken against the page entries
themselves: 32 query heads of ``kv_rank + rope`` against ONE shared key
head, values the first ``kv_rank`` of the same entry.

Rotary frequencies are YaRN's (``nn/rotary.py``), the pairing is
interleaved, and the softmax scale carries ``mscale_all_dim``'s square."""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..ops.decode_attention import (dense_decode_attention,
                                    paged_loop_attention)
from .attention import dense_attention
from .core import Linear, Module, Params, RMSNorm
from .paged import latent_unsupported
from .rotary import rotate_interleaved, yarn_inv_freq, yarn_mscale

#: rows of queries the warm paged prefill scores at a time: the float32
#: scores of a chunk over [prefix pages | tail] are what it holds
PREFILL_Q_CHUNK = 512


def masked_attention(q, k, v, mask, scale, chunk: int = PREFILL_Q_CHUNK):
    """softmax(q k^T * scale under ``mask``) v for one sequence, the
    queries taken ``chunk`` rows at a time. q: (H, S, Dq); k: (H, K, Dq);
    v: (H, K, Dv); mask: (S, K) bool. Float32 statistics."""
    def rows(args):
        qc, mc = args
        s = jnp.einsum("hqd,hkd->hqk", qc, k).astype(jnp.float32) * scale
        s = jnp.where(mc[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("hqk,hkd->hqd", p, v)

    h, s_q, dq = q.shape
    if s_q <= chunk or s_q % chunk:
        return rows((q, mask))
    n = s_q // chunk
    out = jax.lax.map(rows, (q.reshape(h, n, chunk, dq).transpose(1, 0, 2, 3),
                             mask.reshape(n, chunk, -1)))
    return out.transpose(1, 0, 2, 3).reshape(h, s_q, -1)


class LatentPages(NamedTuple):
    """Latent attention's store (``nn/paged.py``): ONE exact array a
    layer, ``(n_pages, 1, page_len, page_width)``, whose entries
    ``[c | k_r | 0]`` are key and value both. What has not been carried
    over to this layout is refused by name, where the engine or the pool
    that needs it is built (``require``)."""
    entries: Any

    LACKS = {"commit": "speculative decoding (serve/spec)",
             "export": "the disaggregated hand-off (serve/disagg)",
             "adopt": "the disaggregated hand-off (serve/disagg)"}

    @property
    def n_pages(self) -> int:
        return self.entries.shape[0]

    def require(self, op: str) -> None:
        if op in self.LACKS:
            raise latent_unsupported(self.LACKS[op])

    def commit(self, *_):
        self.require("commit")

    def export(self, *_, **__):
        self.require("export")

    def adopt(self, *_, **__):
        self.require("adopt")

    def write(self, entry, dest, wo):
        """One entry a row (B, 1, E), or a prompt's tail (S, 1, E) with
        its own ``dest`` / ``wo`` (S,). (Not ``write_rows``: with one
        head the (page, :, offset) form moves no pool, and the cell's
        programs stay the ones measured.)"""
        return LatentPages(self.entries.at[dest, :, wo].set(
            entry.astype(self.entries.dtype), mode="drop"))

    def rows(self, tables):
        """The entries of the pages ``tables`` (P,) or (B, P) names,
        (1 | B, P * page_len, E)."""
        g = self.entries[tables]
        return g.reshape((g.shape[:-4] or (1,)) + (-1, g.shape[-1]))

    def attend(self, ctx, hq, new, scale, width: int):
        """A decode step, absorbed: hq (B, H, 1, E) against the one
        shared key head, ``new`` (B, 1, 1, E) this step's entries, the
        values the first ``width`` of an entry. (B, H, 1, width)."""
        if ctx.blockwise:
            return paged_loop_attention(
                hq, lambda pids, j: jnp.take(self.entries, pids, axis=0),
                None, ctx.tables, ctx.idx, new, None, scale=scale,
                page_len=ctx.page_len, out_dtype=self.entries.dtype,
                value_width=width)
        k = self.rows(ctx.tables)[:, None]                # (B, 1, W, E)
        k = jnp.where(ctx.write_mask, new.astype(k.dtype), k)
        return dense_decode_attention(hq, k, k, ctx.pos_mask,
                                      scale=scale)[..., :width]

    def resident_bytes(self) -> int:
        return self.entries.nbytes


class LatentAttention(Module):
    """MLA over ``n_heads`` heads of ``nope_dim + rope_dim`` (keys) and
    ``v_dim`` (values). No biases."""

    def __init__(self, dim: int, n_heads: int, *, q_rank: int, kv_rank: int,
                 nope_dim: int, rope_dim: int, v_dim: int,
                 rope_base: float = 10000.0, yarn: Optional[dict] = None,
                 norm_eps: float = 1e-6, attn_fn: Optional[Callable] = None,
                 dtype=jnp.float32):
        self.dim, self.n_heads = dim, n_heads
        self.q_rank, self.kv_rank = q_rank, kv_rank
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.qk_dim = nope_dim + rope_dim
        self.entry_dim = kv_rank + rope_dim
        # a page stores an entry padded to whole lanes of 128: at a width
        # that is no multiple of 128 (576) the TPU lays the pool out with
        # the PAGE axis minor-most, and every step then copies the whole
        # pool into a layout it can gather from and back again
        self.page_width = -(-self.entry_dim // 128) * 128
        self.attn_fn = attn_fn or dense_attention
        self.dtype = dtype
        y = dict(yarn or {})
        factor = float(y.get("factor", 1.0))
        if factor > 1:
            self.inv_freq = yarn_inv_freq(
                rope_dim, rope_base, factor=factor,
                original_max=int(y["original_max_position_embeddings"]),
                beta_fast=float(y.get("beta_fast", 32)),
                beta_slow=float(y.get("beta_slow", 1)))
        else:
            self.inv_freq = rope_base ** (
                -jnp.arange(rope_dim // 2, dtype=jnp.float32) * 2.0 / rope_dim)
        # the family's two mscales: their ratio multiplies cos and sin,
        # the square of the all-dim one the softmax scale
        m_all = yarn_mscale(factor, float(y.get("mscale_all_dim", 0.0)))
        self.rope_mult = yarn_mscale(factor, float(y.get("mscale", 1.0))) \
            / m_all
        self.scale = self.qk_dim ** -0.5 * m_all * m_all
        self.q_a = Linear(dim, q_rank, bias=False, dtype=dtype)
        self.q_norm = RMSNorm(q_rank, norm_eps, dtype=dtype)
        self.q_b = Linear(q_rank, n_heads * self.qk_dim, bias=False,
                          dtype=dtype)
        self.kv_a = Linear(dim, self.entry_dim, bias=False, dtype=dtype)
        self.kv_norm = RMSNorm(kv_rank, norm_eps, dtype=dtype)
        self.kv_b = Linear(kv_rank, n_heads * (nope_dim + v_dim), bias=False,
                           dtype=dtype)
        self.out = Linear(n_heads * v_dim, dim, bias=False, dtype=dtype)

    def init(self, key) -> Params:
        ks = jax.random.split(key, 5)
        return {"q_a": self.q_a.init(ks[0]), "q_norm": self.q_norm.init(None),
                "q_b": self.q_b.init(ks[1]), "kv_a": self.kv_a.init(ks[2]),
                "kv_norm": self.kv_norm.init(None),
                "kv_b": self.kv_b.init(ks[3]), "out": self.out.init(ks[4])}

    # -- the parts ---------------------------------------------------------

    def project(self, params: Params, x, positions):
        """x (B, S, D), positions (S,) or (B, S) -> q_n (B, S, H, nope),
        q_r (B, S, H, rope) rotated, c (B, S, kv_rank) normalised, k_r
        (B, S, rope) rotated."""
        b, s, _ = x.shape
        with jax.named_scope("attn/q_latent"):
            q = self.q_b.apply(params["q_b"], self.q_norm.apply(
                params["q_norm"], self.q_a.apply(params["q_a"], x)))
            q = q.reshape(b, s, self.n_heads, self.qk_dim)
            q_n, q_r = q[..., :self.nope_dim], q[..., self.nope_dim:]
            q_r = rotate_interleaved(q_r, positions[..., None],
                                     self.inv_freq, self.rope_mult)
        with jax.named_scope("attn/kv_latent"):
            ckr = self.kv_a.apply(params["kv_a"], x)
            c = self.kv_norm.apply(params["kv_norm"],
                                   ckr[..., :self.kv_rank])
            k_r = rotate_interleaved(ckr[..., self.kv_rank:], positions,
                                     self.inv_freq, self.rope_mult)
        return q_n, q_r, c, k_r

    def page_entry(self, c, k_r):
        """What a page holds of one token: ``[c | k_r]``, then zeros up
        to ``page_width``."""
        pad = jnp.zeros(c.shape[:-1] + (self.page_width - self.entry_dim,),
                        c.dtype)
        return jnp.concatenate([c, k_r.astype(c.dtype), pad], axis=-1)

    def _kv_b(self, params):
        from ..ops.quant import resolve_weight
        w = resolve_weight(params["kv_b"], "w", self.dtype).reshape(
            self.kv_rank, self.n_heads, self.nope_dim + self.v_dim)
        return w[..., :self.nope_dim], w[..., self.nope_dim:]

    def expand(self, params: Params, c):
        """c (..., kv_rank) -> k_n (..., H, nope), v (..., H, v_dim)."""
        with jax.named_scope("attn/kv_latent"):
            kv = self.kv_b.apply(params["kv_b"], c).reshape(
                c.shape[:-1] + (self.n_heads, self.nope_dim + self.v_dim))
            return kv[..., :self.nope_dim], kv[..., self.nope_dim:]

    def project_out(self, params: Params, o):
        """o (B, S, H, v_dim) -> (B, S, D)."""
        with jax.named_scope("attn/out"):
            return self.out.apply(
                params["out"], o.reshape(o.shape[:2] + (-1,)))

    def _key_heads(self, k_n, k_r, v):
        """(B, H, S, qk) keys, the shared rotary part copied to every
        head, and (B, H, S, v_dim) values."""
        k = jnp.concatenate(
            [k_n, jnp.broadcast_to(k_r[:, :, None, :],
                                   k_n.shape[:-1] + (self.rope_dim,))],
            -1).transpose(0, 2, 1, 3)
        return k, v.transpose(0, 2, 1, 3)

    def _heads(self, q_n, q_r, k_n, k_r, v):
        """(B, H, S, qk) queries and keys, (B, H, S, v_dim) values."""
        q = jnp.concatenate([q_n, q_r], -1).transpose(0, 2, 1, 3)
        return (q,) + self._key_heads(k_n, k_r, v)

    def _core(self, q, k, v):
        """The pluggable causal core: queries and keys ``qk_dim`` wide,
        values and the result ``v_dim``. A core that says it takes values
        narrower than the keys (``narrow_values``: the dense einsum, the
        flash kernel) gets them as they are; any other (the ring paths
        accumulate at the queries' width) gets them padded to the keys'
        width, and its result is cut back."""
        with jax.named_scope("attn/core"):
            pad = self.qk_dim - self.v_dim
            if pad <= 0 or getattr(self.attn_fn, "narrow_values", False):
                return self.attn_fn(q, k, v, causal=True, scale=self.scale)
            vp = jnp.pad(v, ((0, 0),) * 3 + ((0, pad),))
            o = self.attn_fn(q, k, vp, causal=True, scale=self.scale)
            return o[..., :self.v_dim]

    def apply(self, params: Params, x, *, positions=None, **_):
        """The expanded form over a whole sequence."""
        if positions is None:
            positions = jnp.arange(x.shape[1])
        q_n, q_r, c, k_r = self.project(params, x, positions)
        k_n, v = self.expand(params, c)
        o = self._core(*self._heads(q_n, q_r, k_n, k_r, v))
        return self.project_out(params, o.transpose(0, 2, 1, 3))

    # -- the paged path: the module owns its page layout ----------------------

    def make_pages(self, n_pages: int, n_slots: int, page_len: int, bits,
                   dtype):
        if bits is not None:
            raise latent_unsupported("quantized pages (kv_dtype q8/q4)")
        return LatentPages(jnp.zeros((n_pages, 1, page_len, self.page_width),
                                     dtype))

    def absorb(self, params: Params, q_n, q_r):
        """Queries against page entries: ``[q_n W_k^T | q_r | 0]``,
        (B, S, H, page_width)."""
        with jax.named_scope("attn/absorb"):
            w_k, _ = self._kv_b(params)
            q_c = jnp.einsum("bshd,chd->bshc", q_n, w_k)
            pad = jnp.zeros(q_r.shape[:-1]
                            + (self.page_width - self.entry_dim,), q_r.dtype)
            return jnp.concatenate([q_c, q_r, pad], -1)

    def unabsorb(self, params: Params, o_c):
        """Attention's result in latent space (B, S, H, kv_rank) ->
        (B, S, H, v_dim)."""
        with jax.named_scope("attn/absorb"):
            _, w_v = self._kv_b(params)
            return jnp.einsum("bshc,chd->bshd", o_c, w_v)

    def decode_paged(self, params: Params, x, pages, ctx):
        """One token a row, absorbed. x (B, 1, D); pages: the layer's
        store. Returns (attention's output (B, 1, D), the store)."""
        q_n, q_r, c, k_r = self.project(params, x, ctx.idx[:, None])
        entry = self.page_entry(c, k_r)                        # (B, 1, E)
        with jax.named_scope("page_write"):
            pages = pages.write(entry, ctx.dest, ctx.wo)
        hq = self.absorb(params, q_n, q_r).transpose(0, 2, 1, 3)  # (B,H,1,E)
        o_c = pages.attend(ctx, hq, entry[:, None], self.scale, self.kv_rank)
        o = self.unabsorb(params, o_c.transpose(0, 2, 1, 3))
        return self.project_out(params, o), pages

    def prefill_paged(self, params: Params, x, pages, ctx):
        """The tail of one prompt, expanded, attending over [shared
        prefix pages | tail] at the traced ``ctx.offset``. A cold tail
        (offset 0, every admission of a mix that shares nothing) runs the
        pluggable causal core alone; a warm one widens the prefix entries
        through ``kv_b`` and scores densely, a chunk of queries at a
        time. One program serves both (``lax.cond``)."""
        q_n, q_r, c, k_r = self.project(params, x, ctx.positions)
        entry = self.page_entry(c, k_r)                        # (1, S, E)
        with jax.named_scope("page_write"):
            pages = pages.write(entry[0][:, None, :], ctx.dest, ctx.dest_off)
        k_n, v = self.expand(params, c)
        q, k, v = self._heads(q_n, q_r, k_n, k_r, v)           # (1, H, S, .)

        def cold(_):
            return self._core(q, k, v)

        def warm(_):
            with jax.named_scope("attn/core"):
                pre = pages.rows(ctx.table_row).astype(x.dtype)
                pk_n, pv = self.expand(params, pre[..., :self.kv_rank])
                pk, pv = self._key_heads(
                    pk_n, pre[..., self.kv_rank:self.entry_dim], pv)
                return masked_attention(
                    q[0], jnp.concatenate([pk[0], k[0]], 1),
                    jnp.concatenate([pv[0], v[0]], 1), ctx.mask,
                    self.scale)[None].astype(v.dtype)

        o = jax.lax.cond(ctx.offset > 0, warm, cold, None)
        return self.project_out(params, o.transpose(0, 2, 1, 3)), pages
