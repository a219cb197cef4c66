"""Lightning (linear) attention: a layer whose memory of the past is ONE
STATE a head, ``S_t = decay_h * S_{t-1} + k_t v_t^T`` (``Dk x Dv``,
float32), read by ``o_t = scale * q_t^T S_t``. It does not grow with the
context: a served request keeps one state a layer (``nn.paged.StatePages``)
and no pages.

Over a chunk of C positions from the state ``S`` before it (``i``, ``j``
count from 1, ``L = decay``):

    o_i = scale * q_i^T (L^i S + sum_{j<=i} L^(i-j) k_j v_j^T)
        = scale * [ ((Q K^T) * M) V + diag(L^1 .. L^C) Q S ]_i,
                                   M_ij = L^(i-j) for j <= i, else 0
    S'  = L^C S + sum_j L^(C-j) k_j v_j^T

(unroll the recurrence: ``S_i = L^i S + sum_{j<=i} L^(i-j) k_j v_j^T``).
``apply`` (a whole sequence from the zero state), ``prefill_paged`` (a
prompt's chunk from its slot's state) and the reference's plain recurrence
are the same sums in another order; ``M`` is formed from ``i - j``
directly, never as ``L^i * L^-j`` (a fast head's ``L^-j`` overflows).

The decay is Lightning Attention-2's slope, one a head and no layer
factor: ``decay_h = exp(-2^(-8 h / H))``, ``h = 1 .. H``. The products
that touch the state are made at ``Precision.HIGHEST`` (a float32 operand
of a default product is rounded to bfloat16 on a TPU)."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .attention import MultiHeadAttention, _scopes
from .core import Linear, Params, RMSNorm

#: positions a step of the scan over a sequence or a prompt's chunk: the
#: masked product is (H, SCAN_CHUNK, SCAN_CHUNK) float32 a step
SCAN_CHUNK = 256

_HIGHEST = jax.lax.Precision.HIGHEST


def head_decays(n_heads: int):
    """``log(decay_h)`` (H,) float32: ``-2^(-8 h / H)``, h = 1 .. H."""
    return -(2.0 ** (-8.0 * jnp.arange(1, n_heads + 1, dtype=jnp.float32)
                     / n_heads))


def chunk_scan(q, k, v, state, n, log_decay, scale):
    """One chunk of the recurrence, in its chunk form (module docstring):
    q, k, v (H, C, D) at the chunk's C positions, of which the first ``n``
    (traced, 0 .. C) are real; ``state`` (H, Dk, Dv) float32 before the
    chunk. Returns (o (H, C, Dv) float32, the state after the ``n``-th
    position). A row past ``n`` adds nothing to the state and its output
    is read by no one."""
    c = q.shape[1]
    i = jnp.arange(c)
    real = (i < n)[None, :, None]
    k, v = jnp.where(real, k, 0), jnp.where(real, v, 0)
    ld = log_decay[:, None, None]
    with jax.named_scope("intra"):
        back = i[:, None] - i[None, :]
        mask = jnp.where(back >= 0, jnp.exp(ld * jnp.maximum(back, 0)), 0.0)
        sc = jnp.einsum("hid,hjd->hij", q, k,
                        preferred_element_type=jnp.float32) * mask
        o = jnp.einsum("hij,hjd->hid", sc.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
    with jax.named_scope("state"):
        o = o + jnp.einsum("hid,hde->hie", q.astype(jnp.float32), state,
                           precision=_HIGHEST) \
            * jnp.exp(ld * (i + 1)[None, :, None])
        wk = jnp.exp(ld * jnp.maximum(n - 1 - i, 0)[None, :, None])
        state = jnp.exp(ld * n) * state + jnp.einsum(
            "hjd,hje->hde", k.astype(jnp.float32) * wk,
            v.astype(jnp.float32), precision=_HIGHEST)
    return o * scale, state


def scan_sequence(q, k, v, state, n, log_decay, scale, chunk=SCAN_CHUNK):
    """:func:`chunk_scan` over a sequence (H, S, D) in steps of ``chunk``
    positions, the state carried from step to step; ``n`` real positions
    (traced). S is padded up to whole steps. -> (o (H, S, Dv), state)."""
    h, s, d = q.shape
    c = min(chunk, s)
    steps = -(-s // c)
    if steps * c != s:
        pad = ((0, 0), (0, steps * c - s), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    split = lambda t: jnp.moveaxis(t.reshape(h, steps, c, t.shape[-1]), 1, 0)

    def step(state, xs):
        qc, kc, vc, u = xs
        o, state = chunk_scan(qc, kc, vc, state, jnp.clip(n - u * c, 0, c),
                              log_decay, scale)
        return state, o

    state, o = jax.lax.scan(step, state, (split(q), split(k), split(v),
                                          jnp.arange(steps)))
    return jnp.moveaxis(o, 0, 1).reshape(h, steps * c, -1)[:, :s], state


class LightningAttention(MultiHeadAttention):
    """``y = W_o(sigmoid(W_g u) * rms_o(o))`` with ``o`` the linear
    attention of the module docstring over ``q = rope(rms_q(W_q u))``,
    ``k = rope(rms_k(W_k u))``, ``v = W_v u``: ``n_heads`` heads of
    ``head_dim``, as many key/value heads, no biases. The projections,
    the q/k norms (``qk_norm``, an epsilon or None) and the rotation
    (``rope``, rotate-half, ``rope_base``) are multi-head attention's own
    (``project_qkv``, ``maybe_rope``); ``out_norm`` (an epsilon, or None)
    is an RMSNorm over each head's ``head_dim`` outputs with one learned
    scale; ``out_gate`` the logistic gate ``dim -> n_heads * head_dim``;
    ``scale`` multiplies the read (default ``head_dim^-1/2``)."""

    def __init__(self, dim: int, n_heads: int, *, head_dim: int,
                 rope: bool = True, rope_base: float = 10000.0,
                 qk_norm: Optional[float] = 1e-6,
                 out_norm: Optional[float] = 1e-6, out_gate: bool = True,
                 scale: Optional[float] = None, dtype=jnp.float32):
        super().__init__(dim, n_heads, causal=True, rope=rope,
                         rope_base=rope_base, dtype=dtype, head_dim=head_dim,
                         bias=False, qk_norm=qk_norm)
        self.scale = scale if scale is not None else 1.0 / math.sqrt(head_dim)
        self.log_decay = head_decays(n_heads)
        self.gate = Linear(dim, n_heads * head_dim, bias=False, dtype=dtype) \
            if out_gate else None
        self.o_norm = None if out_norm is None \
            else RMSNorm(head_dim, eps=out_norm, dtype=dtype)

    def init(self, key) -> Params:
        p = super().init(key)
        if self.gate is not None:
            p["gate"] = self.gate.init(jax.random.fold_in(key, 1))
        if self.o_norm is not None:
            p["o_norm"] = self.o_norm.init(key)
        return p

    def project_out(self, params: Params, o, x):
        """o (B, H, S, Dh) float32, x the layer's input (the gate's) ->
        (B, S, D): the output norm, the gate, the projection."""
        b, h, s, dh = o.shape
        with jax.named_scope("attn/out"):
            if self.o_norm is not None:
                o = self.o_norm.apply(params["o_norm"], o)
            o = o.astype(x.dtype).transpose(0, 2, 1, 3).reshape(b, s, h * dh)
            if self.gate is not None:
                o = jax.nn.sigmoid(self.gate.apply(params["gate"], x)) * o
            return self.out.apply(params["out"], o)

    def apply(self, params: Params, x, *, positions=None, **_):
        s = x.shape[1]
        q, k, v = self.project_qkv(params, x)
        q, k = self.maybe_rope(q, k, positions)
        zero = jnp.zeros((self.n_heads, self.head_dim, self.head_dim),
                         jnp.float32)
        with _scopes("attn/core", "linear_attention"):
            o = jax.vmap(lambda qq, kk, vv: scan_sequence(
                qq, kk, vv, zero, s, self.log_decay, self.scale)[0])(q, k, v)
        return self.project_out(params, o, x)

    # -- the paged path: one state a slot (nn/paged.py StatePages) ---------

    def make_pages(self, n_pages: int, n_slots: int, page_len: int, bits,
                   dtype):
        from .paged import StatePages, mixers_unsupported
        if bits is not None:
            raise mixers_unsupported(StatePages.LACKS["quantized"])
        return StatePages.zeros(
            (self.n_heads, self.head_dim, self.head_dim), n_slots)

    def decode_paged(self, params: Params, x, pages, ctx):
        """One token a row: one step of the recurrence on every active
        row's state, in place. x (B, 1, D) normed -> ((B, 1, D), the
        store written)."""
        q, k, v = self.project_qkv(params, x)
        q, k = self.maybe_rope(q, k, ctx.idx[:, None, None])
        with _scopes("decode_attention", "linear_attention", "state"):
            pages = pages.step(k[:, :, 0], v[:, :, 0],
                               jnp.exp(self.log_decay), ctx.active)
            o = jnp.einsum("bhd,bhde->bhe", q[:, :, 0].astype(jnp.float32),
                           pages.s, precision=_HIGHEST) * self.scale
        return self.project_out(params, o[:, :, None], x), pages

    def prefill_paged(self, params: Params, x, pages, ctx):
        """A prompt's chunk from its slot's state: x (1, S, D) normed ->
        ((1, S, D), the store with the state after the chunk's last real
        row)."""
        q, k, v = self.project_qkv(params, x)
        q, k = self.maybe_rope(q, k, ctx.positions)
        with _scopes("attn/core", "linear_attention"):
            with jax.named_scope("state"):
                state = pages.read(ctx.slot)
            o, state = scan_sequence(q[0], k[0], v[0], state, ctx.true_len,
                                     self.log_decay, self.scale)
            with jax.named_scope("state"):
                pages = pages.write(ctx.slot, state)
        return self.project_out(params, o[None], x), pages
