"""A transformer block made of parts: a norm, an attention module, a
feed-forward module and a residual path.

``TransformerBlock`` (``nn/attention.py``) is one fixed class: LayerNorm,
multi-head attention, a GELU MLP, ``x + f(x)``. This block takes each of
the four as an object, so that a model can say per layer what it is made
of (``models/transformer.py`` ``block_kinds``): RMSNorm or LayerNorm;
any attention module that has ``apply`` and hands out its page store
(``make_pages`` / ``decode_paged`` / ``prefill_paged``, and
``block_paged`` where the model generates by blocks: ``nn/latent.py``,
``nn/attention.py``, ``nn/paged.py``); a gated MLP of any width or a
dropless expert layer (``parallel/moe.py``); the plain residual sum or
``streams`` parallel residual streams under hyper-connections
(``nn/hyper.py``), in which case the block's input and output are
(B, S, streams, D); ``branch_scale`` multiplies what each sublayer adds
to the plain residual sum (muP's depth scaling, ``x + s * f(norm(x))``)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .core import Module, Params
from .hyper import HyperConnection


class Block(Module):
    """Pre-norm block: ``x <- R1(x, attn . norm1)``, ``x <- R2(x, ffn .
    norm2)``, with ``R`` the residual path."""

    def __init__(self, dim: int, *, norm1: Module, attn: Module,
                 norm2: Module, ffn: Module, streams: int = 0,
                 hc: Optional[dict] = None, branch_scale: float = 1.0):
        if streams and branch_scale != 1.0:
            raise ValueError("branch_scale scales the plain residual sum; "
                             "hyper-connections mix their streams by "
                             "learned weights")
        self.dim, self.streams = dim, streams
        self.branch_scale = branch_scale
        self.ln1, self.attn, self.ln2, self.ffn = norm1, attn, norm2, ffn
        self.hc1 = HyperConnection(dim, streams, **(hc or {})) \
            if streams else None
        self.hc2 = HyperConnection(dim, streams, **(hc or {})) \
            if streams else None
        # an expert layer takes a row mask and reports its counts
        self._sparse = hasattr(ffn, "routed")

    def init(self, key) -> Params:
        ks = jax.random.split(key, 6)
        p = {"ln1": self.ln1.init(ks[0]), "attn": self.attn.init(ks[1]),
             "ln2": self.ln2.init(ks[2]), "ffn": self.ffn.init(ks[3])}
        if self.streams:
            p["hc1"] = self.hc1.init(ks[4])
            p["hc2"] = self.hc2.init(ks[5])
        return p

    def _residual(self, hc, params, x, fn):
        """``fn`` maps the sublayer's input to its output or to (output,
        aux); the residual path decides what the input is and where the
        output goes."""
        if hc is not None:
            return hc.apply(params, x, fn)
        out = fn(x)
        if self.branch_scale != 1.0:
            scaled = lambda y: (self.branch_scale * y).astype(y.dtype)
            out = (scaled(out[0]), out[1]) if isinstance(out, tuple) \
                else scaled(out)
        return (x + out[0], out[1]) if isinstance(out, tuple) else x + out

    def _ffn(self, params: Params, x, row_mask=None, moe_stats=None):
        """-> ``(x, load)``; ``load`` is None where the layer is dense."""
        def fn(u):
            h = self.ln2.apply(params["ln2"], u)
            if self._sparse:
                return self.ffn.apply(params["ffn"], h, row_mask=row_mask,
                                      stats=moe_stats)
            return self.ffn.apply(params["ffn"], h)
        out = self._residual(self.hc2, params.get("hc2"), x, fn)
        return out if self._sparse else (out, None)

    def apply(self, params: Params, x, *, positions=None, row_mask=None,
              **_):
        """-> ``(x, load)``: the pairs this call's router sent to each
        expert (``parallel.moe.DroplessMoE.apply``), None for a dense
        layer; ``row_mask`` (B, S) bool leaves positions out of the
        experts' dispatch and of the load."""
        x = self._residual(
            self.hc1, params.get("hc1"), x,
            lambda u: self.attn.apply(params["attn"],
                                      self.ln1.apply(params["ln1"], u),
                                      positions=positions))
        return self._ffn(params, x, row_mask)

    def _with_pages(self, step, params, x, pages, ctx, row_mask):
        x, pages = self._residual(
            self.hc1, params.get("hc1"), x,
            lambda u: step(params["attn"], self.ln1.apply(params["ln1"], u),
                           pages, ctx))
        return self._ffn(params, x, row_mask, ctx.moe_stats)[0], pages

    def decode_paged(self, params: Params, x, pages, ctx):
        """x (B, 1[, streams], D), this layer's page store -> (x, the
        store written). Idle slots are left out of the expert dispatch."""
        return self._with_pages(self.attn.decode_paged, params, x, pages, ctx,
                           ctx.active[:, None])

    def prefill_paged(self, params: Params, x, pages, ctx):
        """x (1, S[, streams], D): the padded tail of one prompt."""
        return self._with_pages(self.attn.prefill_paged, params, x, pages, ctx,
                           ctx.row_mask[None, :])

    def block_paged(self, params: Params, x, pages, ctx):
        """x (B, L[, streams], D): one pass over every row's block of a
        model that generates by blocks (``nn.paged.BlockCtx``). Idle
        slots are left out of the expert dispatch."""
        return self._with_pages(self.attn.block_paged, params, x, pages, ctx,
                           jnp.broadcast_to(ctx.active[:, None],
                                            x.shape[:2]))
