"""Manifold-constrained hyper-connections (mHC): the residual path as
``n`` parallel streams mixed by a doubly stochastic matrix.

Hyper-Connections (Zhu et al. 2024) widen the residual stream to ``n``
copies ``X`` (n, D) and give every sublayer ``F`` three learned,
input-dependent maps: ``H_pre`` (n) reads the sublayer's input out of the
streams, ``H_post`` (n) writes its output back, ``H_res`` (n, n) mixes the
streams among themselves:

    X' = H_res X + outer(H_post, F(H_pre X)).

mHC constrains ``H_res`` to the doubly stochastic matrices (rows and
columns sum to 1) by Sinkhorn-Knopp on ``exp`` of its logits, so a stack
of layers can neither blow the streams up nor let one die, and squashes
``H_pre`` to (0, 1) and ``H_post`` to (0, 2) with a logistic function.
The logits are an affine map of the RMS-normalised flattened streams,
``a (x~ P) + b``. Everything here is float32; the streams keep their own
type. Scopes: ``hc`` > ``coeffs``, ``sinkhorn``, ``mix``."""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from .core import Module, Params

_HI = jax.lax.Precision.HIGHEST


def sinkhorn(logits, iters: int, eps: float, clamp: Tuple[float, float]):
    """``logits`` (..., n, n) -> a doubly stochastic matrix of the same
    shape: ``exp`` of the clamped logits, then ``iters`` times each row
    divided by (its sum + eps) and each column by (its sum + eps). The
    token axes are moved last for the loop, so that every division is an
    elementwise pass over whole vectors of tokens."""
    with jax.named_scope("sinkhorn"):
        n = logits.shape[-1]
        m = jnp.exp(jnp.clip(logits.astype(jnp.float32), *clamp))
        m = jnp.moveaxis(m.reshape((-1, n, n)), 0, -1)          # (n, n, T)
        for _ in range(iters):
            m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)   # rows
            m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)   # columns
        return jnp.moveaxis(m, -1, 0).reshape(logits.shape)


class HyperConnection(Module):
    """The residual path of ONE sublayer over ``n`` streams of ``dim``."""

    def __init__(self, dim: int, n: int, *, sinkhorn_iters: int = 20,
                 eps: float = 1e-6, clamp: Tuple[float, float] = (-30., 30.)):
        self.dim, self.n = dim, n
        self.iters, self.eps, self.clamp = sinkhorn_iters, eps, clamp

    def init(self, key) -> Params:
        n, nd = self.n, self.n * self.dim
        k = jax.random.split(key, 3)
        small = lambda kk, w: 0.02 * jax.random.normal(kk, (nd, w))
        # at the start H_res is near the identity, every stream is read
        # alike and the sublayer's output is written to every stream once
        return {"p_pre": small(k[0], n), "p_post": small(k[1], n),
                "p_res": small(k[2], n * n),
                "a_pre": jnp.float32(0.01), "a_post": jnp.float32(0.01),
                "a_res": jnp.float32(0.01),
                "b_pre": jnp.zeros((n,)), "b_post": jnp.zeros((n,)),
                "b_res": 4.0 * jnp.eye(n)}

    def coeffs(self, params: Params, xs):
        """xs (..., n, D) -> H_pre (..., n), H_post (..., n), H_res
        (..., n, n), float32."""
        n = self.n
        lead = xs.shape[:-2]
        with jax.named_scope("coeffs"):
            flat = xs.reshape(lead + (n * self.dim,)).astype(jnp.float32)
            flat = flat * jax.lax.rsqrt(
                jnp.mean(jnp.square(flat), -1, keepdims=True) + self.eps)
            f32 = lambda name: params[name].astype(jnp.float32)
            dot = lambda name: jnp.matmul(flat, f32(name), precision=_HI)
            pre = f32("a_pre") * dot("p_pre") + f32("b_pre")
            post = f32("a_post") * dot("p_post") + f32("b_post")
            res = f32("a_res") * dot("p_res").reshape(lead + (n, n)) \
                + f32("b_res")
            h_pre = jax.nn.sigmoid(pre)
            h_post = 2.0 * jax.nn.sigmoid(post)
        return h_pre, h_post, sinkhorn(res, self.iters, self.eps, self.clamp)

    def apply(self, params: Params, xs, fn: Callable, **_):
        """xs (..., n, D); ``fn`` maps the sublayer's input (..., D) to
        its output (it holds its own norm). Returns the new streams, or
        ``(streams, aux)`` where ``fn`` returns ``(y, aux)``."""
        with jax.named_scope("hc"):
            h_pre, h_post, h_res = self.coeffs(params, xs)
            with jax.named_scope("mix"):
                # n is small: sums over streams are written out, so that
                # each mix is one elementwise pass and no reduction
                xf = [xs[..., j, :].astype(jnp.float32)
                      for j in range(self.n)]
                u = sum(h_pre[..., j, None] * xf[j]
                        for j in range(self.n)).astype(xs.dtype)
        out = fn(u)
        y, aux = out if isinstance(out, tuple) else (out, None)
        with jax.named_scope("hc"), jax.named_scope("mix"):
            mixed = sum(h_res[..., :, j, None] * xf[j][..., None, :]
                        for j in range(self.n))
            new = (mixed + h_post[..., None]
                   * y.astype(jnp.float32)[..., None, :]).astype(xs.dtype)
        return new if aux is None else (new, aux)
