"""Loss functions (the reference uses ``nn.CrossEntropyLoss()``,
``min_DDP.py:75``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def cross_entropy_per_example(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Per-example softmax cross-entropy, labels as int class ids.

    ``logits``: (..., C); ``labels``: (...). Matches torch
    ``CrossEntropyLoss(reduction='none')`` numerics (log-softmax gather)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(
        logits, labels[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    return logz - true_logit


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean cross-entropy — torch ``CrossEntropyLoss()`` default reduction."""
    return jnp.mean(cross_entropy_per_example(logits, labels))


def fused_linear_cross_entropy(hidden: jnp.ndarray, w: jnp.ndarray,
                               labels: jnp.ndarray, *,
                               chunk_rows: int = 512) -> jnp.ndarray:
    """Mean CE of ``softmax(hidden @ w)`` vs ``labels`` without ever
    materializing the full ``(N, vocab)`` logits.

    For a language model the vocab projection dominates activation memory:
    at batch 8 x seq 1024 x vocab 32k the logits are 1 GiB in f32, and the
    standard loss keeps them (plus their cotangent) live across the whole
    backward. This streams row chunks through a ``lax.scan`` whose body is
    ``jax.checkpoint``-ed, so the forward saves only the scan inputs and the
    backward recomputes one ``(chunk, vocab)`` logits tile at a time —
    activation memory drops from O(N*V) to O(chunk*V), buying batch size
    (and therefore MFU) on memory-bound configs.

    Each chunk is still a ``(chunk, d) @ (d, vocab)`` matmul — large enough
    to keep the MXU saturated (use ``chunk_rows`` >= 256). The matmul
    accumulates in f32 (``preferred_element_type``), which for bf16 inputs
    is *more* precise than the unfused bf16-logits path at identical MXU
    cost.

    ``hidden``: (..., d); ``w``: (d, vocab) — the (in, out) layout of
    ``nn.core.Linear``; ``labels``: integer ids, shape ``hidden.shape[:-1]``.
    """
    d = hidden.shape[-1]
    h = hidden.reshape(-1, d)
    y = labels.reshape(-1).astype(jnp.int32)
    n = h.shape[0]
    c = min(int(chunk_rows), n)
    n_chunks = -(-n // c)
    pad = n_chunks * c - n
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, d), h.dtype)])
        y = jnp.concatenate([y, jnp.zeros((pad,), y.dtype)])
    valid = (jnp.arange(n_chunks * c) < n).astype(jnp.float32)

    def body(total, inp):
        h_i, y_i, m_i = inp
        logits = jnp.matmul(h_i, w, preferred_element_type=jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        true_logit = jnp.take_along_axis(logits, y_i[:, None], axis=-1)[:, 0]
        return total + jnp.sum((logz - true_logit) * m_i), None

    total, _ = lax.scan(
        jax.checkpoint(body),
        jnp.zeros((), jnp.float32),
        (h.reshape(n_chunks, c, d), y.reshape(n_chunks, c),
         valid.reshape(n_chunks, c)))
    return total / n


def lm_mtp_loss(model, params, tokens, *, weight: float):
    """Next-token loss plus ``weight`` times the multi-token-prediction
    module's (DeepSeek-V3 section 2.2), each a mean over its own
    positions: tokens (B, S + 1) -> ``(loss, aux)``.

    Both heads go through :func:`fused_linear_cross_entropy` with
    ``model.head_weight(params)``, so no ``(B, S, vocab)`` logits are
    formed, once or twice (scopes ``loss`` > ``main`` and ``loss`` >
    ``mtp``); the shared head and embedding get both heads' gradients.
    ``model`` is a ``TransformerLM(mtp=1)`` (``heads_hidden``). ``aux``
    holds float32 scalars ``loss_main`` and ``loss_mtp`` and, where the
    model has expert layers, ``moe_load`` (layers, n_routed) int32, which
    a bias rule reads (``TransformerLM.balance_router_bias``), and the
    scalars of ``TransformerLM.router_metrics``."""
    main, mtp, load = model.heads_hidden(params, tokens, train=True)
    w = model.head_weight(params)
    with jax.named_scope("loss"):
        with jax.named_scope("main"):
            loss_main = fused_linear_cross_entropy(main, w, tokens[:, 1:])
        with jax.named_scope("mtp"):
            loss_mtp = fused_linear_cross_entropy(mtp, w, tokens[:, 2:])
    aux = {"loss_main": loss_main, "loss_mtp": loss_mtp}
    if load is not None:
        aux.update(moe_load=load, **model.router_metrics(params, load))
    return loss_main + weight * loss_mtp, aux


def vocab_parallel_cross_entropy(logits_local: jnp.ndarray, labels,
                                 *, axis_name: str = "tp") -> jnp.ndarray:
    """Per-example CE from VOCAB-SHARDED logits — call inside
    ``shard_map`` with each device holding its contiguous
    ``(..., V/n)`` vocab slice (shard r owns ids ``[r*V/n, (r+1)*V/n)``,
    the layout ``P(..., tp)`` produces). ``labels`` are GLOBAL ids.

    The Megatron-LM vocab-parallel loss: the full (..., V) logits are
    never gathered — two scalar-per-row collectives (a pmax for the
    stabilizer, ONE fused psum of local sum-exp, masked target logit,
    and label-ownership count) replace the O(V) all-gather XLA would
    otherwise insert between a tp-sharded head and an unsharded loss.
    The max is detached (mathematically the logsumexp shift cancels in
    the gradient), so gradients flow only through differentiable psums
    — exactness vs the gathered loss is pinned by tests/test_models.py.
    A label no shard owns (out-of-range ids such as -100 padding)
    yields NaN, matching the gathered path — silent finite garbage
    would corrupt training instead of surfacing the masking bug."""
    from ..comm import primitives as prim

    v_loc = logits_local.shape[-1]
    my = prim.axis_index(axis_name)
    offset = my * v_loc
    lf = logits_local.astype(jnp.float32)
    # stop_gradient BEFORE the pmax: the stabilizer shift cancels in the
    # gradient mathematically, and pmax has no differentiation rule —
    # a zero-tangent operand keeps it out of the linearized graph
    gmax = prim.pmax(
        jax.lax.stop_gradient(jnp.max(lf, axis=-1)), axis_name)
    loc = labels.astype(jnp.int32) - offset
    in_shard = (loc >= 0) & (loc < v_loc)
    loc_c = jnp.clip(loc, 0, v_loc - 1)
    tgt_local = jnp.take_along_axis(lf, loc_c[..., None], axis=-1)[..., 0]
    # one all-reduce for all three per-row scalars (psum takes a pytree)
    denom, tgt, owned = prim.psum(
        (jnp.sum(jnp.exp(lf - gmax[..., None]), axis=-1),
         jnp.where(in_shard, tgt_local, 0.0),
         in_shard.astype(jnp.float32)), axis_name)
    loss = jnp.log(denom) + gmax - tgt
    return jnp.where(owned > 0, loss, jnp.float32(jnp.nan))


def make_vocab_parallel_ce_fn(mesh, *, dp: str = "dp", tp: str = "tp"):
    """``fn(hidden, head_w, labels) -> per-example CE`` fusing the vocab
    projection INTO the tp island: hidden (B, S, D) replicated over tp,
    ``head_w`` (D, V) sharded ``P(None, tp)`` (the TransformerLM head
    layout), labels (B, S) global ids. Each device computes only its
    (B, S, V/n) logits slice and the loss reduces with scalar-per-token
    collectives — the (B, S, V) logits never exist on any device, in
    forward or backward. The GSPMD alternative (plain
    ``cross_entropy_per_example`` on a sharded head) all-gathers the
    full logits; at B8 x S1024 x V32k that is 1 GiB per step."""
    from jax.sharding import PartitionSpec as P

    def island(hidden, w_local, labels):
        logits_local = jnp.matmul(hidden, w_local,
                                  preferred_element_type=jnp.float32)
        return vocab_parallel_cross_entropy(logits_local, labels,
                                            axis_name=tp)

    def fn(hidden, head_w, labels):
        return jax.shard_map(
            island, mesh=mesh,
            in_specs=(P(dp, None, None), P(None, tp), P(dp, None)),
            out_specs=P(dp, None), check_vma=False)(hidden, head_w,
                                                    labels)
    return fn
