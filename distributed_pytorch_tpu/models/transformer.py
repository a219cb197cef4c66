"""Transformer language model — the framework's flagship model.

Covers the reference ladder's 'nn.TransformerEncoder LM on WikiText-2' rung
(BASELINE.json) as a decoder-only causal LM (the modern equivalent of the
masked-encoder LM setup). Designed mesh-first: every parameter has a
tensor-parallel PartitionSpec (``parallel/tensor.py``), attention takes a
pluggable core so sequence parallelism (ring attention) drops in, and the
forward is pure static-shape jnp — one XLA program per step at any mesh
shape.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from ..nn.attention import MultiHeadAttention, TransformerBlock
from ..nn.block import Block
from ..nn.core import (Embedding, GatedMLP, LayerNorm, Linear, Module, Params,
                       RMSNorm)
from ..nn.latent import LatentAttention
from ..nn.linear_attention import LightningAttention
from ..nn.sparse_attention import Selection, SparseAttention

#: Named per-layer rematerialization policies (docs/compute.md).
#: ``none``  — save every activation (fastest step, most HBM);
#: ``full``  — ``jax.checkpoint`` the whole block: save only the block
#:             boundary, recompute the block in backward (~1/3 more
#:             forward FLOPs for O(n_layers) less activation HBM);
#: ``dots_saveable`` — ``jax.checkpoint_policies.dots_saveable``: save
#:             matmul outputs, recompute only the cheap elementwise
#:             chain (LN/GELU/softmax) — most of ``full``'s memory win
#:             at a fraction of its recompute.
REMAT_POLICIES = ("none", "full", "dots_saveable")


def resolve_remat(remat: Union[bool, str, None]) -> str:
    """Canonical policy name for a ``remat=`` argument: bools keep
    their historical meaning (False -> ``none``, True -> ``full``),
    ``None`` defers to the typed ``DPX_REMAT`` env knob, strings must
    name a member of :data:`REMAT_POLICIES`."""
    if remat is None:
        from ..runtime import env as _env
        remat = _env.get("DPX_REMAT")
    if remat is False:
        return "none"
    if remat is True:
        return "full"
    if remat not in REMAT_POLICIES:
        raise ValueError(
            f"remat must be a bool or one of {'|'.join(REMAT_POLICIES)}, "
            f"got {remat!r}")
    return remat


def apply_remat_policy(fn: Callable, policy: str) -> Callable:
    """Wrap a per-layer forward with the named checkpoint policy — the
    ONE place a policy name becomes a ``jax.checkpoint`` call, shared
    by :class:`TransformerLM` and any custom trainer that wants the
    same vocabulary. Unknown names raise (a typo'd policy silently
    becoming a different memory/recompute tradeoff is exactly what the
    typed vocabulary exists to stop); callers with bools/None resolve
    through :func:`resolve_remat` first."""
    if policy not in REMAT_POLICIES:
        raise ValueError(
            f"unknown remat policy {policy!r}; choose from "
            f"{'|'.join(REMAT_POLICIES)}")
    if policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn)
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)


#: positions a trip when a prompt's tail reads a global layer's resident
#: pages beside window layers (``nn.paged.KVPages.attend_tail``): the
#: scores of a 1024-token chunk of 64 heads are 134 MB in float32 at 512
TAIL_BLOCK = 512


def _with_bias(block_params, bias):
    """A block's parameters with its router's bias replaced."""
    ffn = block_params["ffn"]
    return {**block_params,
            "ffn": {**ffn, "router": {**ffn["router"], "bias": bias}}}


class TransformerLM(Module):
    """Decoder-only causal LM: tok+pos embed → N pre-norm blocks → LN →
    vocab projection.

    By default every block is the one fixed ``TransformerBlock``
    (LayerNorm, multi-head attention, a GELU MLP of ``mlp_ratio * dim``).
    Any of ``block_kinds``, ``attention="latent"``, ``norm="rms"`` or
    ``hyper_connections`` builds the blocks from parts instead
    (``nn/block.py``):

    - ``block_kinds``: one of ``"dense"`` (a gated SiLU MLP of
      ``ffn_dim``) or ``"moe"`` (a dropless expert layer,
      ``parallel.moe.DroplessMoE(dim, **moe)``) a layer; default all dense;
    - ``attention``: ``"mha"`` or ``"latent"``
      (``nn.latent.LatentAttention(dim, n_heads, **latent)``, which brings
      its own rotary part: ``pos`` must not be ``"learned"``);
    - ``norm``: ``"layer"`` or ``"rms"``, with ``norm_eps``;
    - ``hyper_connections``: the number of parallel residual streams
      (``nn/hyper.py``; 0 = the plain residual sum), ``hc`` their
      keyword arguments. The embedding is copied into every stream and
      the streams are summed before the final norm.

    ``layer_windows`` (blocks made of parts, ``attention="mha"``) mixes
    sliding-window and global layers in one model: a width a layer, or
    None where the layer sees every earlier position. Window layer ``l``
    attends to keys ``i - layer_windows[l] < j <= i`` and keeps, when
    served, a ring of its last entries a slot in place of pages
    (``nn.paged.WindowPages``); a global layer keeps pages and reads a
    prompt's resident prefix in blocks of ``TAIL_BLOCK`` positions.
    ``layer_rope`` (one bool a layer, with ``pos="rope"``) says which
    layers rotate q and k; the default rotates every layer. Such a model
    is served by the paged engine alone (``serve/pages/cache.py``).

    ``layer_mixers`` (blocks made of parts) says what mixes the positions
    of each layer in place of multi-head attention: ``"linear"``
    (``nn.linear_attention.LightningAttention(dim, n_heads, head_dim=...,
    **linear)``: one decaying state a head, no cache that grows) or
    ``"sparse"`` (``nn.sparse_attention.SparseAttention(...,
    select=Selection(**sparse), **sparse_kw)``: grouped-query attention
    over blocks it chooses, ``sparse`` the sizes of the choice, the other
    keys of the dict the module's own). ``pos="rope"`` makes the linear
    layers rotate (give ``linear=dict(rope=False)`` for none); a sparse
    layer rotates only when told (``sparse=dict(rope=True)``). Served,
    a linear layer keeps one state a slot and a sparse layer its pages and
    compressed keys (``nn.paged.StatePages`` / ``SelectedPages``), through
    the paged engine alone. ``emb_scale`` multiplies the embedding,
    ``branch_scale`` what each sublayer adds to the residual sum, and the
    final hidden states are divided by ``logit_scale`` before the head
    (muP's three scalings).

    ``mtp=1`` adds one multi-token-prediction module (DeepSeek-V3 section
    2.2; needs blocks made of parts and the plain residual sum): position
    ``i`` merges the embedding of token ``i + 1`` with the last block's
    output at ``i`` (a norm each, concatenated embedding first, a
    ``2 dim -> dim`` projection), runs one more block of the last layer's
    kind and a norm, and predicts token ``i + 2`` through the model's own
    head. :meth:`heads_hidden` is its entry, ``ops.losses.lm_mtp_loss``
    the loss over both heads; ``apply`` and serving never run it."""

    def __init__(self, vocab: int = 256, dim: int = 128, n_layers: int = 2,
                 n_heads: int = 4, max_seq: int = 512, mlp_ratio: int = 4,
                 dropout: float = 0.0, n_kv_heads: Optional[int] = None,
                 pos: str = "learned", rope_base: float = 10000.0,
                 tie_embeddings: bool = False,
                 attn_fn: Optional[Callable] = None,
                 remat: Union[bool, str, None] = False,
                 dtype=jnp.float32,
                 block_kinds: Optional[Sequence[str]] = None,
                 attention: str = "mha", latent: Optional[dict] = None,
                 norm: str = "layer", norm_eps: Optional[float] = None,
                 ffn_dim: Optional[int] = None, moe: Optional[dict] = None,
                 hyper_connections: int = 0, hc: Optional[dict] = None,
                 mtp: int = 0, head_dim: Optional[int] = None,
                 attn_bias: bool = True, qk_norm: Optional[float] = None,
                 gen_block: Optional[int] = None,
                 mask_id: Optional[int] = None,
                 layer_windows: Optional[Sequence[Optional[int]]] = None,
                 layer_rope: Optional[Sequence[bool]] = None,
                 layer_mixers: Optional[Sequence[str]] = None,
                 linear: Optional[dict] = None,
                 sparse: Optional[dict] = None,
                 emb_scale: float = 1.0, branch_scale: float = 1.0,
                 logit_scale: float = 1.0):
        if pos not in ("learned", "rope", "none"):
            raise ValueError(f"pos must be learned|rope|none, got {pos!r}")
        if attention not in ("mha", "latent"):
            raise ValueError(f"attention must be mha|latent, got {attention!r}")
        if norm not in ("layer", "rms"):
            raise ValueError(f"norm must be layer|rms, got {norm!r}")
        if attention == "latent" and pos == "learned":
            raise ValueError("latent attention rotates a part of its own "
                             "keys: pos must be 'rope' or 'none'")
        if gen_block is not None:
            if gen_block < 1 or mask_id is None:
                raise ValueError("gen_block needs a block length >= 1 and "
                                 "the mask_id unfilled positions hold")
            if attention != "mha" or mtp or pos == "learned":
                raise ValueError(
                    "a model that generates by blocks is built of "
                    "multi-head attention without a prediction module "
                    "(attention='mha', mtp=0) and pos='rope' or 'none'")
        self.gen_block, self.mask_id = gen_block, mask_id
        if layer_windows is not None or layer_rope is not None:
            if attention != "mha" or gen_block is not None or mtp:
                raise ValueError(
                    "layer_windows / layer_rope describe multi-head "
                    "attention layers of a model that yields a token a "
                    "step (attention='mha', gen_block=None, mtp=0)")
            if layer_rope is not None and pos != "rope":
                raise ValueError("layer_rope chooses among rotating "
                                 "layers: pos must be 'rope'")
            for name, per in (("layer_windows", layer_windows),
                              ("layer_rope", layer_rope)):
                if per is not None and len(per) != n_layers:
                    raise ValueError(f"{name} must have one entry for each "
                                     f"of {n_layers} layers, got {per}")
        if layer_mixers is not None:
            if (attention != "mha" or gen_block is not None or mtp
                    or layer_windows is not None or layer_rope is not None
                    or hyper_connections or pos == "learned"):
                raise ValueError(
                    "layer_mixers describes a model that yields a token a "
                    "step over the plain residual sum, without windows of "
                    "its own (attention='mha', gen_block=None, mtp=0, no "
                    "layer_windows / layer_rope / hyper_connections, "
                    "pos='rope' or 'none')")
            if (len(layer_mixers) != n_layers
                    or set(layer_mixers) - {"linear", "sparse"}):
                raise ValueError(
                    f"layer_mixers must name linear|sparse for each of "
                    f"{n_layers} layers, got {tuple(layer_mixers)}")
            if "sparse" in layer_mixers and not sparse:
                raise ValueError(
                    "layer_mixers names a sparse-attention layer: give "
                    "sparse=dict(kernel=..., stride=..., block=..., "
                    "topk=..., init_blocks=..., window=..., dense_len=...)")
        elif linear or sparse:
            raise ValueError("linear= / sparse= describe the layers that "
                             "layer_mixers names")
        #: "linear" or "sparse" a layer, or None for a model of multi-head
        #: (or latent) attention: what ``models.generate.refuse_mixers``
        #: and the paged pool read
        self.layer_mixers = None if layer_mixers is None \
            else tuple(layer_mixers)
        self.emb_scale, self.logit_scale = emb_scale, logit_scale
        #: a width a layer (None: global), or None for a model that was
        #: not told: what ``models.generate.layer_windows`` reads
        self.layer_windows = None if layer_windows is None \
            else tuple(layer_windows)
        self.vocab = vocab
        self.dim = dim
        self.n_layers = n_layers
        self.n_heads = n_heads
        # GQA: n_kv_heads < n_heads shrinks k/v projections and the
        # decode KV cache by the group factor (nn/attention.py)
        self.n_kv_heads = n_kv_heads if n_kv_heads is not None else n_heads
        self.max_seq = max_seq
        # named per-layer remat policy (REMAT_POLICIES); bools keep
        # their historical meaning, None defers to DPX_REMAT.
        # self.remat stays the truthy back-compat view of the policy.
        self.remat_policy = resolve_remat(remat)
        self.remat = self.remat_policy != "none"
        self.dtype = dtype
        # positional scheme: "learned" absolute table (the classic GPT-2
        # setup), "rope" rotary phases inside attention (no positional
        # parameters; extrapolates — nn/rotary.py), or "none"
        self.pos_kind = pos
        # dimension-aware table init (std 1/sqrt(dim)): behind the first
        # LayerNorm either scale trains, but with tied embeddings the
        # table IS the output projection and unit-std rows diverge
        self.tok = Embedding(vocab, dim, std=dim ** -0.5, dtype=dtype)
        self.pos = Embedding(max_seq, dim, std=dim ** -0.5, dtype=dtype) \
            if pos == "learned" else None
        self.attention = attention
        self.streams = int(hyper_connections)

        def make_norm(scope="norm"):
            kw = {} if norm_eps is None else {"eps": norm_eps}
            cls = RMSNorm if norm == "rms" else LayerNorm
            return cls(dim, dtype=dtype, scope=scope, **kw)

        mha = {} if (head_dim is None and attn_bias and qk_norm is None
                     and gen_block is None) \
            else dict(head_dim=head_dim, bias=attn_bias, qk_norm=qk_norm,
                      gen_block=gen_block)
        from_parts = (block_kinds is not None or attention != "mha"
                      or norm != "layer" or self.streams > 0 or bool(mha)
                      or layer_windows is not None or layer_rope is not None
                      or layer_mixers is not None or branch_scale != 1.0)
        if not from_parts:
            self.blocks = [
                TransformerBlock(dim, n_heads, mlp_ratio, causal=True,
                                 dropout=dropout, n_kv_heads=n_kv_heads,
                                 rope=(pos == "rope"), rope_base=rope_base,
                                 attn_fn=attn_fn, dtype=dtype)
                for _ in range(n_layers)
            ]
        else:
            kinds = tuple(block_kinds) if block_kinds is not None \
                else ("dense",) * n_layers
            if len(kinds) != n_layers or set(kinds) - {"dense", "moe"}:
                raise ValueError(f"block_kinds must name dense|moe for each "
                                 f"of {n_layers} layers, got {kinds}")
            if "moe" in kinds and not moe:
                raise ValueError("block_kinds names an expert layer: give "
                                 "moe=dict(n_routed=..., width=..., top_k=...)")

            select = {k: v for k, v in (sparse or {}).items()
                      if k in Selection._fields}

            def make_mixer(kind):
                hd = head_dim if head_dim is not None else dim // n_heads
                if kind == "linear":
                    return LightningAttention(
                        dim, n_heads, head_dim=hd, rope_base=rope_base,
                        qk_norm=qk_norm, dtype=dtype,
                        **{"rope": pos == "rope", **(linear or {})})
                return SparseAttention(
                    dim, n_heads, n_kv_heads=self.n_kv_heads, head_dim=hd,
                    select=Selection(**select), max_seq=max_seq,
                    rope_base=rope_base, qk_norm=qk_norm, dtype=dtype,
                    **{k: v for k, v in sparse.items() if k not in select})

            def make_attn(layer=None):
                if layer is not None and layer_mixers is not None:
                    return make_mixer(layer_mixers[layer])
                if attention == "latent":
                    return LatentAttention(
                        dim, n_heads, rope_base=rope_base, attn_fn=attn_fn,
                        dtype=dtype, **(latent or {}))
                kw, rope = dict(mha), pos == "rope"
                if layer is not None and layer_rope is not None:
                    rope = bool(layer_rope[layer])
                if layer is not None and layer_windows is not None:
                    w = layer_windows[layer]
                    kw.update(window=w,
                              tail_block=None if w is not None else TAIL_BLOCK)
                return MultiHeadAttention(
                    dim, n_heads, causal=True, n_kv_heads=n_kv_heads,
                    rope=rope, rope_base=rope_base,
                    attn_fn=attn_fn, dtype=dtype, **kw)

            def make_ffn(kind):
                if kind == "moe":
                    from ..parallel.moe import DroplessMoE
                    return DroplessMoE(dim, dtype=dtype, **moe)
                return GatedMLP(dim, ffn_dim or mlp_ratio * dim, dtype=dtype)

            def make_block(kind, layer=None):
                return Block(dim, norm1=make_norm(), attn=make_attn(layer),
                             norm2=make_norm(), ffn=make_ffn(kind),
                             streams=self.streams, hc=hc,
                             branch_scale=branch_scale)

            self.blocks = [make_block(kind, layer)
                           for layer, kind in enumerate(kinds)]
        if mtp not in (0, 1):
            raise ValueError(f"mtp must be 0 or 1 (the depth of the "
                             f"prediction module), got {mtp!r}")
        if mtp and (not from_parts or self.streams):
            raise ValueError("mtp=1 needs blocks made of parts "
                             "(block_kinds, attention, norm) and the plain "
                             "residual sum")
        self.mtp = None if not mtp else {
            "norm_e": make_norm(), "norm_h": make_norm(),
            "proj": Linear(2 * dim, dim, bias=False, dtype=dtype),
            "block": make_block(kinds[-1]), "norm": make_norm()}
        self.ln_f = make_norm(scope="ln_f")
        # tied embeddings (the GPT-2 recipe): the vocab projection reuses
        # the token table transposed — no head parameter exists
        self.tie_embeddings = tie_embeddings
        self.head = None if tie_embeddings \
            else Linear(dim, vocab, bias=False, dtype=dtype)

    def init(self, key) -> Params:
        ks = jax.random.split(key, self.n_layers + 3)
        p = {
            "tok": self.tok.init(ks[0]),
            "blocks": [b.init(k) for b, k in zip(self.blocks, ks[2:-1])],
            "ln_f": self.ln_f.init(ks[-1]),
        }
        if self.head is not None:
            p["head"] = self.head.init(ks[-1])
        if self.pos is not None:
            p["pos"] = self.pos.init(ks[1])
        if self.mtp is not None:
            km = jax.random.split(jax.random.fold_in(key, self.n_layers + 3),
                                  len(self.mtp))
            p["mtp"] = {name: part.init(k)
                        for (name, part), k in zip(self.mtp.items(), km)}
        return p

    def head_weight(self, params):
        """The (dim, vocab) vocab-projection matrix — the head's weight,
        or the transposed token table when ``tie_embeddings``; either may
        be int8-quantized (ops/quant.py). The input contract of
        ``ops.losses.fused_linear_cross_entropy``."""
        from ..ops.quant import resolve_weight
        if self.tie_embeddings:
            return resolve_weight(params["tok"], "emb", self.dtype).T
        return resolve_weight(params["head"], "w", self.dtype)

    @staticmethod
    def router_bias_mask(params):
        """A tree of bools shaped like ``params``, True at every expert
        layer's router bias: the leaves that live outside the optimizer
        (``parallel.Buffers(mask=...)``)."""
        return jax.tree_util.tree_map_with_path(
            lambda path, _: [getattr(k, "key", None) for k in path[-2:]]
            == ["router", "bias"], params)

    def router_metrics(self, params: Params, load):
        """A step's counters of the expert layers, float32 scalars, from
        ``load`` (layers, n_routed): ``moe_pairs_here`` (the pairs sent to
        the experts held here, all layers), ``moe_load_max`` and
        ``moe_load_mean`` (the largest and the mean load among the held
        experts of a layer), ``moe_bias_abs_max`` (the largest router
        bias, before this step's update), and how far a layer that holds
        a share got through its sorted pairs: ``moe_dispatch_blocks_run``
        of ``moe_dispatch_blocks`` (``DroplessMoE.dispatch_blocks``, all
        layers; a layer's row of ``load`` sums to the pairs it routed)."""
        ffn = next(b.ffn for b in self.blocks + (
            [self.mtp["block"]] if self.mtp else [])
            if getattr(b, "_sparse", False))
        held = load[:, ffn.first:ffn.first + ffn.count]
        here = held.astype(jnp.float32)
        run, blocks = ffn.dispatch_blocks(jnp.sum(load, -1),
                                          jnp.sum(held, -1))
        biases = [x for x, m in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(self.router_bias_mask(params))) if m]
        return {"moe_pairs_here": jnp.sum(here),
                "moe_load_max": jnp.max(here),
                "moe_load_mean": jnp.mean(here),
                "moe_bias_abs_max": jnp.max(jnp.abs(jnp.stack(biases))),
                "moe_dispatch_blocks_run": jnp.sum(run).astype(jnp.float32),
                "moe_dispatch_blocks": jnp.sum(blocks).astype(jnp.float32)}

    def balance_router_bias(self, params: Params, load, speed: float):
        """``params`` with every router bias moved by its layer's rule
        (``DroplessMoE.balance``) from ``load`` (layers, n_routed), the
        ``moe_load`` of ``heads_hidden``: the ``update`` of
        ``parallel.Buffers``. Copies no other leaf."""
        new = {**params, "blocks": list(params["blocks"])}
        slots = [(blk, new["blocks"], j) for j, blk in enumerate(self.blocks)]
        if self.mtp is not None:
            new["mtp"] = dict(params["mtp"])
            slots.append((self.mtp["block"], new["mtp"], "block"))
        # in the order ``heads_hidden`` stacks the loads
        sparse = [s for s in slots if getattr(s[0], "_sparse", False)]
        for (blk, holder, key), layer_load in zip(sparse, load):
            holder[key] = _with_bias(holder[key], blk.ffn.balance(
                holder[key]["ffn"]["router"]["bias"], layer_load, speed))
        return new

    def streams_in(self, x):
        """The embedding (..., D) as the blocks take it: copied into every
        residual stream (..., streams, D) under hyper-connections."""
        if not self.streams:
            return x
        return jnp.broadcast_to(x[..., None, :],
                                x.shape[:-1] + (self.streams, self.dim))

    def streams_out(self, x):
        """What the final norm takes: the sum of the streams."""
        if not self.streams:
            return x
        return jnp.sum(x.astype(jnp.float32), axis=-2).astype(x.dtype)

    def embed(self, params, tokens):
        """Token ids -> what the first block takes before the positional
        table and the streams: the embedding, times ``emb_scale``."""
        x = self.tok.apply(params["tok"], tokens)
        return x if self.emb_scale == 1.0 \
            else (x * self.emb_scale).astype(x.dtype)

    def head_input(self, x):
        """The final norm's output as the head takes it: divided by
        ``logit_scale``."""
        return x if self.logit_scale == 1.0 \
            else (x / self.logit_scale).astype(x.dtype)

    def project_vocab(self, params, x, out_dtype=None):
        """Hidden states (..., dim) → logits (..., vocab). Single source
        of truth for the output projection (training apply and the cached
        decode path both route through it). ``out_dtype``: the logits'
        type where it is not the operands' (a block step picks by
        confidence from float32 logits)."""
        with jax.named_scope("head"):
            return jnp.matmul(self.head_input(x), self.head_weight(params),
                              preferred_element_type=out_dtype)

    def apply(self, params: Params, tokens, *, rng=None, train: bool = False,
              pos_offset=0, positions=None, return_hidden: bool = False,
              **_):
        """tokens: (B, S) int32 → logits (B, S, vocab).

        ``pos_offset`` shifts position ids — under sequence parallelism each
        device holds a local block whose global positions start at
        ``axis_index(sp) * S_local``. ``positions`` (S,) int overrides the
        ids entirely — the contract for PERMUTED token layouts
        (``parallel.sequence.stripe_tokens``: pass the striped ids so
        RoPE/learned embeddings see each token's true position).

        ``return_hidden=True`` returns the post-final-norm hidden states
        (B, S, dim) *instead of* logits, skipping the vocab projection — the
        input contract of ``ops.losses.fused_linear_cross_entropy`` (pass
        ``model.head_weight(params)`` as its weight), which streams the projection
        chunkwise so the full (B, S, vocab) logits never materialize."""
        x, _ = self._trunk(params, tokens, rng=rng, train=train,
                           pos_offset=pos_offset, positions=positions)
        x = self.ln_f.apply(params["ln_f"], x)
        if return_hidden:
            return self.head_input(x)
        return self.project_vocab(params, x)

    def _run_block(self, blk, p, x, **kw):
        """One block under the model's remat policy -> ``(x, load)``: an
        expert layer's block hands out the pairs its router sent to each
        expert (as an output, so that they survive its
        rematerialisation); None for every other block. One name for
        every layer: readers of a trace sum over layers, and XLA may
        still share their computations."""
        def run_block(p, x):
            with jax.named_scope("blocks"):
                out = blk.apply(p, x, **kw)
            return out if isinstance(out, tuple) else (out, None)

        # per-layer remat policy: "full" recomputes the block in
        # backward instead of saving its activations (~1/3 more
        # FLOPs for O(n_layers) less activation HBM, buying batch
        # size on memory-bound configs); "dots_saveable" keeps the
        # matmul outputs and recomputes only the elementwise chain
        return apply_remat_policy(run_block, self.remat_policy)(p, x)

    def _trunk(self, params, tokens, *, rng=None, train=False, pos_offset=0,
               positions=None):
        """Embedding and blocks: what the final norm takes, and each
        expert layer's pairs per expert, in layer order."""
        s = tokens.shape[1]
        x = self.embed(params, tokens)
        if positions is None:
            positions = pos_offset + jnp.arange(s)
        if self.pos is not None:
            x = x + self.pos.apply(params["pos"], positions)
        x = self.streams_in(x)
        loads = []
        for i, blk in enumerate(self.blocks):
            r = jax.random.fold_in(rng, i) if rng is not None else None
            x, load = self._run_block(
                blk, params["blocks"][i], x, rng=r, train=train,
                positions=positions)
            if load is not None:
                loads.append(load)
        return self.streams_out(x), loads

    def heads_hidden(self, params: Params, tokens, *, rng=None,
                     train: bool = False):
        """What a loss over both heads needs, without logits: tokens
        (B, S + 1) -> the main head's hidden states (B, S, dim) after the
        final norm (position ``i`` predicts token ``i + 1``), the
        prediction module's (B, S - 1, dim) after its own norm (position
        ``i`` predicts token ``i + 2``; None with ``mtp=0``), and the
        pairs each expert layer's router sent to each expert, (layers,
        n_routed) int32, the module's layer last (None without expert
        layers). Both go through ``head_weight(params)``
        (``ops.losses.lm_mtp_loss``).

        The module runs over all S positions, so that its sequence is the
        trunk's (and a length the attention kernel tiles); the last
        position, which has no token ``i + 2`` to predict, is left out of
        its experts' dispatch and load and cut from the result. Under
        causal attention no other position sees it."""
        s = tokens.shape[1] - 1
        positions = jnp.arange(s)
        h, loads = self._trunk(params, tokens[:, :-1], rng=rng, train=train,
                               positions=positions)
        main = self.head_input(self.ln_f.apply(params["ln_f"], h))
        mtp = None
        if self.mtp is not None:
            m, p = self.mtp, params["mtp"]
            live = jnp.broadcast_to(positions < s - 1, tokens[:, 1:].shape)
            # the module is one more layer: its parts read mtp/blocks/...
            # in a trace (``_run_block`` opens ``blocks`` itself)
            with jax.named_scope("mtp"):
                with jax.named_scope("blocks"), jax.named_scope("merge"):
                    e = self.tok.apply(params["tok"], tokens[:, 1:])
                    x = m["proj"].apply(p["proj"], jnp.concatenate(
                        [m["norm_e"].apply(p["norm_e"], e),
                         m["norm_h"].apply(p["norm_h"], h)], -1))
                x, load = self._run_block(
                    m["block"], p["block"], x, positions=positions,
                    row_mask=live)
                if load is not None:
                    loads.append(load)
                with jax.named_scope("blocks"):
                    mtp = self.head_input(
                        m["norm"].apply(p["norm"], x[:, :-1]))
        return main, mtp, (jnp.stack(loads) if loads else None)
