"""Expert parallelism: two mixture-of-experts layers, and why both stand.

**Which layer is which.** :class:`MoELayer` is the *training example's*
layer (``models/moe_lm.py``, ``examples/train_moe_lm.py``): Switch/GShard
routing as dense tensor algebra, a one-hot ``(tokens, experts, capacity)``
dispatch with a capacity factor, tokens over capacity dropped, GELU
experts with biases, softmax gates and the auxiliary losses a trainer
needs. Its shapes are static and its exchange over an ``ep`` mesh axis
falls out of the SPMD partitioner, which is what a trained-from-scratch
example wants. :class:`DroplessMoE` is the layer published sparse models
have (``nn/block.py``, ``models/transformer.py`` ``block_kinds``), served
and trained: sigmoid
scores with a bias-corrected top-k over all routed experts, the chosen
(token, expert) pairs sorted by expert, one grouped matmul a projection
(``jax.lax.ragged_dot``; on a TPU, in a program that takes no gradient
of it, the Mosaic kernel of ``ops/grouped_matmul_kernel.py``, which
copies each touched expert's weights once) over the experts this chip
holds, the results
gathered back and weighted (a chip that holds a SHARE of the experts
gathers, multiplies and adds back only the blocks of sorted pairs that
hold a pair of its own: ``DroplessMoE._pairs_here``), one shared SwiGLU
expert added. No capacity,
no token dropped, none padded into an expert it did not choose, and no
one-hot tensor: a decode step reads only the experts its batch touches.
Published sparse models route this way and a served model must compute
what it was trained to compute, so the capacity layer cannot stand in
for it. It trains through ``jax.lax.ragged_dot``'s own derivatives (``dx``
another grouped matmul, ``dW`` a product whose contracting dimension is
the ragged one; :func:`grouped_matmul` zeroes the rows of ``dx`` that no
group's kernel writes) and balances its experts without an auxiliary loss: the
router's bias is a leaf outside the optimizer that the step moves from the
step's own counts (:meth:`DroplessMoE.balance`, ``parallel.Buffers``,
docs/front_door.md). It has no exchange over an ``ep`` axis yet (ROADMAP
Reach), so it does not replace the example's.

No reference analog (SURVEY.md §2.4: EP absent). TPU-native design
(GShard): routing is *dense tensor algebra* — one-hot dispatch/combine
einsums with a fixed per-expert capacity — so shapes stay static and the
whole layer is three einsums XLA maps onto the MXU. Expert weights carry a
``P('ep', ...)`` spec; the SPMD partitioner turns the dispatch einsum into
the all-to-all over the ``ep`` mesh axis (the same program a hand-written
MPI alltoall would compute, derived from layout instead of code).

Two routers. **Token-choice** (default) is top-k (``top_k=1`` = Switch,
``top_k=2`` = GShard): each token
is dispatched to its k highest-probability experts, first choices queueing
ahead of second choices for the fixed per-expert capacity; overflow tokens
are dropped (contribute zero — the transformer's residual path carries
them). Gate values are renormalized over the selected experts when k > 1.
Losses/diagnostics returned by :meth:`MoELayer.apply_with_metrics`:

- ``aux_loss`` — Switch load-balancing loss (Switch Transformer eq. 4:
  E * sum_e f_e * P_e over first-choice assignments),
- ``z_loss`` — router z-loss (ST-MoE: mean logsumexp(logits)^2), which
  keeps router logits small and training stable; callers weight it
  (~1e-3) into the loss,
- ``drop_rate`` — fraction of (token, choice) dispatches dropped for
  capacity,
- ``expert_load`` — (E,) share of the KEPT dispatches handled by each
  expert (sums to 1 whenever anything was kept; dropped slots are
  accounted in ``drop_rate``, not here).

**Expert-choice** (``router="experts"``, Zhou et al. 2022) inverts the
selection: each expert takes its top-capacity tokens, making load balance
exact with no auxiliary loss (see :meth:`MoELayer._expert_choice` for the
batch-dependence caveat).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..nn.core import GatedMLP, Linear, Module, Params, gelu
from ..ops import grouped_matmul_kernel
from ..ops.decode_attention import _on_one_device


class MoELayer(Module):
    """Token-routed expert FFN bank: x (..., D) -> (y (..., D), aux_loss)."""

    def __init__(self, dim: int, n_experts: int, mlp_ratio: int = 4,
                 capacity_factor: float = 1.25, top_k: int = 1,
                 normalize_gates: bool = True, router: str = "tokens",
                 n_shared_experts: int = 0, dtype=jnp.float32):
        if not 1 <= top_k <= n_experts:
            raise ValueError(f"top_k={top_k} not in [1, {n_experts}]")
        if router not in ("tokens", "experts"):
            raise ValueError(f"router must be tokens|experts, got {router!r}")
        if n_shared_experts < 0:
            raise ValueError(
                f"n_shared_experts must be >= 0, got {n_shared_experts}")
        self.dim = dim
        self.n_experts = n_experts
        self.hidden = mlp_ratio * dim
        self.capacity_factor = capacity_factor
        self.top_k = top_k
        self.normalize_gates = normalize_gates
        self.router = router
        # DeepSeekMoE-style shared experts: a dense always-on FFN (width
        # n_shared * hidden) every token passes through, added to the
        # routed output — common knowledge lives here, so the routed
        # experts specialize. Replicated over ep (every group runs it),
        # tp-shardable like any dense MLP (moe_param_specs).
        self.n_shared = n_shared_experts
        self.dtype = dtype

    def init(self, key) -> Params:
        kg, k1, k2, ks1, ks2 = jax.random.split(key, 5)
        bound1 = 1.0 / math.sqrt(self.dim)
        bound2 = 1.0 / math.sqrt(self.hidden)
        e, d, h = self.n_experts, self.dim, self.hidden
        p = {
            "gate": {"w": jax.random.uniform(kg, (d, e), self.dtype,
                                             -bound1, bound1)},
            "fc1": {"w": jax.random.uniform(k1, (e, d, h), self.dtype,
                                            -bound1, bound1),
                    "b": jnp.zeros((e, h), self.dtype)},
            "fc2": {"w": jax.random.uniform(k2, (e, h, d), self.dtype,
                                            -bound2, bound2),
                    "b": jnp.zeros((e, d), self.dtype)},
        }
        if self.n_shared:
            hs = self.n_shared * h
            bound2s = 1.0 / math.sqrt(hs)
            p["shared"] = {
                "fc1": {"w": jax.random.uniform(ks1, (d, hs), self.dtype,
                                                -bound1, bound1),
                        "b": jnp.zeros((hs,), self.dtype)},
                "fc2": {"w": jax.random.uniform(ks2, (hs, d), self.dtype,
                                                -bound2s, bound2s),
                        "b": jnp.zeros((d,), self.dtype)},
            }
        return p

    def _shared_ffn(self, params, xt):
        from ..ops.quant import resolve_weight
        w1 = resolve_weight(params["shared"]["fc1"], "w", self.dtype)
        w2 = resolve_weight(params["shared"]["fc2"], "w", self.dtype)
        h = gelu(xt.astype(jnp.float32) @ w1.astype(jnp.float32)
                 + params["shared"]["fc1"]["b"])
        return h @ w2.astype(jnp.float32) + params["shared"]["fc2"]["b"]

    def apply_with_metrics(self, params: Params, x,
                           **_) -> Tuple[Any, Dict[str, Any]]:
        orig_shape = x.shape
        n = math.prod(orig_shape[:-1])
        xt = x.reshape(n, self.dim)
        e, k = self.n_experts, self.top_k
        cap = max(int(self.capacity_factor * n * k / e), 1)

        from ..ops.quant import resolve_weight
        gate_w = resolve_weight(params["gate"], "w", self.dtype)
        logits = (xt @ gate_w).astype(jnp.float32)               # (N, E)
        probs = jax.nn.softmax(logits, axis=-1)
        if self.router == "experts":
            return self._expert_choice(params, x, xt, probs, logits,
                                       orig_shape, n)
        top_p, top_i = jax.lax.top_k(probs, k)                   # (N, K)
        gates = top_p
        if k > 1 and self.normalize_gates:
            gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        onehot = jax.nn.one_hot(top_i, e, dtype=jnp.float32)     # (N, K, E)
        # Per-expert queue positions with first choices ahead of second
        # choices (GShard priority): cumsum over the choice-major flat
        # order. k=1 reduces exactly to the Switch cumsum over tokens.
        flat = onehot.transpose(1, 0, 2).reshape(k * n, e)
        pos_flat = jnp.cumsum(flat, axis=0) * flat - 1.0
        pos = pos_flat.reshape(k, n, e).transpose(1, 0, 2)       # (N, K, E)
        keep = (pos >= 0) & (pos < cap)
        # one_hot of -1 / >=cap is all-zero, so `keep` is belt-and-braces
        disp_k = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                                dtype=jnp.float32) * keep[..., None]
        dispatch = disp_k.sum(axis=1)                            # (N, E, C)
        combine = jnp.einsum("nkec,nk->nec", disp_k, gates)      # (N, E, C)

        y = self._expert_ffn(params, dispatch, combine, xt)
        if self.n_shared:
            y = y + self._shared_ffn(params, xt)

        # Switch aux loss over FIRST-choice assignments (eq. 4)
        frac = onehot[:, 0, :].mean(axis=0)
        mean_prob = probs.mean(axis=0)
        aux = e * jnp.sum(frac * mean_prob)
        # ST-MoE router z-loss: penalize large router logits
        z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        kept = disp_k.sum(axis=(2, 3))                           # (N, K)
        per_expert = dispatch.sum(axis=(0, 2))                   # (E,)
        metrics = {
            "aux_loss": aux,
            "z_loss": z_loss,
            "drop_rate": 1.0 - kept.mean(),
            "expert_load": per_expert / jnp.maximum(per_expert.sum(), 1.0),
        }
        return y.reshape(orig_shape).astype(x.dtype), metrics

    def _expert_ffn(self, params, dispatch, combine, xt):
        """Shared dispatch → per-expert GELU MLP → combine block: the
        routers differ only in how they build the (N, E, C) dispatch and
        combine tensors."""
        from ..ops.quant import resolve_weight
        w1 = resolve_weight(params["fc1"], "w", self.dtype)
        w2 = resolve_weight(params["fc2"], "w", self.dtype)
        expert_in = jnp.einsum("nec,nd->ecd", dispatch,
                               xt.astype(jnp.float32))           # (E, C, D)
        h = gelu(jnp.einsum("ecd,edh->ech", expert_in, w1)
                 + params["fc1"]["b"][:, None, :])
        expert_out = (jnp.einsum("ech,ehd->ecd", h, w2)
                      + params["fc2"]["b"][:, None, :])          # (E, C, D)
        return jnp.einsum("nec,ecd->nd", combine, expert_out)

    def _expert_choice(self, params, x, xt, probs, logits, orig_shape, n):
        """Expert-choice routing (Zhou et al. 2022): each EXPERT takes
        its top-capacity tokens by gate score, so load balance is exact
        by construction — no auxiliary loss, no priority queues; tokens
        chosen by nobody ride the residual. Capacity uses the same
        ``capacity_factor * n / e`` budget (``top_k`` does not apply).

        Caveat (as in the paper): selection compares scores ACROSS the
        batch/sequence, so a token's output depends on its neighbors —
        fine for training and encoders, not a causal decoding scheme
        (cached autoregressive decode would see different routing than
        training; pair it with training-only workloads or accept the
        mismatch)."""
        e = self.n_experts
        # clamp to n: top_k requires k <= the token count (a generous
        # capacity_factor with few experts would otherwise overshoot)
        cap = min(max(int(self.capacity_factor * n / e), 1), n)
        scores = probs.T                                        # (E, N)
        top_s, top_idx = jax.lax.top_k(scores, cap)             # (E, C)
        disp = jax.nn.one_hot(top_idx, n, dtype=jnp.float32)    # (E, C, N)
        dispatch = disp.transpose(2, 0, 1)                      # (N, E, C)
        combine = (disp * top_s[..., None]).transpose(2, 0, 1)  # (N, E, C)
        y = self._expert_ffn(params, dispatch, combine, xt)
        if self.n_shared:
            y = y + self._shared_ffn(params, xt)

        picks_per_token = dispatch.sum(axis=(1, 2))             # (N,)
        metrics = {
            # balanced by construction; 0 keeps the trainable-aux
            # contract (loss + c*aux) router-agnostic
            "aux_loss": jnp.zeros((), jnp.float32),
            "z_loss": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
            "drop_rate": jnp.mean(picks_per_token == 0),
            "expert_load": jnp.full((e,), 1.0 / e, jnp.float32),
        }
        return y.reshape(orig_shape).astype(x.dtype), metrics

    def apply(self, params: Params, x, **kw) -> Tuple[Any, Any]:
        """Back-compat contract: ``(y, aux_loss)`` with aux the Switch
        load-balancing loss (z-loss and drop diagnostics via
        :meth:`apply_with_metrics`)."""
        y, m = self.apply_with_metrics(params, x, **kw)
        return y, m["aux_loss"]


def moe_param_specs(ep_axis: str = "ep", tp_axis: Optional[str] = None,
                    n_shared_experts: int = 0):
    """PartitionSpecs for MoELayer params: experts sharded over ``ep``
    (optionally expert-internal hidden over ``tp``). Shared experts —
    a dense FFN — replicate over ``ep`` and shard their hidden over
    ``tp`` like any Megatron MLP."""
    t = tp_axis
    specs = {
        "gate": {"w": P()},
        "fc1": {"w": P(ep_axis, None, t), "b": P(ep_axis, t)},
        "fc2": {"w": P(ep_axis, t, None), "b": P(ep_axis, None)},
    }
    if n_shared_experts:
        specs["shared"] = {"fc1": {"w": P(None, t), "b": P(t)},
                           "fc2": {"w": P(t, None), "b": P()}}
    return specs


#: Calls of :func:`grouped_matmul` that took the kernel, counted where
#: they are traced: a pool reads it around the trace of its decode
#: program (``stats()["moe_kernel_matmuls"]``).
_kernel_traces = 0


def kernel_traces() -> int:
    return _kernel_traces


def _kernel_interpret(xs, w) -> Optional[bool]:
    """``False`` (compile the kernel) for a call on one TPU device whose
    operands the kernel takes (``grouped_matmul_kernel.kernel_fits``);
    ``None`` (``ragged_dot``) anywhere else. No rule on the rows: on the
    chip the kernel is as fast as ``ragged_dot`` or faster from 4 rows a
    group to 4096 (``benchmarks/grouped_matmul_sweep.py``; PERF.md
    Findings, PR 38). The Pallas interpreter is never a default: a test
    that wants the kernel on a CPU puts its own answer in this function's
    place."""
    if jax.default_backend() != "tpu" or not _on_one_device(xs):
        return None
    return False if grouped_matmul_kernel.kernel_fits(xs, w) else None


@jax.custom_vjp
def grouped_matmul(xs, w, sizes):
    """``jax.lax.ragged_dot`` with float32 results: rows of ``xs`` (R, K),
    sorted into groups of ``sizes`` (G,), each group through its own
    ``w[g]`` (G, K, N). Rows past the last group belong to no group and
    what the result holds there is not a result: ``ragged_dot``'s TPU
    kernel never writes them.

    A call that is not differentiated (this body: a serving program's)
    goes through ``ops/grouped_matmul_kernel.py`` where
    :func:`_kernel_interpret` says so: the same arithmetic, each touched
    group's weights streamed once, rows past the last group zeros. A
    differentiated call (:func:`_grouped_matmul_fwd`) is ``ragged_dot``.

    Its derivatives are ``ragged_dot``'s own (``dxs`` another grouped
    matmul, ``dw`` a product whose contracting dimension is the ragged
    one), with one repair: the rows of ``dxs`` past the last group are
    set to zero. Left as the kernel leaves them they are whatever the
    buffer held before, and a trainer scatters every row of ``dxs`` back
    onto its token (on the chip, with a sixteenth of the experts held,
    that is 19 rows in 20: PERF.md Findings, PR 32)."""
    mode = _kernel_interpret(xs, w)
    if mode is None:
        return _ragged_dot(xs, w, sizes)
    global _kernel_traces
    _kernel_traces += 1
    return grouped_matmul_kernel.grouped_matmul(xs, w, sizes,
                                                interpret=mode)


def _ragged_dot(xs, w, sizes):
    return jax.lax.ragged_dot(xs, w, sizes,
                              preferred_element_type=jnp.float32)


def _grouped_matmul_fwd(xs, w, sizes):
    return _ragged_dot(xs, w, sizes), (xs, w, sizes)


def _grouped_matmul_bwd(res, g):
    xs, w, sizes = res
    _, pull = jax.vjp(lambda a, b: _ragged_dot(a, b, sizes), xs, w)
    dxs, dw = pull(g)
    grouped = (jnp.arange(xs.shape[0]) < jnp.sum(sizes))[:, None]
    return jnp.where(grouped, dxs, 0), dw, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


#: Rows of one block of the sorted pairs. A layer that holds a share of
#: the experts walks its sorted pairs in blocks of this many rows and
#: stops at the last block that holds a pair of its own. Chosen on the
#: chip (``benchmarks/moe_dispatch_sweep.py``; PERF.md Findings, PR 44).
_BLOCK_ROWS = 1024


def _blocks(rows: int) -> Tuple[int, int]:
    """``(rows a block, blocks)`` that ``rows`` sorted pairs are walked
    in: one block of them all up to ``_BLOCK_ROWS``."""
    size = min(_BLOCK_ROWS, rows)
    return size, -(-rows // size)


def _each_block(n, rows: int, body, carry):
    """``body(size, first_row, live, carry) -> carry`` for every block of
    ``rows`` rows that holds a row before ``n`` (int32, traced), in
    order; ``live`` (size, 1) says which of the block's rows lie before
    ``n``. The trip count is ``n``'s: a block wholly past it costs
    nothing. One block is run as it stands, without a loop."""
    size, blocks = _blocks(rows)

    def step(b, carry):
        at = b * size
        live = (at + jnp.arange(size, dtype=jnp.int32) < n)[:, None]
        return body(size, at, live, carry)

    if blocks == 1:
        return step(0, carry)
    return jax.lax.fori_loop(0, (n + size - 1) // size, step, carry)


def _cut(a, at, size):
    return jax.lax.dynamic_slice_in_dim(a, at, size)


def _paste(a, block, at):
    return jax.lax.dynamic_update_slice_in_dim(a, block.astype(a.dtype), at, 0)


# The three walks below hand on (R, ...) buffers of which only the rows
# before ``n`` are results. Every other row is what a grouped matmul's
# rows past its last group are, not a result and never read as one: an
# unrun block is not written, so it holds what the buffer held, zeros
# (:func:`_fresh`) or the operand the derivative is written over.

def _fresh(shape, dtype, n):
    """A buffer for a walk to fill, zeros. The zero is ``n < 0``, which
    the compiler cannot fold: a constant's broadcast, like ``lax.empty``,
    depends on nothing, and XLA then makes every layer's buffer at the
    top of the step program and keeps them all to their walks (nine
    times 256 MiB in the JoyAI step: PERF.md Findings, PR 44)."""
    return jnp.full(shape, (n < 0).astype(dtype))


def _gather_blocks(src, idx, wts, n, over=None):
    """``src[idx[i]] * wts[i]`` in the rows ``i < n`` of an (R, D)
    buffer, ``src``'s type without ``wts`` and float32 with them. With
    ``over`` (R, D) float32 the rows are written over it, and the
    products ``<src[idx[i]], over[i]>`` (R,) float32, zeros from ``n``
    on, come back beside them."""
    rows = idx.shape[0]

    def body(size, at, live, carry):
        out, dots = carry
        got = jnp.take(src, _cut(idx, at, size), axis=0, mode="clip")
        if over is not None:
            dot = jnp.sum(got.astype(jnp.float32) * _cut(out, at, size), -1)
            dots = _paste(dots, jnp.where(live[:, 0], dot, 0.0), at)
        if wts is not None:
            got = got.astype(jnp.float32) * _cut(wts, at, size)[:, None]
        return _paste(out, got, at), dots

    if over is not None:
        return _each_block(n, rows, body,
                           (over, jnp.zeros((rows,), jnp.float32)))
    return _each_block(n, rows, body, (_fresh(
        (rows, src.shape[1]),
        src.dtype if wts is None else jnp.float32, n), None))[0]


def _scatter_blocks(rows, idx, wts, n, t: int):
    """(t, D) float32: ``rows[i] * wts[i]`` added onto row ``idx[i]``
    for every ``i < n``."""
    def body(size, at, live, acc):
        new = _cut(rows, at, size).astype(jnp.float32)
        if wts is not None:
            new = new * _cut(wts, at, size)[:, None]
        return acc.at[_cut(idx, at, size)].add(
            jnp.where(live, new, 0.0), mode="promise_in_bounds")

    return _each_block(n, idx.shape[0], body,
                       jnp.zeros((t, rows.shape[1]), jnp.float32))


@jax.custom_vjp
def _take_rows(src, idx, n):
    """The dispatch of a layer that holds a share: ``src[idx[i]]`` in
    the rows ``i < n`` of an (R, D) buffer, gathered a block at a time
    and only in the blocks that hold such a row. Its transpose is
    :func:`_add_rows` over the same blocks."""
    return _gather_blocks(src, idx, None, n)


def _take_rows_fwd(src, idx, n):
    # a (T, 0) array of src's type: what the transpose has to know of it
    return _gather_blocks(src, idx, None, n), (src[:, :0], idx, n)


def _take_rows_bwd(res, g):
    like, idx, n = res
    return (_scatter_blocks(g, idx, None, n, like.shape[0]).astype(
        like.dtype), None, None)


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _add_rows(rows, idx, wts, n, t: int):
    """The combine of a layer that holds a share: (t, D) float32, each
    sorted pair ``i < n`` of ``rows`` (R, D) float32 weighted by
    ``wts[i]`` and added onto its token ``idx[i]``, a block at a time and
    only in the blocks that hold such a pair. No inverse permutation and
    no gather back. The derivative for ``rows`` is written over them."""
    return _scatter_blocks(rows, idx, wts, n, t)


def _add_rows_fwd(rows, idx, wts, n, t):
    return _scatter_blocks(rows, idx, wts, n, t), (rows, idx, wts, n)


def _add_rows_bwd(t, res, g):
    rows, idx, wts, n = res
    d_rows, d_wts = _gather_blocks(g, idx, wts, n, over=rows)
    return d_rows, None, d_wts, None


_add_rows.defvjp(_add_rows_fwd, _add_rows_bwd)


def _map_blocks(fn, n, arrays):
    like = jax.eval_shape(fn, *arrays)

    def body(size, at, live, out):
        return _paste(out, fn(*(_cut(a, at, size) for a in arrays)), at)

    return _each_block(n, arrays[0].shape[0], body,
                       _fresh(like.shape, like.dtype, n))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _map_rows(fn, n, arrays):
    """``fn(*arrays)`` in the rows ``i < n`` of an (R, ...) buffer:
    ``fn`` acts on rows alone and is run a block at a time, only in the
    blocks that hold such a row. Its derivative is ``fn``'s own, block
    by block, written over ``arrays``."""
    return _map_blocks(fn, n, arrays)


def _map_rows_fwd(fn, n, arrays):
    return _map_blocks(fn, n, arrays), (n, arrays)


def _map_rows_bwd(fn, res, g):
    n, arrays = res

    def body(size, at, live, grads):
        # ``grads`` holds the operands until a block's derivative is
        # written over them
        _, pull = jax.vjp(fn, *(_cut(a, at, size) for a in grads))
        return tuple(_paste(a, d, at)
                     for a, d in zip(grads, pull(_cut(g, at, size))))

    return None, _each_block(n, arrays[0].shape[0], body, arrays)


_map_rows.defvjp(_map_rows_fwd, _map_rows_bwd)


class DroplessMoE(Module):
    """Dropless token-choice experts: x (..., D) -> y (..., D).

    Routes over all ``n_routed`` experts by one of two scoring rules,
    both float32 from the activations the layer is given:

    - ``score="sigmoid"`` (the default): ``g = sigmoid(x W_r)``, the
      ``top_k`` largest of ``g + bias`` (the bias corrects the choice
      only, DeepSeek-V3's ``noaux_tc``), weights ``g_e / (sum of the
      chosen g + 1e-20) * scale``;
    - ``score="softmax"``: ``p = softmax(x W_r)`` over all ``n_routed``,
      the ``top_k`` largest ``p``, weights ``p_e / (sum of the chosen p)
      * scale`` (Qwen3-MoE's ``norm_topk_prob``). The router has no bias
      leaf, so :meth:`balance` and the trainers' router-bias buffers do
      not apply to it.

    ``held = (first, count)`` says which
    experts this chip holds (all of them by default): it computes their
    part of the result, pairs routed elsewhere cost the sort and nothing
    after it (:meth:`_pairs_here`), and nothing stands in for the absent
    chips. The shared expert runs on
    every token (every chip computes it alike, so a sum over shares
    counts it once). Scopes: ``moe`` > ``route``, ``dispatch``,
    ``experts``, ``shared``, ``combine``.

    Gradients flow through the weights to the router and through the
    experts; the choice has none, and neither has the bias, which
    :meth:`balance` moves instead, from the load that ``apply``
    returns."""

    def __init__(self, dim: int, n_routed: int, width: int, *, top_k: int,
                 n_shared: int = 1, scale: float = 1.0,
                 held: Optional[Tuple[int, int]] = None,
                 score: str = "sigmoid", dtype=jnp.float32):
        if not 1 <= top_k <= n_routed:
            raise ValueError(f"top_k={top_k} not in [1, {n_routed}]")
        if score not in ("sigmoid", "softmax"):
            raise ValueError(f"score must be sigmoid|softmax, got {score!r}")
        first, count = held if held is not None else (0, n_routed)
        if first < 0 or count < 1 or first + count > n_routed:
            raise ValueError(f"held={held} is no range of {n_routed} experts")
        self.dim, self.n_routed, self.width = dim, n_routed, width
        self.top_k, self.scale, self.dtype = top_k, scale, dtype
        self.first, self.count, self.score = first, count, score
        self.shared = GatedMLP(dim, n_shared * width, dtype=dtype) \
            if n_shared else None

    def init(self, key) -> Params:
        kr, kg, ku, kd, ks = jax.random.split(key, 5)
        c, d, f = self.count, self.dim, self.width
        u = lambda k, shape, fan: jax.random.uniform(
            k, shape, self.dtype, -1.0 / math.sqrt(fan), 1.0 / math.sqrt(fan))
        p = {"router": {"w": u(kr, (d, self.n_routed), d),
                        "bias": jnp.zeros((self.n_routed,), jnp.float32)},
             "experts": {"gate": u(kg, (c, d, f), d), "up": u(ku, (c, d, f), d),
                         "down": u(kd, (c, f, d), f)}}
        if self.shared is not None:
            p["shared"] = self.shared.init(ks)
        if self.score == "softmax":
            del p["router"]["bias"]
        return p

    def route(self, params: Params, xt):
        """xt (T, D) -> chosen experts (T, k) int32, their weights (T, k)
        float32 and the scores (T, E). Float32 from the activations the
        layer is given."""
        with jax.named_scope("route"):
            if self.score == "softmax":
                p = jax.nn.softmax(jnp.matmul(
                    xt.astype(jnp.float32),
                    params["router"]["w"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST), axis=-1)
                top_p, top_i = jax.lax.top_k(p, self.top_k)
                w = top_p / jnp.sum(top_p, -1, keepdims=True) * self.scale
                return top_i.astype(jnp.int32), w, p
            g = jax.nn.sigmoid(jnp.matmul(
                xt.astype(jnp.float32),
                params["router"]["w"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            _, top_i = jax.lax.top_k(
                g + params["router"]["bias"].astype(jnp.float32), self.top_k)
            top_g = jnp.take_along_axis(g, top_i, axis=-1)
            w = top_g / (jnp.sum(top_g, -1, keepdims=True) + 1e-20) \
                * self.scale
            return top_i.astype(jnp.int32), w, g

    def routed(self, params: Params, xt, row_mask=None):
        """The held experts' part of the result for xt (T, D), float32,
        the counts ``(tokens_routed, experts_touched,
        tokens_max_expert)`` of this call, and its load: the (token,
        expert) pairs the router sent to each of ALL ``n_routed`` experts
        (int32), whichever of them are held here. ``row_mask`` (T,) bool
        leaves rows out of the dispatch (idle slots, a padded tail) and
        of the load."""
        t, k, c = xt.shape[0], self.top_k, self.count
        top_i, w, _ = self.route(params, xt)
        with jax.named_scope("route"):
            sent = jnp.ones((t, k), jnp.int32) if row_mask is None \
                else jnp.broadcast_to(row_mask[:, None], (t, k)).astype(
                    jnp.int32)
            load = jnp.zeros((self.n_routed,), jnp.int32).at[
                top_i.reshape(-1)].add(sent.reshape(-1))
        with jax.named_scope("dispatch"):
            eid = top_i.reshape(-1) - self.first
            here = (eid >= 0) & (eid < c)
            if row_mask is not None:
                here &= jnp.repeat(row_mask, k)
            key = jnp.where(here, eid, c)          # not here: sorted last
            order = jnp.argsort(key)               # stable
            sizes = jnp.sum(key[:, None] == jnp.arange(c)[None, :], axis=0,
                            dtype=jnp.int32)
        dot = lambda a, b: grouped_matmul(a, b, sizes)
        e = params["experts"]
        # a fact of the layer's construction, nothing a caller sets: a
        # layer that holds every expert has no pair to skip
        form = self._every_pair if c == self.n_routed else self._pairs_here
        y = form(xt, e, dot, order, here, w, sizes)
        counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0),
                            jnp.max(sizes)]).astype(jnp.int32)
        return y, counts, load

    def _every_pair(self, xt, e, dot, order, here, w, sizes):
        """The sorted pairs through the experts and back, for a layer
        that holds EVERY routed expert: no pair is elsewhere, so all
        ``T * k`` rows are gathered, and gathered back into token order
        (a gather costs a third of a scatter-add over the same rows)."""
        t, k = xt.shape[0], self.top_k
        with jax.named_scope("dispatch"):
            xs = jnp.take(xt, order // k, axis=0)              # (T*k, D)
        with jax.named_scope("experts"):
            h = jax.nn.silu(dot(xs, e["gate"])) * dot(xs, e["up"])
            ys = dot(h.astype(xt.dtype), e["down"])
        with jax.named_scope("combine"):
            # rows past the last group belong to no expert here: what the
            # grouped matmul left there is not a result
            ys = jnp.where((jnp.arange(t * k) < jnp.sum(sizes))[:, None],
                           ys, 0.0)
            back = jnp.zeros((t * k,), jnp.int32).at[order].set(
                jnp.arange(t * k, dtype=jnp.int32))
            pairs = jnp.take(ys, back, axis=0).reshape(t, k, self.dim)
            return jnp.sum(
                pairs * jnp.where(here.reshape(t, k), w, 0.0)[..., None],
                axis=1)

    def _pairs_here(self, xt, e, dot, order, here, w, sizes):
        """The same, under the same signature, for a layer that holds a
        SHARE of the experts: the sort put the ``n = sum(sizes)`` pairs
        that are here first, so rows come in, pass the elementwise and go
        back onto their tokens a block of ``_BLOCK_ROWS`` sorted pairs at
        a time, and a block wholly past
        ``n`` is never worked on (:func:`_each_block`). The grouped
        matmuls stay one call a projection over the whole buffer: they
        read each touched expert's weights once and no row past ``n``.
        Dropless at any routing: with every pair here every block runs.
        A token's pairs are summed in float32, in the sorted order."""
        t, k = xt.shape[0], self.top_k
        size, blocks = _blocks(t * k)
        pad = lambda a: jnp.pad(a, (0, size * blocks - t * k))
        with jax.named_scope("dispatch"):
            n = jnp.sum(sizes)
            tok = pad(order // k)
            xs = _take_rows(xt, tok, n)
        with jax.named_scope("experts"):
            h = _map_rows(
                lambda g, u: (jax.nn.silu(g) * u).astype(xt.dtype), n,
                (dot(xs, e["gate"]), dot(xs, e["up"])))
            ys = dot(h, e["down"])
        with jax.named_scope("combine"):
            return _add_rows(ys, tok, pad(jnp.take(w.reshape(-1), order)),
                             n, t)

    def dispatch_blocks(self, pairs, pairs_here, calls: int = 1):
        """``(blocks run, blocks)`` of ``calls`` calls that routed
        ``pairs`` pairs each, ``pairs_here`` of them in all to the held
        experts (ints or arrays): how much of the sorted buffers
        dispatch, elementwise and combine worked on. All of it where
        every expert is held, and where a call is one block, which
        always runs. Of several calls of several blocks each only the
        fewest blocks that many pairs can lie in are known."""
        blocks = calls * -(-pairs // _BLOCK_ROWS)
        if self.count == self.n_routed:
            return blocks, blocks
        return jnp.maximum(-(-pairs_here // _BLOCK_ROWS),
                           calls * (blocks == calls)), blocks

    def apply(self, params: Params, x, *, row_mask=None, stats=None, **_):
        """-> ``(y, load)``: the call's pairs per expert (n_routed,)
        int32 are an output, which is how a trainer gets them out of a
        rematerialised block; a program that does not use them does not
        compute them. ``stats``: a list the call's counts (3,) int32 are
        appended to (``stats()``'s ``moe_*`` counters are their sums)."""
        with jax.named_scope("moe"):
            xt = x.reshape(-1, self.dim)
            y, counts, load = self.routed(
                params, xt, None if row_mask is None else row_mask.reshape(-1))
            if stats is not None:
                stats.append(counts)
            if self.shared is not None:
                with jax.named_scope("shared"):
                    y = y + self.shared.apply(params["shared"], xt)
            return y.reshape(x.shape).astype(x.dtype), load

    @staticmethod
    def balance(bias, load, speed: float):
        """The router bias after a step that sent ``load`` (n_routed,)
        pairs to each expert (DeepSeek-V3 section 2.1.2, ``noaux_tc``):
        ``b_e + speed * sign(mean(load) - load_e)``. An overloaded expert
        is chosen a little less, with no gradient and no loss term."""
        with jax.named_scope("moe/bias_update"):
            load = load.astype(jnp.float32)
            return bias + speed * jnp.sign(
                jnp.mean(load, -1, keepdims=True) - load).astype(bias.dtype)
