"""Mesh trainer: dp × tp × sp (× ep) composed in one jitted step, GSPMD-style.

The scaling recipe ("How to Scale Your Model"): pick a mesh, annotate the
shardings of inputs and params, let XLA's SPMD partitioner insert the
collectives, profile, iterate. Here:

* batch axis 0 → ``dp``; sequence axis 1 → ``sp``; tensor-parallel params →
  ``tp`` specs from :mod:`.tensor`; everything else replicated.
* The step body is ordinary model code — no manual collectives. Gradient
  all-reduce over dp, Megatron all-reduces around the tp matmul pairs, and
  sequence-axis resharding all come out of the partitioner.
* The one part GSPMD would get wrong by itself — attention over an
  sp-sharded sequence would all-gather K/V — is carved out as a
  ``shard_map`` island running ring attention (:mod:`.sequence`), composing
  with the surrounding GSPMD program.

This trainer subsumes pure DP (tp=sp=1 gives exactly the data-parallel
semantics of :mod:`.data_parallel`, which remains the lean facade path).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..optim import Optimizer
from ..runtime import context
from .sequence import (ring_attention, ring_flash_attention,
                       striped_ring_flash_attention, ulysses_attention)


class SpmdStepOutput(NamedTuple):
    params: Any
    opt_state: Any
    loss: jnp.ndarray   # scalar global-mean loss
    metrics: Any


def make_gspmd_ring_attn_fn(mesh: Mesh, *, dp: str = "dp", tp: str = "tp",
                            sp: str = "sp", core: str = "dense",
                            block_q=None, block_k=None,
                            interpret=None, window=None):
    """An ``attn_fn`` for use INSIDE a GSPMD-jitted model: a shard_map
    island that runs ring attention over the ``sp`` axis while batch/heads
    stay sharded over ``dp``/``tp``. ``core='flash'`` swaps the per-hop
    dense block for the pallas flash kernel
    (:func:`..parallel.sequence.ring_flash_attention`) — the long-context
    fast path, O(S_local) attention memory per device. ``core='striped'``
    runs the LOAD-BALANCED striped causal ring
    (:func:`..parallel.sequence.striped_ring_flash_attention`): q/k/v
    (and the model's tokens/targets/position ids) must be in
    :func:`..parallel.sequence.stripe_tokens` layout, and every hop runs
    a triangular kernel — ~2x less attention compute per device at large
    sp. Striped is causal-only. ``core='ulysses'`` swaps the ring for
    the all-to-all mode (:func:`..parallel.sequence.ulysses_attention`):
    two collectives reshard heads<->sequence around a full-sequence
    flash kernel — lower collective count, O(S) attention memory, head
    counts must divide sp. ``window`` (causal sliding-window attention)
    is supported by the flash ring (far hops skip statically — O(S*W)
    across the ring) and by ulysses (the full-sequence kernel's banded
    frontier); not by the dense ring or the striped layout."""
    if core not in ("dense", "flash", "striped", "ulysses"):
        raise ValueError(f"unknown ring attention core {core!r}")
    if window is not None and core not in ("flash", "ulysses"):
        raise ValueError(f"window is supported by core='flash' and "
                         f"core='ulysses', not {core!r}")
    qkv_spec = P(dp, tp, sp, None)  # (B, H, S, Dh)

    def attn_fn(q, k, v, *, causal: bool = False, scale=None):
        if core == "striped" and not causal:
            raise ValueError(
                "striped ring attention is causal-only (striping exists "
                "to balance the causal frontier); use core='flash' for "
                "non-causal attention")

        def island(q, k, v):
            if core == "ulysses":
                return ulysses_attention(
                    q, k, v, axis_name=sp, causal=causal, scale=scale,
                    block_q=block_q, block_k=block_k, interpret=interpret,
                    window=window)
            if core == "striped":
                return striped_ring_flash_attention(
                    q, k, v, axis_name=sp, scale=scale,
                    block_q=block_q, block_k=block_k, interpret=interpret)
            if core == "flash":
                return ring_flash_attention(
                    q, k, v, axis_name=sp, causal=causal, scale=scale,
                    block_q=block_q, block_k=block_k, interpret=interpret,
                    window=window)
            return ring_attention(q, k, v, axis_name=sp, causal=causal,
                                  scale=scale)
        return jax.shard_map(island, mesh=mesh,
                             in_specs=(qkv_spec, qkv_spec, qkv_spec),
                             out_specs=qkv_spec,
                             check_vma=False)(q, k, v)
    return attn_fn


def make_gspmd_striped_ring_attn_fn(mesh: Mesh, *, dp: str = "dp",
                                    tp: str = "tp", sp: str = "sp",
                                    block_q=None, block_k=None,
                                    interpret=None):
    """:func:`make_gspmd_ring_attn_fn` with ``core='striped'`` — kept as
    a named front door for the load-balanced causal ring."""
    return make_gspmd_ring_attn_fn(mesh, dp=dp, tp=tp, sp=sp,
                                   core="striped", block_q=block_q,
                                   block_k=block_k, interpret=interpret)


def make_spmd_train_step(loss_fn: Callable, optimizer: Optimizer,
                         mesh: Optional[Mesh] = None,
                         param_specs: Optional[Any] = None,
                         batch_spec: Any = None,
                         donate: Optional[bool] = None) -> Callable:
    """Compile ``step(params, opt_state, batch) -> SpmdStepOutput`` where
    sharding is carried by the *inputs* (place params with
    ``tensor.shard_params`` / batch with :func:`shard_batch_spec` first);
    the partitioner propagates from there. ``loss_fn(params, batch) ->
    (loss, metrics)`` computes the GLOBAL mean loss — under GSPMD the code
    sees logical (global) shapes, so it is written exactly like
    single-device code.

    Thin shim over the front door (:func:`.front_door.make_step` with
    ``specs=FROM_INPUTS`` — docs/front_door.md): builder cache, compile
    counters, and whole-step donation (``DPX_DONATE``) come from there.
    """
    del mesh, param_specs, batch_spec  # carried by input shardings
    from .front_door import FROM_INPUTS, make_step
    return make_step(loss_fn, optimizer, specs=FROM_INPUTS,
                     donate=donate)


def shard_batch_spec(batch, mesh: Mesh, spec: P):
    """Place a host batch on the mesh with an explicit PartitionSpec
    (e.g. ``P('dp', 'sp')`` for (B, S) token batches)."""
    sharding = NamedSharding(mesh, spec)
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), batch)
