"""Pipeline parallelism: GPipe-style microbatch schedule over the ``pp``
mesh axis, as a differentiable shard_map island.

The reference has no pipeline concept (SURVEY.md §2.4). TPU-native design:

* Stage s holds its slice of the (homogeneous) layer stack — stacked layer
  params sharded over ``pp`` on the leading axis. Heterogeneous ends
  (embedding, LM head) stay *outside* the island in the surrounding GSPMD
  program, so the pipelined middle is uniform.
* The schedule is a ``lax.scan`` over T + S - 1 ticks: each tick every
  stage computes its current microbatch and hands its activation to the
  next stage via ``collective-permute`` (one ICI neighbor hop). No
  data-dependent control flow — validity is handled by masking, keeping
  the whole schedule one static XLA program.
* **Backward is free**: the schedule is ordinary traceable code, so
  ``jax.grad`` through the island yields the reverse pipeline (cotangents
  ppermute backwards through the ring) without any hand-written schedule.

Two schedules share the island machinery:

* **GPipe** (:func:`make_gspmd_pipeline_fn`): forward-only scan;
  ``jax.grad`` through it yields the reverse pipeline automatically — at
  the cost of storing the activations of every scan tick, so activation
  memory grows with the number of microbatches T.
* **1F1B** (:func:`make_pipeline_train_fn`): the training step computes
  gradients *inside* the schedule. The last stage evaluates the loss per
  microbatch and starts that microbatch's backward immediately; cotangents
  ppermute down the ring while later forwards continue. Each stage keeps
  only a ring of in-flight stage *inputs* (depth <= S+1, independent of
  T) and recomputes its forward inside the backward phase (standard
  rematerializing 1F1B) — so activation memory is O(S), not O(T). The
  schedule is built host-side (:func:`_build_1f1b_schedule`, S and T are
  static) and driven as data through one ``lax.scan``; gradients ride the
  scan carry, so no autodiff ever runs across ticks.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..comm import primitives as prim


def pipeline_apply(stage_params, microbatches, stage_fn, *,
                   axis_name: str = "pp"):
    """Run the GPipe schedule inside ``shard_map``.

    stage_params: this stage's params (leading singleton stage axis already
    stripped by the caller's spec). microbatches: (T, mb, ...) — replicated
    on every stage; only stage 0 reads them. Returns (T, mb, ...) outputs,
    valid on the LAST stage (zeros elsewhere); callers psum-mask to
    replicate.
    """
    n_stages = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    t_total = microbatches.shape[0] + n_stages - 1
    n_micro = microbatches.shape[0]
    mb_shape = microbatches.shape[1:]

    def tick(carry, t):
        recv, outputs = carry
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        first_in = lax.dynamic_index_in_dim(microbatches, mb_idx, axis=0,
                                            keepdims=False)
        x = jnp.where(my == 0, first_in, recv)
        y = stage_fn(stage_params, x)
        out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        valid = (t >= n_stages - 1) & (my == n_stages - 1)
        prev = lax.dynamic_index_in_dim(outputs, out_idx, axis=0,
                                        keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(valid, y, prev), out_idx, axis=0)
        send = prim.line_shift(y, axis_name, 1)
        return (send, outputs), None

    recv0 = jnp.zeros(mb_shape, microbatches.dtype)
    out0 = jnp.zeros_like(microbatches)
    (_, outputs), _ = lax.scan(tick, (recv0, out0), jnp.arange(t_total))
    return outputs


def make_gspmd_pipeline_fn(mesh: Mesh, stage_fn: Callable,
                           n_microbatches: int, *, axis_name: str = "pp",
                           param_axis_spec: P = None):
    """A GSPMD-island pipeline: ``fn(stacked_stage_params, x) -> y`` for use
    inside a jitted program.

    stacked_stage_params: pytree with leading axis = n_stages on every leaf
    (sharded P('pp', ...)). x: (B, ...) activations; B is padded up to a
    multiple of n_microbatches and the padding sliced off the output, so
    any batch size works. stage_fn(stage_params, x_mb) maps one microbatch
    through one stage's layers. ``param_axis_spec`` overrides the default
    ``P(axis_name)`` leaf spec (e.g. ``P('pp', 'tp')`` to co-shard stage
    params over tensor parallelism).
    """
    def fn(stacked_params, x):
        b = x.shape[0]
        mb = -(-b // n_microbatches)
        micro = _pad_batch(x, mb * n_microbatches).reshape(
            n_microbatches, mb, *x.shape[1:])

        def island(stacked_params, micro):
            # P('pp') on the leading (layer) axis leaves each stage holding
            # its (layers_per_stage, ...) slice — exactly stage_fn's input.
            outs = pipeline_apply(stacked_params, micro, stage_fn,
                                  axis_name=axis_name)
            n_stages = lax.psum(1, axis_name)
            my = lax.axis_index(axis_name)
            # replicate the last stage's outputs to every stage
            mask = (my == n_stages - 1).astype(outs.dtype)
            return lax.psum(outs * mask, axis_name)

        leaf_spec = param_axis_spec if param_axis_spec is not None \
            else P(axis_name)
        param_specs = jax.tree_util.tree_map(
            lambda _: leaf_spec, stacked_params)
        y = jax.shard_map(
            island, mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=P(),
            check_vma=False,
        )(stacked_params, micro)
        return y.reshape(mb * n_microbatches, *y.shape[2:])[:b]
    return fn


def _pad_batch(x, total):
    """Pad axis 0 up to ``total`` rows (relaxes the microbatch
    divisibility constraint; padded rows carry weight 0)."""
    pad = total - x.shape[0]
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths)


def _build_1f1b_schedule(n_stages: int, n_micro: int):
    """Host-side 1F1B schedule tables.

    Microbatch m is injected at stage 0 at tick ``inject[m]``; forwards
    flow freely (stage s forwards m at ``inject[m] + s``), the last stage
    backwards m in the same tick as its forward, and the cotangent walks
    back one stage per tick. Injection is throttled so stage 0 never holds
    more than ``n_stages`` in-flight microbatches — that single throttle
    bounds every stage's residual ring independently of T (the 1F1B
    memory property). Each tick has a forward sub-slot then a backward
    sub-slot.

    Returns ``(fwd, bwd, depth)``: int32 tables of shape (n_ticks,
    n_stages) holding the microbatch index scheduled in that sub-slot
    (-1 = idle), and the exact residual-ring depth required.
    """
    S, T = n_stages, n_micro
    inject = []
    for m in range(T):
        if m < S:
            inject.append(m)
        else:
            # stage 0 frees microbatch m-S at tick inject[m-S] + 2(S-1)
            # (its backward sub-slot); the slot is reusable next tick.
            inject.append(max(inject[m - 1] + 1,
                              inject[m - S] + 2 * (S - 1) + 1))
    n_ticks = inject[-1] + 2 * (S - 1) + 1
    fwd = -np.ones((n_ticks, S), np.int32)
    bwd = -np.ones((n_ticks, S), np.int32)
    for m, t0 in enumerate(inject):
        for s in range(S):
            fwd[t0 + s, s] = m
            bwd[t0 + (S - 1) + (S - 1 - s), s] = m
    # exact in-flight bound -> ring depth (a stage's resident microbatches
    # are a contiguous id range, so distinct slots need depth >= range).
    depth = 1
    for s in range(S):
        live = 0
        for t in range(n_ticks):
            if fwd[t, s] >= 0:
                live += 1
                depth = max(depth, live)
            if bwd[t, s] >= 0:
                live -= 1
    return fwd, bwd, depth


def make_pipeline_train_fn(mesh: Mesh, stage_fn: Callable,
                           loss_fn: Callable, n_microbatches: int, *,
                           axis_name: str = "pp", schedule: str = "1f1b",
                           param_axis_spec: P = None):
    """A pipelined TRAINING step: ``fn(stacked_params, x, targets) ->
    (loss, grads)`` with grads stacked/sharded like the params.

    ``stage_fn(stage_params, x_mb) -> y_mb`` maps one microbatch through
    one stage (homogeneous stages: x and y share a shape).
    ``loss_fn(y_mb, target_mb) -> (mb,)`` returns PER-EXAMPLE losses —
    the per-example contract is what lets the batch be padded to any
    microbatch count (padded rows get weight 0), relaxing the
    divisibility constraint. The returned ``loss`` is the mean over the
    real examples; ``grads`` are d(mean loss)/d(params).

    ``schedule='1f1b'`` runs the memory-bounded in-schedule backward;
    ``schedule='gpipe'`` differentiates the forward island with
    ``jax.grad`` (same numerics, activation memory grows with T) — kept
    as the comparison baseline.
    """
    if schedule not in ("1f1b", "gpipe"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    n_stages = mesh.shape[axis_name]
    leaf_spec = param_axis_spec if param_axis_spec is not None \
        else P(axis_name)

    if schedule == "gpipe":
        def fn(stacked_params, x, targets):
            b = x.shape[0]
            mb = -(-b // n_microbatches)
            total = mb * n_microbatches
            xp = _pad_batch(x, total)
            tp = _pad_batch(targets, total)
            w = (jnp.arange(total) < b).astype(jnp.float32)
            pipe = make_gspmd_pipeline_fn(
                mesh, stage_fn, n_microbatches, axis_name=axis_name,
                param_axis_spec=param_axis_spec)

            def total_loss(params):
                y = pipe(params, xp)
                return jnp.sum(loss_fn(y, tp) * w) / b
            loss, grads = jax.value_and_grad(total_loss)(stacked_params)
            return loss, grads
        return fn

    fwd_np, bwd_np, depth = _build_1f1b_schedule(n_stages, n_microbatches)
    fwd_tab, bwd_tab = jnp.asarray(fwd_np), jnp.asarray(bwd_np)
    n_ticks = fwd_np.shape[0]

    def fn(stacked_params, x, targets):
        b = x.shape[0]
        mb = -(-b // n_microbatches)
        total = mb * n_microbatches
        micro_x = _pad_batch(x, total).reshape(
            n_microbatches, mb, *x.shape[1:])
        micro_t = _pad_batch(targets, total).reshape(
            n_microbatches, mb, *targets.shape[1:])
        micro_w = ((jnp.arange(total) < b).astype(jnp.float32)
                   .reshape(n_microbatches, mb))

        def island(params, micro_x, micro_t, micro_w):
            my = lax.axis_index(axis_name)
            is_first = my == 0
            is_last = my == n_stages - 1
            mb_shape = micro_x.shape[1:]

            def tick(carry, t):
                f_recv, b_recv, ring, gacc, loss_acc = carry

                # ---- forward sub-slot
                fm = fwd_tab[t, my]
                dof = fm >= 0
                fms = jnp.maximum(fm, 0)
                x_in = jnp.where(
                    is_first,
                    lax.dynamic_index_in_dim(micro_x, fms, 0, False),
                    f_recv)
                y = stage_fn(params, x_in)
                slot = fms % depth
                old = lax.dynamic_index_in_dim(ring, slot, 0, False)
                ring = lax.dynamic_update_index_in_dim(
                    ring, jnp.where(dof, x_in, old), slot, 0)
                f_recv = prim.line_shift(y, axis_name, 1)

                # ---- backward sub-slot (recompute fwd from the stored
                # stage input, then pull the cotangent through)
                bm = bwd_tab[t, my]
                dob = bm >= 0
                bms = jnp.maximum(bm, 0)
                x_res = lax.dynamic_index_in_dim(ring, bms % depth, 0,
                                                 False)
                y_b, vjp = jax.vjp(stage_fn, params, x_res)
                tgt = lax.dynamic_index_in_dim(micro_t, bms, 0, False)
                w = lax.dynamic_index_in_dim(micro_w, bms, 0, False)

                def wsum(yy):
                    return jnp.sum(loss_fn(yy, tgt) * w)
                lval, dy_loss = jax.value_and_grad(wsum)(y_b)
                dy = jnp.where(is_last, dy_loss, b_recv)
                dp, dx = vjp(dy)
                keep = dob.astype(jnp.float32)
                gacc = jax.tree_util.tree_map(
                    lambda a, g: a + g * keep.astype(a.dtype), gacc, dp)
                loss_acc = loss_acc + lval * keep * is_last.astype(
                    jnp.float32)
                b_recv = prim.line_shift(dx, axis_name, -1)

                return (f_recv, b_recv, ring, gacc, loss_acc), None

            carry0 = (
                jnp.zeros(mb_shape, micro_x.dtype),
                jnp.zeros(mb_shape, micro_x.dtype),
                jnp.zeros((depth,) + mb_shape, micro_x.dtype),
                jax.tree_util.tree_map(jnp.zeros_like, params),
                jnp.zeros((), jnp.float32),
            )
            (_, _, _, gacc, loss_acc), _ = lax.scan(
                tick, carry0, jnp.arange(n_ticks))
            # loss lives on the last stage only; grads are stage-local
            return lax.psum(loss_acc, axis_name), gacc

        param_specs = jax.tree_util.tree_map(
            lambda _: leaf_spec, stacked_params)
        loss_sum, grads = jax.shard_map(
            island, mesh=mesh,
            in_specs=(param_specs, P(), P(), P()),
            out_specs=(P(), param_specs),
            check_vma=False,
        )(stacked_params, micro_x, micro_t, micro_w)
        inv_b = 1.0 / b
        grads = jax.tree_util.tree_map(
            lambda g: g * jnp.asarray(inv_b, g.dtype), grads)
        return loss_sum * inv_b, grads

    return fn


def stack_layer_params(layer_params_list):
    """Stack per-layer param pytrees (a list of identical-structure trees)
    into one tree with leading axis = n_layers — the layout the pipeline
    shards over ``pp``."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *layer_params_list)
