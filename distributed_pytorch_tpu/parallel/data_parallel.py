"""Data parallelism — the DDP engine (reference ``distributed.py:112-115``
and the C++ reducer behind it, SURVEY.md §2.3 row 4).

What torch DDP does eagerly — broadcast params at construction, then hook
autograd to all-reduce gradient buckets during backward and average by world
size — compiles here into **one XLA program per step**:

    forward → backward → gradient pmean over the ``dp`` mesh axis →
    optimizer update → metrics

via ``shard_map`` over the batch axis: every device runs the same program on
its batch shard with *replicated* params, ``pmean`` lowers to a single fused
all-reduce over ICI (XLA buckets/fuses it — no hand-written bucketing
needed), and the optimizer update runs redundantly-but-identically on each
device, keeping params replicated with zero extra communication. Numerics
match DDP: the synchronized gradient is the mean over ranks of per-rank
mean-gradients, which equals the global-batch mean gradient because the
sharded sampler pads every rank to equal shard sizes (``data/sampler.py``).

Per-rank observability (the reference prints per-rank loss/acc every step,
``min_DDP.py:110-116``) is preserved: the step returns per-rank losses
stacked ``(world,)`` and per-example metrics stacked in rank order — exactly
the "stacked" layout the eager collectives consume (``comm/collectives.py``).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..comm import primitives as prim
from ..optim import Optimizer
from ..runtime import context
from ..runtime.context import DATA_AXIS


class StepOutput(NamedTuple):
    params: Any
    opt_state: Any
    loss: jnp.ndarray        # (world,) per-rank mean losses (stacked layout)
    metrics: Any             # pytree of (world*B, ...) per-example values


class StatefulStepOutput(NamedTuple):
    params: Any
    state: Any               # model state (e.g. BatchNorm running stats)
    opt_state: Any
    loss: jnp.ndarray
    metrics: Any


#: grad_reduce spellings accepted by :func:`make_train_step`.
GRAD_REDUCE_MODES = ("mean", "int8", "quant", "q4", "adaptive")

#: mixed_precision policies accepted by :func:`make_train_step`.
MP_POLICIES = ("off", "bf16")


def mp_cast_params(params):
    """The bf16 compute copy of an f32 master tree: float32 leaves cast
    to bfloat16, everything else (int tables, already-low-precision
    leaves, quantized int8 weights) untouched. The ONE definition of
    the mixed-precision working-copy cast — the train step and the
    tests pin the same rule."""
    return jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16)
        if hasattr(p, "dtype") and p.dtype == jnp.float32 else p, params)


def _wrap_mixed_precision(loss_fn: Callable, policy: str,
                          buffers=None) -> Callable:
    """``bf16``: the loss consumes the bf16 CAST of the f32 params
    (leaves outside the optimizer, ``buffers``, are state and not weights:
    the loss sees them as the step carries them).

    This is the master-weights recipe (docs/compute.md, the same
    error-feedback shape as PR 7's sharded gather leg and
    ``optim.with_master_f32``): the authoritative copy stays float32 —
    the optimizer only ever updates the master, so sub-``2^-8``
    updates are never lost to bf16 rounding — while every matmul in
    forward AND backward runs on bf16 operands (activations follow the
    params' dtype through the first embedding/projection). The cast is
    linear, so JAX returns the gradients in the MASTER's dtype (f32):
    both comm front doors, the quantized wire, and the sharded ZeRO-1
    update all see the exact f32 gradient tree they already speak.

    Softmax and LayerNorm statistics stay f32 by the kernels' own
    contract (``nn.attention.dense_attention``, the flash kernel,
    ``ops.decode_attention``), which is what keeps bf16 compute from
    degrading accumulation — guarded by tests, not by hope.
    """
    if policy == "off":
        return loss_fn

    def mp_loss(params, batch):
        with jax.named_scope("cast"):
            working = mp_cast_params(
                params if buffers is None else buffers.trainable(params))
        if buffers is not None:
            working = buffers.merge(working, params)
        return loss_fn(working, batch)

    return mp_loss


def _wire_format(grad_reduce: str) -> str:
    """Map a grad_reduce spelling onto the front doors' wire-format
    vocabulary (comm/host_backend.WIRE_FORMATS)."""
    if grad_reduce in ("quant", "int8"):
        return "quant"
    return grad_reduce  # "q4" / "adaptive" pass through


def make_train_step(loss_fn: Callable, optimizer: Optimizer,
                    donate: Optional[bool] = None,
                    grad_reduce: str = "mean",
                    weight_update: Optional[str] = None,
                    overlap: Optional[bool] = None,
                    comm_buckets: Optional[int] = None,
                    on_bucket_ready: Optional[Callable] = None,
                    mixed_precision: Optional[str] = None,
                    buffers=None) -> Callable:
    """Compile a data-parallel training step.

    Thin shim over the one mesh-addressed front door
    (:func:`.front_door.make_step` — docs/front_door.md): this builder
    keeps the historical DDP-facing signature; the engine, the builder
    cache, whole-step buffer donation (``donate=None`` reads the typed
    ``DPX_DONATE`` knob, default on) with out == in shardings, and the
    compile-counter discipline all live there.

    ``loss_fn(params, batch) -> (loss, metrics)`` where ``loss`` is the
    *local-batch mean* scalar and ``metrics`` a pytree of per-example arrays
    (leading axis = local batch). Returns
    ``step(params, opt_state, batch) -> StepOutput`` operating on the global
    batch (axis 0 sharded over ``dp``); at world==1 the same signature runs
    unsharded, so the identical training script covers 1..N devices — the
    reference's graceful-degradation contract (``distributed.py:54-58``).

    ``grad_reduce``: ``"mean"`` (exact all-reduce, the reference's DDP
    semantics), ``"quant"`` (alias ``"int8"``; wire width from the
    typed ``DPX_WIRE_WIDTH`` knob, default 8-bit), ``"q4"`` (force the
    nibble-packed 4-bit wire, ~7.9x less gradient traffic than f32), or
    ``"adaptive"`` (per-bucket width from observed dynamic range with
    hysteresis — :class:`..comm.wire.WidthChooser`; the chooser state
    is exposed as ``step.width_chooser``). Both front doors honor every
    mode: the SPMD path quantizes the stacked-leaf bucket before the
    ``dp``-axis reduce (:func:`..comm.primitives.quantized_pmean`; the
    adaptive mode compiles ONE program per width — bounded by the
    chooser's hysteresis — and ships one scalar dynamic-range statistic
    to the host per step); the host front door ships the flat bucket
    over the native chunk-pipelined quantized ring with an
    error-feedback residual (:class:`..ops.quant.ErrorFeedback`)
    carrying each step's quantization error — q4's larger one included
    — into the next step's bucket. Under ``DPX_HIER_RING=L`` the host
    bucket rides the two-level hierarchical ring (:mod:`..comm.hier`).

    ``overlap`` (host front door; default from ``DPX_COMM_OVERLAP``):
    split the gradient tree into ``comm_buckets`` buckets
    (``DPX_COMM_BUCKETS`` default) and issue each bucket's ring traffic
    as soon as its leaves materialize — while later buckets' backward
    is still executing on the device — instead of one blocking reduce
    after the full backward. Non-final buckets' comm time lands in
    CommStats ``overlapped_s``; only the final bucket's is ``exposed_s``
    (docs/comms.md has the accounting contract). ``on_bucket_ready(b,
    n_buckets, nbytes)`` is called as each bucket becomes host-visible
    — the hook a custom trainer uses to interleave its own work. The
    compiled SPMD path ignores these (XLA already schedules the fused
    reduce against compute).

    ``mixed_precision``: ``"off"`` (f32 throughout) or ``"bf16"``
    (default from the typed ``DPX_MP_POLICY`` knob): run forward and
    backward on the bf16 CAST of the params while the f32 tree the
    step carries stays the authoritative master the optimizer updates
    — the master-weights pattern (docs/compute.md). Orthogonal to
    every other mode: the wrap happens before front-door dispatch, so
    SPMD, host, sharded (ZeRO-1) and overlapped steps all honor it,
    and the gradients crossing any wire remain f32 (quantization error
    feedback composes unchanged).

    ``weight_update``: ``"replicated"`` (every rank runs the full
    optimizer step — DDP/torch semantics) or ``"sharded"`` (ZeRO-1,
    arXiv 2004.13336: reduce-scatter the grads, step only the owned
    1/world slice, all-gather the updated params — 1/world optimizer
    memory and update compute; :mod:`..optim.sharded`). Defaults to the
    typed env knob ``DPX_WEIGHT_UPDATE``. The sharded step's
    ``opt_state`` comes from the returned step's
    ``init_opt_state(params)``, not ``optimizer.init`` — the moments
    live on flat 1/world slices. The sharded path speaks the fixed q8
    wire only (its gather leg's error feedback owns the exact master
    copy); combine q4/adaptive with ``weight_update="replicated"``.

    ``buffers``: a :class:`.front_door.Buffers`, the leaves of the
    parameter tree that live outside the optimizer and the rule that
    moves them (docs/front_door.md, "Leaves outside the optimizer").
    """
    from .front_door import make_step
    return make_step(loss_fn, optimizer, wire=grad_reduce,
                     weight_update=weight_update,
                     mixed_precision=mixed_precision,
                     overlap=overlap, comm_buckets=comm_buckets,
                     on_bucket_ready=on_bucket_ready, donate=donate,
                     buffers=buffers)


def _partition_contiguous(sizes, k: int):
    """Split leaf indices into <= k contiguous groups balanced by
    element count (greedy by the running target). Deterministic in the
    sizes alone, so every rank partitions identically."""
    k = max(1, min(int(k), len(sizes)))
    if k == 1:
        return [list(range(len(sizes)))]
    total = sum(sizes)
    groups, cur, acc = [], [], 0
    for i, s in enumerate(sizes):
        cur.append(i)
        acc += s
        # close the group once the cumulative count crosses the next
        # k-quantile of the total (k is a cap — tiny trees yield fewer)
        if acc * k >= total * (len(groups) + 1) \
                and len(groups) < k - 1:
            groups.append(cur)
            cur = []
    if cur:
        groups.append(cur)
    return groups


def _make_host_train_step(loss_fn: Callable, optimizer: Optimizer,
                          grad_reduce: str = "mean",
                          overlap: Optional[bool] = None,
                          comm_buckets: Optional[int] = None,
                          on_bucket_ready: Optional[Callable] = None
                          ) -> Callable:
    """Per-rank-process DDP step (host front door): compiled local
    forward/backward, then native ring allreduce(s) over flat gradient
    bucket(s) (the reference DDP reducer's bucketed gradient averaging,
    SURVEY.md §2.3 row 4), then compiled optimizer update.

    Same ``step(params, opt_state, batch) -> StepOutput`` signature as the
    SPMD path, but ``batch`` is this rank's LOCAL batch and ``loss`` has
    shape (1,) (this rank's mean loss) — each process holds only its own
    view, exactly like the reference's workers.

    ``grad_reduce="quant"``/``"int8"``/``"q4"``/``"adaptive"``: the
    bucket rides the native chunk-pipelined quantized ring (width per
    the mode / ``DPX_WIRE_WIDTH``; two-level under ``DPX_HIER_RING``).
    A per-bucket :class:`..ops.quant.ErrorFeedback` residual (per
    process, carried across steps) pre-rounds the bucket onto its
    CURRENT wire grid, so the first hop transmits exactly and
    systematic rounding bias — q4's larger step included — cancels over
    steps. The reduced bucket is bit-identical on every rank, so ranks
    cannot drift apart, and the adaptive chooser feeding on it steps
    identically world-wide (asserted via the schedule recorder).

    ``overlap``: split the gradient tree into buckets and pipeline each
    bucket's ring traffic against the PREVIOUS bucket's optimizer
    update, which is dispatched asynchronously on the device and left
    unfenced while the next bucket's comm blocks the control thread.
    (With one fused backward, XLA delivers ALL gradients atomically —
    there is no later-layer backward left to hide behind once the first
    leaf is host-visible; the genuinely overlappable device work on
    this front door is the replicated optimizer update, which the
    dp8_sharded bench showed DOMINATES the replicated step.) Accounting
    is MEASURED, not positional: comm counts as ``overlapped_s`` only
    when a previously dispatched bucket update was genuinely still
    executing at issue time (``jax.Array.is_ready``), else
    ``exposed_s``. The overlapped step keeps per-bucket optimizer
    states — take ``opt_state`` from the exposed
    ``step.init_opt_state(params)`` (the PR 7 convention the examples
    already follow); per-bucket updates are numerically identical for
    elementwise optimizers (each bucket keeps its own identical step
    counter) — wrappers that reduce ACROSS leaves (global-norm
    clipping) are unsupported under overlap, same restriction as the
    sharded update.
    """
    import numpy as np

    from ..comm import host_backend as _hb
    from ..obs import metrics as _dpxmon
    from ..obs import trace as _dpxtrace
    from ..ops.quant import ErrorFeedback
    from ..runtime import env as _envmod

    comm = context.get_host_comm()
    world = comm.world
    quant = grad_reduce != "mean"
    width = _hb.resolve_wire_width(_wire_format(grad_reduce)) \
        if quant else None
    chooser = None
    if width == "adaptive":
        from ..comm.wire import WidthChooser
        chooser = WidthChooser()
    local_world = int(_envmod.get("DPX_HIER_RING"))
    use_hier = quant and local_world > 1 and world % local_world == 0
    if overlap is None:
        overlap = bool(_envmod.get("DPX_COMM_OVERLAP"))
    n_buckets = comm_buckets if comm_buckets is not None \
        else int(_envmod.get("DPX_COMM_BUCKETS"))
    if not overlap:
        n_buckets = 1

    # dpxlint: disable=DPX006 grads-only jit; params re-read every step
    vg = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
    # dpxlint: disable=DPX006 host door interleaves update with ring comm on the same buffers
    upd = jax.jit(optimizer.update)
    efs = {}  # bucket index -> ErrorFeedback (sizes differ per bucket)

    def _ring(flat, bits, hidden):
        if use_hier:
            from ..comm.hier import hier_ring
            hier_ring(comm, local_world).allreduce(flat, bits=bits,
                                                   hidden=hidden)
        elif bits == 4:
            comm.allreduce_q4(flat, hidden=hidden)
        else:
            comm.allreduce_q8(flat, hidden=hidden)

    def _reduce_bucket(b, flat, bits, hidden):
        if quant:
            ef = efs.setdefault(b, ErrorFeedback())
            flat = ef.compensate(flat, bits=bits)
            _ring(flat, bits, hidden)
        else:
            comm.allreduce(flat, hidden=hidden)
        flat /= world  # DDP averages gradients
        return flat

    def _observe(reduced):
        if chooser is not None:
            # the chooser feeds on the reduced MEAN bucket — identical
            # bits on every rank (quant ring bit-identity), so the
            # width state machine cannot diverge across ranks
            chooser.observe(np.concatenate(reduced)
                            if len(reduced) > 1 else reduced[0])

    if not overlap:
        def step(params, opt_state, batch):
            # dpxtrace spans (obs/trace.py, no-ops unless DPX_TRACE):
            # host_step > backward / bucket(wire nests inside) / update
            # is the bucket→wire→update breakdown the cross-rank
            # timeline renders per rank
            with _dpxtrace.span("host_step", wire=grad_reduce,
                                buckets=1):
                with _dpxtrace.span("backward"):
                    (loss, metrics), grads = vg(params, batch)
                    leaves, tree = jax.tree_util.tree_flatten(grads)
                    bits = (chooser.width if chooser is not None
                            else (width or 8))
                    # the concat materializes the grads: backward time
                    # is attributed here, not to the async dispatch
                    flat = np.concatenate(
                        [np.asarray(l, dtype=np.float32).ravel()
                         for l in leaves])
                if on_bucket_ready is not None:
                    on_bucket_ready(0, 1, flat.nbytes)
                with _dpxtrace.span("bucket", b=0, nbytes=flat.nbytes,
                                    bits=bits):
                    flat = _reduce_bucket(0, flat, bits, False)
                _observe([flat])
                outs, off = [], 0
                for l in leaves:
                    outs.append(jnp.asarray(
                        flat[off:off + l.size].reshape(l.shape),
                        dtype=l.dtype))
                    off += l.size
                grads = jax.tree_util.tree_unflatten(tree, outs)
                with _dpxtrace.span("update"):
                    params, opt_state = upd(grads, opt_state, params)
            # dpxmon step hook (obs/metrics.py, one global read when
            # off): steps counter + cadence histogram + the
            # DPX_MON_EVERY snapshot auto-emission
            _dpxmon.on_train_step("host_step")
            return StepOutput(params, opt_state,
                              jnp.asarray(loss)[None], metrics)

        step.width_chooser = chooser
        return step

    # -- overlapped path: per-bucket states + interleaved async updates

    def _groups_for(tree_like):
        return _partition_contiguous(
            [l.size for l in jax.tree_util.tree_leaves(tree_like)],
            n_buckets)

    def init_opt_state(params):
        leaves = jax.tree_util.tree_leaves(params)
        return [optimizer.init([leaves[i] for i in idx])
                for idx in _groups_for(params)]

    def _outstanding(pending):
        # MEASURED overlap: a dispatched update counts as outstanding
        # only while the device genuinely hasn't finished it (is_ready
        # is False). Backends without is_ready fall back to "dispatched
        # and unfenced = outstanding".
        for leaf in pending:
            ready = getattr(leaf, "is_ready", None)
            if ready is None:
                return True
            if not ready():
                return True
        return False

    def step(params, opt_state, batch):
        with _dpxtrace.span("host_step", wire=grad_reduce,
                            buckets=n_buckets, overlap=True):
            with _dpxtrace.span("backward"):
                (loss, metrics), grads = vg(params, batch)
                gleaves, gtree = jax.tree_util.tree_flatten(grads)
            pleaves = jax.tree_util.tree_leaves(params)
            groups = _partition_contiguous([l.size for l in gleaves],
                                           n_buckets)
            # a LIST specifically: optimizer states are NamedTuples/
            # dicts/bare tuples, so requiring the exact container
            # init_opt_state returns keeps a full-tree state from ever
            # being indexed as per-bucket states (an AdamWState IS a
            # 3-tuple — a len check alone can collide with a 3-bucket
            # partition)
            if not isinstance(opt_state, list) \
                    or len(opt_state) != len(groups):
                raise TypeError(
                    "the overlapped host step keeps PER-BUCKET "
                    "optimizer states — build opt_state with "
                    "step.init_opt_state(params), not optimizer.init")
            bits = chooser.width if chooser is not None else (width or 8)
            new_p = [None] * len(gleaves)
            new_states = [None] * len(groups)
            pending = []   # dispatched, unfenced update outputs
            reduced = []
            for b, idx in enumerate(groups):
                flat = np.concatenate(
                    [np.asarray(gleaves[i], dtype=np.float32).ravel()
                     for i in idx])
                if on_bucket_ready is not None:
                    on_bucket_ready(b, len(groups), flat.nbytes)
                hidden = _outstanding(pending)
                # the bucket span carries the MEASURED overlap verdict
                # (hidden = a prior bucket's update was genuinely still
                # executing at comm-issue time); the wire span nests
                # inside via CommStats.timed
                with _dpxtrace.span("bucket", b=b,
                                    nbytes=flat.nbytes, bits=bits,
                                    hidden=hidden):
                    flat = _reduce_bucket(b, flat, bits, hidden)
                reduced.append(flat)
                g_sub, off = [], 0
                for i in idx:
                    n = gleaves[i].size
                    g_sub.append(jnp.asarray(
                        flat[off:off + n].reshape(gleaves[i].shape),
                        dtype=gleaves[i].dtype))
                    off += n
                # dispatch this bucket's update and DON'T fence it: the
                # device chews on it while the next bucket's ring
                # traffic blocks the control thread — that concurrency
                # is what the is_ready probe above measures into
                # overlapped_s
                with _dpxtrace.span("update", b=b):
                    out_p, out_state = upd(g_sub, opt_state[b],
                                           [pleaves[i] for i in idx])
                pending.extend(out_p)
                for j, i in enumerate(idx):
                    new_p[i] = out_p[j]
                new_states[b] = out_state
            _observe(reduced)
            params = jax.tree_util.tree_unflatten(gtree, new_p)
        _dpxmon.on_train_step("host_step")
        return StepOutput(params, new_states,
                          jnp.asarray(loss)[None], metrics)

    step.width_chooser = chooser
    step.init_opt_state = init_opt_state
    return step


def make_stateful_train_step(loss_fn: Callable, optimizer: Optimizer,
                             donate: bool = True) -> Callable:
    """Like :func:`make_train_step` for models with non-trained state
    (BatchNorm running stats): ``loss_fn(params, state, batch) ->
    (loss, (new_state, metrics))``. Returns
    ``step(params, state, opt_state, batch) -> StatefulStepOutput``.

    State follows torch-DDP BatchNorm semantics: each device updates stats
    from its *local* shard (no cross-device sync); the returned state is
    the per-device state (kept sharded per rank under world>1).
    """
    world = context.get_world_size()

    def local_step(params, state, opt_state, batch):
        (loss, (new_state, metrics)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, state, batch)
        if world > 1:
            grads = prim.pmean(grads, DATA_AXIS)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, new_state, opt_state, loss[None], metrics

    if world == 1:
        def step(params, state, opt_state, batch):
            return StatefulStepOutput(*local_step(params, state, opt_state,
                                                  batch))
        return jax.jit(step, donate_argnums=(0, 1, 2) if donate else ())

    mesh = context.get_mesh()
    # state in/out spec: each device keeps its own running stats. The state
    # arrives replicated (same init everywhere) but diverges per device; we
    # shard-map it as per-device local values stacked on a leading axis.
    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(DATA_AXIS), P(), P(DATA_AXIS)),
        out_specs=(P(), P(DATA_AXIS), P(), P(DATA_AXIS), P(DATA_AXIS)),
        check_vma=False,
    )

    def step(params, state, opt_state, batch):
        return StatefulStepOutput(*sharded(params, state, opt_state, batch))

    return jax.jit(step, donate_argnums=(0, 1, 2) if donate else ())


def make_eval_step(eval_fn: Callable) -> Callable:
    """Compile a data-parallel evaluation step (no gradients, no update).

    ``eval_fn(params, batch) -> metrics`` returns a pytree of per-example
    arrays (leading axis = local batch). The returned
    ``step(params, batch)`` runs on the global batch (axis 0 sharded over
    ``dp``) and yields the metrics in global rank order — the inference
    analog of :func:`make_train_step`, with the same 0/1/N graceful
    degradation."""
    world = context.get_world_size()
    if world == 1:
        # dpxlint: disable=DPX006 eval does not own the params (the trainer still does)
        return jax.jit(eval_fn)
    mesh = context.get_mesh()
    sharded = jax.shard_map(
        eval_fn, mesh=mesh,
        in_specs=(P(), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS),
        check_vma=False,
    )
    # dpxlint: disable=DPX006 eval does not own the params (the trainer still does)
    return jax.jit(sharded)


def make_stateful_eval_step(eval_fn: Callable) -> Callable:
    """Like :func:`make_eval_step` for models with state (BatchNorm
    running stats): ``eval_fn(params, state, batch) -> metrics``. State is
    per-device (the stacked layout of :func:`stack_state`) and read-only —
    eval mode uses running stats without updating them."""
    world = context.get_world_size()
    if world == 1:
        # dpxlint: disable=DPX006 eval does not own the params (the trainer still does)
        return jax.jit(eval_fn)
    mesh = context.get_mesh()
    sharded = jax.shard_map(
        eval_fn, mesh=mesh,
        in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS),
        check_vma=False,
    )
    # dpxlint: disable=DPX006 eval does not own the params (the trainer still does)
    return jax.jit(sharded)


def stack_state(state, world: Optional[int] = None):
    """Stack a single model-state pytree to the per-rank layout the
    stateful step expects (leading axis = world)."""
    w = world or context.get_world_size()
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(jnp.asarray(x)[None],
                                   (w,) + jnp.shape(x)), state)


def make_scan_train_steps(loss_fn: Callable, optimizer: Optimizer,
                          n_steps: int, donate: bool = True) -> Callable:
    """Fuse ``n_steps`` training steps into ONE compiled XLA program via
    ``lax.scan`` over pre-staged batches.

    This is the TPU-idiomatic answer to per-step dispatch overhead (the
    reference pays Python + NCCL launch latency every iteration;
    SURVEY.md §3.3): the scanned program keeps params/opt state resident
    on-device and runs F/B/all-reduce/update n_steps times per host
    round-trip. Returns
    ``run(params, opt_state, batches) -> (params, opt_state, losses)`` with
    ``batches`` a pytree whose leaves are stacked (n_steps, global_batch,
    ...) and ``losses`` shaped (n_steps, world).
    """
    world = context.get_world_size()

    def local_scan(params, opt_state, batches):
        def body(carry, batch):
            params, opt_state = carry
            (loss, _), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            if world > 1:
                grads = prim.pmean(grads, DATA_AXIS)
            params, opt_state = optimizer.update(grads, opt_state, params)
            return (params, opt_state), loss
        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), batches)
        return params, opt_state, losses[:, None]

    if world == 1:
        def run(params, opt_state, batches):
            p, o, l = local_scan(params, opt_state, batches)
            return p, o, l
        return jax.jit(run, donate_argnums=(0, 1) if donate else ())

    mesh = context.get_mesh()
    # batches: (n_steps, global_batch, ...) — shard axis 1 over dp
    sharded = jax.shard_map(
        local_scan, mesh=mesh,
        in_specs=(P(), P(), P(None, DATA_AXIS)),
        out_specs=(P(), P(), P(None, DATA_AXIS)),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())


class DataParallel:
    """Module wrapper installing DP — the ``prepare_ddp_model`` result
    (reference ``distributed.py:112-115``).

    Construction replicates the params pytree onto every mesh device — the
    analog of DDP's constructor broadcast from rank 0. ``train_step`` is the
    compiled synchronized step described in the module docstring;
    ``apply`` runs a (sharded-batch) forward.
    """

    def __init__(self, module, params: Any):
        if params is None:
            raise ValueError(
                "DataParallel needs the model's params pytree: pass "
                "prepare_ddp_model(model, params=params) or set model.params"
            )
        self.module = module
        self.params = context.replicate(params)

    def apply(self, params, x, **kwargs):
        return self.module.apply(params, x, **kwargs)

    __call__ = apply

    def make_train_step(self, loss_fn: Callable, optimizer: Optimizer,
                        **kw) -> Callable:
        return make_train_step(loss_fn, optimizer, **kw)


def prepare_ddp_model(model, device_ids=None, params: Optional[Any] = None,
                      *args, **kwargs):
    """Wrap iff world > 1, else return unchanged — exact contract of the
    reference (``distributed.py:112-115``). ``device_ids`` is accepted for
    signature parity and ignored: the mesh already fixes placement."""
    del device_ids, args, kwargs
    if context.get_world_size() > 1:
        if params is None and hasattr(model, "params"):
            params = model.params
        return DataParallel(model, params)
    return model
