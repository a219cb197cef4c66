"""One mesh-addressed pjit/GSPMD front door for every train step.

Eleven PRs grew THREE parallel implementations of the paper's one
capability — a data-parallel train step: the SPMD mesh engine
(``data_parallel.make_train_step``), the GSPMD constraint ladder
(``fsdp.make_fsdp_train_step`` / ``spmd.make_spmd_train_step``), and
the ZeRO-1 flat-bucket engine (``optim.sharded.spmd``) — and every
feature since (quantized wire, adaptive width, sharded update, bf16
mixed precision, remat) landed as per-front-door duplicates. This
module is the de-duplication: ONE spec-driven builder where dp / fsdp /
tp / ZeRO-1 are just PartitionSpec choices, resolved through the
existing ``parallel.shard_layouts`` / ``opt_state_specs`` contract, and
the historical builders are thin shims over it (kept API-compatible).

The pjit discipline (``in_axis_resources`` / ``out_axis_resources`` /
``donate_argnums``, mesh at the call site):

* **Whole-step buffer donation by default** (``donate=None`` reads the
  typed ``DPX_DONATE`` knob, default on): params + optimizer state are
  donated into the step with ``out_shardings`` pinned EQUAL to
  ``in_shardings``, so XLA aliases the output buffers onto the donated
  inputs — the ZeRO paper's point (arXiv 2004.13336) that the sharded
  update's memory win only fully lands when the update runs in place.
  The win is observable: :meth:`FrontDoorStep.memory_analysis` reports
  XLA's own accounting (``alias_size_in_bytes`` > 0, peak bytes
  strictly below the copy build — the ``dp8_donate`` bench arm gates
  this in CI).
* **One compiled program per (mesh, specs, width) point**: builds are
  cached on the FULL config tuple (mesh fingerprint, spec trees, wire,
  weight_update, mixed_precision, remat, donate, pad_multiple — the
  regression class where a kwargs combo missed the cache and silently
  dropped donation is structurally closed), and every program carries a
  trace-time compile counter (``step.compiles`` /
  ``step.trace_counts``) so tests assert the discipline instead of
  trusting it — the serve/ PR 3/PR 8 pattern applied to training.
* **Reshard-free pjit-to-pjit handoff**: the step exposes its
  ``out_shardings``; :func:`make_eval_step` pins its ``in_shardings``
  to them and :func:`verify_handoff` asserts (never copies) that a
  params tree already carries the expected shardings — so the
  train step → eval → serve-admit chain moves ZERO bytes between
  programs (``serve.EngineConfig(param_shardings=...)`` runs the same
  assertion at admission).

Spec resolution (docs/front_door.md has the full table)::

    specs=None          pure DP: replicated params, batch over "dp",
                        per-rank stacked losses (the DDP contract)
    specs=FROM_INPUTS   GSPMD propagate: sharding carried by the
                        inputs (the classic pjit shape; spmd.py shim)
    specs=StepSpecs(..) constraint ladder: params/opt/grad spec trees
                        pin ZeRO-3/2/1 + tp layouts (fsdp.py shims)
    weight_update=      the ZeRO-1 flat-bucket engine (optim/sharded)
      "sharded"         behind the same signature

The host (per-rank-process) front door is dispatched to unchanged —
its engines live in ``data_parallel._make_host_train_step`` and
``optim.sharded.host``; donation/shardings are an XLA-program property
and do not apply there.
"""

from __future__ import annotations

import collections
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import metrics as _dpxmon
from ..obs import trace as _dpxtrace
from ..optim import Optimizer
from ..runtime import compile_cache, context
from ..runtime.context import DATA_AXIS
from .data_parallel import (GRAD_REDUCE_MODES, MP_POLICIES, StepOutput,
                            _wire_format, _wrap_mixed_precision)

#: weight_update spellings accepted by :func:`make_step`.
WEIGHT_UPDATES = ("replicated", "sharded")


class _FromInputs:
    """Sentinel: sharding is carried by the inputs (GSPMD propagate)."""

    def __repr__(self):  # stable cache-key repr
        return "FROM_INPUTS"


FROM_INPUTS = _FromInputs()


class StepSpecs(NamedTuple):
    """The constraint-ladder spec trees (``None`` defaults follow the
    fsdp ladder: ``opt`` <- ``params``, ``grads`` <- ``opt``)."""

    params: Any
    opt: Any = None
    grads: Any = None


class Buffers(NamedTuple):
    """Leaves of the parameter tree that live outside the optimizer:
    state the model carries that no gradient moves (a router's balancing
    bias, a running statistic kept beside the weights).

    ``mask(params)`` is a tree of bools shaped like ``params``, True at
    such a leaf. ``update(params, metrics)`` is the model's rule: it gets
    the parameters after ``optimizer.update`` and the loss's own second
    output, and returns the parameters with those leaves moved; what it
    does to any other leaf is dropped. The step (docs/front_door.md):

    * differentiates with respect to the other leaves only, so no
      gradient is formed for a buffer;
    * hands the optimizer trees with ``None`` at the buffers (``init`` on
      :meth:`trainable` makes no moments there, ``update`` applies no
      decay);
    * leaves them out of the mixed-precision cast;
    * applies ``update`` inside the same compiled program, under the
      scope ``optimizer`` > ``buffers``, donated like the rest."""

    mask: Callable[[Any], Any]
    update: Callable[[Any, Any], Any]

    def trainable(self, params):
        """``params`` with None at every buffer: what ``optimizer.init``
        takes (``opt_state = opt.init(buffers.trainable(params))``)."""
        return jax.tree_util.tree_map(
            lambda m, p: None if m else p, self.mask(params), params)

    def merge(self, trained, params):
        """The buffers of ``params`` beside the other leaves of
        ``trained`` (which may hold anything, or None, at a buffer)."""
        return jax.tree_util.tree_map(
            lambda m, t, p: p if m else t, self.mask(params), trained,
            params, is_leaf=lambda x: x is None)


def _value_and_grad(loss_fn, buffers, params, batch):
    """((loss, metrics), grads): with ``buffers``, the gradient's tree
    has None where a buffer is."""
    if buffers is None:
        return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
    return jax.value_and_grad(
        lambda t: loss_fn(buffers.merge(t, params), batch),
        has_aux=True)(buffers.trainable(params))


def _update(optimizer, buffers, grads, opt_state, params, metrics):
    """The optimizer's step, then the buffers' rule."""
    with jax.named_scope("optimizer"):
        if buffers is None:
            return optimizer.update(grads, opt_state, params)
        trained, opt_state = optimizer.update(
            grads, opt_state, buffers.trainable(params))
        params = buffers.merge(trained, params)
        with jax.named_scope("buffers"):
            # only the buffers are taken from the rule's result
            params = buffers.merge(params, buffers.update(params, metrics))
        return params, opt_state


class HandoffMismatch(ValueError):
    """A pjit-to-pjit handoff would have resharded: the tree does not
    already carry the expected shardings. Raised INSTEAD of copying —
    the front-door contract is that train -> eval -> admit moves zero
    bytes between programs."""

    def __init__(self, what: str, path: str, got, want):
        self.what, self.path, self.got, self.want = what, path, got, want
        super().__init__(
            f"reshard-free handoff violated for {what}: leaf {path!r} "
            f"carries sharding {got} but the consumer pins {want} — "
            f"place the producer's out_shardings on it (or fix the "
            f"producer) instead of letting pjit silently copy")


# ---------------------------------------------------------------------------
# config + cache
# ---------------------------------------------------------------------------


def _mesh_key(mesh: Mesh) -> Tuple:
    return (tuple(mesh.axis_names), tuple(mesh.shape.values()),
            tuple(d.id for d in mesh.devices.flat))


def _spec_key(specs) -> str:
    # PartitionSpec trees repr deterministically; a string key survives
    # unhashable containers (dicts/lists of P) inside the trees
    return repr(specs)


def _shardings(mesh: Mesh, spec_tree):
    """NamedSharding tree from a PartitionSpec tree (P is a tuple
    subclass — without is_leaf, tree_map would recurse into it)."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _place(tree, shardings):
    """``tree`` on the step's pinned in-shardings. The sharding is part
    of an argument's type, so a fresh ``model.init`` tree (one device,
    no mesh) and the step's own mesh-sharded outputs would trace and
    compile the same program twice; placing first makes every call the
    same type. The steady state — the step fed its own outputs — is
    recognized leaf by leaf and costs no ``device_put`` dispatch."""
    with _dpxtrace.span("train.place"):
        leaves = jax.tree_util.tree_leaves(tree)
        want = ([shardings] * len(leaves)
                if isinstance(shardings, NamedSharding)
                else jax.tree_util.tree_leaves(shardings))
        if len(want) == len(leaves) and all(
                getattr(leaf, "sharding", None) == w
                for leaf, w in zip(leaves, want)):
            return tree
        return jax.device_put(tree, shardings)


def _dispatch(prog, *args):
    """Hand the step program its (already placed) arguments."""
    with _dpxtrace.span("train.dispatch"):
        return prog(*args)


#: Bounded LRU of built steps. The cache exists for the no-silent-
#: retrace / donation-key contract, which an LRU preserves for live
#: configs; a hard bound keeps a long-lived process that builds steps
#: with fresh loss closures (sweeps, notebooks — keys that can never
#: hit again) from retaining every compiled program + closed-over
#: model forever. Evicted steps keep working — callers own them; only
#: a LATER identical-config request would rebuild.
_CACHE_MAX = 64
_CACHE: "collections.OrderedDict[Tuple, FrontDoorStep]" = \
    collections.OrderedDict()


def cache_clear() -> None:
    """Drop every cached compiled-step builder (tests)."""
    _CACHE.clear()


def cache_info() -> Dict[Tuple, "FrontDoorStep"]:
    return dict(_CACHE)


# ---------------------------------------------------------------------------
# the step object
# ---------------------------------------------------------------------------


class FrontDoorStep:
    """A compiled, donated, mesh-addressed train step.

    Callable as ``step(params, opt_state, batch)``; carries the
    observability surface the compile-counter/handoff contracts assert:

    * ``trace_counts`` — program key (wire width) -> times traced;
      ``compiles`` is their sum. One program per (mesh, spec, width)
      point means every value stays 1. ``xla_compiles`` is the
      process-wide count beside it (``compile_cache.compile_events``:
      every program XLA built, whoever asked), and ``calls`` the steps
      taken.
    * ``in_shardings`` / ``out_shardings`` — dicts with ``params`` /
      ``opt`` / ``batch`` entries (None on the single-device and host
      paths). Params and opt are PINNED equal in/out.
    * ``memory_analysis(params, opt_state, batch)`` — XLA's compiled
      memory accounting for the current program (peak/alias bytes; the
      donation win, measured not narrated).
    * ``donated``, ``config`` — what was built.
    * ``width_chooser`` — the adaptive wire's state machine (None
      otherwise); ``init_opt_state`` / ``state_specs`` on the sharded
      engine.
    """

    def __init__(self, config: Tuple, donated: bool):
        self.config = config
        self.donated = donated
        self.trace_counts: Dict[Any, int] = {}
        self.in_shardings: Optional[Dict[str, Any]] = None
        self.out_shardings: Optional[Dict[str, Any]] = None
        self.width_chooser = None
        self._programs: Dict[Any, Any] = {}   # key -> jitted program
        self._counting = True
        self._call = None                      # bound by the builder
        self.calls = 0

    # -- observability ------------------------------------------------------

    @property
    def compiles(self) -> int:
        return sum(self.trace_counts.values())

    @property
    def xla_compiles(self) -> Dict[str, Any]:
        return compile_cache.compile_events()

    def _bump(self, key) -> None:
        # trace-time only: executed while jax traces the program body
        if self._counting:
            self.trace_counts[key] = self.trace_counts.get(key, 0) + 1

    def program(self, key=None):
        """The jitted program for ``key`` (default: the only/current
        one) — the AOT handle ``memory_analysis`` lowers."""
        if key is None:
            if self.width_chooser is not None:
                key = self.width_chooser.width
            elif len(self._programs) == 1:
                key = next(iter(self._programs))
            else:
                raise KeyError(
                    f"program key required, have {set(self._programs)}")
        return self._programs[key]

    def lower(self, params, opt_state, batch, key=None):
        """The step program lowered for these arguments (``.as_text()``
        shows which kernels it holds, ``.compile()`` gives XLA's
        accounting). The lowering retrace is excluded from
        ``trace_counts``."""
        self._counting = False
        try:
            return self.program(key).lower(params, opt_state, batch)
        finally:
            self._counting = True

    def memory_analysis(self, params, opt_state, batch, key=None) -> dict:
        """Compile-time memory accounting of the step program via XLA's
        ``memory_analysis`` (the donation A/B evidence): peak bytes =
        arguments + outputs + temps - aliased (donated buffers alias
        their outputs, so the donated build's peak is strictly lower)."""
        ma = self.lower(params, opt_state, batch,
                        key).compile().memory_analysis()
        out = {k: int(getattr(ma, k + "_size_in_bytes"))
               for k in ("argument", "output", "temp", "alias")}
        out["peak_bytes"] = (out["argument"] + out["output"]
                             + out["temp"] - out["alias"])
        return out

    # -- call ---------------------------------------------------------------

    def __call__(self, params, opt_state, batch):
        self.calls += 1
        # children: train.place (each tree, in _place) and train.dispatch
        with _dpxtrace.span("train.step_call", step=self.calls):
            out = self._call(params, opt_state, batch)
        # dpxmon step hook (obs/metrics.py; one global read when off):
        # the mesh engines' python wrapper is the per-call seam — the
        # host-door builders return their own step functions and hook
        # themselves, so no call is ever double-counted
        _dpxmon.on_train_step("front_door")
        return out


# ---------------------------------------------------------------------------
# handoff
# ---------------------------------------------------------------------------


def verify_handoff(tree, shardings, *, what: str = "params"):
    """Assert ``tree`` already carries ``shardings`` — the reshard-free
    pjit-to-pjit handoff check. Returns ``tree`` UNCHANGED (zero
    copies); raises :class:`HandoffMismatch` naming the first diverging
    leaf otherwise. ``shardings`` is a single ``NamedSharding``
    (applied to every leaf) or an exact tree of them; ``None`` skips
    the check (single-device / host paths have no sharding contract)."""
    if shardings is None:
        return tree
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if isinstance(shardings, NamedSharding):
        want = [shardings] * len(leaves)
    else:
        want = jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
        if len(want) != len(leaves):
            raise HandoffMismatch(what, "<structure>",
                                  f"{len(leaves)} leaves",
                                  f"{len(want)} shardings")
    paths = [
        "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                 for k in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    for path, leaf, w in zip(paths, leaves, want):
        got = getattr(leaf, "sharding", None)
        if got is None:
            raise HandoffMismatch(what, path, "<uncommitted host value>",
                                  w)
        if not got.is_equivalent_to(w, jnp.ndim(leaf)):
            raise HandoffMismatch(what, path, got, w)
    return tree


def handoff_shardings(step) -> Optional[Any]:
    """The params out-shardings a downstream pjit program (eval, serve
    admit) should pin as its in-shardings. None when the step has no
    sharding contract (world 1, host door)."""
    out = getattr(step, "out_shardings", None)
    return out.get("params") if isinstance(out, dict) else None


# ---------------------------------------------------------------------------
# builder
# ---------------------------------------------------------------------------


def make_step(loss_fn: Callable, optimizer: Optimizer, *,
              mesh: Optional[Mesh] = None,
              specs: Any = None,
              wire: str = "mean",
              weight_update: Optional[str] = None,
              mixed_precision: Optional[str] = None,
              remat: Any = None,
              overlap: Optional[bool] = None,
              comm_buckets: Optional[int] = None,
              on_bucket_ready: Optional[Callable] = None,
              donate: Optional[bool] = None,
              pad_multiple: Optional[int] = None,
              buffers: Optional[Buffers] = None) -> Callable:
    """Build THE train step: ``step(params, opt_state, batch)``.

    ``loss_fn(params, batch) -> (loss, metrics)``. Parallelism is a
    spec choice, not a builder choice:

    * ``specs=None`` — pure DP over the ``dp`` mesh axis (replicated
      params, per-rank stacked losses: :class:`..data_parallel
      .StepOutput`); every ``wire`` mode (mean | quant/int8 | q4 |
      adaptive) composes here.
    * ``specs=FROM_INPUTS`` — GSPMD propagate (global scalar loss:
      ``SpmdStepOutput``); place params/batch with explicit shardings
      first, the partitioner derives the collectives.
    * ``specs=StepSpecs(params, opt, grads)`` — the constraint ladder
      (ZeRO-3/2/1, tp): spec trees from ``fsdp_param_specs`` /
      ``shard_layouts`` / ``transformer_lm_param_specs``.
    * ``weight_update="sharded"`` — the ZeRO-1 flat-bucket engine
      (``optim/sharded``): reduce-scatter -> owned-slice step ->
      all-gather, state specs exported for the sharded checkpointer.

    ``mixed_precision`` / ``remat`` resolve through the typed
    ``DPX_MP_POLICY`` / ``DPX_REMAT`` knobs and wrap ``loss_fn`` before
    engine dispatch, so every engine (host door included) honors them.
    ``donate=None`` reads ``DPX_DONATE`` (default on): params + opt
    state are donated with out == in shardings pinned. ``overlap`` /
    ``comm_buckets`` / ``on_bucket_ready`` are host-door knobs
    (bucketed update overlap); the compiled mesh engines ignore them
    (XLA already schedules the fused reduce against compute).

    ``buffers`` (:class:`Buffers`): leaves outside the optimizer and the
    rule that moves them from the loss's second output, inside the step
    program. Taken where the loss is the whole batch's: one device, or
    ``specs=FROM_INPUTS``; the per-shard engines refuse it by name (a
    rule would see one shard's metrics).

    Builds are cached on the full config tuple — re-requesting an
    identical config returns the SAME step object (compile counters
    prove no silent re-trace); any differing kwarg is a different
    cache point, so a donate/wire/mp change can never inherit a stale
    program built under other flags.
    """
    from ..runtime import compile_cache, env as _env

    compile_cache.enable()
    if wire not in GRAD_REDUCE_MODES:
        raise ValueError(f"wire (grad_reduce) must be one of "
                         f"{'|'.join(GRAD_REDUCE_MODES)}, got {wire!r}")
    if mixed_precision is None:
        mixed_precision = _env.get("DPX_MP_POLICY")
    if mixed_precision not in MP_POLICIES:
        raise ValueError(f"mixed_precision must be one of "
                         f"{'|'.join(MP_POLICIES)}, got "
                         f"{mixed_precision!r}")
    if weight_update is None:
        weight_update = _env.get("DPX_WEIGHT_UPDATE")
    if weight_update not in WEIGHT_UPDATES:
        raise ValueError(f"weight_update must be "
                         f"{'|'.join(WEIGHT_UPDATES)}, got "
                         f"{weight_update!r}")
    if donate is None:
        donate = bool(_env.get("DPX_DONATE"))
    if weight_update == "sharded" and wire in ("q4", "adaptive"):
        raise ValueError(
            "weight_update='sharded' supports wire mean|quant|int8 only "
            "(the sharded gather leg pins the q8 grid its exact-master "
            "error feedback assumes); use weight_update='replicated' "
            "with q4/adaptive")

    from ..models.transformer import apply_remat_policy, resolve_remat
    remat_policy = resolve_remat(remat)

    base_loss = loss_fn
    # the step's words in the JAX name stack (names only): the loss
    # closure reads ``jvp(loss)`` and not ``jvp()`` on the device plane
    loss_fn = jax.named_scope("loss")(loss_fn)
    loss_fn = _wrap_mixed_precision(loss_fn, mixed_precision, buffers)
    if remat_policy != "none":
        loss_fn = apply_remat_policy(loss_fn, remat_policy)

    # -- host (per-rank-process) door: its engines are not pjit programs
    if context.get_host_comm() is not None:
        _refuse_buffers(buffers, "the host door")
        if weight_update == "sharded":
            from ..optim.sharded.host import make_host_sharded_train_step
            if pad_multiple is not None:
                raise ValueError(
                    "pad_multiple applies to the SPMD/global-state "
                    "engine; the host engine derives its layout from "
                    "the live world")
            return make_host_sharded_train_step(loss_fn, optimizer,
                                                grad_reduce=wire)
        from .data_parallel import _make_host_train_step
        return _make_host_train_step(loss_fn, optimizer, grad_reduce=wire,
                                     overlap=overlap,
                                     comm_buckets=comm_buckets,
                                     on_bucket_ready=on_bucket_ready)

    if mesh is None:
        mesh = context.get_mesh()
    world = context.get_world_size()

    key = ("front_door", base_loss, optimizer, _mesh_key(mesh), world,
           _spec_key(specs), wire, weight_update, mixed_precision,
           remat_policy, bool(donate), pad_multiple, buffers)
    try:
        cached = _CACHE.get(key)
    except TypeError:                    # unhashable loss/optimizer
        cached, key = None, None
    if cached is not None:
        _CACHE.move_to_end(key)          # LRU touch
        return cached

    step = FrontDoorStep(config=key or ("front_door", "<unhashable>"),
                         donated=bool(donate))
    if weight_update == "sharded":
        _refuse_buffers(buffers, "weight_update='sharded'")
        _build_sharded(step, loss_fn, optimizer, mesh, world,
                       wire=wire, donate=donate, pad_multiple=pad_multiple)
    elif isinstance(specs, _FromInputs):
        _build_propagate(step, loss_fn, optimizer, donate=donate,
                         buffers=buffers)
    elif specs is None:
        if world > 1:
            _refuse_buffers(buffers, "the dp island over several devices")
        _build_stacked_dp(step, loss_fn, optimizer, mesh, world,
                          wire=wire, donate=donate, buffers=buffers)
    else:
        _refuse_buffers(buffers, "the constraint ladder (StepSpecs)")
        if not isinstance(specs, StepSpecs):
            specs = StepSpecs(params=specs)
        _build_constrained(step, loss_fn, optimizer, mesh, specs,
                           donate=donate)
    if key is not None:
        _CACHE[key] = step
        while len(_CACHE) > _CACHE_MAX:
            _CACHE.popitem(last=False)   # evict least-recently-used
    return step


# ---------------------------------------------------------------------------
# engine: pure DP over the dp axis (stacked per-rank losses)
# ---------------------------------------------------------------------------


def _refuse_buffers(buffers, where: str) -> None:
    if buffers is not None:
        raise ValueError(
            f"buffers= is not carried by {where}: its rule would see one "
            f"shard's metrics, or its state is laid out from the whole "
            f"parameter tree. Use one device or specs=FROM_INPUTS "
            f"(docs/front_door.md, 'Leaves outside the optimizer')")


def _leaf_offsets(leaves, block: int):
    """Start offset of each leaf inside the block-padded flat bucket."""
    offs, off = [], 0
    for g in leaves:
        offs.append(off)
        off += g.size + ((-g.size) % block)
    return offs


def _build_stacked_dp(step, loss_fn, optimizer, mesh, world, *,
                      wire, donate, buffers=None):
    """The DDP engine: forward -> backward -> gradient mean over ``dp``
    -> replicated update, ONE XLA program, per-rank stacked losses.
    Quantized wires ride one flat block-aligned bucket through
    ``comm.primitives``; the adaptive mode compiles one program per
    width (bounded by the chooser's hysteresis) and ships one scalar
    statistic to the host-side chooser."""
    from ..comm import primitives as prim

    def _reduce_grads(grads, bits=8, want_flat=False):
        if wire == "mean":
            return prim.pmean(grads, DATA_AXIS), None
        # ONE compressed collective pair for the whole tree: flatten
        # every leaf into a single f32 bucket, reduce, unflatten —
        # dozens of per-leaf all-to-alls would pay per-collective
        # latency on exactly the meshes this targets. Each leaf is
        # zero-padded to a QUANT_BLOCK multiple so no quantization-scale
        # block ever spans two leaves — a tiny layernorm grad sharing a
        # block with an embedding grad's tail would quantize to zero
        # under the big leaf's scale. (The per-leaf padding is also why
        # this is hand-rolled rather than jax.flatten_util.ravel_pytree.)
        bs = prim.QUANT_BLOCK
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        padded = []
        for g in leaves:
            f = jnp.ravel(g).astype(jnp.float32)
            pad = (-f.shape[0]) % bs
            padded.append(jnp.pad(f, (0, pad)) if pad else f)
        red = prim.quantized_pmean(jnp.concatenate(padded), DATA_AXIS,
                                   bits=bits)
        out, off = [], 0
        for g in leaves:
            out.append(red[off:off + g.size].reshape(g.shape)
                       .astype(g.dtype))
            off += g.size + ((-g.size) % bs)
        # the chooser statistic runs on the UNPADDED concatenation —
        # the per-leaf pad zeros above would deflate their blocks' rms
        # and read as dynamic range, pinning the adaptive width at q8
        # for any model with many small leaves; dropping them also
        # matches the host front door's chooser input (raw ravel
        # concat), so both front doors walk the same policy
        flat = jnp.concatenate(
            [red[o:o + g.size] for o, g in
             zip(_leaf_offsets(leaves, bs), leaves)]) \
            if want_flat else None
        return jax.tree_util.tree_unflatten(treedef, out), flat

    adaptive = wire == "adaptive" and world > 1
    fixed_bits = 8
    if wire in ("quant", "int8", "q4") and world > 1:
        from ..comm import host_backend as _hb
        resolved = _hb.resolve_wire_width(_wire_format(wire))
        if resolved == "adaptive":      # DPX_WIRE_WIDTH=adaptive
            adaptive = True
        else:
            fixed_bits = resolved

    def make_local_step(bits, want_stat):
        def local_step(params, opt_state, batch):
            step._bump(bits)             # trace-time compile counter
            (loss, metrics), grads = _value_and_grad(loss_fn, buffers,
                                                     params, batch)
            stat = jnp.float32(0.0)
            if world > 1:
                grads, red = _reduce_grads(grads, bits,
                                           want_flat=want_stat)
                if want_stat and red is not None:
                    from ..comm.wire import DYNRANGE_THRESH
                    from ..ops.quant import block_outlier_frac_jnp
                    stat = block_outlier_frac_jnp(
                        red, prim.QUANT_BLOCK, DYNRANGE_THRESH)
            params, opt_state = _update(optimizer, buffers, grads,
                                        opt_state, params, metrics)
            return params, opt_state, loss[None], metrics, stat
        return local_step

    dargs = (0, 1) if donate else ()

    if world == 1:
        inner = make_local_step(8, False)
        prog = jax.jit(inner, donate_argnums=dargs)
        step._programs[8] = prog

        def call(params, opt_state, batch):
            return StepOutput(*_dispatch(prog, params, opt_state,
                                         batch)[:4])
        step._call = call
        return

    rep = NamedSharding(mesh, P())
    dp = NamedSharding(mesh, P(DATA_AXIS))
    # the pinned pjit contract: params/opt donated, out == in (rep),
    # loss/metrics stacked over dp, the chooser stat replicated
    step.in_shardings = {"params": rep, "opt": rep, "batch": dp}
    step.out_shardings = {"params": rep, "opt": rep, "loss": dp,
                          "metrics": dp}

    def compile_width(bits, want_stat):
        sharded = jax.shard_map(
            make_local_step(bits, want_stat), mesh=mesh,
            in_specs=(P(), P(), P(DATA_AXIS)),
            out_specs=(P(), P(), P(DATA_AXIS), P(DATA_AXIS), P()),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=dargs,
                       in_shardings=(rep, rep, dp),
                       out_shardings=(rep, rep, dp, dp, rep))

    if not adaptive:
        prog = compile_width(fixed_bits, False)
        step._programs[fixed_bits] = prog

        def call(params, opt_state, batch):
            return StepOutput(*_dispatch(prog, _place(params, rep),
                                         _place(opt_state, rep),
                                         batch)[:4])
        step._call = call
        return

    # adaptive: one compiled program per width (the chooser's hysteresis
    # bounds the flapping, so at most two programs ever exist); the
    # dynamic-range statistic is computed INSIDE the step on the reduced
    # bucket — bit-identical across devices — and only that scalar
    # crosses to the host, where the chooser (shared policy with the
    # host front door) picks the next step's program.
    from ..comm.wire import WidthChooser
    step.width_chooser = chooser = WidthChooser()
    step._programs.update({8: compile_width(8, True),
                           4: compile_width(4, True)})

    def call(params, opt_state, batch):
        p, o, loss, metrics, stat = _dispatch(
            step._programs[chooser.width],
            _place(params, rep), _place(opt_state, rep), batch)
        chooser.observe_frac(float(stat))
        return StepOutput(p, o, loss, metrics)
    step._call = call


# ---------------------------------------------------------------------------
# engine: GSPMD propagate (sharding carried by the inputs)
# ---------------------------------------------------------------------------


def _build_propagate(step, loss_fn, optimizer, *, donate, buffers=None):
    from .spmd import SpmdStepOutput

    def body(params, opt_state, batch):
        step._bump("propagate")
        (loss, metrics), grads = _value_and_grad(loss_fn, buffers, params,
                                                 batch)
        params, opt_state = _update(optimizer, buffers, grads, opt_state,
                                    params, metrics)
        return SpmdStepOutput(params, opt_state, loss, metrics)

    prog = jax.jit(body, donate_argnums=(0, 1) if donate else ())
    step._programs["propagate"] = prog
    step._call = lambda *args: _dispatch(prog, *args)


# ---------------------------------------------------------------------------
# engine: the constraint ladder (ZeRO-3/2/1, tp — spec-driven)
# ---------------------------------------------------------------------------


def _build_constrained(step, loss_fn, optimizer, mesh, specs: StepSpecs,
                       *, donate):
    """The fsdp ladder as ONE pjit program: in/out shardings pinned
    from the spec trees (params and opt state donated, out == in), the
    gradient constraint inside picking the ZeRO rung, opt-state specs
    derived through the ``opt_state_specs`` contract at first call."""
    from .fsdp import opt_state_specs
    from .spmd import SpmdStepOutput

    param_specs = specs.params
    state_specs = specs.opt if specs.opt is not None else param_specs
    grad_specs = specs.grads if specs.grads is not None else state_specs

    def constrain(tree, tree_specs):
        return jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)),
            tree, tree_specs, is_leaf=lambda x: x is None)

    def body(params, opt_state, batch):
        step._bump("constrained")
        o_specs = opt_state_specs(opt_state, state_specs, params=params)
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        grads = constrain(grads, grad_specs)   # reduce-scatter/all-reduce
        with jax.named_scope("optimizer"):
            params, opt_state = optimizer.update(grads, opt_state, params)
        params = constrain(params, param_specs)
        opt_state = constrain(opt_state, o_specs)
        return SpmdStepOutput(params, opt_state, loss, metrics)

    p_sh = _shardings(mesh, param_specs)
    step.in_shardings = {"params": p_sh, "opt": None, "batch": None}
    step.out_shardings = {"params": p_sh, "opt": None}
    holder = {}

    def call(params, opt_state, batch):
        prog = holder.get("prog")
        if prog is None:
            # opt-state structure is only known with a concrete state:
            # derive its spec tree once, pin in == out, donate
            o_specs = opt_state_specs(opt_state, state_specs,
                                      params=params)
            o_sh = _shardings(mesh, o_specs)
            step.in_shardings["opt"] = o_sh
            step.out_shardings["opt"] = o_sh
            prog = jax.jit(
                body, donate_argnums=(0, 1) if donate else (),
                in_shardings=(p_sh, o_sh, None),
                out_shardings=SpmdStepOutput(p_sh, o_sh, None, None))
            holder["prog"] = prog
            step._programs["constrained"] = prog
        return _dispatch(prog, _place(params, p_sh),
                         _place(opt_state, step.in_shardings["opt"]), batch)

    step._call = call


# ---------------------------------------------------------------------------
# engine: ZeRO-1 flat-bucket sharded update (optim/sharded, SPMD door)
# ---------------------------------------------------------------------------


def _build_sharded(step, loss_fn, optimizer, mesh, world, *,
                   wire, donate, pad_multiple):
    """The ``reduce-scatter -> owned-slice step -> all-gather`` engine
    (arXiv 2004.13336) on mesh collectives under ``shard_map``:
    ``psum_scatter`` hands each device its 1/world chunk of the flat
    grad bucket, the wrapped optimizer updates the chunk's moments +
    master, ``all_gather`` rebuilds the replicated params — with
    ``wire="quant"`` both legs ride the block-int8 codec and the gather
    leg is bit-identical across devices by construction. The sharded
    state is GLOBAL flat vectors sharded ``P("dp")`` — the spec tree
    ``step.state_specs`` exports for the resharding checkpointer; at
    world == 1 the same structure runs through a plain jitted step, so
    checkpoints stay portable across 1..N."""
    from ..comm import primitives as prim
    from ..optim.sharded.layout import build_layout
    from ..optim.sharded.optimizer import shard_optimizer

    quant = wire in ("quant", "int8")
    holder = step.holder = {}

    def _ensure(params):
        if "layout" not in holder:
            holder["layout"] = build_layout(params, world,
                                            pad_multiple=pad_multiple)
            holder["sharded"] = shard_optimizer(optimizer,
                                                holder["layout"])
        return holder["layout"], holder["sharded"]

    def init_opt_state(params):
        layout, sharded = _ensure(params)
        state = sharded.init_global(params)
        if world > 1:
            from .tensor import shard_params
            state = shard_params(state, state_specs(state), mesh)
        return state

    def state_specs(opt_state, axis: str = DATA_AXIS):
        layout = holder.get("layout")
        if layout is None:
            raise RuntimeError(
                "state_specs needs the layout — call init_opt_state "
                "(or run one step) first")
        return layout.state_specs(opt_state, axis=axis)

    def _local_step(layout, sharded, params, state, batch):
        step._bump("sharded")
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        flat_g = layout.flatten_jnp(grads)
        if world > 1:
            if quant:
                g_slice = prim.quantized_reduce_scatter(
                    flat_g, DATA_AXIS) / world
            else:
                g_slice = prim.reduce_scatter(flat_g, DATA_AXIS) / world
        else:
            g_slice = flat_g
        with jax.named_scope("optimizer"):
            new_master, new_state = sharded.update_flat(g_slice, state)
        if world > 1:
            if quant:
                flat_new = prim.quantized_all_gather(new_master,
                                                     DATA_AXIS)
            else:
                flat_new = prim.all_gather(new_master, DATA_AXIS,
                                           axis=0, tiled=True)
        else:
            flat_new = new_master
        new_params = layout.unflatten_jnp(flat_new)
        return new_params, new_state, loss[None], metrics

    def _build(params, opt_state):
        layout, sharded = _ensure(params)
        dargs = (0, 1) if donate else ()
        if world == 1:
            def local(params, state, batch):
                return StepOutput(*_local_step(layout, sharded, params,
                                               state, batch))
            return jax.jit(local, donate_argnums=dargs)

        specs = state_specs(opt_state)
        rep = NamedSharding(mesh, P())
        dp = NamedSharding(mesh, P(DATA_AXIS))
        o_sh = _shardings(mesh, specs)
        step.in_shardings = {"params": rep, "opt": o_sh, "batch": dp}
        step.out_shardings = {"params": rep, "opt": o_sh, "loss": dp,
                              "metrics": dp}
        island = lambda p, s, b: _local_step(layout, sharded, p, s, b)
        sharded_fn = jax.shard_map(
            island, mesh=mesh,
            in_specs=(P(), specs, P(DATA_AXIS)),
            out_specs=(P(), specs, P(DATA_AXIS), P(DATA_AXIS)),
            check_vma=False)

        def stepper(params, state, batch):
            return StepOutput(*sharded_fn(params, state, batch))
        return jax.jit(stepper, donate_argnums=dargs,
                       in_shardings=(rep, o_sh, dp),
                       out_shardings=StepOutput(rep, o_sh, dp, dp))

    def call(params, opt_state, batch):
        if "compiled" not in holder:
            holder["compiled"] = _build(params, opt_state)
            step._programs["sharded"] = holder["compiled"]
        if world > 1:
            params = _place(params, step.in_shardings["params"])
            opt_state = _place(opt_state, step.in_shardings["opt"])
        return _dispatch(holder["compiled"], params, opt_state, batch)

    step._call = call
    step.init_opt_state = init_opt_state
    step.state_specs = state_specs


# ---------------------------------------------------------------------------
# eval: the pjit-to-pjit consumer side
# ---------------------------------------------------------------------------


def make_eval_step(eval_fn: Callable, *, like=None,
                   mesh: Optional[Mesh] = None) -> Callable:
    """Compile a data-parallel eval step whose params ``in_shardings``
    are pinned to ``like``'s params OUT-shardings (``like`` is a train
    :class:`FrontDoorStep`) — the reshard-free handoff's consumer half:
    feeding it the train step's output params moves zero bytes.

    ``eval_fn(params, batch) -> metrics`` (per-example leading axis);
    the returned ``step(params, batch)`` runs on the global batch and
    carries the same ``trace_counts`` / ``in_shardings`` surface.

    Two consumer shapes, chosen by what ``like`` pins:

    * a single replicated ``NamedSharding`` (the dp/sharded engines, or
      no ``like``): eval is the ``shard_map`` island over ``dp``;
    * a TREE of shardings (the constraint-ladder engines — ZeRO-3/tp
      params arrive SHARDED): eval is a GSPMD-propagate jit pinned to
      exactly that tree, so the partitioner derives the gathers around
      the sharded weights instead of this step replicating them up
      front — the params still move zero bytes at the boundary.
    """
    if mesh is None:
        mesh = context.get_mesh()
    world = context.get_world_size()
    pinned = handoff_shardings(like) if like is not None else None

    counters = {"n": 0}

    def body(params, batch):
        counters["n"] += 1               # trace-time only
        return eval_fn(params, batch)

    if world == 1:
        # dpxlint: disable=DPX006 eval does not own the params (the trainer still does)
        prog = jax.jit(body)
        in_sh = None
    elif pinned is not None and not isinstance(pinned, NamedSharding):
        # tree-shaped producer shardings (constrained ladder): pin the
        # whole tree verbatim — a replicated fallback here would make
        # pjit silently all-gather the weights on entry, the exact copy
        # this module exists to forbid
        dp = NamedSharding(mesh, P(DATA_AXIS))
        # dpxlint: disable=DPX006 eval does not own the params (the trainer still does)
        prog = jax.jit(body, in_shardings=(pinned, dp))
        in_sh = {"params": pinned, "batch": dp}
    else:
        rep = pinned if isinstance(pinned, NamedSharding) \
            else NamedSharding(mesh, P())
        dp = NamedSharding(mesh, P(DATA_AXIS))
        island = jax.shard_map(body, mesh=mesh,
                               in_specs=(P(), P(DATA_AXIS)),
                               out_specs=P(DATA_AXIS), check_vma=False)
        # dpxlint: disable=DPX006 eval does not own the params (the trainer still does)
        prog = jax.jit(island, in_shardings=(rep, dp), out_shardings=dp)
        in_sh = {"params": rep, "batch": dp}

    def run(params, batch):
        return prog(params, batch)

    run.trace_counts = counters
    run.in_shardings = in_sh
    run.program = lambda: prog
    return run
