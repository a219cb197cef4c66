"""Slot-pooled KV cache: fixed shapes, one jitted decode for any mix.

The pool is the continuous-batching counterpart of
``models.generate.KVCache``: per layer one (n_slots, Hkv, width, Dh)
buffer for K and V (width = ``max_len``, or the model's sliding window
under the rolling O(window) layout) plus a per-slot ``lengths``
(n_slots,) int32 vector. All shapes are static, so the whole serving
life of the engine is exactly

- ONE compiled decode program (all slots advance one token, each at its
  own position — ``decode_step_slots``), and
- one compiled admit program PER PREFILL BUCKET (prompts are
  right-padded to a bounded set of lengths; ``prefill_partial`` keeps
  the true length traced).

Slot recycling needs no clearing: a freed slot's stale K/V rows are
never attended, because the per-row position mask only exposes
positions ≤ the slot's current length and every position ≤ length was
written by the CURRENT occupant (admission rewrites the prefix, decode
writes each position as it reaches it; the windowed layout zero-fills
unreached slots at admission).

Compile counts are observable (``CompileCounts``) so tests can assert
the bounded-variants contract instead of trusting it.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generate import (_sample, decode_step_slots,
                               prefill_partial, refuse_blocks, refuse_latent,
                               refuse_mixed, refuse_mixers,
                               spec_commit_slots,
                               spec_verify_slots)
from ..obs import trace as dpxtrace


@dataclass
class CompileCounts:
    """Trace-time counters — each jitted program bumps its counter when
    (re)traced, so ``decode == 1`` after a whole serving run IS the
    zero-recompile claim, asserted."""

    decode: int = 0
    #: layers of the decode program, as last traced, whose attention
    #: took the Mosaic kernel (ops/paged_attention_kernel.py)
    decode_kernel_layers: int = 0
    #: grouped-matmul call sites of the decode (or block-step) program,
    #: as last traced, that took the Mosaic kernel
    #: (ops/grouped_matmul_kernel.py)
    moe_kernel_matmuls: int = 0
    prefill: Dict[int, int] = field(default_factory=dict)  # bucket -> n
    sample: int = 0
    #: the program that puts a new row's first token among the rows'
    #: tokens on the device (``RowSampler.place``)
    place: int = 0
    verify: Dict[int, int] = field(default_factory=dict)   # k+1 -> n
    commit: Dict[int, int] = field(default_factory=dict)   # k+1 -> n

    def bump_prefill(self, bucket: int) -> None:
        self.prefill[bucket] = self.prefill.get(bucket, 0) + 1

    def bump_verify(self, s: int) -> None:
        self.verify[s] = self.verify.get(s, 0) + 1

    def bump_commit(self, s: int) -> None:
        self.commit[s] = self.commit.get(s, 0) + 1


def named_program(fn, name: str, **bound):
    """``partial(fn, **bound)`` as ``jax.jit`` will call it: the
    program's name on the profiler's device plane (``jit_<name>``; a bare
    ``partial`` reads ``jit__unknown`` there, which no reader of a trace
    can search for)."""
    fn = partial(fn, **bound)
    fn.__name__ = name
    return fn


def upload(mirror) -> jnp.ndarray:
    """A host mirror (page tables, lengths, the rows' current tokens) as
    a device array, from a copy numpy makes HERE and nobody else holds:
    the host goes on changing the mirror right after the call. Neither
    ``jnp.asarray`` (may alias the buffer) nor ``jnp.array`` is enough:
    the transfer of a host buffer may wait behind a program in flight
    (on the CPU backend half of 50 trials read the value the host wrote
    AFTER ``jnp.array`` returned), and since chunked prefill a decode
    step is dispatched behind a chunk nobody waits for."""
    return jnp.asarray(np.array(mirror))


def upload_pass(pool, iteration: Optional[int], mirrors, fresh=()):
    """Every host-to-device copy of one decode (or block) pass's
    arguments, and nothing else, under the span ``serve.decode.upload``
    (docs/observability.md), so that what the copies cost can be told
    from the jitted call that follows them: ``mirrors`` through
    :func:`upload`, ``fresh`` (host arrays made for this pass alone,
    which nobody writes again) through ``jnp.asarray``. An argument that
    is on the device already (the engine's tokens: the pass before's
    output) comes back as it is, uncopied and uncounted. The span's
    ``arrays`` and ``bytes`` are counted here, where the work happens;
    the region's nanoseconds add up in ``pool.upload_ns`` (the engine's
    ``host_ns["decode_upload"]``). The span is the engine loop's:
    ``iteration`` None (the draft model's steps, the disaggregated
    decode loop, which have no spans of their own) copies without it."""
    t0 = time.perf_counter_ns()
    copy = lambda: [m if isinstance(m, jax.Array) else upload(m)
                    for m in mirrors] + [jnp.asarray(a) for a in fresh]
    if iteration is None:
        out = copy()
    else:
        with dpxtrace.span("serve.decode.upload", iteration=iteration) as up:
            out = copy()
            sent = [a for a, m in zip(out, (*mirrors, *fresh)) if a is not m]
            up.set(arrays=len(sent), bytes=sum(int(a.nbytes) for a in sent))
    pool.upload_ns += time.perf_counter_ns() - t0
    return out


def greedy_tokens(logits):
    """The tail of every decode program: each slot's greedy token,
    (n_slots,) int32 — ``_sample`` at temperature 0 over the same
    float32 logits, so a greedy stream is bit for bit what a sampler
    program of its own gave it. The engine reads these in one fetch
    (``serve/sampling.py`` replaces the rows that sample)."""
    return _sample(logits, None, 0.0, None)


class SlotPool:
    """Owns the pooled cache arrays and the jitted slot programs."""

    def __init__(self, model, n_slots: int, max_len: int,
                 window: Optional[int] = None):
        refuse_latent(model, "the contiguous SlotPool (paged=False)")
        refuse_blocks(model, "the contiguous SlotPool (paged=False)")
        refuse_mixed(model, "the contiguous SlotPool (paged=False)")
        refuse_mixers(model, "the contiguous SlotPool (paged=False)")
        self.model = model
        self.n_slots = n_slots
        self.max_len = max_len
        self.window = window
        self.width = window if window is not None else max_len
        dh = model.dim // model.n_heads
        h_kv = getattr(model, "n_kv_heads", model.n_heads)
        shape = (n_slots, h_kv, self.width, dh)
        self.ks: List[jax.Array] = [jnp.zeros(shape, model.dtype)
                                    for _ in range(model.n_layers)]
        self.vs: List[jax.Array] = [jnp.zeros(shape, model.dtype)
                                    for _ in range(model.n_layers)]
        self.lengths = jnp.zeros((n_slots,), jnp.int32)
        self.compiles = CompileCounts()
        self.upload_ns = 0          # cumulative, see upload_pass
        self._admit_fns: Dict[int, callable] = {}
        # donate the pool buffers: the caller always replaces its
        # references with the returned pools, and without donation the
        # decode hot loop would copy the WHOLE pool every token (2x
        # peak KV memory) instead of updating in place
        self._decode_fn = jax.jit(self._decode, donate_argnums=(1, 2, 3))

    # -- jitted programs ---------------------------------------------------

    def _decode(self, params, ks, vs, lengths, tokens, active):
        self.compiles.decode += 1          # trace-time only
        logits, ks, vs = decode_step_slots(self.model, params, ks, vs,
                                           lengths, tokens,
                                           window=self.window)
        lengths = jnp.where(active, lengths + 1, lengths)
        return greedy_tokens(logits), logits, ks, vs, lengths

    def _admit(self, params, ks, vs, lengths, tokens, true_len, slot,
               *, bucket: int):
        self.compiles.bump_prefill(bucket)  # trace-time only
        logits, kr, vr = prefill_partial(self.model, params, tokens,
                                         true_len, window=self.window)
        if self.window is None:
            # write the bucket-wide prefix of the slot row; positions
            # ≥ true_len hold pad/stale K/V the mask never exposes
            at = (slot, 0, 0, 0)
            ks = [jax.lax.dynamic_update_slice(k, r.astype(k.dtype), at)
                  for k, r in zip(ks, kr)]
            vs = [jax.lax.dynamic_update_slice(v, r.astype(v.dtype), at)
                  for v, r in zip(vs, vr)]
        else:
            # rolling layout is already width-W (zero-filled where
            # unreached): replace the whole row, clearing stale state
            ks = [k.at[slot].set(r[0].astype(k.dtype))
                  for k, r in zip(ks, kr)]
            vs = [v.at[slot].set(r[0].astype(v.dtype))
                  for v, r in zip(vs, vr)]
        lengths = lengths.at[slot].set(true_len)
        return logits, ks, vs, lengths

    def _verify(self, params, ks, vs, lengths, tokens):
        # trace-time only; shapes bake s = k+1, so one compile (and one
        # counter bump) per draft-length bucket falls out of jit
        self.compiles.bump_verify(tokens.shape[1])
        return spec_verify_slots(self.model, params, ks, vs, lengths,
                                 tokens)

    def _commit(self, ks, vs, lengths, sk, sv, commit):
        self.compiles.bump_commit(sk[0].shape[2])   # trace-time only
        return spec_commit_slots(ks, vs, lengths, sk, sv, commit)

    # -- host front ends ---------------------------------------------------

    def spec_verify(self, params, tokens):
        """Score all rows' k+1 candidate tokens ((n_slots, k+1) int32)
        in one batched forward WITHOUT touching the pool — no donation:
        acceptance is decided on the host afterwards and only then does
        :meth:`spec_commit` write (the rejected suffix simply never
        lands). Returns (logits (n_slots, k+1, vocab), sk, sv) with
        sk/sv the per-layer f32 candidate K/V scratch."""
        fn = getattr(self, "_verify_fn", None)
        if fn is None:
            fn = self._verify_fn = jax.jit(self._verify)
            # NOTE deliberately NOT donated (the pool survives verify)
        return fn(params, self.ks, self.vs, self.lengths, tokens)

    def spec_commit(self, sk, sv, commit) -> None:
        """Write each row's accepted prefix (``commit`` (n_slots,)
        int32, 0 = row not speculating) from the verify scratch and
        advance lengths by ``commit``."""
        fn = getattr(self, "_commit_fn", None)
        if fn is None:
            # the verify scratch (sk/sv) stays undonated: its (B, Hkv,
            # k+1, Dh) layout can never alias the (B, Hkv, W, Dh) pool
            # outputs, so donating it only buys an XLA warning
            fn = self._commit_fn = jax.jit(
                self._commit, donate_argnums=(0, 1, 2))
        self.ks, self.vs, self.lengths = fn(
            self.ks, self.vs, self.lengths, sk, sv, commit)

    def admit(self, params, tokens_padded, true_len: int, slot: int):
        """Prefill ``tokens_padded`` (1, bucket) into ``slot``; returns
        the last-real-position logits (1, vocab). One compile per
        distinct bucket width."""
        bucket = tokens_padded.shape[1]
        fn = self._admit_fns.get(bucket)
        if fn is None:
            fn = jax.jit(named_program(self._admit, f"prefill_b{bucket}",
                                       bucket=bucket),
                         donate_argnums=(1, 2, 3))
            self._admit_fns[bucket] = fn
        logits, self.ks, self.vs, self.lengths = fn(
            params, self.ks, self.vs, self.lengths, tokens_padded,
            jnp.asarray(true_len, jnp.int32), jnp.asarray(slot, jnp.int32))
        return logits

    def decode(self, params, tokens, active: np.ndarray,
               iteration: Optional[int] = None):
        """Advance every slot one position (dead slots masked: their
        lengths freeze and their outputs are discarded by the caller).
        tokens/active: (n_slots,) int32 / bool. ``tokens`` on the
        device (a pass's output, the engine's) goes to the program as it
        is; a host array is copied here with ``active``
        (:func:`upload_pass`; the lengths live on the device). Returns
        each slot's greedy token (n_slots,) int32 and the (n_slots,
        vocab) logits, both left on the device."""
        tokens, active = upload_pass(self, iteration, (tokens,), (active,))
        out, logits, self.ks, self.vs, self.lengths = self._decode_fn(
            params, self.ks, self.vs, self.lengths, tokens, active)
        return out, logits

    def release(self, slot: int) -> None:
        """Zero a retired slot's length (the engine's every exit path
        calls this, mirroring ``PagedSlotPool.release``). Correctness
        never needed it — a freed slot's stale rows are unreachable
        under the position mask — but the blockwise decode's trip count
        is ``max(lengths)``: a frozen 2000-token length would keep every
        co-resident short request paying for 2000 positions until the
        slot was reused, exactly the O(capacity) tax the kernel
        removes."""
        self.lengths = self.lengths.at[slot].set(0)
