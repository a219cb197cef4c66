"""What the slot pools' programs share: compile counts, program names,
the copies a pass makes to the device, the greedy tail.

The pool itself is ``serve.pages.PagedSlotPool``: the engine's, the draft
model's (``serve/spec/``) and the disaggregated engines'
(``serve/disagg/``). They build their programs with:

- :class:`CompileCounts`: each jitted program bumps its counter when
  (re)traced, so tests assert the bounded-variants contract (ONE decode
  program, one admit program a prefill bucket) instead of trusting it;
- :func:`named_program`: a program's name on the profiler's device plane;
- :func:`upload` / :func:`upload_pass`: a host mirror as a device array
  nobody else holds, and a pass's copies under one span;
- :func:`greedy_tokens`: the tail of every decode program.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generate import _sample
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
