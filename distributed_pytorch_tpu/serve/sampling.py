"""Where a served token is chosen (the engines share this).

A decode program ends in the greedy token of every slot
(``PagedSlotPool``), so a greedy row needs nothing more.
A row that samples joins the rows of its setting: ONE program a distinct
``(temperature, top_k, top_p)`` among the running rows picks all of
them from the whole ``(n_slots, vocab)`` logits — each row with its own
key, ``req.rngs[i]`` for its token ``i``, so every request keeps
``generate()``'s split schedule and its tokens — and merges them into
the greedy tokens on the device. Every shape is ``n_slots`` wide
whatever the number of rows: nothing compiles as the batch breathes. The
caller reads the tokens of a pass in one fetch, and hands them to the
next pass where they are: a new row's first token, a prefill's, is put
among them on the device too (``RowSampler.place``).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generate import _sample
from .cache import CompileCounts
from .types import Request

#: the rows of one sampling setting, n_slots wide: each row's PRNG key
#: (zeros where the row is not of the group) and the row mask
Group = Tuple[np.ndarray, np.ndarray]


def _program_name(prefix: str, sampler_key: tuple) -> str:
    """The program's name on the profiler's device plane."""
    return prefix + "_".join(str(v) for v in sampler_key)


def block_confidence(logits):
    """``x0 = argmax logits`` (B, L) int32 and its confidence ``max
    softmax(logits)`` (B, L) in float32, a position."""
    with jax.named_scope("denoise/confidence"):
        lg = logits.astype(jnp.float32)
        top = jnp.max(lg, axis=-1)
        # max softmax = exp(top - logsumexp) = 1 / sum exp(lg - top)
        return (jnp.argmax(lg, axis=-1).astype(jnp.int32),
                1.0 / jnp.sum(jnp.exp(lg - top[..., None]), axis=-1))


def fill_surest(x0, conf, tokens, masked, n_fill):
    """``tokens`` with the ``n_fill`` masked positions of highest
    ``conf`` (> 0; ties: the lowest position) given their ``x0``, and
    which positions those were: (B, 2, L) int32."""
    with jax.named_scope("denoise/fill"):
        conf = jnp.where(masked, conf, -1.0)       # a filled one is last
        i = jnp.arange(tokens.shape[1])
        a, b = conf[:, None, :], conf[:, :, None]  # b: the position ranked
        ahead = (a > b) | ((a == b) & (i[None, None, :] < i[None, :, None]))
        fill = masked & (jnp.sum(ahead, axis=-1) < n_fill[:, None])
        return jnp.stack([jnp.where(fill, x0, tokens),
                          fill.astype(jnp.int32)], axis=1)


@jax.named_scope("sample")
def fill_block(logits, tokens, masked, n_fill):
    """The pick of one pass of block generation, on the device: at the
    positions of a block that are still masked, ``x0 = argmax logits``
    and its confidence ``max softmax(logits)`` in float32; the
    ``n_fill`` masked positions of highest confidence (ties: the lowest
    position) take their ``x0`` and are never masked again (SDAR's
    ``low_confidence_static``).

    logits (B, L, vocab); tokens (B, L) int32; masked (B, L) bool;
    n_fill (B,) int32 (0 for a row whose block is clean: its commit
    pass, or an idle row). Returns (B, 2, L) int32, the block after the
    pass and which positions this pass filled, one array so that the
    engine reads a pass in one fetch."""
    x0, conf = block_confidence(logits)
    return fill_surest(x0, conf, tokens, masked, n_fill)


@jax.named_scope("sample/denoise/open")
def open_blocks(blocks, given, n_given, mask_id: int):
    """The blocks one pass runs over. ``blocks`` (B, 3, L) int32 is what
    the pass before left on the device (:func:`carry_blocks`): the rows'
    tokens and, in its last row, 1 where a position is still masked. A
    row the host opens a block for (``n_given`` (B,) int32 >= 0: a
    request's first block, which its prompt's remainder begins) takes
    instead the first ``n_given`` of ``given`` (B, L) int32, then
    ``mask_id``, masked; -1 keeps the row's block as it was left.
    Returns tokens (B, L) int32 and masked (B, L) bool."""
    rest = jnp.arange(blocks.shape[2])[None, :] >= n_given[:, None]
    opened = (n_given >= 0)[:, None]
    return (jnp.where(opened, jnp.where(rest, mask_id, given), blocks[:, 0]),
            jnp.where(opened, rest, blocks[:, 2] != 0))


@jax.named_scope("sample/denoise/carry")
def carry_blocks(out, masked, commit, mask_id: int):
    """What a pass leaves on the device, (B, 3, L) int32: both what the
    host reads of it a pass later (the blocks' tokens, the positions the
    pass filled) and what the next pass runs over (the tokens, and 1
    where a position is still masked). ``out`` is :func:`fill_block`'s
    of this pass over blocks that were ``masked`` (B, L) bool before it.
    A row in ``commit`` (B,) bool ran over its clean block, whose keys
    and values this pass made resident: it leaves with its next block,
    every position ``mask_id`` and masked (the host streamed the clean
    block a pass earlier and reads no token of a commit pass)."""
    fresh = commit[:, None]
    left = fresh | (masked & (out[:, 1] == 0))
    return jnp.stack([jnp.where(fresh, mask_id, out[:, 0]), out[:, 1],
                      left.astype(jnp.int32)], axis=1)


def fill_counts(block: int, steps: int):
    """SDAR's transfer schedule: how many positions pass ``s`` of a
    block fills, ``block // steps`` and one more in the first ``block %
    steps`` passes. (steps,) int."""
    return np.asarray([block // steps + (s < block % steps)
                       for s in range(steps)], np.int32)


class RowSampler:
    """The jitted samplers of one engine, by sampling setting."""

    def __init__(self, n_slots: int, compiles: CompileCounts):
        self.n_slots = n_slots
        self.compiles = compiles
        self.dispatches = 0          # batched sampler programs dispatched
        self._one: Dict[tuple, callable] = {}
        self._rows: Dict[tuple, callable] = {}
        self._place = None

    def first(self, req: Request, logits):
        """Dispatch the request's sampler on ``logits`` (1, vocab), a
        prefill's; returns the token still on the device, shape (1,) —
        the caller fetches it, so that dispatch and wait can be told
        apart."""
        key = req.params.sampler_key
        fn = self._one.get(key)
        if fn is None:
            t, k, p = key
            compiles = self.compiles

            def sample(lg, rng):
                compiles.sample += 1               # trace-time only
                return _sample(lg, rng, t, k, p)
            sample.__name__ = _program_name("sample_", key)
            fn = self._one[key] = jax.jit(sample)
        return fn(logits, jnp.asarray(req.rngs[len(req.out_tokens)]))

    def place(self, tokens, first, slot: int):
        """The rows' tokens (n_slots,) int32 on the device with
        ``first`` (1,), a new row's first token as :meth:`first` left it
        there, at ``slot``; ``tokens`` None: there are none yet. One
        small program, and no copy of the token back to the device.
        Every array the decode program is handed as its tokens comes out
        of a program that ran on the engine's params (this one's
        ``first`` does), the first of them too: jit keys a compile on
        whether an argument is committed to its device, and an uploaded
        array is not where such an output may be."""
        if tokens is None:
            tokens = jnp.broadcast_to(first, (self.n_slots,))
        if self._place is None:
            compiles = self.compiles

            def place_first_token(tokens, first, slot):
                compiles.place += 1                # trace-time only
                return tokens.at[slot].set(first[0])
            self._place = jax.jit(place_first_token)
        return self._place(tokens, first, np.int32(slot))

    def join(self, groups: Dict[tuple, Group], slot: int, req: Request,
             step: Optional[int] = None) -> None:
        """File a running row under its sampling setting in ``groups``:
        the key of its token ``step`` (default: its next, for a caller
        that has read every pass it dispatched) and its place in the row
        mask. A greedy row joins none: it takes the decode program's own
        token, and its key stays on the host."""
        if req.params.temperature == 0.0:
            return
        group = groups.get(req.params.sampler_key)
        if group is None:
            group = groups[req.params.sampler_key] = (
                np.zeros((self.n_slots, 2), np.uint32),
                np.zeros(self.n_slots, bool))
        keys, mask = group
        keys[slot] = req.rngs[len(req.out_tokens) if step is None else step]
        mask[slot] = True

    def merge(self, tokens, logits, groups: Dict[tuple, Group]):
        """``tokens`` (n_slots,) with every joined row's token replaced
        by its sample from ``logits`` (n_slots, vocab): one program a
        group, all on the device."""
        for key, (keys, mask) in groups.items():
            fn = self._rows.get(key)
            if fn is None:
                fn = self._rows[key] = self._build_rows(key)
            tokens = fn(logits, keys, mask, tokens)
            self.dispatches += 1
        return tokens

    def _build_rows(self, key: tuple):
        t, k, p = key
        compiles = self.compiles

        def one_row(lg, rng):
            # (1, vocab), as first() and generate() sample it
            return _sample(lg[None], rng, t, k, p)[0]

        def sample_rows(logits, keys, mask, tokens):
            compiles.sample += 1                   # trace-time only
            return jnp.where(mask, jax.vmap(one_row)(logits, keys), tokens)
        sample_rows.__name__ = _program_name("sample_rows_", key)
        return jax.jit(sample_rows)
