"""Draft-model state for speculative decoding (``serve/spec/``).

The draft model keeps its keys in a page pool of its own
(:class:`~..pages.PagedSlotPool`: the target's ``page_len``, no prefix
sharing, exact pages, and the FULL budget of pages, every slot's worst
case at once, so the draft can never want for a page and has no failure
path of its own), slot-for-slot aligned with the target engine's pool:
admitting / retiring / crash-draining a target slot releases the draft
slot through the SAME exit paths, so draft state can never leak past its
request. The invariant the whole subsystem rests on is

    draft cache length == target cache length, holding the SAME
    accepted token stream

— maintained by construction: propose runs the draft ``k + 1`` greedy
steps past the shared current token (the extra step writes the key of
the last draft so a fully-accepted iteration leaves the draft cache
complete), and after the target commits ``e`` accepted positions the
draft ROLLS BACK to ``length + e`` by rewriting its pool's lengths (a
host array) from the mirror kept here: the rejected draft suffix simply
becomes unreachable under the position mask, exactly how slot recycling
already works; the pages it lies in stay the slot's and are written
again by the next propose.

A slot's draft row is ``draft_len + 1`` positions longer than the
target's ``max_len``: propose writes ``k + 1`` keys past a row's
accepted length, and a page table would clamp a position past its last
page into that page, over keys the row has accepted.

Proposals are argmax (greedy) and consume NO rng, so the request's
``jax.random.split`` schedule is untouched — the accepted stream's
bit-exactness to ``generate()`` never depends on draft behaviour, only
the SPEED does (that is the whole point of speculation).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from ..pages import PagedSlotPool


@dataclass
class SpecConfig:
    """Speculative-decoding knobs resolved by the engine: the draft
    model/params pair and ``draft_len`` — how many tokens the draft
    proposes per engine iteration (k; verify scores k + 1 positions in
    one program)."""

    draft_model: Any
    draft_params: Any
    draft_len: int = 4


class SpecState:
    """Owns the draft slot pool and the host-side draft bookkeeping."""

    def __init__(self, cfg: SpecConfig, n_slots: int, max_len: int,
                 page_len: int):
        if cfg.draft_len < 1:
            raise ValueError(
                f"draft_len must be >= 1, got {cfg.draft_len}")
        self.cfg = cfg
        # room for a propose from a row's last accepted position (the
        # module's docstring)
        draft_max = max_len + cfg.draft_len + 1
        self.pool = PagedSlotPool(
            cfg.draft_model, n_slots, draft_max, page_len=page_len,
            n_pages=n_slots * -(-draft_max // page_len),
            prefix_share=False, kv_dtype="f32")
        # the DRAFT truth: propose advances the pool's lengths k+1 steps
        # past the accepted stream, rollback rewrites them from here
        self.len = np.zeros((n_slots,), np.int32)
        #: slot is speculating (draft prefilled and aligned)
        self.active = np.zeros((n_slots,), bool)

    # -- lifecycle ---------------------------------------------------------

    def admit(self, prompt: np.ndarray, slot: int,
              buckets: Sequence[int]) -> None:
        """Prefill the WHOLE prompt into the draft slot, chunk by chunk
        (the admit logits are discarded — the target's admission token
        is the stream's first token either way)."""
        self.pool.admit(self.cfg.draft_params, prompt, slot, tuple(buckets))
        self.len[slot] = prompt.shape[0]
        self.active[slot] = True

    def release(self, slot: int) -> None:
        """Every target-slot exit path (retire, typed failure, crash
        drain) funnels here via the engine's ``_free_slot``."""
        self.active[slot] = False
        self.len[slot] = 0
        self.pool.release(slot)

    # -- the propose / rollback pair ---------------------------------------

    def propose(self, slots: Sequence[int],
                cur_tokens: np.ndarray) -> np.ndarray:
        """k + 1 sequential greedy draft steps for the speculating
        ``slots`` (others masked inactive), starting from each slot's
        shared current token. Returns the proposals (n_spec, k) int32;
        the extra (k+1)-th step emits nothing — it writes the LAST
        proposal's key so a fully-accepted iteration (e = k + 1) leaves
        the draft cache covering every committed position."""
        k = self.cfg.draft_len
        n = self.pool.n_slots
        active = np.zeros((n,), bool)
        active[np.asarray(slots)] = True
        for slot in slots:
            self.pool.ensure_spec_capacity(slot, k + 1)
        toks = np.zeros((n,), np.int32)
        toks[np.asarray(slots)] = cur_tokens
        drafts = np.zeros((len(slots), k), np.int32)
        for j in range(k + 1):
            nxt, _ = self.pool.decode(self.cfg.draft_params, toks, active)
            if j < k:
                drafts[:, j] = np.asarray(nxt)[np.asarray(slots)]
                toks[np.asarray(slots)] = drafts[:, j]
        return drafts

    def rollback(self, slots: Sequence[int],
                 commits: np.ndarray) -> None:
        """Truth update after the target committed: each slot's draft
        length becomes pre-propose length + accepted count, discarding
        the rejected suffix (and propose's k+1 provisional advances) in
        one lengths rewrite."""
        if len(slots):
            self.len[np.asarray(slots)] += np.asarray(commits, np.int32)
        self.pool.lengths[:] = self.len


def accept_greedy(drafts: np.ndarray, logits: np.ndarray,
                  remaining: int,
                  eos: Optional[int]) -> Tuple[List[int], int]:
    """The greedy acceptance rule, host-side and pure.

    ``drafts`` (k,) are the draft's proposals d_1..d_k; ``logits``
    (k+1, vocab) are the target's verify scores at positions len..len+k
    (position j scored AFTER reading [cur, d_1..d_j]). With g = argmax
    per position, the longest accepted prefix is the largest m with
    d_j == g[j-1] for all j <= m, and the emitted stream is
    g[0..m] — m accepted drafts plus the one bonus token the verify
    computed for free. Every emitted token is the target's own argmax
    given previously-emitted context, so the accepted stream equals
    ``generate()``'s greedy stream BY CONSTRUCTION; the draft only
    controls how many tokens each iteration yields.

    ``remaining`` (max_new budget) and ``eos`` truncate the emission;
    both truncations retire the request immediately, so the cache never
    continues from a truncated commit. Returns ``(tokens, e)`` with
    ``e == len(tokens) >= 1``."""
    k = int(drafts.shape[0])
    g = np.argmax(logits, axis=-1).astype(np.int32)
    m = 0
    while m < k and int(drafts[m]) == int(g[m]):
        m += 1
    e = min(m + 1, int(remaining))
    out = [int(t) for t in g[:e]]
    if eos is not None:
        for j, t in enumerate(out):
            if t == eos:
                out = out[:j + 1]
                break
    return out, len(out)
