"""What a paged step function hands every block (``models/generate.py``:
``decode_step_slots_paged``, ``prefill_partial_paged``).

The step function owns what is the same for every layer: where this
step's entries go in the pool, the positions, the masks. A block owns its
page layout: its attention module says which arrays a layer keeps
(``page_shapes``), writes its entries and attends over them
(``decode_paged`` / ``prefill_paged``). Multi-head attention keeps a K
and a V array of ``(n_pages, Hkv, page_len, Dh)``; latent attention keeps
ONE array of ``(n_pages, 1, page_len, kv_rank + rope_dim)``."""

from __future__ import annotations

from typing import Any, NamedTuple, Optional


class DecodeCtx(NamedTuple):
    """One decode step over every slot. ``dest`` (B,) is the page each
    row's new entry goes to (``n_pages`` for an inactive row: dropped),
    ``wo`` (B,) the offset inside it; ``idx`` (B,) the positions;
    ``active`` (B,) bool; ``pos_mask`` / ``write_mask`` serve the dense
    (``blockwise=False``) path; ``moe_stats`` is a list an expert layer
    appends its counts to, or None."""
    tables: Any
    idx: Any
    dest: Any
    wo: Any
    active: Any
    pos_mask: Any
    write_mask: Any
    page_len: int
    blockwise: bool = True
    moe_stats: Optional[list] = None


class PrefillCtx(NamedTuple):
    """The tail of one prompt: ``positions`` (S,) = ``offset`` + arange,
    ``dest`` / ``dest_off`` (S,) where each tail entry goes (pad rows
    route out of bounds), ``mask`` (S, W + S) over [prefix pages | tail] of
    ``width`` W, ``row_mask`` (S,) the tail's real rows."""
    table_row: Any
    positions: Any
    offset: Any
    dest: Any
    dest_off: Any
    mask: Any
    row_mask: Any
    width: int
    moe_stats: Optional[list] = None
