"""The page stores' one contract (``nn/paged.py``, ``nn/latent.py``), the
same cases for every format of resident page: exact K/V, K/V quantized to
8 and to 4 bits, and latent attention's one array. A step function, a
pool and a program see a store only through these operations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.models.generate import (LatentPagesUnsupported,
                                                     spec_commit_slots_paged)
from distributed_pytorch_tpu.nn.latent import LatentAttention, LatentPages
from distributed_pytorch_tpu.nn.paged import (DecodeCtx, ExactSide, KVPages,
                                              PrefillCtx, QuantSide)
from distributed_pytorch_tpu.ops.decode_attention import (
    dense_decode_attention)

B, HKV, G, L, DH, P = 3, 2, 2, 8, 16, 4          # P pages a row
N_PAGES, SCALE, WIDTH = B * P + 2, 0.25, 8       # WIDTH: latent values
#: every row owns its pages, in a shuffled order
TABLES = jnp.asarray(np.random.default_rng(0).permutation(B * P)
                     .reshape(B, P), jnp.int32)
FORMATS = ["exact", "q8", "q4", "latent"]


def _rand(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


class Harness:
    """One format's spelling of the contract's operations: ``entries``
    makes a step's (or a tail's) random entries, the rest hand them to
    the store."""

    def __init__(self, fmt):
        self.fmt, self.latent = fmt, fmt == "latent"
        self.heads = 1 if self.latent else HKV

    def make(self):
        if self.latent:
            return LatentPages(jnp.zeros((N_PAGES, 1, L, DH)))
        return KVPages.zeros((HKV, L, DH), N_PAGES, B,
                             {"exact": None, "q8": 8, "q4": 4}[self.fmt],
                             jnp.float32)

    def entries(self, rng, rows, s=1):
        """(rows, heads, s, DH) keys and values; a latent entry is both."""
        k = _rand(rng, (rows, self.heads, s, DH))
        return (k, k) if self.latent else (k, _rand(rng, k.shape))

    def write(self, st, kv, dest, wo, j=0):
        if self.latent:
            return st.write(kv[0][:, :, j, :], dest, wo)
        return st.write(*kv, dest, wo, j)

    def write_tail(self, st, kv, ctx):
        if self.latent:
            return st.write(jnp.moveaxis(kv[0][0], 1, 0), ctx.dest,
                            ctx.dest_off)
        return st.write_tail(*kv, ctx)

    def attend(self, st, ctx, hq, kv):
        if self.latent:
            return st.attend(ctx, hq, kv[0], SCALE, WIDTH)
        return st.attend(ctx, hq, *kv, SCALE)

    def rows(self, st, tables, idx):
        """The store's own dense rows, keys and values (a latent entry
        is both: its values are the first WIDTH of a result)."""
        if self.latent:
            k = st.rows(tables)[:, None]
            return k, k
        return st.k.rows(tables, idx), st.v.rows(tables, idx)


def decode_ctx(idx, active, blockwise=True):
    width = P * L
    wp = jnp.take_along_axis(TABLES, (idx // L)[:, None], axis=1)[:, 0]
    return DecodeCtx(
        tables=TABLES, idx=idx, dest=jnp.where(active, wp, N_PAGES),
        wo=idx % L, active=active,
        pos_mask=jnp.arange(width)[None, :] <= idx[:, None],
        write_mask=(jnp.arange(width)[None, :]
                    == idx[:, None])[:, None, :, None],
        page_len=L, blockwise=blockwise)


def decode_steps(h, st, rng, idx, steps, active=None):
    """``steps`` decode writes a row from positions ``idx`` on; returns
    the store, the positions reached and the last step's (ctx, hq, kv)."""
    active = jnp.ones((B,), bool) if active is None else active
    for _ in range(steps):
        ctx = decode_ctx(idx, active)
        kv = h.entries(rng, B)
        st = h.write(st, kv, ctx.dest, ctx.wo)
        hq = _rand(rng, (B, h.heads * G, 1, DH))
        last = (ctx, hq, kv)
        idx = idx + active.astype(jnp.int32)
    return st, idx, last


@pytest.mark.parametrize("fmt", FORMATS)
def test_decode_write_then_attend_is_dense_attention_over_the_rows(fmt):
    """Rows at different depths, one idle, across two page completions:
    the blockwise ``attend`` is the dense softmax over the store's own
    dense rows with this step's entry re-selected at the write position,
    and so is the store's ``blockwise=False`` path."""
    h, rng = Harness(fmt), np.random.default_rng(1)
    st, idx = h.make(), jnp.asarray([0, 5, 11], jnp.int32)
    active = jnp.asarray([True, True, False])
    for step in range(2 * L + 3):
        st, nxt, (ctx, hq, kv) = decode_steps(h, st, rng, idx, 1, active)
        k, v = h.rows(st, TABLES, idx)
        wm = ctx.write_mask
        ref = dense_decode_attention(
            hq, jnp.where(wm, kv[0], k), jnp.where(wm, kv[1], v),
            ctx.pos_mask, scale=SCALE)
        for blockwise in (True, False):
            out = h.attend(st, ctx._replace(blockwise=blockwise), hq, kv)
            np.testing.assert_allclose(out, ref[..., :out.shape[-1]],
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=f"step {step} {blockwise}")
        idx = nxt


def prefill_ctx(offset, true_len, s, slot):
    positions = offset + jnp.arange(s)
    row = TABLES[slot]
    return PrefillCtx(
        table_row=row, positions=positions, offset=jnp.int32(offset),
        true_len=jnp.int32(true_len), slot=jnp.int32(slot),
        dest=jnp.where(jnp.arange(s) < true_len,
                       row[jnp.clip(positions // L, 0, P - 1)], N_PAGES),
        dest_off=positions % L, mask=None,
        row_mask=jnp.arange(s) < true_len, width=P * L)


@pytest.mark.parametrize("true_len", [5, L, 2 * L + 3])
@pytest.mark.parametrize("fmt", FORMATS)
def test_prefill_tail_then_decode_is_decode_alone(fmt, true_len):
    """A prompt's tail written at once (padded to a bucket, behind one
    shared page) and the same entries written one decode step at a time
    leave a store that later decode steps read alike: the rows up to the
    length, and the next steps' attention, bit for bit."""
    h, slot, offset, s = Harness(fmt), 1, L, 3 * L
    rng = np.random.default_rng(true_len)
    tail = h.entries(rng, 1, s)                          # (1, heads, s, DH)
    shared = h.entries(rng, B, L)                        # the prefix page
    base = h.make()
    for j in range(L):                                   # every row's page 0
        base = h.write(base, shared, TABLES[:, 0], jnp.full((B,), j), j)
    at_once = h.write_tail(base, tail, prefill_ctx(offset, true_len, s, slot))
    only = jnp.arange(B) == slot
    stepwise = base
    for j in range(true_len):
        ctx = decode_ctx(jnp.full((B,), offset + j, jnp.int32), only)
        row = tuple(jnp.broadcast_to(t[:, :, j:j + 1, :],
                                     (B,) + t.shape[1:2] + (1, DH))
                    for t in tail)
        stepwise = h.write(stepwise, row, ctx.dest, ctx.wo)
    idx = jnp.full((B,), offset + true_len, jnp.int32)
    outs = []
    for st in (at_once, stepwise):
        rng2 = np.random.default_rng(7)
        st, _, (ctx, hq, kv) = decode_steps(h, st, rng2, idx, L + 2, only)
        k, v = h.rows(st, TABLES, ctx.idx)
        seen = ctx.pos_mask[slot][None, :, None]
        outs.append((h.attend(st, ctx, hq, kv)[slot],
                     jnp.where(seen, k[slot], 0), jnp.where(seen, v[slot], 0)))
    for a, b in zip(*outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("fmt", ["exact", "q8", "q4"])
def test_commit_of_an_accepted_prefix_is_the_decode_steps(fmt):
    """``spec_commit_slots_paged`` over four candidates a row, accepted
    2 / 4 / 0, across a page completion: every array of the store equals
    the same entries written by decode steps in which a rejected
    position's row is idle."""
    h, rng = Harness(fmt), np.random.default_rng(3)
    st, idx, _ = decode_steps(h, h.make(), rng,
                              jnp.asarray([3, 13, 2], jnp.int32), 1)
    sk, sv = h.entries(rng, B, 4)
    commit = jnp.asarray([2, 4, 0], jnp.int32)
    (committed,) = spec_commit_slots_paged([st], TABLES, idx, [sk], [sv],
                                           commit, page_len=L)
    stepwise = st
    for j in range(4):
        ctx = decode_ctx(idx + j, j < commit)
        stepwise = stepwise.write(sk, sv, ctx.dest, ctx.wo, j)
    for a, b in zip(jax.tree.leaves(committed), jax.tree.leaves(stepwise)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if fmt != "exact":      # row 1 completed its page 1, once; row 0 none
        ones = np.ones_like(np.asarray(st.k.scales[0]))
        assert not np.array_equal(
            np.asarray(committed.k.scales[TABLES[1, 1]]), ones)
        np.testing.assert_array_equal(
            np.asarray(committed.k.scales[TABLES[0, 0]]), ones)


def test_which_store_an_attention_module_hands_out():
    from distributed_pytorch_tpu.nn.attention import MultiHeadAttention
    mha = MultiHeadAttention(64, 4, n_kv_heads=2)
    exact = mha.make_pages(6, 2, L, None, jnp.bfloat16)
    assert type(exact.k) is ExactSide and exact.n_pages == 6
    assert exact.k.pages.shape == (6, 2, L, 16)
    assert exact.k.pages.dtype == jnp.bfloat16
    assert exact.resident_bytes() == 2 * 6 * 2 * L * 16 * 2
    for bits, last, dt in ((8, 16, jnp.int8), (4, 8, jnp.uint8)):
        q = mha.make_pages(6, 2, L, bits, jnp.bfloat16)
        assert type(q.v) is QuantSide and q.k.bits == bits
        assert q.k.q.shape == (6, 2, L, last) and q.k.q.dtype == dt
        assert q.k.tail.shape == (2, 2, L, 16)
        assert q.k.tail.dtype == jnp.float32
        assert q.resident_bytes() == 2 * (q.k.q.nbytes + q.k.scales.nbytes)
    with pytest.raises(ValueError, match="must be even"):
        MultiHeadAttention(12, 4).make_pages(6, 2, L, 4, jnp.float32)


def test_latent_store_refuses_by_name_what_it_lacks():
    attn = LatentAttention(32, 2, q_rank=8, kv_rank=WIDTH, nope_dim=8,
                           rope_dim=8, v_dim=8)
    st = attn.make_pages(4, 2, L, None, jnp.float32)
    assert type(st) is LatentPages and st.entries.shape == (4, 1, L, 128)
    for bits in (8, 4):
        with pytest.raises(LatentPagesUnsupported, match="quantized pages"):
            attn.make_pages(4, 2, L, bits, jnp.float32)
    for op, name in (("commit", "serve/spec"), ("export", "serve/disagg"),
                     ("adopt", "serve/disagg")):
        with pytest.raises(LatentPagesUnsupported, match=name):
            st.require(op)
        with pytest.raises(LatentPagesUnsupported, match=name):
            getattr(st, op)()
    with pytest.raises(LatentPagesUnsupported, match="latent .MLA. pages"):
        spec_commit_slots_paged([st], TABLES[:2], jnp.zeros((2,), jnp.int32),
                                [jnp.zeros((2, 1, 2, 8))] * 1,
                                [jnp.zeros((2, 1, 2, 8))] * 1,
                                jnp.ones((2,), jnp.int32), page_len=L)
    st.require("write")                      # what it has, it does not refuse
