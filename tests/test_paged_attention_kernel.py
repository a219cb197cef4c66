"""The paged decode-attention kernel (ops/paged_attention_kernel.py), run
by the Pallas interpreter on the CPU: against the ``fori_loop`` it stands
in for and the dense gather, the contract's poison tests, the rule that
picks it, the engine's counter, and the page write's row form. The last
two tests compile for a described (not attached) v5e."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_pytorch_tpu import models
from distributed_pytorch_tpu.nn.attention import write_rows
from distributed_pytorch_tpu.nn.latent import LatentPages
from distributed_pytorch_tpu.nn.paged import DecodeCtx, ExactSide, KVPages
from distributed_pytorch_tpu.ops import decode_attention, paged_attention_kernel
from distributed_pytorch_tpu.ops.decode_attention import (
    dense_decode_attention, kernel_traces, paged_decode_attention)
from distributed_pytorch_tpu.ops.paged_attention_kernel import (
    block_pages_for, kernel_fits, paged_attention)
from distributed_pytorch_tpu.serve import (EngineConfig, InferenceEngine,
                                           SamplingParams)

SCALE = 0.3
DH = 128


def _rand(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _pool(rng, *, b, hkv, g, page_len, pages_per_row, dtype, spare=3):
    """Random queries and a pool in which every row owns its pages, in a
    shuffled order, with a few pages nobody owns."""
    n_pages = b * pages_per_row + spare
    hq = _rand(rng, (b, hkv * g, 1, DH), dtype)
    kp = _rand(rng, (n_pages, hkv, page_len, DH), dtype)
    vp = _rand(rng, (n_pages, hkv, page_len, DH), dtype)
    tables = rng.permutation(b * pages_per_row).reshape(b, pages_per_row)
    return hq, kp, vp, jnp.asarray(tables, jnp.int32)


def _new_rows(pool, tables, idx, page_len):
    """The step's K or V as ``decode_paged`` hands it to the loop: what
    the pool holds at each row's write position."""
    b = tables.shape[0]
    pages = tables[jnp.arange(b), idx // page_len]
    return pool[pages, :, idx % page_len][:, :, None, :]


def _loop(hq, kp, vp, tables, idx, page_len):
    return paged_decode_attention(
        hq, kp, vp, tables, idx, _new_rows(kp, tables, idx, page_len),
        _new_rows(vp, tables, idx, page_len), scale=SCALE, page_len=page_len)


def _dense(hq, kp, vp, tables, idx, page_len):
    def rows(pool):
        g = pool[tables]                      # (B, P, Hkv, page_len, Dh)
        b, p, h, l, d = g.shape
        return g.transpose(0, 2, 1, 3, 4).reshape(b, h, p * l, d)
    width = tables.shape[1] * page_len
    mask = jnp.arange(width)[None, :] <= idx[:, None]
    return dense_decode_attention(hq, rows(kp), rows(vp), mask, scale=SCALE)


def _ragged(page_len, pages_per_row, block_pages):
    """A row of length 0 (inactive too), one of one token, one that ends
    inside a page, one inside a block, one that fills its table."""
    block = block_pages * page_len
    idx = [0, 0, page_len + 3, block + page_len + 1,
           pages_per_row * page_len - 1]
    active = [False, True, True, True, True]
    return jnp.asarray(idx, jnp.int32), jnp.asarray(active)


# (group size, page_len, pages a row, pages a block)
SHAPES = [pytest.param(12, 16, 8, 3, id="g12-page16-block3"),
          pytest.param(1, 16, 8, 2, id="g1-page16-block2"),
          pytest.param(12, 64, 4, 2, id="g12-page64-block2"),
          pytest.param(1, 64, 3, 1, id="g1-page64-block1")]
DTYPES = [pytest.param(jnp.float32, 5e-6, id="f32"),
          pytest.param(jnp.bfloat16, 5e-2, id="bf16")]


@pytest.mark.parametrize("dtype,tol", DTYPES)
@pytest.mark.parametrize("g,page_len,pages_per_row,block_pages", SHAPES)
def test_kernel_matches_the_loop_and_the_dense_gather(
        g, page_len, pages_per_row, block_pages, dtype, tol):
    rng = np.random.default_rng(g * page_len + block_pages)
    hq, kp, vp, tables = _pool(rng, b=5, hkv=2, g=g, page_len=page_len,
                               pages_per_row=pages_per_row, dtype=dtype)
    idx, active = _ragged(page_len, pages_per_row, block_pages)
    out = paged_attention(hq, kp, vp, tables, idx, active, scale=SCALE,
                          page_len=page_len, block_pages=block_pages,
                          interpret=True)
    assert out.shape == hq.shape and out.dtype == dtype
    a = np.asarray(active)
    got = np.asarray(out, np.float32)
    for ref in (_loop(hq, kp, vp, tables, idx, page_len),
                _dense(hq, kp, vp, tables, idx, page_len)):
        np.testing.assert_allclose(got[a], np.asarray(ref, np.float32)[a],
                                   rtol=tol, atol=tol)
    assert (got[~a] == 0).all()


def test_f32_stats_under_bf16():
    """512 identical keys in a bf16 pool: a bf16 normalizer stops
    counting at 256 (256 + 1 == 256), so the kernel's statistics and
    accumulator must be float32 for the mean of ones to be exactly 1."""
    page_len, pages = 16, 32
    hq = jnp.ones((1, 1, 1, DH), jnp.bfloat16)
    kp = jnp.ones((pages, 1, page_len, DH), jnp.bfloat16)
    tables = jnp.arange(pages, dtype=jnp.int32)[None]
    out = paged_attention(hq, kp, kp, tables, jnp.asarray([511], jnp.int32),
                          jnp.asarray([True]), scale=SCALE,
                          page_len=page_len, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out, np.float32), 1.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_dead_pages_and_dead_tails_poisoned_bit_equal(dtype):
    """Everything a row must not see holds NaN: the pages nobody's live
    positions are on (dead table entries name them, or no page at all),
    and the tail of each row's last page. The result is the clean
    pool's, bit for bit."""
    rng = np.random.default_rng(7)
    page_len, pages_per_row, block_pages = 16, 8, 3
    hq, kp, vp, tables = _pool(rng, b=5, hkv=2, g=12, page_len=page_len,
                               pages_per_row=pages_per_row, dtype=dtype)
    idx, active = _ragged(page_len, pages_per_row, block_pages)
    clean = paged_attention(hq, kp, vp, tables, idx, active, scale=SCALE,
                            page_len=page_len, block_pages=block_pages,
                            interpret=True)
    live = np.zeros(kp.shape[:1] + (page_len,), bool)   # (page, offset)
    t = np.asarray(tables)
    for b in np.flatnonzero(np.asarray(active)):
        for pos in range(int(idx[b]) + 1):
            live[t[b, pos // page_len], pos % page_len] = True
    dead = jnp.asarray(~live)[:, None, :, None]
    kp_p, vp_p = jnp.where(dead, jnp.nan, kp), jnp.where(dead, jnp.nan, vp)
    # dead table entries: a poisoned page, or a page that does not exist
    col = np.arange(pages_per_row)[None, :]
    dead_entry = col > (np.asarray(idx) // page_len)[:, None]
    t_p = np.where(dead_entry, np.where(col % 2, kp.shape[0] - 1, 10 ** 6), t)
    t_p[~np.asarray(active)] = 10 ** 6
    poisoned = paged_attention(hq, kp_p, vp_p, jnp.asarray(t_p, jnp.int32),
                               idx, active, scale=SCALE, page_len=page_len,
                               block_pages=block_pages, interpret=True)
    assert bool(jnp.all(jnp.isfinite(poisoned)))
    np.testing.assert_array_equal(np.asarray(clean, np.float32),
                                  np.asarray(poisoned, np.float32))


def test_inactive_rows_are_skipped_and_finite():
    """No row active: nothing is copied (the whole pool is NaN and every
    table entry names a page that does not exist), every row zeros."""
    hq = _rand(np.random.default_rng(0), (3, 2, 1, DH))
    kp = jnp.full((4, 2, 8, DH), jnp.nan, jnp.float32)
    tables = jnp.full((3, 4), 10 ** 6, jnp.int32)
    out = paged_attention(hq, kp, kp, tables, jnp.asarray([5, 0, 31],
                                                          jnp.int32),
                          jnp.zeros((3,), bool), scale=SCALE, page_len=8,
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_one_compile_for_every_mix():
    rng = np.random.default_rng(11)
    hq, kp, vp, tables = _pool(rng, b=4, hkv=1, g=2, page_len=8,
                               pages_per_row=4, dtype=jnp.float32)
    fn = jax.jit(lambda *a: paged_attention(*a, scale=SCALE, page_len=8,
                                            block_pages=2, interpret=True))
    mixes = [([0, 9, 31, 16], [True, True, True, True]),
             ([3, 0, 0, 30], [True, False, False, True]),
             ([31, 31, 31, 31], [False, True, True, False])]
    for idx, active in mixes:
        idx, active = jnp.asarray(idx, jnp.int32), jnp.asarray(active)
        tables = jnp.asarray(rng.permutation(16).reshape(4, 4), jnp.int32)
        out = fn(hq, kp, vp, tables, idx, active)
        a = np.asarray(active)
        np.testing.assert_allclose(
            np.asarray(out)[a],
            np.asarray(_loop(hq, kp, vp, tables, idx, 8))[a],
            rtol=5e-6, atol=5e-6)
    assert fn._cache_size() == 1


# -- the rule that picks the path -------------------------------------------

def _call(dtype=jnp.bfloat16, dh=DH, page_len=16, latent=False,
          scales=False, active=True, interpret=None):
    """A decode step's attention on a small pool, through the exact
    entry or, for the latent and the quantized format, through the
    store's ``attend``; returns how many times it took the kernel (0 or
    1)."""
    rng = np.random.default_rng(5)
    b, hkv, g, pages = 2, 1, 2, 4
    hq = _rand(rng, (b, hkv * g, 1, dh), dtype)
    kp = _rand(rng, (b * pages, hkv, page_len, dh), dtype)
    tables = jnp.arange(b * pages, dtype=jnp.int32).reshape(b, pages)
    idx = jnp.asarray([3, 2 * page_len + 1], jnp.int32)
    nk = _new_rows(kp, tables, idx, page_len)
    ctx = DecodeCtx(tables=tables, idx=idx, dest=None, wo=None,
                    active=jnp.ones((b,), bool), pos_mask=None,
                    write_mask=None, page_len=page_len)
    before = kernel_traces()
    if latent:
        out = LatentPages(kp).attend(ctx, hq, nk, SCALE, dh // 2)
    elif scales:
        out = KVPages.zeros((hkv, page_len, dh), b * pages, b, 8,
                            dtype).attend(ctx, hq, nk, nk, SCALE)
    else:
        out = paged_decode_attention(
            hq, kp, kp, tables, idx, nk, nk, scale=SCALE, page_len=page_len,
            active=ctx.active if active else None, interpret=interpret)
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    return kernel_traces() - before


CHOICES = [
    pytest.param(dict(interpret=True), 1, id="bf16-page16-takes-the-kernel"),
    pytest.param(dict(interpret=True, dtype=jnp.float32, page_len=8), 1,
                 id="f32-page8-takes-the-kernel"),
    pytest.param(dict(), 0, id="cpu-default-takes-the-loop"),
    pytest.param(dict(latent=True), 0, id="latent-loop"),
    pytest.param(dict(scales=True), 0, id="quantized-loop"),
    pytest.param(dict(interpret=True, dh=64), 0, id="head-64-loop"),
    pytest.param(dict(interpret=True, page_len=8), 0,
                 id="bf16-page8-is-half-a-tile-loop"),
    pytest.param(dict(interpret=True, active=False), 0,
                 id="no-active-mask-loop"),
]


@pytest.mark.parametrize("kw,took", CHOICES)
def test_which_inputs_take_the_kernel(kw, took, monkeypatch):
    if "interpret" not in kw and kw:
        # a store of another format never asks: even where the probe
        # would answer with a kernel, it hands the loop its loaders
        monkeypatch.setattr(decode_attention, "_kernel_interpret",
                            lambda interpret: True)
    assert _call(**kw) == took


def test_a_mesh_of_several_devices_keeps_the_loop():
    """GSPMD cannot partition a Mosaic call: operands typed over a mesh
    of two devices take the loop, which partitions like any JAX code."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    if jax.device_count() < 2:
        pytest.skip("needs two devices")
    rng = np.random.default_rng(9)
    hq, kp, vp, tables = _pool(rng, b=4, hkv=1, g=2, page_len=8,
                               pages_per_row=2, dtype=jnp.float32)
    idx = jnp.asarray([3, 9, 15, 0], jnp.int32)
    nk, nv = _new_rows(kp, tables, idx, 8), _new_rows(vp, tables, idx, 8)
    mesh = jax.make_mesh((2,), ("dp",))
    hq = jax.device_put(hq, NamedSharding(mesh, P("dp")))

    def attend(hq):
        return paged_decode_attention(
            hq, kp, vp, tables, idx, nk, nv, scale=SCALE, page_len=8,
            active=jnp.ones((4,), bool), interpret=True)
    before = kernel_traces()
    out = jax.jit(attend)(hq)
    assert kernel_traces() == before
    np.testing.assert_allclose(out, _dense(hq, kp, vp, tables, idx, 8),
                               rtol=5e-6, atol=5e-6)


def test_backend_probe_is_the_only_default(monkeypatch):
    """On a TPU the kernel is compiled, anywhere else there is none; a
    test's own ``interpret`` is passed through."""
    probe = decode_attention._kernel_interpret
    assert probe(None) is None and probe(True) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert probe(None) is False and probe(True) is True


def test_block_pages_and_fit():
    bf = jnp.zeros((4, 2, 16, DH), jnp.bfloat16)
    assert kernel_fits(bf, bf, 16)
    assert block_pages_for(bf, 128) == paged_attention_kernel.KV_BLOCK // 16
    assert block_pages_for(bf, 5) == 5                  # a short table
    wide = jax.ShapeDtypeStruct((4, 64, 64, DH), jnp.bfloat16)
    assert block_pages_for(wide, 128) == 1              # the VMEM budget
    assert not kernel_fits(bf, bf.astype(jnp.float32), 16)
    assert not kernel_fits(bf.astype(jnp.int8), bf.astype(jnp.int8), 16)


# -- the engine's counter ---------------------------------------------------

def _tiny_engine():
    model = models.TransformerLM(vocab=61, dim=256, n_layers=2, n_heads=2,
                                 n_kv_heads=1, pos="rope", max_seq=64)
    params = model.init(jax.random.PRNGKey(0))
    return InferenceEngine(model, params, EngineConfig(
        paged=True, n_slots=3, max_len=64, page_len=8, buckets=(16,)))


def _serve(eng):
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 61, n).astype(np.int32) for n in (5, 11, 16)]
    with eng:
        hs = [eng.submit(p, SamplingParams(max_new_tokens=12))
              for p in prompts]
        tokens = [list(h.result(timeout=600)) for h in hs]
        return tokens, eng.stats()


def test_engine_counts_the_layers_that_took_the_kernel(monkeypatch):
    """A CPU engine takes the loop in every layer; where the rule's one
    backend probe sees a TPU (here: answers with the interpreter) every
    layer of the one decode program takes the kernel, and the greedy
    streams are the loop's."""
    loop_tokens, s = _serve(_tiny_engine())
    assert s["decode_attention_kernel_layers"] == 0
    assert s["pages"]["decode_attention_kernel_layers"] == 0
    monkeypatch.setattr(decode_attention, "_kernel_interpret",
                        lambda interpret: True)
    kernel_tokens, s = _serve(_tiny_engine())
    assert s["decode_attention_kernel_layers"] == 2
    assert s["pages"]["decode_attention_kernel_layers"] == 2
    assert s["decode_compiles"] == 1
    assert kernel_tokens == loop_tokens


# -- the page write ---------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", ["decode", "prefill", "tail", "commit"])
def test_write_rows_is_the_page_scatter(case, dtype):
    """The row form writes what ``pool.at[dest, :, wo].set`` writes, a
    dropped row (``dest`` past the end) included, bit for bit: a decode
    step's rows, a prompt's tail, a quantized side's per-slot tail pages
    (row b to slot b, an idle row dropped) and one position of a
    speculative commit (rejected rows dropped)."""
    n_rows = {"decode": 3, "prefill": 20, "tail": 5, "commit": 4}[case]
    rng = np.random.default_rng(n_rows)
    n_pages, hkv, page_len = 6, 2, 8
    rows = _rand(rng, (n_rows, hkv, 16), jnp.float32)
    if case == "tail":
        n_pages = n_rows                       # one page a slot
        dest = jnp.arange(n_rows, dtype=jnp.int32).at[2].set(n_pages)
        wo = jnp.asarray(rng.integers(0, page_len, n_rows), jnp.int32)
    else:
        slots = rng.permutation(n_pages * page_len)[:n_rows]   # no duplicates
        dest = jnp.asarray(slots // page_len, jnp.int32).at[1].set(n_pages)
        wo = jnp.asarray(slots % page_len, jnp.int32)
    if case == "commit":                       # candidate j of a scratch
        rows = _rand(rng, (n_rows, hkv, 3, 16), jnp.float32)[:, :, 1, :]
        dest = jnp.where(jnp.asarray([2, 0, 1, 3]) > 1, dest, n_pages)
    pool = _rand(rng, (n_pages, hkv, page_len, 16), dtype)
    ref = pool.at[dest, :, wo].set(rows.astype(dtype), mode="drop")
    out = write_rows(pool, dest, wo, rows)
    assert out.dtype == dtype
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))


# -- compile-only, for a described v5e --------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


# (slots, kv heads, group, page_len, pages a row, dtype)
CHIP_SHAPES = [
    pytest.param(64, 2, 12, 16, 128, jnp.bfloat16, id="starcoder2-page16"),
    pytest.param(64, 2, 12, 64, 32, jnp.bfloat16, id="starcoder2-page64"),
    pytest.param(8, 4, 1, 8, 16, jnp.float32, id="f32-mha-page8"),
    # a global layer beside window layers: rows of up to 33 280 tokens,
    # 8 query heads a KV head (520 pages a row: 66 KB of tables in SMEM)
    pytest.param(32, 8, 8, 64, 520, jnp.bfloat16, id="long-rows-page64"),
    # a sparse-attention layer's chosen pages (PR 45): the pool seen as
    # pages of ONE KV head, a row a (slot, KV head), 16 query heads, a
    # table of the 1 + 33 + 64 chosen pages; and its dense rows' walk of
    # the slot's own table, 1032 pages a row (132 KB of tables in SMEM)
    pytest.param(64, 1, 16, 64, 98, jnp.bfloat16, id="chosen-pages-folded"),
    pytest.param(32, 2, 16, 64, 1032, jnp.bfloat16, id="dense-rows-1032"),
]


@pytest.mark.parametrize("b,hkv,g,page_len,pages_per_row,dtype", CHIP_SHAPES)
def test_kernel_compiles_for_v5e(one_chip, b, hkv, g, page_len,
                                 pages_per_row, dtype):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = s((b * pages_per_row, hkv, page_len, DH), dtype)
    fn = jax.jit(lambda *a: paged_attention(*a, scale=SCALE,
                                            page_len=page_len,
                                            interpret=False))
    compiled = fn.lower(s((b, hkv * g, 1, DH), dtype), pool, pool,
                        s((b, pages_per_row), jnp.int32),
                        s((b,), jnp.int32), s((b,), jnp.bool_)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_decode_program_moves_no_pool_for_v5e(one_chip, monkeypatch):
    """Two layers of StarCoder2's widths: each layer's attention is one
    Mosaic call, and no array of the pool's shape is copied (the scatter
    ``pool.at[dest, :, wo]`` cost two such copies a pool, PR 29)."""
    from distributed_pytorch_tpu.models.generate import (
        decode_step_slots_paged)
    monkeypatch.setattr(decode_attention, "_kernel_interpret",
                        lambda interpret: False)
    layers, slots, page_len, pages_per_row = 2, 64, 16, 128
    model = models.TransformerLM(vocab=1024, dim=3072, n_layers=layers,
                                 n_heads=24, n_kv_heads=2, pos="rope",
                                 max_seq=2048, dtype=jnp.bfloat16)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = jax.tree.map(
        lambda x: s(x.shape, jnp.bfloat16),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = [s((slots * pages_per_row, 2, page_len, DH), jnp.bfloat16)] * layers

    def step(params, state, tables, lengths, tokens, active):
        return decode_step_slots_paged(model, params, state, tables,
                                       lengths, tokens, active,
                                       page_len=page_len)
    text = jax.jit(step, donate_argnums=(1,)).lower(
        params, [KVPages(ExactSide(p), ExactSide(p)) for p in pool],
        s((slots, pages_per_row), jnp.int32),
        s((slots,), jnp.int32), s((slots,), jnp.int32),
        s((slots,), jnp.bool_)).compile().as_text()
    assert text.count("tpu_custom_call") == layers
    moved = [line for line in text.splitlines()
             if " copy(" in line and "8192,2,16,128" in line]
    assert not moved, moved[:2]


def test_mixers_decode_program_moves_no_store_for_v5e(one_chip, monkeypatch):
    """A sparse and a linear layer at MiniCPM-SALA's widths, 32 slots of
    66 048 tokens: the sparse layer's attention is two Mosaic calls (the
    chosen pages, the dense rows), and no array of a store's shape is
    copied: not the pool, which the chosen pages' table sees reshaped to
    pages of one KV head, not the compressed keys, not the states."""
    from distributed_pytorch_tpu.models.generate import (
        decode_step_slots_paged)
    monkeypatch.setattr(decode_attention, "_kernel_interpret",
                        lambda interpret: False)
    slots, page_len, pages_per_row = 32, 64, 1032
    model = models.TransformerLM(
        vocab=1024, dim=4096, n_layers=2, n_heads=32, n_kv_heads=2,
        head_dim=DH, attn_bias=False, qk_norm=1e-6, pos="rope",
        max_seq=pages_per_row * page_len, norm="rms", ffn_dim=16384,
        layer_mixers=("sparse", "linear"),
        sparse=dict(kernel=32, stride=16, block=64, topk=64, init_blocks=1,
                    window=2048, dense_len=8192), dtype=jnp.bfloat16)

    def shapes(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)
    params = shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    state = shapes(jax.eval_shape(lambda: [
        blk.attn.make_pages(slots * pages_per_row, slots, page_len, None,
                            jnp.bfloat16) for blk in model.blocks]))

    def step(params, state, tables, lengths, tokens, active):
        return decode_step_slots_paged(model, params, state, tables,
                                       lengths, tokens, active,
                                       page_len=page_len)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    text = jax.jit(step, donate_argnums=(1,)).lower(
        params, state, i32(slots, pages_per_row), i32(slots), i32(slots),
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)) \
        .compile().as_text()
    assert text.count("tpu_custom_call") == 2
    moved = [line for line in text.splitlines() if " copy(" in line and any(
        shape in line for shape in ("33024,2,64,128", "66048,1,64,128",
                                    "32,2,4128,128", "32,32,128,128"))]
    assert not moved, moved[:2]


@pytest.mark.parametrize("s,d_v", [(8192, 128), (8192, 192), (1024, 128),
                                   (3000, 128)])
def test_flash_at_head_192_compiles_for_v5e(one_chip, s, d_v):
    """Latent attention's expanded form (one row, 32 heads, keys of nope
    128 + rope 64, values 128 as the module hands them, or as wide as the
    keys) at the training cell's 8192 positions, a cold prefill chunk's
    1024 and a length that is no whole tile: forward and both backward
    kernels at the default tiles of head widths over 128 -- forward 1024
    x 1024, backward square, half the sequence, between 256 and 1024 --
    with the clamped index maps and the VMEM limit each states, which the
    chip's compiler has to take whole."""
    from distributed_pytorch_tpu.ops import flash_attention
    from distributed_pytorch_tpu.ops.flash_attention import _block_sizes

    tile = {8192: 1024, 3000: 1024, 1024: 512}[s]
    assert _block_sizes(s, s, None, None, d=192) == (1024, 1024)
    assert _block_sizes(s, s, None, None, d=192, bwd=True) == (tile, tile)
    qk = jax.ShapeDtypeStruct((1, 32, s, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 32, s, d_v), jnp.bfloat16,
                             sharding=one_chip)
    grad = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, scale=192 ** -0.5,
            interpret=False).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    assert grad.lower(qk, qk, v).compile().as_text().count(
        "tpu_custom_call") == 3


# (groups, rows, K, N): the expert layers' calls in the serving programs
GROUPED_SHAPES = [
    pytest.param(128, 2048, 2048, 768, id="sdar-block-step"),
    pytest.param(128, 8192, 768, 2048, id="sdar-chunk-down"),
    pytest.param(64, 256, 3584, 1024, id="xing4-decode-step"),
    pytest.param(64, 4096, 1024, 3584, id="xing4-chunk-down"),
    pytest.param(8, 40, 256, 128, id="rows-no-whole-tile"),
    pytest.param(4, 512, 4096, 2048, id="a-slab-of-16-MiB"),
    pytest.param(16, 1024, 6144, 2048, id="kexaone-chunk-gate"),
    pytest.param(16, 256, 2048, 6144, id="kexaone-decode-down"),
]


@pytest.mark.parametrize("g,r,k,n", GROUPED_SHAPES)
def test_grouped_matmul_compiles_for_v5e(one_chip, g, r, k, n):
    """The grouped expert matmul (``ops/grouped_matmul_kernel.py``) at
    the serving programs' shapes: one Mosaic call whose weight block is
    an expert's whole slab (7.3 MB at Xing4's widths, double buffered),
    inside the VMEM limit the call states."""
    from distributed_pytorch_tpu.ops.grouped_matmul_kernel import (
        grouped_matmul)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = jax.jit(grouped_matmul).lower(
        s((r, k), jnp.bfloat16), s((g, k, n), jnp.bfloat16),
        s((g,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
