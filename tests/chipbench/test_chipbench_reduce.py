"""``trace_reduce.py`` on a small recorded trace, and ``flops.py`` against
numbers worked by hand."""

import json
import os

import pytest

from chipbench import flops, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def config(name):
    with open(os.path.join(REPO, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


# -- flops -------------------------------------------------------------------

def test_gpt2_xl_flops_and_parameters():
    cfg = config("gpt2-xl")
    assert flops.param_count(cfg) == 1_557_611_200        # as published
    shape = flops.config_shape(cfg, 1024)
    # a layer: q,out 10.24M + k,v 10.24M + mlp 40.96M + causal attention
    # 4*1024*1600/2 = 3.2768M = 64.7168M; 48 of them; head 2*1600*50257
    assert flops.model_flops_per_token(**shape) == pytest.approx(
        48 * 64.7168e6 + 160.8224e6)
    assert flops.train_flops_per_token(**shape) == pytest.approx(9.8016864e9)


def test_starcoder2_3b_flops_and_parameters_gqa():
    cfg = config("starcoder2-3b")
    assert flops.param_count(cfg) == 3_030_371_328
    shape = flops.config_shape(cfg, 1024)
    assert shape["n_kv_heads"] == 2 and shape["mlp_dim"] == 12288
    # q,out 2*3072*3072*2 = 37.748736M; k,v 2*3072*256*2 = 3.145728M (two
    # of 24 heads); mlp 2*3072*12288*2 = 150.994944M; attention 6.291456M
    assert flops.model_flops_per_token(**shape) == pytest.approx(
        30 * 198.180864e6 + 301.989888e6)


def test_flash_call_cost_by_hand():
    f, b = flops.flash_call_cost(batch=8, n_heads=25, n_kv_heads=25,
                                 seq_q=1024, seq_k=1024, head_dim=64,
                                 kind="fwd")
    assert f == 26_843_545_600          # 200 heads * 4*1024*1024*64 / 2
    assert b == 105_676_800             # q,k,v,o in bf16 + f32 row stats
    f_kv, _ = flops.flash_call_cost(batch=8, n_heads=25, n_kv_heads=25,
                                    seq_q=1024, seq_k=1024, head_dim=64,
                                    kind="dkv")
    f_q, _ = flops.flash_call_cost(batch=8, n_heads=25, n_kv_heads=25,
                                   seq_q=1024, seq_k=1024, head_dim=64,
                                   kind="dq")
    assert (f_kv, f_q) == (2 * f, 1.5 * f)


# -- intervals ---------------------------------------------------------------

def test_union_and_subtract():
    merged = trace_reduce.union([(0, 10), (5, 12), (20, 30), (30, 31)])
    assert merged == [[0, 12], [20, 31]]
    assert trace_reduce.length(merged) == 23
    left = trace_reduce.subtract([[0, 12], [20, 31]], [[3, 5], [11, 25]])
    assert left == [[0, 3], [5, 11], [25, 31]]


def test_collective_and_container_names():
    for n in ("all-gather.12", "all-reduce", "reduce-scatter.3",
              "all-gather-start.4", "all-reduce-done.1"):
        assert trace_reduce.is_collective(n), n
    for n in ("fusion.12", "all-gather-fusion", "copy.3"):
        assert not trace_reduce.is_collective(n), n
    assert trace_reduce.CONTAINER.match("while.3")
    assert not trace_reduce.CONTAINER.match("while_body_fusion.1")


# -- the recorded trace ------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def brute_busy_ns(ops, lo, hi, accept=lambda n, h: True, step=1000):
    """Busy time by painting a timeline of ``step`` ns cells: slow, plain,
    and independent of ``union``."""
    import numpy as np
    cells = np.zeros((hi - lo) // step + 1, bool)
    for _, n, s, e, hlo in ops:
        if accept(n, hlo):
            cells[(s - lo) // step:(e - lo + step - 1) // step] = True
    return int(cells.sum()) * step


def test_busy_idle_and_mosaic_time_on_the_recorded_trace(recorded):
    t = trace_reduce.from_records(recorded)
    ops = recorded["ops"]
    lo, hi = min(o[2] for o in ops), max(o[3] for o in ops)
    assert t.window == (lo, hi)
    want = brute_busy_ns(ops, lo, hi)
    assert abs(t.busy_s() * 1e9 - want) <= 0.002 * want
    assert t.idle_share() == pytest.approx(1 - t.busy_s() / t.window_s())
    # two steps of 20 layers: forward twice (remat), dK/dV and dQ a layer
    mosaic = [o for o in ops if "tpu_custom_call" in o[4]]
    assert len(mosaic) == 2 * 20 * 4
    assert t.op_seconds(trace_reduce.is_mosaic) * 1e9 == pytest.approx(
        sum(o[3] - o[2] for o in mosaic))
    assert len(t.module_events(lambda n: n.startswith("jit_"))) == 2
    top = t.top_ops(3)
    assert top[0][1] >= top[1][1] >= top[2][1] > 0
    assert {n for n, _, _ in t.host} <= set(trace_reduce.HOST_SPANS)


def test_exposed_collective_time_on_the_recorded_trace(recorded):
    """The one-chip trace has no collective, so three are laid over it:
    one inside a busy stretch (hidden), one inside the longest idle gap
    (exposed whole), one half over each."""
    t0 = trace_reduce.from_records(recorded)
    busy = t0.busy(0)
    gap = max(zip(busy[:-1], busy[1:]), key=lambda ab: ab[1][0] - ab[0][1])
    g0, g1 = gap[0][1], gap[1][0]
    assert g1 - g0 >= 2000
    long = max(busy, key=lambda b: b[1] - b[0])
    extra = [[0, "all-gather.1", long[0] + 1, long[0] + 1001, ""],
             [0, "reduce-scatter.2", g0, g1, ""],
             [0, "all-reduce.3", g1 - 1000, g1 + 1000, ""]]
    rec = dict(recorded, ops=recorded["ops"] + extra)
    t = trace_reduce.from_records(rec)
    exposed = t.exposed_s(trace_reduce.is_collective) * 1e9
    assert exposed == pytest.approx(g1 - g0)
    assert t.op_seconds(trace_reduce.is_collective) * 1e9 == pytest.approx(
        1000 + (g1 - g0) + 2000)


def test_load_reads_an_xplane_file(tmp_path):
    """The reader itself, on a trace of the CPU backend: no TPU plane, so
    nothing is busy, and the benchmark's own annotation is found."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("step_call"):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = next(os.path.join(b, f) for b, _, fs in os.walk(tmp_path)
                for f in fs if f.endswith(".xplane.pb"))
    t = trace_reduce.load(path, 1)
    assert t.chips == [] and t.busy_s() == 0.0 and t.idle_share() is None
    assert [n for n, _, _ in t.host] == ["step_call"]
    assert trace_reduce.short_name(
        "%fusion.12 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop") \
        == "fusion.12"
