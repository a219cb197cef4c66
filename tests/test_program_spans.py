"""The program's own instruments (PERF.md section 3): dpxtrace spans as
profiler annotations on the profiler's clock, the spans and counters of
the engine loop and the train step, the process-wide compile counter, and
the ``jax.named_scope``s inside the programs."""

import json
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import models, optim
from distributed_pytorch_tpu.obs import trace as dpxtrace
from distributed_pytorch_tpu.parallel import make_train_step
from distributed_pytorch_tpu.runtime import compile_cache
from distributed_pytorch_tpu.serve import (EngineConfig, InferenceEngine,
                                           SamplingParams)
from distributed_pytorch_tpu.utils import profiler


@pytest.fixture(autouse=True)
def _tracing_off():
    dpxtrace.reset()
    dpxtrace.configure(enabled=False, log_path=None)
    yield
    dpxtrace.reset()


def host_spans(logdir):
    """{line index: [(name, start ns, end ns, attrs)]} of the ``dpx:``
    events in the one ``.xplane.pb`` under ``logdir``, read with JAX's own
    reader (``chipbench/program_trace.py`` has its own tests)."""
    path = [os.path.join(b, f) for b, _, fs in os.walk(logdir) for f in fs
            if f.endswith(".xplane.pb")]
    assert len(path) == 1
    out = {}
    data = jax.profiler.ProfileData.from_file(path[0])
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            got = [(e.name[4:], e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("dpx:")]
            if got:
                out[i] = sorted(got, key=lambda s: (s[1], -s[2]))
    return out


def named(spans, name):
    return [s for line in spans.values() for s in line if s[0] == name]


# -- a span is a profiler annotation ------------------------------------------


def test_span_lands_in_the_profile_on_its_threads_line(tmp_path):
    def other():
        with dpxtrace.span("other.thread", k=1):
            time.sleep(0.002)

    with profiler.trace(str(tmp_path)):
        with dpxtrace.span("outer", iteration=3) as sp:
            sp.set(rows=2)
            with dpxtrace.span("inner", slot=1, trace_id="ab-1"):
                time.sleep(0.002)
        t = threading.Thread(target=other, name="test-other-thread")
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    spans = host_spans(tmp_path)
    (outer,), (inner,) = named(spans, "outer"), named(spans, "inner")
    assert outer[3] == {"iteration": 3, "rows": 2}
    assert inner[3] == {"slot": 1, "trace_id": "ab-1"}
    assert outer[1] <= inner[1] and inner[2] <= outer[2]      # nested
    line_of = {s[0]: i for i, line in spans.items() for s in line}
    assert line_of["outer"] == line_of["inner"] != line_of["other.thread"]


def test_recorded_span_is_an_annotation_too(tmp_path):
    dpxtrace.configure(enabled=True, ring=16)
    with profiler.trace(str(tmp_path)):
        with dpxtrace.span("both", n=1):
            pass
    assert [s[3] for s in named(host_spans(tmp_path), "both")] == [{"n": 1}]
    ring, _ = dpxtrace.flight_snapshot()
    assert [r["name"] for r in ring] == ["both"]


def test_no_session_and_tracing_off_records_nothing():
    first, second = dpxtrace.span("a", k=1), dpxtrace.span("b")
    assert first is second          # the shared no-op: nothing is built
    with first as sp:
        sp.event("x")
        sp.set(rows=1)
        assert sp.span_id is None and sp.trace_id is None
    assert dpxtrace.flight_snapshot() == ([], 0)


def test_annotate_is_a_span(tmp_path):
    with profiler.trace(str(tmp_path)):
        with profiler.annotate("data-load", shard=2):
            pass
    assert [s[3] for s in named(host_spans(tmp_path), "data-load")] \
        == [{"shard": 2}]


# -- the engine loop ----------------------------------------------------------


def tiny_lm(**kw):
    return models.TransformerLM(**{**dict(
        vocab=61, dim=32, n_layers=2, n_heads=4, n_kv_heads=2, pos="rope",
        max_seq=64), **kw})


@pytest.fixture(scope="module")
def engine_run(tmp_path_factory):
    """A tiny paged engine serving three requests under a profiler
    session, two greedy ones of five tokens and a sampled one of three:
    (spans by line, stats before and after)."""
    logdir = tmp_path_factory.mktemp("engine_profile")
    model = tiny_lm()
    params = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, EngineConfig(
        paged=True, n_slots=4, max_len=64, buckets=(8, 16), page_len=8))
    with eng:
        # warm every program first: a compile inside the session would
        # only make the trace larger
        sampled = SamplingParams(max_new_tokens=3, temperature=0.7)
        for sp in (SamplingParams(max_new_tokens=2), sampled):
            eng.submit(np.arange(5, dtype=np.int32), sp).result(timeout=300)
        before = eng.stats()
        with profiler.trace(str(logdir)):
            hs = [eng.submit(np.arange(3 + i, dtype=np.int32), sp)
                  for i, sp in enumerate((SamplingParams(max_new_tokens=5),
                                          SamplingParams(max_new_tokens=5),
                                          sampled))]
            for h in hs:
                h.result(timeout=300)
        after = eng.stats()
    return host_spans(logdir), before, after


def test_engine_spans_are_the_loops_tree(engine_run):
    spans, _, _ = engine_run
    engine_line = [i for i, line in spans.items()
                   if any(s[0] == "serve.iter" for s in line)]
    assert len(engine_line) == 1
    line = spans[engine_line[0]]
    names = {s[0] for s in line}
    assert {"serve.iter", "serve.sweep", "serve.admit",
            "serve.admit.prefill", "serve.admit.first_token",
            "serve.decode.capacity", "serve.decode.dispatch",
            "serve.decode.upload", "serve.decode.rows",
            "serve.decode.sample", "serve.decode.fetch",
            "serve.row.sample", "serve.row.fetch", "serve.row.emit"} <= names
    # the caller's thread holds serve.submit, not the engine's
    assert "serve.submit" not in names and len(named(spans, "serve.submit")) == 3

    # the session opens and closes in the middle of an iteration (the
    # warm-up request's last; the one that retires the last request, whose
    # serve.iter is still open when the session ends): only what lies
    # between the first and the last whole iteration is held to the tree
    iters = [s for s in line if s[0] == "serve.iter"]
    t0, t1 = min(s[1] for s in iters), max(s[2] for s in iters)

    def inside(child, parent):
        return all(any(p[1] <= c[1] and c[2] <= p[2] for p in line
                       if p[0] == parent)
                   for c in line
                   if c[0] == child and t0 <= c[1] and c[2] <= t1)
    for child, parent in (
            ("serve.admit", "serve.iter"),
            ("serve.admit.prefill", "serve.admit"),
            # the first token is taken after the read of the pass in flight
            ("serve.admit.first_token", "serve.iter"),
            ("serve.decode.dispatch", "serve.iter"),
            ("serve.decode.upload", "serve.decode.dispatch"),
            ("serve.decode.rows", "serve.iter"),
            # a pass's samplers are dispatched with it, an iteration
            # before the pass is read (serve.decode.rows)
            ("serve.decode.sample", "serve.iter"),
            ("serve.decode.fetch", "serve.decode.rows"),
            ("serve.row.sample", "serve.iter"),
            ("serve.row.fetch", "serve.decode.rows"),
            ("serve.row.emit", "serve.decode.rows")):
        assert inside(child, parent), (child, parent)
    assert all("iteration" in s[3] for s in line
               if s[0].startswith("serve.") and s[0] != "serve.idle")
    admit = named(spans, "serve.admit")
    assert sorted(a[3]["prompt_len"] for a in admit) == [3, 4, 5]
    assert all(a[3]["bucket"] == 8 and a[3]["n_hit"] == 0
               and "request_id" in a[3] and "trace_id" in a[3]
               for a in admit)


def test_engine_row_spans_count_the_rows_decoded(engine_run):
    spans, before, after = engine_run
    rows = after["rows_decoded"] - before["rows_decoded"]
    assert rows == 4 + 4 + 2    # the first token of each comes from admit
    for name in ("serve.row.sample", "serve.row.fetch", "serve.row.emit"):
        assert len(named(spans, name)) == rows
    assert sum(s[3]["rows"] for s in named(spans, "serve.decode.rows")) == rows
    assert after["admitted"] - before["admitted"] == 3


def test_engine_fetches_once_an_iteration_and_samples_by_setting(engine_run):
    spans, before, after = engine_run
    # a pass is named by the iteration that dispatched it: a read carries
    # it as ``dispatched``, beside the iteration it is read in (the next)
    loops = {s[3]["dispatched"]: s for s in named(spans, "serve.decode.rows")}
    fetches = named(spans, "serve.decode.fetch")
    assert all(s[3]["iteration"] == d + 1 for d, s in loops.items())
    # one read of the tokens a decode pass, of all its rows
    assert sorted(f[3]["dispatched"] for f in fetches) == sorted(loops)
    assert all(f[3]["rows"] == loops[f[3]["dispatched"]][3]["rows"]
               and f[3]["iteration"] == f[3]["dispatched"] + 1
               for f in fetches)
    assert after["decode_fetches"] - before["decode_fetches"] == len(fetches)
    # every row joins its group before the read (in the iteration that
    # dispatches the pass) and gets its token after (in the next)
    for name, after_fetch in (("serve.row.sample", False),
                              ("serve.row.fetch", True),
                              ("serve.row.emit", True)):
        for f in fetches:
            assert sum(r[3]["iteration"] == f[3]["dispatched"] + after_fetch
                       and (r[1] >= f[2] if after_fetch else r[2] <= f[1])
                       for r in named(spans, name)) == f[3]["rows"], name
    # a request holds a row from the iteration that admits it (its first
    # token is its prefill's, sampled on the device behind the prefill
    # and read at that iteration's end) for max_new - 1 decode passes,
    # and the paged engine admits one prompt an iteration: the three
    # overlap. A sampler is dispatched only while the sampled request
    # (the last submitted, three tokens) has a row: its two decode passes
    admitted = sorted((a[3]["request_id"], a[3]["iteration"])
                      for a in named(spans, "serve.admit"))
    its = [it for _, it in admitted]
    assert len(set(its)) == 3
    held = [set(range(it, it + n - 1)) for it, n in zip(its, (5, 5, 3))]
    assert set(loops) == set().union(*held)
    dispatches = named(spans, "serve.decode.dispatch")
    assert {d[3]["iteration"] for d in dispatches} == set(loops)
    samples = named(spans, "serve.decode.sample")
    assert {s[3]["iteration"] for s in samples} == held[2]
    assert len(samples) == 2
    assert all(s[3]["groups"] == 1 for s in samples)
    assert after["sample_dispatches"] - before["sample_dispatches"] == 2
    fetch_of = {f[3]["dispatched"]: f for f in fetches}
    assert all(s[2] <= fetch_of[s[3]["iteration"]][1] for s in samples)
    # with a pass in flight, the next is dispatched BEFORE it is read
    start = {d[3]["iteration"]: d[1] for d in dispatches}
    assert all(start[d + 1] < f[1] for d, f in fetch_of.items()
               if d + 1 in start)
    ahead = after["decode_passes_ahead"] - before["decode_passes_ahead"]
    assert ahead == sum(d + 1 in start for d in fetch_of) == len(loops) - 1
    assert after["decode_rows_dropped"] == 0


def test_engine_host_ns_counters_nest(engine_run):
    _, _, after = engine_run
    host = after["host_ns"]
    assert set(host) == {"idle", "admit", "decode_dispatch", "row_loop",
                         "iter", "decode_upload", "decode_fetch"}
    assert all(v > 0 for v in host.values())
    assert host["iter"] >= host["row_loop"] + host["decode_dispatch"] \
        + host["admit"]
    # the copies are a part of the dispatch, the wait for the program of
    # the row loop
    assert host["decode_upload"] <= host["decode_dispatch"]
    assert host["decode_fetch"] <= host["row_loop"]
    assert after["xla_compiles"]["compiles"] \
        + after["xla_compiles"]["cache_hits"] > 0


BLOCK_LM = dict(vocab=97, n_heads=8, head_dim=8, attn_bias=False,
                qk_norm=1e-6, rope_base=1e6,
                norm="rms", norm_eps=1e-6, block_kinds=("moe", "moe"),
                moe=dict(n_routed=8, width=16, top_k=2, n_shared=0,
                         score="softmax"), gen_block=4, mask_id=96)


@pytest.mark.parametrize("pool,lm,cfg,program,arrays", [
    ("paged", {}, dict(paged=True, page_len=8, buckets=(8, 16)),
     "_decode_fn", 3),
    # window layers' rings beside no page by table: the same three copies
    ("rings", dict(layer_windows=(8, 8)),
     dict(page_len=8, buckets=(8, 16), prefix_share=False), "_decode_fn", 3),
    ("blocks", BLOCK_LM, dict(paged=True, page_len=8, buckets=(8, 16)),
     "_block_fn", 6)])
def test_a_pass_uploads_under_one_span_before_its_program(pool, lm, cfg,
                                                          program, arrays):
    """``serve.decode.upload``: once a pass, inside that pass's
    ``serve.decode.dispatch``, over every copy of the pass's arguments
    (a token pass's tokens are on the device already: the pass before's
    output), and closed before the pool calls its jitted program. Read
    from the
    flight ring under ``DPX_TRACE=1``: no profiler session."""
    dpxtrace.configure(enabled=True, ring=4096, log_path=None)
    model = tiny_lm(**lm)
    params = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, EngineConfig(
        n_slots=3, max_len=64, **cfg))
    count = lambda name: sum(r["name"] == name
                             for r in dpxtrace.flight_snapshot()[0])
    jitted, seen = getattr(eng.pool, program), []

    def spied(*args):
        seen.append((count("serve.decode.upload"),
                     count("serve.decode.dispatch")))
        return jitted(*args)
    setattr(eng.pool, program, spied)
    with eng:
        for n in (5, 6):
            eng.submit(np.arange(n, dtype=np.int32),
                       SamplingParams(max_new_tokens=6)).result(timeout=300)
        stats = eng.stats()
    ring, dropped = dpxtrace.flight_snapshot()
    assert dropped == 0
    ups = [r for r in ring if r["name"] == "serve.decode.upload"]
    dispatch = {r["span_id"]: r for r in ring
                if r["name"] == "serve.decode.dispatch"}
    # the n-th call of the program: n uploads closed, its dispatch open
    assert len(seen) == len(ups) == len(dispatch) >= 5
    assert seen == [(n + 1, n) for n in range(len(seen))]
    for up in ups:
        parent = dispatch[up["parent_id"]]
        assert up["attrs"]["iteration"] == parent["attrs"]["iteration"]
        assert up["attrs"]["arrays"] == arrays
    n = eng.config.n_slots
    # a block pass: the tokens a request's first block opens with (the
    # blocks themselves stay on the device), their count, n_fill
    given = n * 4 * (model.gen_block or 0)
    held = eng.pool.tables.nbytes + n * 4
    extra = 2 * n * 4 if model.gen_block else 0
    assert {u["attrs"]["bytes"] for u in ups} == {held + given + n + extra}
    assert stats["host_ns"]["decode_upload"] == eng.pool.upload_ns \
        >= sum(u["dur_ns"] for u in ups)


def test_block_passes_are_read_an_iteration_after_their_dispatch():
    """A block generator's ``serve.decode.rows`` and ``serve.decode.fetch``
    carry ``dispatched`` as the token path's do, the next pass's dispatch
    begins before the read, ``serve.block.advance`` lies inside the read,
    and the ``serve.stats`` mark carries the counters of the pass in
    flight beside the block counters. From the flight ring."""
    dpxtrace.configure(enabled=True, ring=4096, log_path=None)
    model = tiny_lm(**BLOCK_LM)
    eng = InferenceEngine(model, model.init(jax.random.PRNGKey(0)),
                          EngineConfig(n_slots=3, max_len=64, paged=True,
                                       page_len=8, buckets=(8, 16)))
    with eng:
        hs = [eng.submit(np.arange(n, dtype=np.int32),
                         SamplingParams(max_new_tokens=new))
              for n, new in ((9, 7), (4, 10))]
        for h in hs:
            h.result(timeout=300)
        stats = eng.stats()
    ring, dropped = dpxtrace.flight_snapshot()
    assert dropped == 0
    by = lambda name: [r for r in ring if r["name"] == name]
    rows, fetches = by("serve.decode.rows"), by("serve.decode.fetch")
    dispatch = {r["attrs"]["iteration"]: r for r in by("serve.decode.dispatch")}
    assert len(rows) == len(fetches) == len(dispatch) \
        == stats["decode_fetches"] >= 10
    for read in rows + fetches:
        at = read["attrs"]
        assert at["iteration"] == at["dispatched"] + 1
        assert at["rows"] == dispatch[at["dispatched"]]["attrs"]["rows"]
    assert sorted(r["attrs"]["dispatched"] for r in rows) == sorted(dispatch)
    ended = {r["span_id"]: i for i, r in enumerate(ring)}  # in that order
    for f in fetches:
        assert {"commits", "fills"} <= set(f["attrs"])
        # the next pass is on its way before this one is read
        nxt = dispatch.get(f["attrs"]["iteration"])
        assert nxt is None or ended[nxt["span_id"]] < ended[f["span_id"]]
    parents = {r["span_id"] for r in rows}
    advances = by("serve.block.advance")
    assert len(advances) == len(rows)
    assert all(a["parent_id"] in parents for a in advances + fetches)
    assert sum(a["attrs"]["blocks"] for a in advances) \
        == stats["blocks_emitted"]
    assert sum(f["attrs"]["fills"] for f in fetches) == stats["block_fills"]
    assert sum(f["attrs"]["commits"] for f in fetches) \
        == stats["block_commits"]
    assert stats["decode_passes_ahead"] == len(dispatch) - 1
    (mark,) = by("serve.stats")
    for key in ("decode_passes_ahead", "decode_rows_dropped", "block_passes",
                "block_commits", "block_fills", "blocks_emitted",
                "tokens_emitted"):
        assert mark["attrs"][key] == stats[key], key


@pytest.mark.parametrize("mon", [True, False])
def test_snapshot_span_only_on_a_pass_that_emits(tmp_path, mon):
    from distributed_pytorch_tpu.obs import metrics as dpxmon
    from distributed_pytorch_tpu.utils.logging import MetricsLogger

    dpxtrace.configure(enabled=True, ring=4096, log_path=None)
    dpxmon.reset()
    dpxmon.configure(enabled=mon)
    logger = MetricsLogger(path=str(tmp_path / "serve.jsonl"))
    model = tiny_lm()
    try:
        with InferenceEngine(model, model.init(jax.random.PRNGKey(0)),
                             EngineConfig(n_slots=2, max_len=64,
                                          metrics=logger, log_every=3)) as eng:
            eng.submit(np.arange(5, dtype=np.int32),
                       SamplingParams(max_new_tokens=11)).result(timeout=300)
            passes = eng.stats()["iterations"]
    finally:
        logger.close()
        dpxmon.reset()
    ring, _ = dpxtrace.flight_snapshot()
    snaps = [r for r in ring if r["name"] == "serve.snapshot"]
    assert [s["attrs"]["iteration"] for s in snaps] \
        == (list(range(3, passes + 1, 3)) if mon else [])
    # between two passes: under no serve.iter
    assert all(s["parent_id"] is None for s in snaps) and passes >= 9
    if mon:
        rows = [json.loads(l) for l in
                (tmp_path / "serve.jsonl").read_text().splitlines()]
        last = [r for r in rows if r.get("event") == "metrics_snapshot"][-1]
        shares = {k: v for k, v in last["metrics"].items()
                  if k.startswith("serve.host_share.")}
        assert set(shares) == {"serve.host_share." + k for k in (
            "admit", "decode_dispatch", "decode_upload", "row_loop",
            "decode_fetch", "idle")}
        assert 0 < shares["serve.host_share.decode_upload"] \
            <= shares["serve.host_share.decode_dispatch"]
        assert 0 < shares["serve.host_share.decode_fetch"] \
            <= shares["serve.host_share.row_loop"] < 1


def test_spec_step_emits_each_span_once_an_iteration(tmp_path):
    model = tiny_lm()
    params = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, EngineConfig(
        n_slots=4, max_len=64, spec_decode=True, draft_model=model,
        draft_params=params, draft_len=2))
    with eng:
        eng.submit(np.arange(4, dtype=np.int32),
                   SamplingParams(max_new_tokens=3)).result(timeout=300)
        with profiler.trace(str(tmp_path)):
            hs = [eng.submit(np.arange(4 + i, dtype=np.int32),
                             SamplingParams(max_new_tokens=7))
                  for i in range(3)]
            for h in hs:
                h.result(timeout=300)
    spans = host_spans(tmp_path)
    verify = named(spans, "serve.spec.verify")
    # several requests speculate in one iteration: one span, not one each
    its = [v[3]["iteration"] for v in verify]
    assert len(its) == len(set(its)) and max(v[3]["rows"] for v in verify) > 1
    assert all(v[3]["draft_len"] == 2 for v in verify)
    for name in ("serve.spec.propose", "serve.spec.commit"):
        assert sorted(s[3]["iteration"] for s in named(spans, name)) \
            == sorted(its)


# -- the compile counter ------------------------------------------------------


def test_compile_events_count_a_fresh_compile_and_not_a_cached_call(tmp_path):
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(7.0)
    built = lambda e: e["compiles"] + e["cache_hits"]
    e0 = compile_cache.compile_events()
    with profiler.trace(str(tmp_path)):
        with dpxtrace.span("caller"):
            f(x).block_until_ready()
    e1 = compile_cache.compile_events()
    f(x).block_until_ready()
    e2 = compile_cache.compile_events()
    assert built(e1) == built(e0) + 1 and built(e2) == built(e1)
    assert e1["compile_s"] + e1["cache_load_s"] \
        > e0["compile_s"] + e0["cache_load_s"]
    # the mark falls inside the span that caused the build
    spans = host_spans(tmp_path)
    (mark,), (caller,) = named(spans, "xla.compile"), named(spans, "caller")
    assert caller[1] <= mark[1] <= mark[2] <= caller[2]
    assert set(mark[3]) == {"secs", "cached"}


# -- scopes inside the programs -----------------------------------------------


def op_names(lowered):
    return set(re.findall(r'loc\("(jit\([^"]+)"',
                          lowered.as_text(debug_info=True)))


def test_train_step_names_its_layers():
    model = tiny_lm(remat="full")
    params = model.init(jax.random.PRNGKey(0))

    def loss_fn(p, tokens):
        logits = model.apply(p, tokens[:, :-1]).astype(jnp.float32)
        hit = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - hit), {}

    opt = optim.adamw(1e-3)
    step = make_train_step(loss_fn, opt, mixed_precision="bf16")
    batch = jnp.zeros((2, 9), jnp.int32)
    names = op_names(step.lower(params, opt.init(params), batch))
    for want in ("jvp(loss)/embed/", "jvp(loss)/blocks/attn/qkv/",
                 "jvp(loss)/blocks/attn/core/", "jvp(loss)/blocks/attn/out/",
                 "jvp(loss)/blocks/mlp/", "jvp(loss)/blocks/norm/",
                 "jvp(loss)/ln_f/", "jvp(loss)/head/", "jvp(cast)/",
                 "transpose(jvp(loss))/", "rematted_computation/blocks/mlp/",
                 "jit(local_step)/optimizer/"):
        assert any(want in n for n in names), want
    out = step(params, opt.init(params), batch)
    assert np.isfinite(float(out.loss[0])) and step.calls == 1
    assert set(step.xla_compiles) == {"compiles", "compile_s", "cache_hits",
                                      "cache_load_s"}


def test_serving_programs_name_their_layers_and_themselves():
    model = tiny_lm()
    params = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, EngineConfig(
        paged=True, n_slots=2, max_len=64, buckets=(8, 16), page_len=8))
    pool = eng.pool
    names = op_names(pool._decode_fn.lower(
        params, pool.state, pool.moe_counts, jnp.array(pool.tables),
        jnp.array(pool.lengths), jnp.zeros(2, jnp.int32),
        jnp.ones(2, bool)))
    for want in ("/embed/", "/blocks/norm/", "/blocks/attn/qkv/",
                 "/blocks/page_write/", "/blocks/decode_attention/",
                 "/decode_attention/while/body/page_gather/",
                 "/blocks/attn/out/", "/blocks/mlp/", "/ln_f/", "/head/"):
        assert any(want in n for n in names), want
    with eng:
        eng.submit(np.arange(11, dtype=np.int32), SamplingParams(
            max_new_tokens=2, temperature=0.7)).result(timeout=300)
    # the greedy token of every slot is chosen inside the decode program
    # (jnp.argmax is a call there: the scope is the call site's whole name,
    # and XLA prefixes it to the inlined reduce, ".../sample/reduce")
    assert any(n.endswith("/sample") for n in names)
    # decode_step_device_ms finds the program by this
    assert "decode" in pool._decode_fn.__wrapped__.__name__
    (prefill,) = pool._admit_fns.values()
    assert prefill.__wrapped__.__name__ == "prefill_b16"
    (sampler,) = eng._sampler._one.values()
    assert sampler.__wrapped__.__name__ == "sample_0.7_None_None"
    lowered = sampler.lower(jnp.zeros((1, 61)), jax.random.PRNGKey(0))
    assert any("/sample/" in n for n in op_names(lowered))
    # the batched sampler: named apart from the decode program, and its
    # work under the same scope (vmap wraps a scope's name: "vmap(sample)")
    (rows,) = eng._sampler._rows.values()
    assert rows.__wrapped__.__name__ == "sample_rows_0.7_None_None"
    lowered = rows.lower(jnp.zeros((2, 61)), jnp.zeros((2, 2), jnp.uint32),
                         jnp.ones(2, bool), jnp.zeros(2, jnp.int32))
    assert any("/vmap(sample)/" in n for n in op_names(lowered))
