"""``chipbench/program_trace.py`` and the per-layer readers built on it,
on small excerpts cut from real v5e traces: ``program_trace_*.xplane.pb``
from this program (PR 24: its ``dpx:`` spans on the host plane, its
scopes in the ops' name stacks) and ``parent_trace_*.xplane.pb`` from the
program before it had either (PR 23's runs). The excerpts keep the events
inside a few short windows and cut the HLO text of an op's name to its
first hundred characters; everything else is as the profiler wrote it."""

import importlib.util
import json
import os
import shutil
import types

import pytest

from chipbench import program_trace, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
EXCERPTS = ("program_trace_serve", "program_trace_train",
            "parent_trace_serve", "parent_trace_train")


def _uses_program_trace(metric):
    with open(os.path.join(REPO, "chipbench", "layer_metrics",
                           metric + ".py")) as f:
        return "program_trace" in f.read()


with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    _PER_LAYER = json.load(_f)["per_layer"]
#: the readers built on program_trace, by the kind of cell they read
NEW = {kind: [m["name"] for m in _PER_LAYER
              if m["workloads"][0].startswith(kind)
              and _uses_program_trace(m["name"])]
       for kind in ("serve", "train")}


def excerpt(name):
    return os.path.join(HERE, name + ".xplane.pb")


@pytest.fixture(scope="module")
def run_of(tmp_path_factory):
    """(trace_reduce's trace, a cell whose run wrote that trace) for an
    excerpt, as a reader gets them from ``run.py``."""
    made = {}

    def make(name):
        if name not in made:
            out = tmp_path_factory.mktemp(name)
            where = os.path.join(out, "trace", "plugins", "profile", "x")
            os.makedirs(where)
            shutil.copy(excerpt(name), where)
            made[name] = (trace_reduce.load(excerpt(name), 1),
                          types.SimpleNamespace(out_dir=str(out)))
        return made[name]
    return make


def read(metric, trace, cell):
    path = os.path.join(REPO, "chipbench", "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric_under_test",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(trace, {}, cell)


def test_the_new_readers_are_the_ones_the_issue_lists():
    assert len(NEW["serve"]) == 14 and len(NEW["train"]) == 7


@pytest.mark.parametrize("name", EXCERPTS)
def test_wire_reader_agrees_with_jax_on_every_device_event(name):
    """The same events, names and times as ``jax.profiler.ProfileData``
    gives (``trace_reduce.load`` is built on this reader since PR 26;
    test_chipbench_golden.py holds it to ProfileData in full)."""
    import jax

    mine = program_trace.parse(excerpt(name))
    (plane,) = [p for p in jax.profiler.ProfileData.from_file(
        excerpt(name)).planes if p.name == "/device:TPU:0"]
    theirs = {line.name: [(e.name, int(e.start_ns),
                           int(e.start_ns + e.duration_ns))
                          for e in line.events] for line in plane.lines}
    assert [(n, s, e) for n, s, e, _ in mine.ops[0]] \
        == [(trace_reduce.short_name(n), s, e)
            for n, s, e in theirs[trace_reduce.OPS_LINE]]
    assert mine.modules[0] == theirs[trace_reduce.MODULES_LINE]
    assert len(mine.leaf_ops(0)) == len(
        trace_reduce.load(excerpt(name), 1).leaf_ops(0)) > 20


def test_host_events_are_the_programs_spans_with_line_and_attrs():
    pt = program_trace.parse(excerpt("program_trace_serve"))
    names = {s[0] for s in pt.spans}
    assert {"serve.iter", "serve.decode.rows", "serve.row.sample",
            "serve.row.fetch", "serve.row.emit"} <= names
    engine = pt.thread_of("serve.iter")
    assert all(s[1] == engine for s in pt.spans
               if s[0].startswith(("serve.row.", "serve.decode.")))
    fetch = pt.spans_named("serve.row.fetch")[0]
    assert fetch[3] > fetch[2]
    assert isinstance(fetch[4]["slot"], int) \
        and isinstance(fetch[4]["iteration"], int) \
        and isinstance(fetch[4]["trace_id"], str)
    (it,) = {s[4]["iteration"] for s in pt.spans_named("serve.decode.rows")}
    assert fetch[4]["iteration"] == it
    # sorted by start, the enclosing span first
    assert pt.spans == sorted(pt.spans, key=lambda s: (s[2], -s[3]))
    train = program_trace.parse(excerpt("program_trace_train"))
    assert [(s[0], s[4]) for s in train.spans] \
        == [("train.step_call", {"step": 9}), ("train.dispatch", {})]


def test_ops_carry_the_name_stack_they_were_traced_under():
    serve = program_trace.parse(excerpt("program_trace_serve"))
    stacks = {o[3] for o in serve.leaf_ops(0)}
    assert any("/blocks/decode_attention/while/body/page_gather/" in s
               for s in stacks)
    assert any(program_trace.scopes(s) >= {"blocks", "attn", "qkv"}
               for s in stacks)
    train = program_trace.parse(excerpt("program_trace_train"))
    by_class = {}
    for _, _, _, stack in train.leaf_ops(0):
        by_class.setdefault(program_trace.step_class(stack), set()).add(stack)
    assert set(by_class) == {"fwd", "bwd", "remat", "optimizer", "unscoped"}
    assert any("jvp(loss)/head/" in s for s in by_class["fwd"])
    assert any("transpose(jvp(loss))/head/" in s for s in by_class["bwd"])
    assert all("/optimizer/" in s for s in by_class["optimizer"])
    parent = program_trace.parse(excerpt("parent_trace_train"))
    assert any("jvp()" in o[3] for o in parent.leaf_ops(0))
    assert not any(program_trace.scopes(o[3]) & program_trace.SCOPES
                   for o in parent.leaf_ops(0))


@pytest.mark.parametrize("stack,want", [
    ("jit(local_step)/transpose(jvp(loss))/jvp(loss)/checkpoint/"
     "rematted_computation/blocks/attn/qkv/dot_general:",
     {"local_step", "loss", "checkpoint", "rematted_computation", "blocks",
      "attn", "qkv", "dot_general"}),
    ("jit(_decode)/blocks/decode_attention/while/body/page_gather/"
     "jit(_take)/gather:",
     {"_decode", "blocks", "decode_attention", "while", "body",
      "page_gather", "_take", "gather"}),
    ("k_pages[7]:", {"k_pages[7]"}), ("", {""})])
def test_scopes_takes_the_transforms_off(stack, want):
    assert program_trace.scopes(stack) == want


def test_idle_parts_sum_to_the_idle_share_of_the_same_records(run_of):
    trace, cell = run_of("program_trace_serve")
    parts = program_trace.engine_idle_parts(trace, cell)
    assert set(parts) == {"row_loop", "admit", "other_span", "unattributed"}
    assert all(v >= 0 for v in parts.values())
    assert sum(parts.values()) == pytest.approx(100 * trace.idle_share(),
                                                abs=1e-6)
    # the excerpt's windows hold rows after a decode program and the
    # start of an admission; between the windows nothing was kept
    assert parts["row_loop"] > 0 and parts["admit"] > 0 \
        and parts["unattributed"] > 0
    by_reader = sum(read(f"device_idle_{k}.serve", trace, cell) for k in
                    ("in_row_loop", "in_admit", "in_other_span",
                     "unattributed"))
    assert by_reader == pytest.approx(
        read("device_idle_share.serve", trace, cell), abs=1e-6)


def test_step_split_sums_to_the_busy_time(run_of):
    trace, cell = run_of("program_trace_train")
    split = program_trace.step_split_ms(cell)
    parts = sum(split[k] for k in ("fwd", "bwd", "remat", "optimizer",
                                   "unscoped"))
    # one step program in the excerpt, its ops one after another
    assert parts == pytest.approx(trace.busy_s() * 1e3, rel=1e-6)
    assert 0 < split["head_loss"] <= split["fwd"] + split["bwd"]
    assert read("step_unscoped_share", trace, cell) \
        == pytest.approx(100 * split["unscoped"] / parts)
    assert read("step_fwd_ms", trace, cell) == split["fwd"]


def test_decode_split_is_inside_the_program(run_of):
    _, cell = run_of("program_trace_serve")
    split = program_trace.decode_split_ms(cell)
    assert 0 < split["page_gather"] <= split["attention"] < split["total"]
    assert 0 <= split["unscoped"] < split["total"]


@pytest.mark.parametrize("metric", NEW["serve"])
def test_serve_reader_reads_this_programs_trace(run_of, metric):
    trace, cell = run_of("program_trace_serve")
    value = read(metric, trace, cell)
    assert value is not None and value >= 0


@pytest.mark.parametrize("metric", NEW["train"])
def test_train_reader_reads_this_programs_trace(run_of, metric):
    trace, cell = run_of("program_trace_train")
    value = read(metric, trace, cell)
    assert value is not None and value >= 0


@pytest.mark.parametrize("kind,metric", [
    (k, m) for k in ("serve", "train") for m in NEW[k]])
def test_reader_finds_nothing_in_a_trace_without_program_spans(
        run_of, kind, metric, tmp_path):
    """The parent's traces (the benchmark's files are laid over its
    checkout too): no ``dpx:`` events, no scope in any name stack, no
    program called ``prefill_b*``. And a run that wrote no trace."""
    trace, cell = run_of(f"parent_trace_{kind}")
    assert read(metric, trace, cell) is None
    none = types.SimpleNamespace(out_dir=str(tmp_path))
    assert read(metric, trace_reduce.from_records({"ops": []}), none) is None
