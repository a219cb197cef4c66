"""``chipbench/engine_gap.py``: the chip's idle time between two decode
programs, cut at the engine thread's boundaries. On hand-built
``ProgramTrace`` records, a case each, in microseconds: two passes whose
gap of 800 us is, part by part, 50 of wake, 200 of emit, 200 of turnaround,
200 of upload, 100 of dispatch and 50 of launch. Then on an excerpt cut
from a traced run of ``serve-starcoder2-decode`` (this program, PR 41,
seed 2147510102: ``engine_gap_serve.xplane.pb``): five whole passes of
the engine's loop around one admission. Of the host plane it keeps the
``dpx:`` spans and the runtime's ``tpu::System::Execute`` events, of the
device's the programs and, between two decode programs, every operation
(the admission's prefill is 2 000 of them, which is what the file's
113 KB are: its idle time cannot be read without them); inside a decode
program only the operations within 40 us of its ends. An operation's
metadata is its id and its name's first twenty characters."""

import os
import shutil
import types

import pytest

from chipbench import engine_gap, program_trace, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ENGINE, CALLER = 1, 2
US = 1000


def a_pass(t, program=(350, 1350), upload=True):
    """One pass of the loop that starts its dispatch at ``t``: the spans
    of the engine's thread and the decode program, (name, start, end)."""
    p0, p1 = program
    spans = [("serve.decode.dispatch", t, t + 300),
             ("serve.decode.rows", t + 310, t + 1600),
             ("serve.decode.fetch", t + 320, t + 1400),
             ("serve.iter", t - 150, t + 1650),
             ("serve.sweep", t - 100, t - 80),
             ("serve.decode.capacity", t - 50, t - 10)]
    if upload:
        spans.append(("serve.decode.upload", t + 10, t + 200))
    return spans, [("jit__decode(1)", t + p0, t + p1)]


def build(passes, spans=(), programs=()):
    """A ``ProgramTrace`` of ``passes`` (from :func:`a_pass`), more spans
    ((name, start, end) on the engine's line, or with a line as a fourth)
    and more programs; every program is one device operation long."""
    every, modules = list(spans), list(programs)
    for s, m in passes:
        every += s
        modules += m
    return program_trace.ProgramTrace(
        [(n, (rest + [ENGINE])[0], s * US, e * US, {})
         for n, s, e, *rest in map(list, every)],
        {0: [("fusion", s * US, e * US, "") for _, s, e in modules]},
        {0: [(n, s * US, e * US) for n, s, e in modules]},
        {0: [{"hlo": ""} for _ in modules]}, [])


def gap_us(pt, launches=()):
    busy = trace_reduce.union((s, e) for _, s, e, _ in pt.ops[0])
    got = engine_gap.gap_ns(pt, busy, 0, [t * US for t in launches])
    if got is None:
        return None
    flat = {k: v / US for k, v in got.items()
            if k not in ("turnaround_in", "passes", "shift")}
    flat["passes"] = got["passes"]
    flat["shift"] = got["shift"] if got["shift"] is None \
        else got["shift"] / US
    flat.update({"in." + k: v / US for k, v in got["turnaround_in"].items()})
    return flat


BASE = dict(gap=800, wake=50, emit=200, turnaround=200, admit=0, upload=200,
            dispatch=100, launch=50, under_submit=0, passes=1,
            fetch_wait=1080, shift=None, shift_most=50)
BASE.update({"in.serve.sweep": 20, "in.serve.decode.capacity": 40,
             "in.serve.snapshot": 0, "in.loop": 140})


def test_the_seven_parts_sum_to_the_gap():
    got = gap_us(build([a_pass(0), a_pass(1800)]))
    assert got == BASE
    assert sum(got[p] for p in engine_gap.PARTS) == got["gap"] == 800


def test_a_program_that_starts_before_the_call_returns():
    """``launch`` 0, and ``dispatch`` clipped at the program's start."""
    got = gap_us(build([a_pass(0), a_pass(1800, program=(250, 1250))]))
    assert got == dict(BASE, gap=700, dispatch=50, launch=0)


def test_the_devices_clock_is_moved_to_the_hosts_own_launch():
    """The profiler laid the device's plane 500 us early: each program
    "starts" 400 us before the runtime's call that launches it (250 us
    into the dispatch). Moved later by that, no less and no more, and the
    gap's 800 us stand."""
    early = [a_pass(0, program=(-150, 850)), a_pass(1800, program=(-150, 850))]
    unmoved = gap_us(build(early))
    assert unmoved == dict(
        BASE, wake=550, turnaround=50, upload=0, dispatch=0, launch=0,
        shift_most=550, **{"in.serve.sweep": 0, "in.loop": 50,
                           "in.serve.decode.capacity": 0})
    moved = gap_us(build(early), launches=[250, 2050])
    assert moved == dict(BASE, wake=150, dispatch=50, launch=0, shift=400,
                         shift_most=550)
    # a launch that no pass's dispatch holds moves nothing; one after the
    # program's start neither, and says that there was one to go by
    assert gap_us(build(early), launches=[900]) == unmoved
    assert gap_us(build([a_pass(0), a_pass(1800)]), launches=[250, 2050]) \
        == dict(BASE, shift=0)


def test_an_uploads_own_program_in_the_gap_is_in_no_part():
    got = gap_us(build([a_pass(0), a_pass(1800)], programs=[
        ("jit_convert_element_type(7)", 1850, 1900)]))
    assert got == dict(BASE, gap=750, upload=150)
    assert sum(got[p] for p in engine_gap.PARTS) == 750


def test_an_admission_between_two_passes():
    """The chunk's idle time under ``admit``, its busy time nowhere; what
    the sweep and the capacity check held of the rest is told apart."""
    spans = [("serve.admit", 1610, 1760), ("serve.snapshot", 1761, 1766)]
    got = gap_us(build([a_pass(0), a_pass(1800)], spans=spans,
                       programs=[("jit_prefill_b256(3)", 1650, 1750)]))
    want = dict(BASE, gap=700, admit=50, turnaround=50)
    want.update({"in.serve.sweep": 0, "in.serve.decode.capacity": 30,
                 "in.serve.snapshot": 5, "in.loop": 15})
    assert got == want


@pytest.mark.parametrize("line,under", [(CALLER, 150), (ENGINE, 0)])
def test_a_submit_on_another_thread_is_an_overlay(line, under):
    """1350-1500 us of the gap 1350-2150: the caller's thread held the
    interpreter; a span of that name on the engine's own thread did not."""
    got = gap_us(build([a_pass(0), a_pass(1800)],
                       spans=[("serve.submit", 1300, 1500, line)]))
    assert got == dict(BASE, under_submit=under)


def test_the_first_pass_is_left_out_and_a_pass_cut_by_the_trace():
    three = [a_pass(0), a_pass(1800), a_pass(3600)]
    got = gap_us(build(three))
    assert got == {k: v if k in ("fetch_wait", "shift", "shift_most")
                   else v * 2 for k, v in BASE.items()}
    # the trace ends before the last pass's program: that gap is not read
    spans, _ = a_pass(3600)
    assert gap_us(build(three[:2] + [(spans, [])])) == dict(BASE)
    assert gap_us(build(three[:1])) is None


def test_a_trace_without_the_upload_span_reads_none(monkeypatch):
    old = build([a_pass(0, upload=False), a_pass(1800, upload=False)])
    assert gap_us(old) is None
    assert engine_gap.passes_of(program_trace.ProgramTrace(
        [], {}, {}, {}, []), 0) is None
    monkeypatch.setattr(program_trace, "of", lambda cell: old)
    trace = trace_reduce.from_records({"ops": [[0, "fusion", 0, 9, ""]]})
    assert len(engine_gap.METRICS) == 10
    assert all(engine_gap.read(trace, None, m) is None
               for m in engine_gap.METRICS)


def test_parts_are_milliseconds_a_pass_and_made_once(monkeypatch):
    pt = build([a_pass(0), a_pass(1800), a_pass(3600)])
    made = []
    real = engine_gap.gap_ns
    monkeypatch.setattr(engine_gap, "gap_ns",
                        lambda *a: made.append(1) or real(*a))
    monkeypatch.setattr(program_trace, "of", lambda cell: pt)
    trace = trace_reduce.from_records({"ops": [
        [0, n, s, e, ""] for n, s, e, _ in pt.ops[0]]})
    got = {m: engine_gap.read(trace, None, m) for m in engine_gap.METRICS}
    assert made == [1]
    assert got == {
        "decode_gap_ms": 0.8, "decode_gap_wake_ms": 0.05,
        "decode_gap_emit_ms": 0.2, "decode_gap_turnaround_ms": 0.2,
        "decode_gap_admit_ms": 0.0, "decode_gap_upload_ms": 0.2,
        "decode_gap_dispatch_ms": 0.1, "decode_gap_launch_ms": 0.05,
        "decode_gap_under_submit_ms": 0.0, "engine_fetch_wait_ms": 1.08}
    parts = engine_gap.parts(trace, None)
    assert parts["shift"] is None and parts["shift_most"] == 0.05
    assert parts["passes"] == 2 and parts["turnaround_in"] == {
        "serve.sweep": 0.02, "serve.decode.capacity": 0.04,
        "serve.snapshot": 0.0, "loop": 0.14}
    # a CPU run's trace has the spans and no device plane: nothing read
    pt.ops = {}
    pt.memo.clear()
    assert engine_gap.parts(trace, None) is None


# -- the recorded excerpt ---------------------------------------------------

EXCERPT = os.path.join(HERE, "engine_gap_serve.xplane.pb")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    out = tmp_path_factory.mktemp("engine_gap")
    where = os.path.join(out, "trace", "plugins", "profile", "x")
    os.makedirs(where)
    path = shutil.copy(EXCERPT, where)
    return trace_reduce.load(path, 1), types.SimpleNamespace(
        out_dir=str(out))


def test_the_excerpt_reads_all_ten_and_they_sum(recorded):
    trace, cell = recorded
    assert os.path.getsize(EXCERPT) < 120 * 1024
    got = {m: engine_gap.read(trace, cell, m) for m in engine_gap.METRICS}
    assert all(v is not None and v >= 0 for v in got.values())
    seven = sum(got[f"decode_gap_{p}_ms"] for p in engine_gap.PARTS)
    assert seven == pytest.approx(got["decode_gap_ms"], rel=1e-9)
    assert 0 <= got["decode_gap_under_submit_ms"] <= got["decode_gap_ms"]
    # a few passes and one admission, whose idle time is told apart
    parts = engine_gap.parts(trace, cell)
    pt = program_trace.of(cell)
    assert parts["passes"] == 4 and got["decode_gap_admit_ms"] > 0
    assert len(pt.spans_named("serve.admit")) == 1
    # the host plane holds the runtime's launches: the device's plane was
    # moved, by less than would put a fetch's return before its program
    assert len(engine_gap.launches_of(cell)) >= 5
    assert 0 < parts["shift"] < parts["shift_most"]
    # every span of a pass is in the excerpt, four arrays a pass
    ups = pt.spans_named("serve.decode.upload")
    assert len(ups) >= parts["passes"]
    assert {u[4]["arrays"] for u in ups} == {4}
    assert all(u[4]["bytes"] > 0 and u[4]["iteration"] > 0 for u in ups)


def test_the_excerpts_gaps_are_its_idle_time_between_decode_programs(
        recorded):
    """``decode_gap_ms`` x passes is the idle time between the first and
    the last decode program of the excerpt, to 5 %: the excerpt is cut so
    that every pass between them is whole."""
    trace, cell = recorded
    parts = engine_gap.parts(trace, cell)
    programs = sorted((s, e) for _, n, s, e in trace.module_events(
        program_trace.is_decode_program))
    between = [[programs[0][1], programs[-1][0]]]
    inside = trace_reduce.union(programs)
    idle = trace_reduce.length(trace_reduce.subtract(
        trace_reduce.subtract(between, trace.busy(0)), inside)) / 1e6
    assert parts["gap"] * parts["passes"] == pytest.approx(idle, rel=0.05)
    assert idle > 0
