"""What the benchmark reads from a trace does not move with how the trace
is read: every per-layer reader, the device's busy seconds and window and
the ``breakdown`` over the recorded traces equal, digit for digit, what
the code before PR 26 returned (``golden_readings.json``, written down
from it by ``golden.py``); the file is opened once a run and each
reduction that several readers share is made once."""

import json
import os

import pytest

import golden
from chipbench import program_trace, trace_reduce


@pytest.fixture(scope="module")
def want():
    with open(golden.GOLDEN) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def got(tmp_path_factory):
    made = {}

    def of(fixture):
        if fixture not in made:
            made[fixture] = golden.readings(
                fixture, tmp_path_factory.mktemp(fixture))
        return made[fixture]
    return of


KEYS = golden.READERS + ["device.busy_s", "device.window_s",
                         "breakdown.device_ops", "breakdown.idle_gaps"]


def test_golden_file_covers_every_reader_and_fixture(want):
    assert set(want) == set(golden.FIXTURES)
    assert all(set(r) == set(KEYS) for r in want.values())
    # the excerpt of this program's serving trace feeds every serving
    # reader; a golden file of nothing would hold nothing
    with open(os.path.join(golden.REPO, "BENCHMARK.json")) as f:
        serve = [m["name"] for m in json.load(f)["per_layer"]
                 if m["workloads"][0].startswith("serve")]
    assert all(want["program_trace_serve"][m] is not None for m in serve)


@pytest.mark.parametrize("key", KEYS)
@pytest.mark.parametrize("fixture", sorted(golden.FIXTURES))
def test_reading_equals_what_the_parent_read(want, got, fixture, key):
    assert got(fixture)[key] == want[fixture][key]


@pytest.mark.parametrize("alias", golden.ALIASES)
def test_a_reader_under_a_second_name_reads_what_the_first_reads(
        want, tmp_path, alias):
    """``<name>.moe`` moves the sparse-expert jobs' own rate and is the
    reader ``<name>`` (``chipbench/reader_alias.py``): on every recorded
    training trace it reads the golden file's number, and where the first
    finds nothing to read so does the second."""
    base = alias.rpartition(".")[0]
    assert base in golden.READERS and alias not in want["recorded_trace"]
    with open(os.path.join(golden.REPO, "BENCHMARK.json")) as f:
        by_name = {m["name"]: m for m in json.load(f)["per_layer"]}
    same = {k: v for k, v in by_name[base].items()
            if k not in ("name", "moves", "workloads")}
    assert by_name[alias] == dict(same, name=alias,
                                  moves=by_name[base]["moves"] + ".moe",
                                  workloads=by_name[alias]["workloads"])
    train = [f for f, kind in golden.FIXTURES.items() if kind == "train"]
    for fixture in train:
        tmp = tmp_path / fixture
        tmp.mkdir()
        trace, cell = golden.run_of(fixture, tmp)
        got = golden.read(alias, trace, golden.COUNTERS["train"], cell)
        assert got == want[fixture][base]
    assert any(want[f][base] is not None for f in train)


@pytest.mark.parametrize("fixture,silent", [
    # a traced part with no admission in it (the parent's excerpt holds
    # decode programs alone, and none of the program's spans)
    ("parent_trace_serve", True),
    # this program's excerpt: an admission, a prefill, every span
    ("program_trace_serve", False)])
def test_a_run_names_the_metrics_that_read_nothing(tmp_path, capsys,
                                                   fixture, silent):
    """``run.read_layer_metrics`` leaves a silent metric out of the
    result and names it on ONE line, so that a metric which
    ``BENCHMARK.json`` lists for a cell and no traced run reports is seen
    in the first run that loses it."""
    from chipbench import run

    with open(os.path.join(golden.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    trace, cell = golden.run_of(fixture, tmp_path)
    cell.name = "serve-starcoder2-decode"
    cell.metrics_of = lambda section: [
        m for m in manifest[section] if cell.name in m["workloads"]]
    got = run.read_layer_metrics(cell, trace, golden.COUNTERS["serve"])
    (line,) = [l for l in capsys.readouterr().out.splitlines()
               if "nothing to read" in l]
    named = set(line.split(": ")[-1].split(", ")) - {"none"}
    listed = {m["name"] for m in cell.metrics_of("per_layer")}
    assert named | set(got) == listed and not named & set(got)
    assert ({"engine_admit_ms", "prefill_device_ms"} <= named) is silent
    assert "decode_step_device_ms" in got
    if not silent:
        assert line.endswith(": none") and set(got) == listed


def load_with_jax(path):
    """``trace_reduce.load`` as it was before PR 26: the events as
    ``jax.profiler.ProfileData`` gives them."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name in (trace_reduce.OPS_LINE,
                                 trace_reduce.MODULES_LINE):
                    evs = [(trace_reduce.short_name(e.name), int(e.start_ns),
                            int(e.start_ns + e.duration_ns), {"hlo": e.name})
                           for e in line.events]
                    (ops if line.name == trace_reduce.OPS_LINE
                     else modules)[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            host += [(e.name, int(e.start_ns),
                      int(e.start_ns + e.duration_ns))
                     for line in plane.lines for e in line.events
                     if e.name in trace_reduce.HOST_SPANS]
    return ops, modules, host


@pytest.mark.parametrize("fixture", [f for f in sorted(golden.FIXTURES)
                                     if f != "recorded_trace"])
def test_the_one_parse_holds_what_jax_reads_from_the_file(fixture):
    path = os.path.join(golden.HERE, fixture + ".xplane.pb")
    ops, modules, host = load_with_jax(path)
    mine = trace_reduce.load(path, 1)
    assert mine.ops == ops and len(ops[0]) > 20
    assert mine.modules == modules and mine.host == host
    pt = program_trace.parsed(path)
    assert [o[:3] for o in pt.ops[0]] == [o[:3] for o in ops[0]]
    assert pt.stats[0] == [o[3] for o in ops[0]]


def test_trace_is_opened_once_and_each_reduction_made_once(
        tmp_path, monkeypatch):
    """A run: ``trace_reduce.load``, then every reader (twice over here)
    and the breakdown. Counted: opens of the file, and how often the
    leaf operations and the busy union of a chip are built."""
    opened, built = [], []
    real_open = open

    def counting_open(path, *a, **k):
        opened.append(str(path))
        return real_open(path, *a, **k)

    def counting(cls, method, kept, label):
        real = getattr(cls, method)

        def call(self, chip):
            if chip not in getattr(self, kept):
                built.append((label, chip))
            return real(self, chip)
        monkeypatch.setattr(cls, method, call)

    monkeypatch.setattr(program_trace, "open", counting_open, raising=False)
    counting(trace_reduce.Trace, "leaf_ops", "_leaf", "leaf_ops")
    counting(trace_reduce.Trace, "busy", "_busy", "busy")
    counting(program_trace.ProgramTrace, "leaf_ops", "_leaf",
             "program leaf_ops")
    trace, cell = golden.run_of("program_trace_serve", tmp_path)
    counters = golden.COUNTERS["serve"]
    for _ in range(2):
        for m in golden.READERS:
            golden.read(m, trace, counters, cell)
        trace.busy_s(), trace.top_ops(10), trace.idle_gaps(10)
    assert len(opened) == 1 and opened[0].endswith(".xplane.pb")
    assert sorted(built) == [("busy", 0), ("leaf_ops", 0),
                             ("program leaf_ops", 0)]
    # the split of the idle time and of the decode program: once each
    assert {"engine_idle_parts", "decode_split_ms"} <= set(
        program_trace.of(cell).memo)
