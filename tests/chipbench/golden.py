"""Every per-layer reader, the device record and the ``breakdown`` over
the recorded traces, as ``run.py`` would get them: what
``golden_readings.json`` holds, written down from the code as it stood
before PR 26 touched the parsers (``python tests/chipbench/golden.py``
writes the file anew; do that only where a reader is meant to change).

The four ``.xplane.pb`` excerpts go through ``trace_reduce.load`` and sit
under a cell's ``out_dir`` for ``program_trace.of``;
``recorded_trace.json`` goes through ``trace_reduce.from_records`` and has
no file (the readers built on ``program_trace`` then return nothing).
Counters and cells are fixed stand-ins for what a run hands a reader."""

import importlib.util
import json
import os
import shutil
import types

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "chipbench")
GOLDEN = os.path.join(HERE, "golden_readings.json")
FIXTURES = {"program_trace_serve": "serve", "program_trace_train": "train",
            "parent_trace_serve": "serve", "parent_trace_train": "train",
            "recorded_trace": "train"}


def _stands_for_another(file):
    with open(os.path.join(BENCH, "layer_metrics", file)) as f:
        return "reader_alias.same_as" in f.read()


_FILES = sorted(f for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
                if f.endswith(".py"))
#: a reader under a second name (``chipbench/reader_alias.py``) reads what
#: the first reads; the golden file holds each reader once
ALIASES = [f[:-3] for f in _FILES if _stands_for_another(f)]
READERS = [f[:-3] for f in _FILES if f[:-3] not in ALIASES]
COUNTERS = {
    "train": {"compiles_in_window": 0, "tokens_per_s": 19528.5,
              "traced_tokens_per_s": 19520.25, "steps": 121,
              "traced_steps": 2, "tokens_per_step": 8192, "seq": 1024,
              "rows_per_chip": 8},
    "serve": {"compiles_in_window": 0, "iterations": 451,
              "tokens_emitted": 13936, "queue_depth": (0, 0),
              "active_slots": (31, 33), "window_tokens_per_s": 273.25,
              "ttft_p50_ms": 170.5, "ttft_p95_ms": 262.125}}
CELLS = {"train": ("gpt2-xl-1chip", "pretrain-1k"),
         "serve": ("starcoder2-3b", "code-decode")}


def _json(name):
    with open(os.path.join(BENCH, name)) as f:
        return json.load(f)


def run_of(fixture, tmp):
    """(trace, cell) for a fixture; ``tmp`` is an empty directory."""
    from chipbench import trace_reduce

    kind = FIXTURES[fixture]
    config, mix = CELLS[kind]
    cell = types.SimpleNamespace(
        out_dir=str(tmp), name=f"golden-{kind}", chips=1,
        config=_json(f"configs/{config}.json"),
        traffic=_json(f"traffic/{mix}.json"),
        peaks=_json("peaks.json")["TPU v5 lite"])
    if fixture == "recorded_trace":
        with open(os.path.join(HERE, fixture + ".json")) as f:
            return trace_reduce.from_records(json.load(f)), cell
    where = os.path.join(str(tmp), "trace", "plugins", "profile", "x")
    os.makedirs(where)
    path = shutil.copy(os.path.join(HERE, fixture + ".xplane.pb"), where)
    return trace_reduce.load(path, 1), cell


def read(metric, trace, counters, cell):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_golden",
        os.path.join(BENCH, "layer_metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(trace, counters, cell)
    return None if value is None else float(value)


def readings(fixture, tmp):
    """{reader or ``device.*`` / ``breakdown.*`` key: value}, through JSON
    so that tuples and lists compare alike."""
    trace, cell = run_of(fixture, tmp)
    counters = COUNTERS[FIXTURES[fixture]]
    out = {m: read(m, trace, counters, cell) for m in READERS}
    out["device.busy_s"] = trace.busy_s()
    out["device.window_s"] = trace.window_s()
    out["breakdown.device_ops"] = trace.top_ops(10)
    out["breakdown.idle_gaps"] = trace.idle_gaps(10)
    return json.loads(json.dumps(out))


if __name__ == "__main__":
    import sys
    import tempfile

    sys.path.insert(0, REPO)
    with tempfile.TemporaryDirectory() as tmp:
        got = {}
        for fx in FIXTURES:
            os.makedirs(os.path.join(tmp, fx))
            got[fx] = readings(fx, os.path.join(tmp, fx))
    with open(GOLDEN, "w") as f:
        json.dump(got, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {GOLDEN}: " + ", ".join(
        f"{fx} {sum(v is not None for v in r.values())}/{len(r)}"
        for fx, r in got.items()))
