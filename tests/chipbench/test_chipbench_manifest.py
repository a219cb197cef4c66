"""``BENCHMARK.json`` against the contract's form and the files it names."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= manifest["run_seconds"] <= 51
    assert isinstance(manifest["run_seconds"], int)
    cells = len(manifest["workloads"])
    # a full check with the full 24 cells must fit into 43200 s
    s = manifest["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert 1 <= cells <= 24


def test_names_and_units(manifest):
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[section]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((section in ("end_to_end", "per_layer"),
                          entry["name"]))
    assert len(names) == len(set(names)), "a name is used twice"
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200


def test_files_exist(manifest):
    bench = os.path.join(REPO, manifest["paths"][0])
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert c["name"] in used, f"{c['name']} is used by no cell"
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(
            bench, "reference", cfg["family"] + ".py"))
        assert os.path.exists(os.path.join(
            bench, "adapters", cfg["family"] + ".py"))
    pairs = set()
    for w in manifest["workloads"]:
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        with open(os.path.join(bench, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert os.path.exists(os.path.join(bench, "kinds",
                                           mix["kind"] + ".py"))
        # a traced part is bounded in seconds, and where the pace is the
        # host's and moves by factors (an engine loop), in work as well
        assert mix["trace_seconds"] > 0
        if mix["kind"] == "serve":
            assert mix["trace_iterations"] >= 1
        with open(os.path.join(bench, "limits", w["name"] + ".json")) as f:
            for name, entry in json.load(f).items():
                assert entry["limit"] >= 0 and entry["why"], name
    for m in manifest["per_layer"]:
        assert os.path.exists(os.path.join(bench, "layer_metrics",
                                           m["name"] + ".py")), m["name"]


def test_mixes_say_what_a_traced_part_holds_and_jobs_their_schedule(
        manifest):
    """A serving mix names how many of the window's last arrivals its
    traced part has to hold (without it the readers of an admission and
    of a prefill read nothing in a fast engine, as from PR 29 to PR 40);
    a training job may name a warm-up, with the peak, the steps and where
    they come from."""
    bench = os.path.join(REPO, manifest["paths"][0])
    for w in manifest["workloads"]:
        with open(os.path.join(bench, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        if mix["kind"].startswith("serve"):
            assert isinstance(mix["trace_admissions"], int)
            assert 1 <= mix["trace_admissions"] <= 8
            # the schedule holds that many arrivals inside the cap often
            # enough to be worth asking for: at least one a second
            assert mix["rate_per_s"] * mix["trace_seconds"] \
                >= mix["trace_admissions"]
        else:
            assert "trace_admissions" not in mix
            o = mix["optimizer"]
            assert set(o) <= {"name", "lr", "warmup_steps", "b1", "b2",
                              "eps", "weight_decay"}
            if "warmup_steps" in o:
                assert isinstance(o["warmup_steps"], int)
                assert o["warmup_steps"] >= 1 and o["lr"] > 0
                assert "arXiv" in mix["warmup_why"]


def test_a_training_cell_reports_its_rate_under_one_metric(manifest):
    """A bound belongs to a metric: the dense job's rate (its six-seed
    sets spread by 0.0012 %) keeps ``train_tokens_per_s`` and its 1 %; a
    job of kind ``train_mtp`` names the metric its rate stands under
    (``rate_metric``), its cell is listed there and under no other rate,
    and every reader it shares with the dense cell moves that metric
    under a second name (``chipbench/reader_alias.py``)."""
    bench = os.path.join(REPO, manifest["paths"][0])
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    rates = [n for n in e2e if n.startswith("train_tokens_per_s")]
    assert e2e["train_tokens_per_s"]["bound"] == 0.01
    for w in manifest["workloads"]:
        with open(os.path.join(bench, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        if mix["kind"].startswith("serve"):
            assert not any(w["name"] in e2e[n]["workloads"] for n in rates)
            continue
        rate = mix["rate_metric"] if mix["kind"] == "train_mtp" \
            else "train_tokens_per_s"
        assert "rate_metric" in mix or mix["kind"] == "train"
        assert [n for n in rates if w["name"] in e2e[n]["workloads"]] \
            == [rate], w["name"]
        assert e2e[rate]["unit"] == "tokens/s"
        assert e2e[rate]["better"] == "higher"
        suffix = rate[len("train_tokens_per_s"):]
        mine = [m for m in manifest["per_layer"]
                if w["name"] in m["workloads"]]
        assert mine and all(m["moves"] == rate for m in mine)
        assert all(m["name"].endswith(suffix) for m in mine)
        if suffix:
            by_name = {m["name"]: m for m in manifest["per_layer"]}
            for m in mine:
                first = by_name[m["name"][:-len(suffix)]]
                assert (first["unit"], first["better"], first["source"],
                        first["layer"]) == (m["unit"], m["better"],
                                            m["source"], m["layer"])
                assert first["moves"] == "train_tokens_per_s"
                with open(os.path.join(bench, "layer_metrics",
                                       m["name"] + ".py")) as f:
                    assert "reader_alias.same_as(__file__)" in f.read()


def test_each_layer_metric_moves_one_reported_metric(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells)
           for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in next(
        m for m in manifest["end_to_end"] if m["name"] == "setup_s")
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:
        assert sum(cell in ws for n, ws in e2e.items() if n != "setup_s") >= 1
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"])


def test_at_most_one_four_chip_cell(manifest):
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
