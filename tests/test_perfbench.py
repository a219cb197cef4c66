"""perfbench unit tests: the statistical policy (median/IQR/spread
gate, including the structural withhold path), the versioned record
schema (round-trip through the trajectory store, rejection of malformed
lines and of the null-metric failure mode it exists to forbid),
last_good carry-forward selection, and seeded regression detection
through both trajectory.diff and the tools/benchdiff.py CLI (which must
exit nonzero on a >=10% synthetic regression — the CI contract)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from distributed_pytorch_tpu.perfbench import (  # noqa: E402
    errors, record, stats, trajectory)


# ---------------------------------------------------------------------------
# stats: median / IQR / spread-gate math
# ---------------------------------------------------------------------------


def test_summarize_median_iqr_exact():
    st = stats.summarize([10.0, 20.0, 30.0, 40.0, 50.0], warmup=0,
                         max_spread=10.0)
    assert st.median == 30.0
    assert st.q25 == 20.0 and st.q75 == 40.0
    assert st.iqr == 20.0
    assert st.spread_frac == pytest.approx(20.0 / 30.0)
    assert st.range_frac == pytest.approx(40.0 / 30.0)
    assert st.n == 5


def test_summarize_warmup_discard_excludes_cold_trial():
    # the r05 artifact shape: cold 621.6, warm ~900
    st = stats.summarize([621.6, 900.0, 905.0, 895.0, 902.0], warmup=1,
                         max_spread=0.15)
    assert st.warmup_discarded == (621.6,)
    assert 621.6 not in st.runs
    assert st.trusted
    assert st.median == pytest.approx(901.0)


def test_summarize_never_discards_everything():
    st = stats.summarize([100.0, 101.0], warmup=5, max_spread=0.15)
    assert st.runs == (101.0,)          # warmup capped at len-1
    assert st.warmup_discarded == (100.0,)
    assert not st.trusted               # 1 < MIN_TRUSTED_TRIALS
    assert "too few trials" in st.untrusted_reason


def test_spread_gate_marks_untrusted_with_reason():
    # the r05 CPU-baseline shape: ~70% spread must fail a 15% gate
    st = stats.summarize([100.0, 60.0, 100.0, 140.0, 101.0, 170.0],
                         warmup=1, max_spread=0.15)
    assert not st.trusted
    assert "exceeds gate" in st.untrusted_reason
    quiet = stats.summarize([100.0, 99.0, 101.0, 100.5], warmup=0,
                            max_spread=0.15)
    assert quiet.trusted and quiet.untrusted_reason is None


def test_summarize_empty_raises():
    with pytest.raises(ValueError):
        stats.summarize([])


def test_measure_runs_warmup_plus_trials():
    calls = []

    def thunk():
        calls.append(1)
        return 100.0 + len(calls)  # slight monotone drift, tiny spread

    st = stats.measure(thunk, trials=4, warmup=2, max_spread=0.15)
    assert len(calls) == 6
    assert len(st.warmup_discarded) == 2 and st.n == 4
    assert st.trusted


def test_measure_until_ages_out_mode_switch():
    """A contention mode switch early in the run must age out of the
    sliding window: the first full window straddles both modes (fails
    the gate), later windows sit entirely in the quiet mode."""
    seq = iter([500.0, 200.0, 210.0, 100.0, 101.0, 99.0, 100.5, 100.2])
    st = stats.measure_until(lambda: next(seq), trials=4, warmup=1,
                             max_spread=0.15, budget_s=60.0)
    assert st.trusted
    # first window (200, 210, 100, 101) straddles the modes and fails;
    # one more sample ages 200 out and the window converges
    assert st.runs == (100.0, 101.0, 99.0, 100.5)
    # everything before the converged window is visible, chronological
    assert st.warmup_discarded == (500.0, 200.0, 210.0)


def test_measure_until_budget_returns_untrusted_not_hang():
    """On a host that never goes quiet the budget bounds wall clock and
    the result is honestly untrusted — never laundered to trusted."""
    state = {"n": 0}

    def noisy():
        state["n"] += 1
        return 100.0 if state["n"] % 2 else 200.0

    st = stats.measure_until(noisy, trials=3, warmup=1, max_spread=0.15,
                             budget_s=0.2)
    assert not st.trusted
    assert "no stationary window" in st.untrusted_reason


def test_gated_ratio_withholds_on_untrusted_side():
    noisy = stats.summarize([100.0, 60.0, 140.0, 170.0], warmup=0,
                            max_spread=0.15)
    quiet = stats.summarize([100.0, 99.0, 101.0, 100.0], warmup=0,
                            max_spread=0.15)
    ratio, why = stats.gated_ratio(200.0, noisy)
    assert ratio is None and "denominator untrusted" in why
    ratio, why = stats.gated_ratio(noisy, quiet)
    assert ratio is None and "numerator untrusted" in why
    ratio, why = stats.gated_ratio(200.0, quiet)
    assert ratio == pytest.approx(2.0) and why is None
    ratio, why = stats.gated_ratio(None, quiet)
    assert ratio is None and "missing" in why


# ---------------------------------------------------------------------------
# record: schema round-trip + rejection
# ---------------------------------------------------------------------------


def _measured_record(value=0.42, metric_value=100.0, spread=0.02):
    rec = record.make_record("transformer_lm_mfu_single_chip",
                             "mfu_fraction", device="test-chip")
    rec["value"] = value
    rec["provenance"] = "measured"
    rec["trusted"] = True
    rec.pop("untrusted_reason", None)
    st = stats.summarize(
        [metric_value * (1 + spread * f) for f in (-1, -0.5, 0, 0.5, 1)],
        warmup=0, max_spread=0.15)
    rec["metrics"]["dp8_steps_per_sec"] = record.make_metric(
        None, "steps_per_sec", stats=st)
    return rec


def test_record_roundtrip_through_store(tmp_path):
    rec = _measured_record()
    assert record.validate_record(rec) == []
    store = str(tmp_path / "traj.jsonl")
    assert record.append_row(store, "bench_record", rec, ok=True,
                             wall_s=1.2)
    rows, malformed = record.iter_rows(store)
    assert malformed == []
    assert len(rows) == 1
    assert rows[0]["stage"] == "bench_record" and rows[0]["ok"] is True
    assert rows[0]["result"] == rec     # bit-identical round trip
    assert record.validate_record(rows[0]["result"]) == []


def test_validate_rejects_null_metric_value():
    """A null metric is the round-3 failure mode the schema forbids."""
    rec = _measured_record()
    rec["metrics"]["dp8_steps_per_sec"]["value"] = None
    issues = record.validate_record(rec, strict=False)
    assert any("dp8_steps_per_sec" in i and "value" in i for i in issues)
    with pytest.raises(errors.RecordInvalid) as ei:
        record.validate_record(rec)
    assert "dp8_steps_per_sec" in ei.value.field


def test_validate_unmeasured_forbids_value_requires_error():
    rec = record.make_record("m", "u")
    issues = record.validate_record(rec, strict=False)
    assert any(i.startswith("error:") for i in issues)  # must say why
    rec["error"] = "no healthy TPU backend after retries"
    assert record.validate_record(rec) == []
    rec["value"] = 0.3                  # null-ish headline smuggling
    issues = record.validate_record(rec, strict=False)
    assert any("must be ABSENT" in i for i in issues)


def test_validate_last_good_requires_source_detail():
    rec = _measured_record()
    rec["provenance"] = "last_good"
    issues = record.validate_record(rec, strict=False)
    assert any("last_good" in i for i in issues)
    rec["last_good"] = {"stage": "bench_mfu", "ts": "2026-01-01",
                        "source": "benchmarks/tpu_results.jsonl"}
    assert record.validate_record(rec) == []


def test_vs_baseline_cannot_coexist_with_withheld():
    rec = _measured_record()
    rec["vs_baseline"] = 2.0
    assert record.validate_record(rec) == []
    rec["vs_baseline_withheld"] = "also withheld??"
    issues = record.validate_record(rec, strict=False)
    assert any("must not coexist" in i for i in issues)


def test_untrusted_requires_reason():
    rec = _measured_record()
    rec["trusted"] = False
    issues = record.validate_record(rec, strict=False)
    assert any("untrusted_reason" in i for i in issues)


def test_iter_rows_surfaces_malformed_lines(tmp_path):
    store = tmp_path / "traj.jsonl"
    store.write_text('{"stage": "ok_row", "ok": true}\n'
                     'not json at all\n'
                     '[1, 2, 3]\n'
                     '\n'
                     '{"stage": "ok_row2", "ok": true}\n')
    rows, malformed = record.iter_rows(str(store))
    assert [r["stage"] for r in rows] == ["ok_row", "ok_row2"]
    assert [(n, r.split(":")[0]) for n, r in malformed] == [
        (2, "not valid JSON"), (3, "not a JSON object")]
    with pytest.raises(errors.RecordInvalid) as ei:
        record.iter_rows(str(store), strict=True)
    assert ei.value.line == 2


def test_env_fingerprint_digest_tracks_registry(monkeypatch):
    fp1 = record.env_fingerprint()
    assert "digest" in fp1 and fp1["python"]
    monkeypatch.setenv("DPX_BENCH_TRIALS", "7")
    fp2 = record.env_fingerprint()
    assert fp2["vars"]["DPX_BENCH_TRIALS"] == "7"
    assert fp2["digest"] != fp1["digest"]


# ---------------------------------------------------------------------------
# trajectory: last_good carry-forward selection
# ---------------------------------------------------------------------------


def _store(tmp_path, rows):
    p = tmp_path / "traj.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    return str(p)


def test_last_good_flagship_selection(tmp_path):
    path = _store(tmp_path, [
        # usable but older — a NEWER good row must win
        {"stage": "bench_mfu", "ok": True, "ts": "t1",
         "result": {"mfu": 0.30, "tokens_per_sec": 1000.0}},
        # retracted: never a carry-forward source
        {"stage": "bench_mfu", "ok": True, "retracted": "artifact",
         "ts": "t2", "result": {"mfu": 7.42}},
        # failed row
        {"stage": "bench_mfu", "ok": False, "ts": "t3",
         "result": {"error": "wedged"}},
        # medium arm must never leak into the flagship headline
        {"stage": "bench_mfu_medium", "ok": True, "ts": "t4",
         "result": {"mfu": 0.55}},
        # a carry-forward must never be carried forward again
        {"stage": "bench_record", "ok": True, "ts": "t5",
         "result": {"metric": "transformer_lm_mfu_single_chip",
                    "value": 0.31, "provenance": "last_good"}},
        # the winner
        {"stage": "bench_mfu", "ok": True, "ts": "t6",
         "result": {"mfu": 0.33, "tokens_per_sec": 1100.0}},
        # gate-poisoned record (roofline-implausible): never evidence
        {"stage": "bench_record", "ok": True, "ts": "t7",
         "result": {"metric": "transformer_lm_mfu_single_chip",
                    "value": 0.95, "provenance": "measured",
                    "trusted": False,
                    "untrusted_reason": "exceeds roofline ceiling"}},
        # raw row with a physically impossible MFU fraction (the r02
        # "7.42" dispatch artifact) — the universal <=1 bound rejects it
        {"stage": "bench_mfu", "ok": True, "ts": "t8",
         "result": {"mfu": 7.42, "tokens_per_sec": 9e9}},
    ])
    lg = trajectory.last_good_flagship(path)
    assert lg["mfu"] == 0.33 and lg["ts"] == "t6"
    assert lg["stage"] == "bench_mfu"
    assert lg["source"] == path    # the store actually read, verbatim


def test_last_good_empty_when_nothing_usable(tmp_path):
    path = _store(tmp_path, [
        {"stage": "bench_mfu", "ok": True, "retracted": "r",
         "result": {"mfu": 0.3}},
        {"stage": "bench_dp8", "ok": True, "result": {"steps_per_sec": 9}},
    ])
    assert trajectory.last_good_flagship(path) == {}
    assert trajectory.last_good_flagship(str(tmp_path / "missing")) == {}


# ---------------------------------------------------------------------------
# trajectory.diff: seeded regression detection
# ---------------------------------------------------------------------------


def _baseline_rows(value=100.0, spread=0.02, metric="dp8_steps_per_sec",
                   direction="higher"):
    rec = record.make_record("m", "u")
    rec.update(value=0.4, provenance="measured", trusted=True)
    rec.pop("untrusted_reason", None)
    rec["metrics"] = {metric: {
        "value": value, "unit": "steps_per_sec", "provenance": "measured",
        "direction": direction, "trusted": True,
        "spread_frac": spread,
        "trials": {"runs": [value], "median": value, "spread_frac": spread,
                   "n_trials": 5},
    }}
    return [{"stage": "bench_record", "ok": True, "ts": "t1",
             "result": rec}]


def _new_record(value, spread=0.02, metric="dp8_steps_per_sec",
                direction="higher", trusted=True):
    rec = record.make_record("m", "u")
    rec.update(value=0.4, provenance="measured", trusted=True)
    rec.pop("untrusted_reason", None)
    blob = {"value": value, "unit": "steps_per_sec",
            "provenance": "measured", "direction": direction,
            "trusted": trusted, "spread_frac": spread}
    if not trusted:
        blob["untrusted_reason"] = "spread 40% exceeds gate 15%"
    rec["metrics"] = {metric: blob}
    return rec


def test_diff_flags_significant_regression(tmp_path):
    rows = _baseline_rows(100.0, spread=0.02)
    rep = trajectory.diff(_new_record(85.0), rows, min_drop=0.10)
    assert not rep.ok and len(rep.regressions) == 1
    r = rep.regressions[0]
    assert r["metric"] == "dp8_steps_per_sec"
    assert r["baseline"] == 100.0 and r["measured"] == 85.0
    assert "BENCH REGRESSION" in rep.format()
    with pytest.raises(errors.BenchRegression) as ei:
        rep.raise_first()
    assert ei.value.metric == "dp8_steps_per_sec"
    assert ei.value.drop_frac == pytest.approx(0.15)


def test_diff_change_within_gate_is_unchanged():
    rows = _baseline_rows(100.0, spread=0.02)
    rep = trajectory.diff(_new_record(95.0), rows, min_drop=0.10)
    assert rep.ok and len(rep.unchanged) == 1
    rep = trajectory.diff(_new_record(115.0), rows, min_drop=0.10)
    assert rep.ok and len(rep.improvements) == 1


def test_diff_gate_widens_with_spread():
    """A noisy baseline widens the gate: the same 15% drop that fails a
    2%-spread baseline passes a 20%-spread one."""
    rep = trajectory.diff(_new_record(85.0),
                          _baseline_rows(100.0, spread=0.20),
                          min_drop=0.10)
    assert rep.ok and len(rep.unchanged) == 1


def test_diff_lower_is_better_direction():
    rows = _baseline_rows(100.0, metric="ckpt_save_ms", direction="lower")
    worse = _new_record(120.0, metric="ckpt_save_ms", direction="lower")
    rep = trajectory.diff(worse, rows, min_drop=0.10)
    assert not rep.ok
    better = _new_record(80.0, metric="ckpt_save_ms", direction="lower")
    rep = trajectory.diff(better, rows, min_drop=0.10)
    assert rep.ok and len(rep.improvements) == 1


def test_diff_untrusted_sides_never_produce_verdicts():
    rows = _baseline_rows(100.0)
    rep = trajectory.diff(_new_record(40.0, trusted=False), rows,
                          min_drop=0.10)
    assert rep.ok                       # a 60% "drop" on an untrusted side
    assert rep.skipped and "not comparable" in rep.skipped[0][1]
    rep = trajectory.diff(_new_record(40.0, metric="never_seen"), rows,
                          min_drop=0.10)
    assert rep.ok and "no trusted measured baseline" in rep.skipped[0][1]


def test_diff_zero_baseline_is_skipped_not_crash():
    rep = trajectory.diff(_new_record(40.0), _baseline_rows(0.0),
                          min_drop=0.10)
    assert rep.ok and "baseline value is 0" in rep.skipped[0][1]


def test_diff_malformed_blob_reason_is_not_carry_forward():
    rec = _new_record(40.0)
    rec["metrics"]["dp8_steps_per_sec"] = 123      # not a dict
    rep = trajectory.diff(rec, _baseline_rows(100.0), min_drop=0.10)
    assert rep.ok and "malformed metric blob" in rep.skipped[0][1]


def test_single_observation_blob_is_untrusted():
    """A measured blob without trials detail carries no spread — it must
    not anchor or receive regression verdicts with a zero-width gate
    (the r05 single-rep 2x-swing class)."""
    blob = record.make_metric(0.42, "mfu_fraction")
    assert blob["trusted"] is False
    assert "single observation" in blob["untrusted_reason"]
    assert record.validate_metric_blob("m", blob) == []
    # a carry-forward blob keeps the trust of its traceable source
    lg = record.make_metric(0.42, "mfu_fraction", provenance="last_good",
                            last_good={"stage": "bench_mfu", "ts": "t"})
    assert lg["trusted"] is True
    # and diff() lists the single-rep side as skipped, attributed
    rec = _new_record(100.0)
    rec["metrics"]["dp8_steps_per_sec"] = record.make_metric(
        100.0, "steps_per_sec")
    rep = trajectory.diff(rec, _baseline_rows(200.0), min_drop=0.10)
    assert rep.ok and "single observation" in rep.skipped[0][1]


# ---------------------------------------------------------------------------
# tools/benchdiff.py CLI: the CI contract
# ---------------------------------------------------------------------------


def _run_benchdiff(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.benchdiff", *args],
        capture_output=True, text=True, timeout=60, cwd=REPO)


def test_benchdiff_exits_nonzero_on_injected_regression(tmp_path):
    """The acceptance contract: a synthetic >=10% regression makes the
    CLI exit nonzero with an attributed report."""
    store = _store(tmp_path, _baseline_rows(100.0, spread=0.02))
    rec_file = tmp_path / "new.json"
    rec_file.write_text(json.dumps(_new_record(88.0)))   # -12% drop
    out = _run_benchdiff("--log", store, "--record", str(rec_file))
    assert out.returncode == 1, out.stdout + out.stderr
    assert "BENCH REGRESSION" in out.stdout
    assert "dp8_steps_per_sec" in out.stdout


def test_benchdiff_clean_and_self_diff_exit_zero(tmp_path):
    store = _store(tmp_path, _baseline_rows(100.0, spread=0.02))
    rec_file = tmp_path / "new.json"
    rec_file.write_text(json.dumps(_new_record(101.0)))
    out = _run_benchdiff("--log", store, "--record", str(rec_file))
    assert out.returncode == 0, out.stdout + out.stderr
    # no --record: newest stored schema record vs the rows before it
    rows = (_baseline_rows(100.0)
            + [{"stage": "bench_record", "ok": True, "ts": "t2",
                "result": _new_record(99.0)}])
    out = _run_benchdiff("--log", _store(tmp_path, rows))
    assert out.returncode == 0, out.stdout + out.stderr
    assert json.loads(out.stdout.strip().splitlines()[-1])["unchanged"] == 1


def test_diff_anchors_on_ok_false_record_metrics(tmp_path):
    """Row-level ok gates only the last_good carry-forward. A record
    whose flagship was unmeasured logs ok=false, but its trusted
    measured metrics must still anchor baselines AND be selected as the
    new side in store mode — otherwise benchdiff is vacuous on a
    TPU-less container."""
    base = _baseline_rows(100.0, spread=0.02)
    base[0]["ok"] = False                      # unmeasured flagship
    series = trajectory.metric_series(base)
    assert series["dp8_steps_per_sec"][0]["value"] == 100.0

    rows = base + [{"stage": "bench_record", "ok": False, "ts": "t2",
                    "result": _new_record(85.0)}]   # -15% drop
    out = _run_benchdiff("--log", _store(tmp_path, rows),
                         "--min-drop", "0.10")
    assert out.returncode == 1, out.stdout + out.stderr
    assert "BENCH REGRESSION" in out.stdout


def test_benchdiff_strict_rejects_corrupt_store(tmp_path):
    store = tmp_path / "traj.jsonl"
    store.write_text(json.dumps(_baseline_rows(100.0)[0]) + "\n"
                     + "CORRUPT LINE\n")
    out = _run_benchdiff("--log", str(store), "--strict")
    assert out.returncode == 2
    assert "line 2" in out.stderr
    # non-strict: skipped with a comment, diff proceeds
    rec_file = tmp_path / "new.json"
    rec_file.write_text(json.dumps(_new_record(101.0)))
    out = _run_benchdiff("--log", str(store), "--record", str(rec_file))
    assert out.returncode == 0
    assert "malformed store line 2" in out.stderr


def test_benchdiff_record_mode_excludes_its_own_store_row(tmp_path):
    """bench.py self-logs its record by default — --record mode must not
    diff the record against its own store row (0% forever)."""
    new = _new_record(85.0)                            # -15% vs 100
    rows = _baseline_rows(100.0, spread=0.02) \
        + [{"stage": "bench_record", "ok": True, "ts": "t2",
            "result": new}]
    rec_file = tmp_path / "new.json"
    rec_file.write_text(json.dumps(new))
    out = _run_benchdiff("--log", _store(tmp_path, rows),
                         "--record", str(rec_file), "--min-drop", "0.10")
    assert out.returncode == 1, out.stdout + out.stderr
    assert "BENCH REGRESSION" in out.stdout


def test_report_reader_stays_jax_free(tmp_path):
    """report.py reads the store without importing jax or the package
    (private file-based load — the real package __init__ pulls jax), so
    it renders a log on a machine that has neither."""
    _store(tmp_path, _baseline_rows(100.0))
    code = (
        "import sys; sys.path.insert(0, %r); sys.path.insert(0, %r)\n"
        "import report\n"
        "rows, mal = report.load_rows_checked(%r)\n"
        "assert len(rows) == 1 and not mal\n"
        "assert report.newest_schema_record(rows) is not None\n"
        "assert 'jax' not in sys.modules, 'report pulled jax'\n"
        "assert 'distributed_pytorch_tpu' not in sys.modules, "
        "'report imported (or shadowed) the real package'\n"
        % (REPO, os.path.join(REPO, "benchmarks"),
           str(tmp_path / "traj.jsonl")))
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr


def test_benchdiff_empty_store_is_not_a_failure(tmp_path):
    out = _run_benchdiff("--log", str(tmp_path / "missing.jsonl"))
    assert out.returncode == 0
    assert "nothing to compare" in out.stdout
