"""The driver-facing contracts: bench.py's stage/error plumbing (the
parseable-JSON-on-failure promise) and the __graft_entry__ compile
check. No chip needed."""

import json
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def test_unknown_stage_emits_json_and_rc2():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--stage", "nope"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 2
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert "error" in rec


def test_import_failure_rc0_record_but_smoke_gate_fails():
    """A perfbench import failure keeps the parseable-error-record
    contract (rc 0) for the collector — but under --smoke, which is a
    CI GATE, it must exit nonzero: a gate whose assertions never ran
    must not pass green."""
    sabotage = ("import sys, runpy; sys.argv = ['bench.py'%s]; "
                "sys.modules['distributed_pytorch_tpu.perfbench'] = None; "
                "runpy.run_path(%r, run_name='__main__')")
    bench_py = os.path.join(REPO, "bench.py")
    out = subprocess.run(
        [sys.executable, "-c", sabotage % (", '--smoke'", bench_py)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "perfbench import failed" in out.stdout
    out = subprocess.run(
        [sys.executable, "-c", sabotage % ("", bench_py)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert "perfbench import failed" in rec["error"]
    # a LIBRARY importer must see the real ImportError, not an rc-0
    # process exit behind a flagship-metric error line
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); "
         "sys.modules['distributed_pytorch_tpu.perfbench'] = None; "
         "import bench" % REPO],
        capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
    assert ("ImportError" in out.stderr
            or "ModuleNotFoundError" in out.stderr)


def test_run_stage_parses_last_json_line(monkeypatch):
    """_run_stage must survive noisy stdout and take the last JSON line."""
    def fake_run(argv, **kw):
        class R:
            returncode = 0
            stdout = "warning: blah\n{\"x\": 1}\n"
            stderr = ""
        return R()

    monkeypatch.setattr(subprocess, "run", fake_run)
    assert bench._run_stage("mfu", timeout_s=5) == {"x": 1}


def test_nonzero_exit_keeps_printed_record(monkeypatch):
    """A stage that prints its record then exits nonzero (failed numerics
    validation) must keep its measurements, marked with error + rc."""
    def fake_run(argv, **kw):
        class R:
            returncode = 2
            stdout = '{"numerics_ok": false, "rows": [1, 2]}\n'
            stderr = ""
        return R()

    monkeypatch.setattr(subprocess, "run", fake_run)
    rec = bench.run_json_subprocess(["x"], 5, label="flash")
    assert rec["rows"] == [1, 2]
    assert rec["rc"] == 2 and "error" in rec


def test_run_stage_failure_yields_error_record(monkeypatch):
    def fake_run(argv, **kw):
        class R:
            returncode = 1
            stdout = ""
            stderr = "boom\n"
        return R()

    monkeypatch.setattr(subprocess, "run", fake_run)
    rec = bench._run_stage("mfu", timeout_s=5)
    assert "boom" in rec["error"]


def test_run_stage_timeout_yields_error_record(monkeypatch):
    def fake_run(argv, **kw):
        raise subprocess.TimeoutExpired(argv, kw.get("timeout"))

    monkeypatch.setattr(subprocess, "run", fake_run)
    rec = bench._run_stage("mfu", timeout_s=5)
    assert "timed out" in rec["error"]


def test_probe_requires_tpu_platform(monkeypatch):
    """A CPU fallback must not count as a healthy backend (it would run
    the flagship bench on the host in interpret-mode pallas)."""
    def fake_run(argv, **kw):
        class R:
            returncode = 0
            stdout = '{"platform": "cpu", "kind": "cpu"}\n'
            stderr = ""
        return R()

    monkeypatch.setattr(subprocess, "run", fake_run)
    assert bench.probe_backend() == {}


def test_append_and_last_good_roundtrip(tmp_path, monkeypatch):
    """append_result writes the store's row shape; last_good_record
    surfaces the newest non-retracted FLAGSHIP record only — never the
    medium arm, never a retracted row."""
    log = tmp_path / "results.jsonl"
    monkeypatch.setattr(bench, "RESULTS_LOG", str(log))

    assert bench.last_good_record() == {}  # no log yet

    bench.append_result("bench_mfu", {"mfu": 0.40, "device": "d",
                                      "tokens_per_sec": 1.0})
    bench.append_result("bench_mfu_medium", {"mfu": 0.55, "device": "d"})
    bench.append_result("bench_mfu", {"error": "wedged"})  # ok=False
    rows = [json.loads(l) for l in log.read_text().splitlines()]
    assert [r["ok"] for r in rows] == [True, True, False]
    # the store's row shape, written through the thread-safe
    # append_event path (which stamps event/time on every line)
    assert all(set(r) >= {"stage", "ok", "wall_s", "result", "ts"}
               for r in rows)
    assert all(r["event"] == "bench_row" for r in rows)

    lg = bench.last_good_record()
    assert lg["mfu"] == 0.40 and lg["stage"] == "bench_mfu"

    # a composite headline row supersedes it; a retracted one never does
    bench.append_result("bench_headline",
                        {"metric": "transformer_lm_mfu_single_chip",
                         "value": 0.45, "unit": "mfu_fraction"})
    with open(log, "a") as f:
        f.write(json.dumps({"stage": "bench_headline", "ok": True,
                            "retracted": True,
                            "result": {"metric":
                                       "transformer_lm_mfu_single_chip",
                                       "value": 7.42}}) + "\n")
    lg = bench.last_good_record()
    assert lg["mfu"] == 0.45
    assert lg["source"] == str(log)    # the store actually read


def test_report_renders_latest_nonretracted(tmp_path):
    """benchmarks/report.py: newest ok row per stage wins; retracted rows
    appear only in the audit trail."""
    from benchmarks import report

    log = tmp_path / "log.jsonl"
    rows = [
        {"stage": "bench_mfu", "ok": True, "ts": "T1",
         "result": {"mfu": 0.30, "tokens_per_sec": 1.0,
                    "step_ms_median": 1.0, "config": {}}},
        {"stage": "bench_mfu", "ok": True, "ts": "T2",
         "result": {"mfu": 0.42, "tokens_per_sec": 2.0,
                    "step_ms_median": 1.0,
                    "achieved_tflops_per_sec": 82.7,
                    "peak_bf16_tflops": 197.0,
                    "config": {"batch": 8, "seq": 1024}}},
        {"stage": "bench_mfu", "ok": False, "ts": "T3",
         "result": {"error": "wedged"}},
        {"stage": "old", "ok": True, "retracted": True,
         "reason": "dispatch-rate artifact", "result": {"mfu": 7.4}},
    ]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")

    loaded = report.load_rows(str(log))
    live = report.latest_per_stage(loaded)
    assert set(live) == {"bench_mfu"}
    assert live["bench_mfu"]["result"]["mfu"] == 0.42

    md = report.render(loaded)
    assert "0.42" in md and "7.4" not in md.split("Retracted")[0]
    assert "dispatch-rate artifact" in md


def test_sweep_arm_error_rows_get_footnote_marker(tmp_path):
    """Arms that exited nonzero after printing a record (arm_error/
    arm_rc) must be visibly annotated in the rendered sweep table, not
    indistinguishable from clean measurements."""
    from benchmarks import report

    log = tmp_path / "log.jsonl"
    row = {"stage": "mfu_sweep", "ok": True, "ts": "T1", "result": {
        "sweep": [
            {"arm": {"batch": 8}, "mfu": 0.4, "tokens_per_sec": 2.0,
             "step_ms_median": 1.0},
            {"arm": {"batch": 16}, "mfu": 0.5, "tokens_per_sec": 3.0,
             "step_ms_median": 1.0, "arm_error": "rc 1", "arm_rc": 1},
            {"arm": {"batch": 64}, "error": "OOM"},
        ]}}
    log.write_text(json.dumps(row) + "\n")
    md = report.render(report.load_rows(str(log)))
    clean = next(l for l in md.splitlines() if '"batch": 8' in l
                 and l.startswith("|"))
    suspect = next(l for l in md.splitlines() if '"batch": 16' in l
                   and l.startswith("|"))
    assert "†" not in clean
    assert "†" in suspect
    # the footnote explains the marker and carries the rc + error
    assert "exited nonzero after printing its record" in md
    assert "rc 1" in md
    # genuinely failed arms keep their separate failure list
    assert "OOM" in md


def test_retraction_reasons_not_cut_mid_word(tmp_path):
    """Retraction reasons around ~120 chars must render IN FULL;
    reasons past the cap truncate at a word boundary with an
    ellipsis."""
    from benchmarks import report

    medium = ("retracted: the measured step time was collected with a "
              "timing that did not wait for the device and overstates "
              "throughput by a wide margin")
    assert 100 < len(medium) <= 200
    long = "word " * 60  # 300 chars, > cap
    log = tmp_path / "log.jsonl"
    log.write_text("\n".join(json.dumps(r) for r in [
        {"stage": "bench_mfu", "ok": True, "retracted": True, "ts": "T1",
         "reason": medium},
        {"stage": "mfu_long", "ok": True, "retracted": True, "ts": "T2",
         "reason": long.strip()},
    ]) + "\n")
    md = report.render(report.load_rows(str(log)))
    assert medium in md                      # no truncation at ~120
    cut = next(l for l in md.splitlines() if "mfu_long" in l)
    assert cut.endswith("…")
    body = cut.split("): ", 1)[1][:-1]       # drop the ellipsis
    assert long.startswith(body + " ")       # word-boundary cut


def test_sweep_arm_isolation():
    """--sweep subprocess mode: arms round-trip to CLI flags and run as
    per-arm subprocesses whose records are collected; an arm that dies
    or hangs costs that arm only."""
    import pytest as _pytest

    from benchmarks import mfu_transformer as mt

    # every arm flag is explicit on/off — an absent flag would pick up
    # the FLAGSHIP default in the child after a flagship promotion
    assert mt._arm_argv({"batch": 32, "fused_ce": True}) == \
        ["--batch", "32", "--fused-ce", "--no-remat", "--no-master-f32"]
    assert mt._arm_argv({"remat": True, "master_f32": True}) == \
        ["--no-fused-ce", "--remat", "--master-f32"]
    with _pytest.raises(ValueError):
        mt._arm_argv({"batch": 8, "dtype": "f32"})  # no CLI mapping
    # the child CLI round-trips the explicit negatives to False and the
    # positives to True (tristate: absent defers to FLAGSHIP)
    assert mt._tristate(["--fused-ce"], "--fused-ce") is True
    assert mt._tristate(["--no-fused-ce"], "--fused-ce") is False
    assert mt._tristate([], "--fused-ce") is None

    calls = {"sub": []}

    def fake_sub(argv, timeout_s, **kw):
        calls["sub"].append(argv)
        n = len(calls["sub"])
        if n == 2:   # record printed, then nonzero exit
            return {"mfu": 0.5, "tokens_per_sec": 2.0,
                    "step_ms_median": 1.0, "error": "rc 1", "rc": 1}
        if n == 3:   # hung arm: timeout with kept phase lines
            return {"error": "sweep arm timed out after 900s",
                    "stdout_tail": "# mfu phase: warm; timing"}
        return {"mfu": 0.4, "tokens_per_sec": 1.0, "step_ms_median": 2.0}

    import bench as bench_mod
    orig = bench_mod.run_json_subprocess
    bench_mod.run_json_subprocess = fake_sub
    try:
        out = mt.sweep(arms=[dict(batch=8), dict(batch=16),
                             dict(dtype="f32"),  # no CLI mapping
                             dict(batch=32), dict(batch=64)],
                       steps=7, isolate=True)
    finally:
        bench_mod.run_json_subprocess = orig
    assert len(calls["sub"]) == 4  # bad arm skipped
    assert all("--steps" in a and "7" in a for a in calls["sub"])
    sw = out["sweep"]
    assert sw[0]["mfu"] == 0.4
    # nonzero-exit-with-record: measurements kept, error surfaced on the
    # arm row, NOT on the top-level record (a top-level "error" would
    # fail the whole stage in the collector)
    assert sw[1]["mfu"] == 0.5 and sw[1]["arm_error"] == "rc 1"
    assert out["mfu"] == 0.5 and "error" not in out
    # unmappable arm recorded and skipped, sweep continues
    assert "no CLI mapping" in sw[2]["error"]
    # hung arm keeps the child's phase lines for hang diagnosis
    assert "mfu phase" in sw[3]["stdout_tail"]
    # and the sweep goes on to the next arm
    assert sw[4]["mfu"] == 0.4


def test_roofline_floors_and_measured_wiring():
    """The analytic roofline: flagship is compute-bound on v5e (this is
    the 'not memory-bound, the gap is attackable' claim), ceilings are
    sane, and the measured-row join takes the newest
    non-retracted ok row."""
    from benchmarks import roofline
    from benchmarks.mfu_transformer import FLAGSHIP

    a = roofline.analyze(FLAGSHIP)
    assert a["bound"] == "compute"
    assert a["compute_floor_ms"] > a["hbm_floor_ms"]
    assert 0 < a["mfu_ceiling_no_overlap"] < a["mfu_ceiling"] <= 1.0
    # fused-CE removes the logits item entirely
    af = roofline.analyze(FLAGSHIP, fused_ce=True)
    assert af["hbm_items_gb"]["logits_f32"] == 0.0
    assert af["hbm_gb_per_step"] < a["hbm_gb_per_step"]
    # param count agrees with the live model to within norm/bias noise
    assert abs(a["n_params"] - 135e6) / 135e6 < 0.02

    rows = [
        {"stage": "bench_mfu", "ok": True,
         "result": {"step_ms_median": 99.0}},
        {"stage": "bench_mfu", "ok": True,
         "result": {"step_ms_median": 76.3}},
        {"stage": "bench_mfu", "ok": False,
         "result": {"step_ms_median": 1.0}},
        {"stage": "bench_mfu", "ok": True, "retracted": True,
         "result": {"step_ms_median": 2.0}},
    ]
    assert roofline.measured_step_ms(rows, "bench_mfu") == 76.3
    assert roofline.measured_step_ms(rows, "mfu_mid") is None
    # a NEWER ok row without a step time must yield None, not silently
    # fall back to the stale 76.3 (keeps roofline consistent with
    # report.latest_per_stage about which measurement is current)
    rows.append({"stage": "bench_mfu", "ok": True,
                 "result": {"error": "partial"}})
    assert roofline.measured_step_ms(rows, "bench_mfu") is None


def test_roofline_device_kinds_mirror_peak_table():
    """Every device kind PEAK_BF16 knows must analyze cleanly (v2/v3/v5
    used to raise a bare KeyError on the HBM lookup),
    and an unknown kind gets an EXPLICIT unsupported error."""
    import pytest
    from benchmarks import roofline
    from benchmarks.mfu_transformer import FLAGSHIP, PEAK_BF16

    assert set(roofline.HBM_GBPS) == set(PEAK_BF16)
    for kind in PEAK_BF16:
        a = roofline.analyze(FLAGSHIP, device_kind=kind)
        assert a["hbm_floor_ms"] > 0 and a["compute_floor_ms"] > 0
    with pytest.raises(ValueError, match="unsupported device_kind"):
        roofline.analyze(FLAGSHIP, device_kind="TPU v99")


def test_mfu_record_schema_contract():
    """The keys every consumer joins on (collector ok-gate, report
    tables, roofline measured-join, sweep best-arm pick) — a tiny
    in-process run must produce them all with sane values."""
    from benchmarks.mfu_transformer import run

    rec = run(dim=64, n_layers=1, n_heads=2, vocab=128, seq=128,
              batch=2, steps=2, use_flash=False)
    for key in ("device", "platform", "config", "n_params",
                "step_ms_median", "per_step_fetch_fenced_ms_median",
                "tokens_per_sec", "model_tflops_per_step",
                "achieved_tflops_per_sec", "mfu", "mfu_hw",
                "timing_method", "steps_timed"):
        assert key in rec, key
    assert rec["step_ms_median"] > 0 and rec["tokens_per_sec"] > 0
    assert rec["timing_method"] == "amortized_chain_fetch_fence"
    cfg = rec["config"]
    for key in ("dim", "batch", "seq", "attention", "remat", "fused_ce",
                "optimizer"):
        assert key in cfg, key
    assert cfg["attention"] == "dense"  # use_flash=False
    # error-free record: the collector's ok-gate is "error" not in rec
    assert "error" not in rec


def test_attach_roofline_on_headline_record():
    """The headline record carries the analytic floors, and the
    efficiency gap is computed only when a measured step exists."""
    rec = {"mfu_detail": {"step_ms_median": 76.3}}
    bench.attach_roofline(rec)
    rl = rec["roofline_flagship"]
    assert rl["bound"] == "compute"
    assert rl["measured_step_ms"] == 76.3
    assert rl["efficiency_gap_x"] == round(
        76.3 / rl["compute_floor_ms"], 2)
    assert "warnings" not in rec

    bare = {}
    bench.attach_roofline(bare)
    assert "efficiency_gap_x" not in bare["roofline_flagship"]
    assert bare["roofline_flagship"]["compute_floor_ms"] > 0


def test_graft_entry_compiles_single_device():
    """entry() must stay jittable — the driver compile-checks it."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(REPO, "__graft_entry__.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn, args = mod.entry()
    out = jax.jit(fn).lower(*args).compile()
    assert out is not None
