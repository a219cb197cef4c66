"""dpxtrace observability (obs/) — acceptance + units (ISSUE 14).

The headline contracts: (1) a world-4 chaos run (kill@op=allreduce)
produces a MERGED Chrome trace that parses, with spans from EVERY rank,
and the injected failure's flight-recorder dump names the dying op on
every survivor; (2) a disaggregated serve request shows ONE trace_id
spanning prefill→handoff→decode, with span durations summing exactly to
the TTFT decomposition ``serve/metrics.py`` asserts; (3) the flight
recorder ring wraps with drop ACCOUNTING (never silent loss); (4)
``utils.logging`` event timestamps are monotone non-decreasing even
when the system clock steps backwards (the perf_counter_ns + wall
anchor satellite).
"""

import json
import multiprocessing as mp
import threading
import time

import numpy as np
import pytest

from distributed_pytorch_tpu.obs import detect, export, trace
from distributed_pytorch_tpu.runtime import faults
from distributed_pytorch_tpu.runtime.multiprocess import launch_multiprocess
from distributed_pytorch_tpu.runtime.watchdog import WorkerFailure
from distributed_pytorch_tpu.serve.metrics import aggregate, percentile
from distributed_pytorch_tpu.utils import logging as dpxlog

TIMEOUT_MS = 2000  # per-op deadline for the chaos run


@pytest.fixture(autouse=True)
def _clean_trace():
    """Every test starts and ends with pristine tracing state (the
    module is process-global) and no leftover fault specs."""
    trace.reset()
    faults.reset()
    yield
    trace.reset()
    faults.reset()


def _enable(tmp_path, ring=256):
    log = tmp_path / "trace.jsonl"
    trace.configure(enabled=True, ring=ring, log_path=str(log))
    return log


# ---------------------------------------------------------------------------
# span core
# ---------------------------------------------------------------------------


class TestSpanCore:
    def test_disabled_span_records_nothing(self, tmp_path):
        log = tmp_path / "t.jsonl"
        trace.configure(enabled=False, log_path=str(log))
        with trace.span("x", a=1):
            pass
        spans, dropped = trace.flight_snapshot()
        assert spans == [] and dropped == 0
        assert not log.exists()

    def test_span_nesting_and_lineage(self, tmp_path):
        log = _enable(tmp_path)
        with trace.span("outer", trace_id="T1") as outer:
            with trace.span("inner") as inner:
                pass
        recs, bad = export.read_log(str(log))
        assert bad == []
        by_name = {r["name"]: r for r in recs}
        assert by_name["inner"]["parent_id"] == outer.span_id
        # ambient trace id flows to children
        assert by_name["inner"]["trace_id"] == "T1"
        assert by_name["inner"]["dur_ns"] >= 0
        assert by_name["outer"]["parent_id"] is None
        # inner closed before outer
        assert inner.t1_ns <= outer.t1_ns

    def test_span_exception_annotated_and_stack_repaired(self, tmp_path):
        log = _enable(tmp_path)
        with pytest.raises(ValueError):
            with trace.span("boom"):
                raise ValueError("x")
        # the ambient stack is clean again — a fresh span is a root
        with trace.span("after"):
            pass
        recs, _ = export.read_log(str(log))
        by_name = {r["name"]: r for r in recs}
        assert by_name["boom"]["attrs"]["error"] == "ValueError"
        assert by_name["after"]["parent_id"] is None

    def test_instant_event_attaches_to_open_span(self, tmp_path):
        log = _enable(tmp_path)
        with trace.span("op"):
            trace.event("fault_injected", action="delay")
        recs, _ = export.read_log(str(log))
        (rec,) = [r for r in recs if r["name"] == "op"]
        assert rec["events"][0]["name"] == "fault_injected"
        assert rec["events"][0]["action"] == "delay"

    def test_wall_now_monotone_and_anchored(self):
        stamps = [trace.wall_now() for _ in range(200)]
        assert stamps == sorted(stamps)
        # anchored to real wall time (within a generous minute)
        assert abs(stamps[-1] - time.time()) < 60.0

    def test_wall_from_mono_consistent_with_wall_now(self):
        m = time.monotonic()
        w = trace.wall_from_mono(m)
        assert abs(w - trace.wall_now()) < 0.1


# ---------------------------------------------------------------------------
# flight recorder: wraparound + drop accounting + dump idempotence
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_wraparound_counts_drops(self, tmp_path):
        _enable(tmp_path, ring=4)
        for i in range(10):
            with trace.span(f"s{i}"):
                pass
        spans, dropped = trace.flight_snapshot()
        assert [s["name"] for s in spans] == ["s6", "s7", "s8", "s9"]
        assert dropped == 6  # 10 recorded, 4 resident — NEVER silent

    def test_flight_dump_ships_last_n_and_is_idempotent(self, tmp_path):
        log = _enable(tmp_path, ring=4)
        for i in range(6):
            with trace.span(f"s{i}"):
                pass
        assert trace.flight_dump("CommPeerDied", op="allreduce")
        # no new spans since → a teardown cascade dumps exactly once
        assert not trace.flight_dump("CommPeerDied", op="allreduce")
        recs, _ = export.read_log(str(log))
        dumps = [r for r in recs if r["event"] == "flight_recorder"]
        assert len(dumps) == 1
        d = dumps[0]
        assert d["reason"] == "CommPeerDied" and d["op"] == "allreduce"
        assert d["n_spans"] == 4 and d["dropped"] == 2
        assert [s["name"] for s in d["spans"]] == ["s2", "s3", "s4",
                                                   "s5"]

    def test_empty_ring_dumps_nothing(self, tmp_path):
        log = _enable(tmp_path)
        assert not trace.flight_dump("WorkerFailure")
        assert not (log.exists() and "flight_recorder" in log.read_text())

    def test_on_typed_failure_lifts_attribution(self, tmp_path):
        from distributed_pytorch_tpu.runtime.native import CommTimeout
        log = _enable(tmp_path)
        with trace.span("comm:allreduce"):
            pass
        exc = CommTimeout("deadline", op="allreduce", rank=2, peer=1,
                          deadline_ms=500)
        assert trace.on_typed_failure(exc)
        recs, _ = export.read_log(str(log))
        (d,) = [r for r in recs if r["event"] == "flight_recorder"]
        assert d["reason"] == "CommTimeout"
        assert d["err_op"] == "allreduce" and d["err_peer"] == 1
        assert d["rank"] == 2  # falls back to the error's rank


# ---------------------------------------------------------------------------
# monotone logging timestamps (the utils/logging satellite)
# ---------------------------------------------------------------------------


class TestMonotoneLogging:
    def test_append_event_survives_clock_step_backwards(
            self, tmp_path, monkeypatch):
        log = tmp_path / "m.jsonl"
        monkeypatch.setenv("DPX_METRICS_LOG", str(log))
        dpxlog.append_event("ckpt_save", step=1)
        # the system clock steps BACK two hours mid-run (NTP) — event
        # order in the log must still be non-decreasing
        walk = iter([time.time() - 7200.0] * 10)
        monkeypatch.setattr(time, "time", lambda: next(walk))
        dpxlog.append_event("ckpt_save", step=2)
        dpxlog.append_event("ckpt_save", step=3)
        recs, bad = export.read_log(str(log))
        assert bad == []
        times = [r["time"] for r in recs]
        assert times == sorted(times)
        assert all(t > 1e9 for t in times)  # still real wall stamps

    def test_metrics_logger_monotone(self, tmp_path, monkeypatch):
        log = tmp_path / "m2.jsonl"
        ml = dpxlog.MetricsLogger(str(log))
        ml.log(step=1, loss=1.0)
        monkeypatch.setattr(time, "time",
                            lambda: 12.0)  # absurd backwards clock
        ml.log(step=2, loss=0.9)
        ml.event("worker_failure", rank=0)
        ml.close()
        recs, _ = export.read_log(str(log))
        times = [r["time"] for r in recs]
        assert times == sorted(times) and all(t > 1e9 for t in times)


# ---------------------------------------------------------------------------
# export: merge, rank→pid, clock alignment, validator
# ---------------------------------------------------------------------------


def _mk_span(name, rank, t0, dur_s, span_id, **attrs):
    rec = {"event": "trace_span", "name": name, "trace_id": None,
           "span_id": span_id, "parent_id": None, "t0_wall": t0,
           "dur_ns": int(dur_s * 1e9), "rank": rank, "pid": 1000 + rank,
           "tid": "MainThread"}
    if attrs:
        rec["attrs"] = attrs
    return rec


class TestExport:
    def test_chrome_trace_rank_to_pid_and_parses(self):
        recs = [_mk_span("comm:allreduce", r, 100.0 + r * 0.001, 0.01,
                         f"{r}.1") for r in range(4)]
        ct = export.chrome_trace(recs)
        text = json.dumps(ct)          # must be valid JSON end to end
        parsed = json.loads(text)
        xs = [e for e in parsed["traceEvents"] if e["ph"] == "X"]
        assert {e["pid"] for e in xs} == {0, 1, 2, 3}
        names = [e for e in parsed["traceEvents"] if e["ph"] == "M"]
        assert {m["args"]["name"] for m in names} == {
            "rank 0", "rank 1", "rank 2", "rank 3"}

    def test_clock_alignment_from_matched_collective_exits(self):
        # rank 1's anchor is skewed +5s; its barrier EXITS line up with
        # rank 0's after the estimated offset is subtracted
        recs = []
        for k in range(3):
            base = 100.0 + k
            recs.append(_mk_span("comm:barrier", 0, base, 0.010,
                                 f"0.b{k}"))
            recs.append(_mk_span("comm:barrier", 1, base + 5.0, 0.010,
                                 f"1.b{k}"))
        spans = export.collect_spans(recs)
        offsets = export.estimate_offsets(spans)
        assert abs(offsets[1] - 5.0) < 1e-6 and offsets[0] == 0.0
        ct = export.chrome_trace(recs)
        ts = {(e["pid"], e["name"], round(e["ts"])): e["ts"]
              for e in ct["traceEvents"] if e["ph"] == "X"}
        # after alignment the k-th barrier starts at the same µs on
        # both rank rows
        for k in range(3):
            t0 = (100.0 + k) * 1e6
            assert abs(ts[(0, "comm:barrier", round(t0))] - t0) < 1
            assert abs(ts[(1, "comm:barrier", round(t0))] - t0) < 1

    def test_flight_recorder_spans_dedupe_into_trace(self, tmp_path):
        log = _enable(tmp_path, ring=8)
        trace.set_rank(3)
        with trace.span("comm:allreduce"):
            pass
        trace.flight_dump("CommPeerDied", op="allreduce")
        recs, _ = export.read_log(str(log))
        spans = export.collect_spans(recs)
        # the live-logged span and its flight-recorder copy are ONE
        assert len(spans) == 1 and spans[0]["rank"] == 3

    def test_check_flags_the_three_issue_classes(self, tmp_path):
        log = tmp_path / "bad.jsonl"
        lines = [
            json.dumps({"event": "worker_failure", "rank": 1,
                        "time": 1.0}),
            "{not json",
            json.dumps({"event": "totally_unknown", "time": 1.0}),
            json.dumps({"event": "worker_failure", "time": 2.0}),
            json.dumps({"step": 3, "time": 3.0, "loss": 0.5}),
            json.dumps({"neither": True}),
        ]
        log.write_text("\n".join(lines) + "\n")
        issues = export.check_log(*export.read_log(str(log)))
        msgs = "\n".join(m for _, m in issues)
        lines_flagged = {ln for ln, _ in issues}
        assert any("malformed" in m for _, m in issues)
        assert 2 in lines_flagged          # the broken line, BY NUMBER
        assert "unknown event name 'totally_unknown'" in msgs
        assert "no rank attribution" in msgs
        assert "neither a named event nor a step record" in msgs
        # the well-formed failure event and the step record pass
        assert 1 not in lines_flagged and 5 not in lines_flagged

    def test_dpxtrace_cli_check_and_export(self, tmp_path, capsys):
        from tools import dpxtrace as cli
        log = _enable(tmp_path)
        with trace.span("comm:allreduce", bytes=64):
            pass
        assert cli.main(["check", str(log)]) == 0
        out = tmp_path / "chrome.json"
        assert cli.main(["export", str(log), "-o", str(out)]) == 0
        parsed = json.loads(out.read_text())
        assert parsed["otherData"]["n_spans"] == 1
        (log.parent / "broken.jsonl").write_text("{nope\n")
        assert cli.main(["--check",
                         str(log.parent / "broken.jsonl")]) == 1


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------


class TestDetect:
    def _spans(self, medians_by_rank, n=8):
        recs = []
        for rank, med in medians_by_rank.items():
            for i in range(n):
                recs.append(_mk_span("comm:allreduce", rank, 100.0 + i,
                                     med * (1 + 0.01 * (i % 3)),
                                     f"{rank}.{i}"))
        return export.collect_spans(recs)

    def test_straggler_rank_flagged(self):
        # ranks 0-2 at ~10ms, rank 3 at ~40ms — the classic one-slow-
        # rank pathology (arXiv 1810.11112)
        found = detect.stragglers(self._spans(
            {0: 0.010, 1: 0.0101, 2: 0.0099, 3: 0.040}))
        assert len(found) == 1
        f = found[0]
        assert f["rank"] == 3 and f["op"] == "comm:allreduce"
        assert f["excess_x"] > 3.0

    def test_uniform_ranks_not_flagged(self):
        found = detect.stragglers(self._spans(
            {0: 0.010, 1: 0.0101, 2: 0.0099, 3: 0.0102}))
        assert found == []

    def test_single_rank_op_skipped(self):
        assert detect.stragglers(self._spans({0: 0.010})) == []

    def test_summarize_ops_rows(self):
        rows = detect.summarize_ops(self._spans({0: 0.01, 1: 0.02}))
        assert {r["rank"] for r in rows} == {0, 1}
        assert all(r["op"] == "comm:allreduce" and r["count"] == 8
                   for r in rows)


# ---------------------------------------------------------------------------
# serve/metrics aggregate() edge cases (satellite)
# ---------------------------------------------------------------------------


class TestAggregateEdges:
    def test_empty_window(self):
        out = aggregate([])
        assert out["n_requests"] == 0 and out["n_ok"] == 0
        assert out["ttft_ms_p50"] is None
        assert out["tpot_ms_p99"] is None
        assert out["outcomes"] == {}
        assert out["total_tokens"] == 0

    def test_single_sample(self):
        rec = {"outcome": "ok", "ttft_ms": 12.0, "tpot_ms": None,
               "n_tokens": 1, "prompt_len": 4, "queue_ms": 1.0}
        out = aggregate([rec], wall_s=2.0)
        assert out["ttft_ms_p50"] == 12.0 and out["ttft_ms_p99"] == 12.0
        assert out["tpot_ms_p50"] is None  # 1-token stream: undefined
        assert out["tokens_per_sec"] == 0.5

    def test_all_failed_requests(self):
        recs = [{"outcome": "deadline_queued", "ttft_ms": None,
                 "tpot_ms": None, "n_tokens": 0, "prompt_len": 4},
                {"outcome": "engine_stopped", "ttft_ms": None,
                 "tpot_ms": None, "n_tokens": 0, "prompt_len": 4}]
        out = aggregate(recs)
        assert out["n_requests"] == 2 and out["n_ok"] == 0
        assert out["outcomes"] == {"deadline_queued": 1,
                                   "engine_stopped": 1}
        assert out["ttft_ms_p50"] is None and out["total_tokens"] == 0

    def test_percentile_empty_and_none_filtered(self):
        assert percentile([], 50) is None
        assert percentile([None, None], 99) is None
        assert percentile([None, 3.0], 50) == 3.0


# ---------------------------------------------------------------------------
# serve lifecycle: ONE trace_id, spans == the TTFT decomposition
# ---------------------------------------------------------------------------


def _lm(**kw):
    from distributed_pytorch_tpu import models
    kw.setdefault("vocab", 61)
    kw.setdefault("dim", 32)
    kw.setdefault("n_layers", 1)
    kw.setdefault("n_heads", 4)
    kw.setdefault("n_kv_heads", 2)
    kw.setdefault("pos", "rope")
    kw.setdefault("max_seq", 128)
    return models.TransformerLM(**kw)


class TestServeTrace:
    def test_monolithic_request_spans_one_trace_id(self, tmp_path):
        import jax
        from distributed_pytorch_tpu.serve import (EngineConfig,
                                                   InferenceEngine,
                                                   SamplingParams)
        log = _enable(tmp_path)
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        prompt = np.arange(5, dtype=np.int32) % 61
        with InferenceEngine(model, params,
                             EngineConfig(n_slots=2, max_len=64)) as eng:
            h = eng.submit(prompt, SamplingParams(max_new_tokens=4))
            h.result(timeout=120)
        recs, _ = export.read_log(str(log))
        spans = [r for r in recs if r.get("event") == "trace_span"
                 and str(r["name"]).startswith("serve.")]
        by_name = {s["name"]: s for s in spans}
        tree = {"serve.request", "serve.queue", "serve.prefill",
                "serve.stream"}
        assert tree <= set(by_name)
        # the engine thread's own spans (the loop's phases) either carry
        # this request's trace id (its admission, its rows) or none
        tids = {s["trace_id"] for s in spans} - {None}
        assert tids == {h.metrics["trace_id"]}
        assert all(by_name[n]["trace_id"] in tids for n in tree)
        root = by_name["serve.request"]
        assert all(by_name[n]["parent_id"] == root["span_id"]
                   for n in tree if n != "serve.request")
        # queue + prefill telescope to TTFT (same timestamps, exactly)
        # abs tolerance 0.02 ms: the spans' wall stamps carry the
        # anchor's float ulp (~0.5 µs per value at 1.7e9 s magnitude)
        ttft = (by_name["serve.queue"]["dur_ns"]
                + by_name["serve.prefill"]["dur_ns"]) / 1e6
        assert ttft == pytest.approx(h.metrics["ttft_ms"], abs=0.02)

    def test_disagg_one_trace_id_spans_sum_to_ttft(self, tmp_path):
        import jax
        from distributed_pytorch_tpu.serve import (DisaggConfig,
                                                   DisaggEngine,
                                                   SamplingParams)
        log = _enable(tmp_path)
        model = _lm()
        params = model.init(jax.random.PRNGKey(0))
        prompt = (np.arange(9, dtype=np.int32) * 3) % 61
        with DisaggEngine(model, params,
                          DisaggConfig(n_slots=2, max_len=64,
                                       page_len=8)) as eng:
            h = eng.submit(prompt, SamplingParams(max_new_tokens=4))
            h.result(timeout=120)
        rec = h.metrics
        recs, _ = export.read_log(str(log))
        spans = [r for r in recs if r.get("event") == "trace_span"
                 and str(r["name"]).startswith("serve.")]
        by_name = {s["name"]: s for s in spans}
        # the acceptance shape: ONE trace id across the whole split
        assert {"serve.request", "serve.queue", "serve.prefill",
                "serve.handoff", "serve.decode"} <= set(by_name)
        assert len({s["trace_id"] for s in spans}) == 1
        assert {s["trace_id"] for s in spans} == {rec["trace_id"]}
        # span durations sum EXACTLY to the asserted TTFT decomposition
        # (queue→prefill→handoff→decode telescopes to first_token −
        # submit; serve/metrics.py asserts the same identity in ms)
        total_ms = sum(by_name[n]["dur_ns"] for n in
                       ("serve.queue", "serve.prefill", "serve.handoff",
                        "serve.decode")) / 1e6
        # abs 0.02 ms = 4 spans × the wall anchor's float ulp (~0.5 µs
        # per stamp at 1.7e9 s magnitude) — far below any real leg
        assert total_ms == pytest.approx(rec["ttft_ms"], abs=0.02)
        parts = sum(rec[k] for k in ("queue_ms", "prefill_ms",
                                     "handoff_ms", "decode_ms"))
        assert total_ms == pytest.approx(parts, abs=0.02)


# ---------------------------------------------------------------------------
# THE chaos acceptance: world 4, kill@op=allreduce, tracing on
# ---------------------------------------------------------------------------


def _obs_chaos_worker(rank, world, q):
    """Two clean allreduces + a barrier (an alignment point for the
    export), then rank 1 is killed entering allreduce call 3."""
    import numpy as np

    import distributed_pytorch_tpu as dist

    dist.init_process_group(rank, world)
    dist.barrier()
    for _ in range(2):
        dist.all_reduce(np.ones(4096, np.float32))
    try:
        dist.all_reduce(np.ones(4096, np.float32))
        q.put((rank, None))
    except Exception as e:  # noqa: BLE001 — typed comm error expected
        q.put((rank, type(e).__name__))
        raise


def test_chaos_world4_merged_trace_and_flight_dumps(tmp_path,
                                                    monkeypatch):
    """Acceptance (ISSUE 14): a world-4 chaos run with tracing on and a
    DPX_FAULT kill mid-allreduce yields (1) a merged Chrome trace that
    PARSES and contains spans from every rank, (2) flight-recorder
    dumps from the survivors naming the dying op, and (3) a clock-
    offset estimate for every rank present."""
    log = tmp_path / "chaos.jsonl"
    monkeypatch.setenv("DPX_TRACE", "1")
    monkeypatch.setenv("DPX_METRICS_LOG", str(log))
    monkeypatch.setenv(faults.FAULT_ENV, "kill@op=allreduce,call=3,rank=1")
    monkeypatch.setenv("DPX_COMM_TIMEOUT_MS", str(TIMEOUT_MS))
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    result = {}

    def run():
        try:
            launch_multiprocess(_obs_chaos_worker, 4, q)
        except BaseException as e:  # noqa: BLE001
            result["exc"] = e

    t = threading.Thread(target=run, name="test-obs-chaos", daemon=True)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive(), "chaos run hung"
    assert isinstance(result.get("exc"), WorkerFailure)
    assert result["exc"].rank == 1 and result["exc"].op == "allreduce"

    records, malformed = export.read_log(str(log))
    assert malformed == []
    # (1) the merged Chrome trace parses and carries per-rank timelines
    ct = export.chrome_trace(records)
    parsed = json.loads(json.dumps(ct))
    span_pids = {e["pid"] for e in parsed["traceEvents"]
                 if e["ph"] == "X"}
    assert {0, 1, 2, 3} <= span_pids, \
        f"spans missing for ranks: { {0, 1, 2, 3} - span_pids }"
    # the killed rank's timeline includes its completed collectives
    rank1 = [e for e in parsed["traceEvents"]
             if e["ph"] == "X" and e["pid"] == 1]
    # CommStats books the exact ring as allreduce_sum — the victim's
    # two clean collectives are on its timeline
    assert any(e["name"].startswith("comm:allreduce") for e in rank1)
    # (3) every rank got a clock-offset estimate (barrier alignment)
    assert set(ct["otherData"]["clock_offsets_s"]) == {"0", "1", "2",
                                                       "3"}
    # (2) flight-recorder dumps: every SURVIVOR ships a postmortem that
    # names the dying op; the victim ships its own via the kill hook
    dumps = [r for r in records if r.get("event") == "flight_recorder"]
    by_rank = {}
    for d in dumps:
        by_rank.setdefault(d.get("rank"), []).append(d)
    assert {0, 2, 3} <= set(by_rank), \
        f"survivor dumps missing: {sorted(by_rank)}"
    for r in (0, 2, 3):
        d = by_rank[r][0]
        assert d["err_op"] == "allreduce", d
        assert d["reason"] in ("CommPeerDied", "CommTimeout")
        assert d["n_spans"] >= 1
    assert 1 in by_rank and by_rank[1][0]["reason"] == "fault_kill"
    # the stream itself passes the strict validator
    assert export.check_log(records, malformed) == []


def test_fault_delay_annotated_on_timeline(tmp_path, monkeypatch):
    """An injected delay shows up as a fault_injected instant event on
    the rank's timeline (inside the comm span when one is open)."""
    log = _enable(tmp_path)
    faults.install("delay@op=allreduce,ms=5")
    faults.on_comm_op("allreduce", rank=0)
    recs, _ = export.read_log(str(log))
    # no span open at the hook point → a standalone instant record
    insts = [r for r in recs if r.get("ph") == "i"
             and r["name"] == "fault_injected"]
    assert len(insts) == 1
    assert insts[0]["attrs"]["action"] == "delay"
