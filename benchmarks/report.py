"""Render the measured-results section from the raw records log.

Prose tables are regenerated from the raw records log, never typed by
hand — this is the regenerator. It reads every non-retracted `ok` row
of the log bench.py appends to (`benchmarks/tpu_results.jsonl`, made at
run time), keeps the NEWEST record per stage, and prints a markdown
summary (plus one JSON line for tooling). Retracted rows are listed by
stage + reason so the retraction trail stays visible.

Usage: python benchmarks/report.py [--log FILE] [--trace-log FILE]

--trace-log renders the dpxtrace observability section from a span log
(per-op per-rank duration summary + the k*IQR straggler verdict —
docs/observability.md), appended after the measured-results section.

Reading the store goes through perfbench (``record.iter_rows``), so
malformed lines are surfaced as comments instead of silently skipped,
and the newest schema record renders a gated-metrics table — value,
spread (IQR/median), trial count, trusted — with withheld
``vs_baseline`` rows carrying their reason instead of going blank
(docs/benchmarking.md).
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
DEFAULT_LOG = os.path.join(REPO, "benchmarks", "tpu_results.jsonl")


_PB_RECORD = None

#: Private root the file-based loader fabricates modules under. ONE
#: root for everything report.py loads (perfbench AND obs), so shared
#: dependencies (obs.detect -> ..perfbench.stats) resolve to a single
#: module instance instead of loading twice under separate roots.
_PRIVATE_ROOT = "_report_dpx"


def _load_private(modules):
    """Load package modules file-based under :data:`_PRIVATE_ROOT`,
    WITHOUT importing the real package: report.py renders a log on a
    machine with no jax — the heavy package ``__init__`` (api → jax)
    must never be pulled here, and the genuine package must be neither
    imported nor shadowed.

    ``modules`` is an ordered sequence of ``(pkg, sub)`` pairs (the
    dependency order matters: errors → stats → record); already-loaded
    names are reused. Returns the loaded modules, in order."""
    import importlib.util
    import types

    pkg_dir = os.path.join(REPO, "distributed_pytorch_tpu")
    if _PRIVATE_ROOT not in sys.modules:
        root = types.ModuleType(_PRIVATE_ROOT)
        root.__path__ = [pkg_dir]
        sys.modules[_PRIVATE_ROOT] = root
    out = []
    for pkg, sub in modules:
        parent = f"{_PRIVATE_ROOT}.{pkg}"
        if parent not in sys.modules:
            mod = types.ModuleType(parent)
            mod.__path__ = [os.path.join(pkg_dir, pkg)]
            sys.modules[parent] = mod
        name = f"{parent}.{sub}"
        if name not in sys.modules:
            spec = importlib.util.spec_from_file_location(
                name, os.path.join(pkg_dir, pkg, sub + ".py"))
            mod = importlib.util.module_from_spec(spec)
            sys.modules[name] = mod
            spec.loader.exec_module(mod)
        out.append(sys.modules[name])
    return out


def _perfbench_record():
    """The perfbench record module: the real one when already imported
    (in-process test use), else file-based under the private root."""
    global _PB_RECORD
    if _PB_RECORD is not None:
        return _PB_RECORD
    real = sys.modules.get("distributed_pytorch_tpu.perfbench.record")
    if real is not None:
        _PB_RECORD = real
        return _PB_RECORD
    *_, _PB_RECORD = _load_private(
        [("perfbench", "errors"), ("perfbench", "stats"),
         ("perfbench", "record")])
    return _PB_RECORD


def load_rows_checked(path):
    """(rows, malformed) via perfbench's one store reader — malformed
    is [(1-based line, reason), ...], surfaced by main() as comments."""
    return _perfbench_record().iter_rows(path)


def load_rows(path):
    return load_rows_checked(path)[0]


def latest_per_stage(rows):
    """Newest non-retracted ok row per stage (file order = time order)."""
    out = {}
    for r in rows:
        if r.get("ok") and not r.get("retracted"):
            out[r.get("stage", "?")] = r
    return out


def _truncate_words(s: str, cap: int = 200) -> str:
    """Cap a free-text reason at a WORD boundary with an ellipsis —
    the retraction reasons run ~120 chars and a hard cut would split
    them mid-word."""
    s = str(s)
    if len(s) <= cap:
        return s
    cut = s[:cap].rsplit(None, 1)[0] if " " in s[:cap] else s[:cap]
    return cut + "…"


def _fmt(v, nd=3):
    if isinstance(v, float):
        s = f"{v:.{nd}f}"
        return s.rstrip("0").rstrip(".") if "." in s else s
    return str(v)


def newest_schema_record(rows):
    """Newest non-retracted row carrying a perfbench schema record —
    including not-ok rows: a carry-forward headline is logged ok=False
    (it must never become a future last_good) but its provenance and
    withheld vs_baseline are exactly what the report must show."""
    schema = _perfbench_record().SCHEMA
    best = None
    for r in rows:
        if r.get("retracted"):
            continue
        res = r.get("result", {})
        if isinstance(res, dict) and res.get("schema") == schema:
            best = r
    return best


def render_gated(row):
    """The gated-metrics section of one schema record: headline
    provenance/trust, vs_baseline or its withhold reason (never a
    silent blank), and the per-metric spread/IQR/trusted table."""
    res = row["result"]
    lines = ["", f"### Gated metrics (stage {row.get('stage', '?')}, "
             f"{row.get('ts') or res.get('ts', '?')}; perfbench "
             "spread-gate policy — docs/benchmarking.md)", ""]
    if "value" in res:
        head = (f"Headline `{res.get('metric')}` = "
                f"**{_fmt(float(res['value']), 4)}** {res.get('unit')}, "
                f"provenance **{res.get('provenance')}**")
        lg = res.get("last_good")
        if res.get("provenance") == "last_good" and isinstance(lg, dict):
            head += (f" (carried forward from stage {lg.get('stage')}, "
                     f"{lg.get('ts', '?')})")
        lines.append(head + ".")
    if not res.get("trusted"):
        lines.append(f"**UNTRUSTED**: "
                     f"{_truncate_words(res.get('untrusted_reason', '?'))}")
    if "vs_baseline" in res:
        lines.append(f"vs_baseline: **{_fmt(float(res['vs_baseline']))}x**"
                     " (both sides passed the spread gate).")
    elif "vs_baseline_withheld" in res:
        lines.append(f"vs_baseline **withheld**: "
                     f"{_truncate_words(res['vs_baseline_withheld'])}")
    metrics = res.get("metrics") or {}
    if metrics:
        lines += ["", "| metric | value | unit | spread (IQR/med) | "
                  "trials | trusted |", "|---|---|---|---|---|---|"]
        for name in sorted(metrics):
            b = metrics[name]
            if not isinstance(b, dict):
                continue
            spread = (f"{b['spread_frac']:.1%}"
                      if isinstance(b.get("spread_frac"), (int, float))
                      else "n/a")
            n = (b.get("trials") or {}).get("n_trials", 1)
            if b.get("trusted"):
                trust = ("yes" if b.get("provenance") == "measured"
                         else f"yes ({b.get('provenance')})")
            else:
                trust = ("no: " + _truncate_words(
                    b.get("untrusted_reason", "?"), 80))
            val = (_fmt(float(b["value"]), 4)
                   if isinstance(b.get("value"), (int, float)) else "n/a")
            lines.append(f"| {name} | {val} | {b.get('unit', '?')} | "
                         f"{spread} | {n} | {trust} |")
    return lines


def render(rows) -> str:
    live = latest_per_stage(rows)
    lines = ["## Measured (regenerated from benchmarks/tpu_results.jsonl)",
             ""]
    if not live:
        lines.append("*(no non-retracted successful records on file)*")

    def res(stage):
        return live.get(stage, {}).get("result", {})

    if "bench_mfu" in live:
        src_stage = "bench_mfu"
        mfu = res("bench_mfu")
    else:
        src_stage = ("bench_headline" if "bench_headline" in live
                     else "bench_record")
        mfu = res(src_stage).get("mfu_detail", {})
    med = res("bench_mfu_medium")
    lng = res("mfu_long")
    # the metric table starts whenever ANY MFU row exists — a run where
    # the flagship stage died but medium/long landed still renders
    if any(r.get("mfu") is not None for r in (mfu, med, lng)):
        lines += ["| Metric | Value | Source row |", "|---|---|---|"]
        if mfu.get("mfu") is not None:
            c = mfu.get("config", {})
            src = (f"stage {src_stage}, "
                   f"{live.get(src_stage, {}).get('ts', '?')}")
            lines += [
                f"| **Flagship MFU** | **{_fmt(mfu['mfu'], 4)}** "
                f"({_fmt(mfu.get('achieved_tflops_per_sec', 0), 1)} of "
                f"{_fmt(mfu.get('peak_bf16_tflops', 0), 0)} peak TF/s) | "
                f"{src} |",
                f"| Flagship tokens/s | "
                f"{_fmt(mfu.get('tokens_per_sec', 0))} "
                f"(step {_fmt(mfu.get('step_ms_median', 0))} ms, "
                f"batch {c.get('batch')}, seq {c.get('seq')}) | same |",
            ]
        if med.get("mfu") is not None:
            lines.append(f"| medium (~355M) MFU | {_fmt(med['mfu'], 4)} | "
                         f"stage bench_mfu_medium |")
        if lng.get("mfu") is not None:
            lines.append(
                f"| long-context (seq 4096) MFU | {_fmt(lng['mfu'], 4)}"
                f" (hw {_fmt(lng.get('mfu_hw') or 0, 4)}) | "
                f"stage mfu_long |")
        lines.append("")

    sr = newest_schema_record(rows)
    if sr:
        lines += render_gated(sr)
        lines.append("")

    sv = res("serve_shared")
    sh = (sv.get("arms") or {}).get("engine_paged_shared") or {}
    un = (sv.get("arms") or {}).get("engine_unshared_open") or {}
    if sh:
        pages = sh.get("pages", {})
        lines += ["", "Shared-prefix serving (paged KV, stage "
                  "serve_shared; gated medians — docs/serving.md):", "",
                  "| arm | TTFT p50 (ms) | TTFT p99 (ms) | tokens/s |",
                  "|---|---|---|---|",
                  f"| paged+shared | {_fmt(sh.get('ttft_ms_p50', 0))} | "
                  f"{_fmt(sh.get('ttft_ms_p99', 0))} | "
                  f"{_fmt(sh.get('tokens_per_sec', 0))} |"]
        if un:
            lines.append(
                f"| unshared | {_fmt(un.get('ttft_ms_p50', 0))} | "
                f"{_fmt(un.get('ttft_ms_p99', 0))} | "
                f"{_fmt(un.get('tokens_per_sec', 0))} |")
        lines.append("")
        hr = pages.get("prefix_hit_rate")
        lines.append(
            f"Prefix hit rate {_fmt(hr, 3) if hr is not None else 'n/a'}"
            f" ({pages.get('prefix_hit_pages', 0)} pages), "
            f"prefill tokens saved "
            f"{_fmt(sh.get('prefill_tokens_saved', 0), 0)}, pool "
            f"occupancy {_fmt(pages.get('pool_occupancy', 0), 3)} "
            f"({pages.get('evictions', 0)} evictions).")
        if "vs_unshared_ttft_p50_x" in sv:
            lines.append(f"vs_unshared TTFT p50: "
                         f"**{_fmt(float(sv['vs_unshared_ttft_p50_x']))}x**"
                         " (both sides passed the spread gate).")
        elif "vs_unshared_ttft_p50_withheld" in sv:
            lines.append(f"vs_unshared TTFT p50 **withheld**: "
                         f"{_truncate_words(sv['vs_unshared_ttft_p50_withheld'])}")
        lines.append("")

    hr = res("bench_dp8_hier")
    if hr.get("hier_steps_per_sec") is not None:
        lines += ["", f"Adaptive/hierarchical comm (stage bench_dp8_hier"
                  f", {hr.get('hier_bucket_mb', '?')} MiB bucket, world "
                  f"{hr.get('hier_world', '?')} as "
                  f"{hr.get('hier_world', 0) // max(hr.get('hier_local_world', 1), 1)}"
                  f"x{hr.get('hier_local_world', '?')} hosts — "
                  "docs/comms.md):", "",
                  "| arm | steps/s | wire bytes/rank/step |",
                  "|---|---|---|",
                  f"| flat q8 | {_fmt(hr.get('q8_steps_per_sec', 0))} | "
                  f"{hr.get('q8_wire_bytes', '?')} |",
                  f"| flat q4 | {_fmt(hr.get('q4_steps_per_sec', 0))} | "
                  f"{hr.get('q4_wire_bytes', '?')} |",
                  f"| hier adaptive | "
                  f"{_fmt(hr.get('hier_steps_per_sec', 0))} | "
                  f"slow-hop {hr.get('hier_slow_hop_bytes_per_step', '?')} |"]
        if hr.get("f32_wire_bytes") and hr.get("q4_wire_bytes"):
            lines.append(
                f"q4 wire {_fmt(hr['f32_wire_bytes'] / hr['q4_wire_bytes'])}"
                f"x smaller than f32 (CommStats accounting == wire.py "
                f"formula); adaptive widths {hr.get('hier_width_hist')}.")
        if hr.get("hier_slow_hop_bytes_total"):
            parts = []
            if hr.get("flat_slow_hop_bytes_matched_width"):
                parts.append(
                    f"{_fmt(hr['flat_slow_hop_bytes_matched_width'] / hr['hier_slow_hop_bytes_total'])}"
                    "x below the same-width flat ring (topology)")
            if hr.get("flat_slow_hop_bytes_q8"):
                parts.append(
                    f"{_fmt(hr['flat_slow_hop_bytes_q8'] / hr['hier_slow_hop_bytes_total'])}"
                    "x below the flat q8 ring (topology x width)")
            if parts:
                lines.append("Two-level ring slow-hop total "
                             + "; ".join(parts) + ".")
        ov = hr.get("overlap") or {}
        if ov.get("on") and ov.get("off"):
            line = (f"Comm overlap (bucketed host step): exposed "
                    f"{_fmt(ov['off'].get('exposed_ms', 0))} -> "
                    f"{_fmt(ov['on'].get('exposed_ms', 0))} ms/step "
                    f"({_fmt(ov['on'].get('overlapped_ms', 0))} ms "
                    "measured hidden behind async bucket updates")
            if ov["on"].get("step_ms") is not None:
                line += (f"; wall {_fmt(ov['off'].get('step_ms', 0))}"
                         f" -> {_fmt(ov['on'].get('step_ms', 0))} "
                         "ms/step")
            lines.append(line + ").")
        if "vs_q8" in hr:
            lines.append(f"vs_q8: **{_fmt(float(hr['vs_q8']))}x** (both "
                         "sides passed the spread gate).")
        elif "vs_q8_withheld" in hr:
            lines.append(f"vs_q8 **withheld**: "
                         f"{_truncate_words(hr['vs_q8_withheld'])}")
        lines.append("")

    smoke = res("mfu_smoke")
    if smoke.get("step_ms_median") is not None:
        lines.append(
            f"Chip-liveness smoke (CI-sized model, not a perf claim): "
            f"device {smoke.get('device')}, step "
            f"{_fmt(smoke['step_ms_median'], 2)} ms, "
            f"{live.get('mfu_smoke', {}).get('ts', '?')}.")
        lines.append("")

    dec = res("bench_decode")
    header_done = False
    for arm in ("mha", "gqa", "gqa_int8", "gqa_int8_pinned",
                "gqa_window"):
        d = dec.get(arm, {})
        if d.get("decode_tokens_per_sec"):
            if not header_done:
                lines += ["| Decode arm | tok/s | ms/token | est HBM util |",
                          "|---|---|---|---|"]
                header_done = True
            lines.append(
                f"| {arm} | {_fmt(d['decode_tokens_per_sec'], 1)} | "
                f"{_fmt(d.get('decode_per_token_latency_ms', 0))} | "
                f"{_fmt(d.get('est_hbm_utilization', 0))} |")
    if dec.get("gqa_decode_speedup"):
        line = (f"\nGQA decode speedup {dec['gqa_decode_speedup']}x; "
                f"int8 {dec.get('gqa_int8_decode_speedup')}x")
        if dec.get("gqa_int8_pinned_decode_speedup") is not None:
            line += (f"; int8 pinned (anti-hoist) "
                     f"{dec['gqa_int8_pinned_decode_speedup']}x")
        if dec.get("gqa_window_decode_speedup") is not None:
            line += (f"; sliding-window rolling cache "
                     f"{dec['gqa_window_decode_speedup']}x")
        lines.append(line + ".")

    fa = res("flash_attention")
    if fa.get("rows"):
        lines += ["", "| seq | flash fwd (ms) | dense fwd (ms) | fwd x | "
                  "flash f+b (ms) | dense f+b (ms) | f+b x |",
                  "|---|---|---|---|---|---|---|"]
        for r in fa["rows"]:
            lines.append(
                f"| {r['seq']} | {_fmt(r['flash_fwd_ms'], 2)} | "
                f"{_fmt(r['dense_fwd_ms'], 2)} | "
                f"{_fmt(r['fwd_speedup'], 2)}x | "
                f"{_fmt(r['flash_fwdbwd_ms'], 2)} | "
                f"{_fmt(r['dense_fwdbwd_ms'], 2)} | "
                f"{_fmt(r['fwdbwd_speedup'], 2)}x |")

    sw = res("mfu_sweep")
    if sw.get("sweep"):
        lines += ["", "| MFU-sweep arm | MFU | tokens/s | step ms |",
                  "|---|---|---|---|"]
        # keep arms whose run succeeded even when mfu is None (unknown
        # device kind): tokens/s and step time are still signal
        arms = sorted((a for a in sw["sweep"] if not a.get("error")),
                      key=lambda a: (a.get("mfu") is None,
                                     -(a.get("mfu") or 0),
                                     -(a.get("tokens_per_sec") or 0)))
        for a in arms:
            mfu_cell = (_fmt(a["mfu"], 4) if a.get("mfu") is not None
                        else "n/a")
            # † marks arms that printed a record but then exited nonzero
            # (arm_error/arm_rc): suspect measurements must be visibly
            # distinct from clean rows
            mark = " †" if a.get("arm_error") else ""
            lines.append(
                f"| `{json.dumps(a['arm'], sort_keys=True)}`{mark} | "
                f"{mfu_cell} | {_fmt(a.get('tokens_per_sec', 0))} | "
                f"{_fmt(a.get('step_ms_median', 0), 2)} |")
        suspect = [a for a in arms if a.get("arm_error")]
        if suspect:
            lines.append("")
            for a in suspect:
                lines.append(
                    f"† `{json.dumps(a['arm'], sort_keys=True)}` exited "
                    f"nonzero after printing its record "
                    f"(rc {a.get('arm_rc')}): "
                    f"{str(a['arm_error'])[:90]}")
        failed = [a for a in sw["sweep"] if a.get("error")]
        if failed:
            lines.append("")
            for a in failed:
                lines.append(f"- arm `{json.dumps(a['arm'], sort_keys=True)}`"
                             f" failed: {a['error'][:90]}")

    bw = res("flash_bwd_sweep")
    if bw.get("best"):
        lines += ["", f"Flash {bw.get('mode', 'fwdbwd')} best block sizes "
                  "(block-size sweep):",
                  "", "| seq | block_q | block_k | ms |", "|---|---|---|---|"]
        for s in sorted(bw["best"], key=int):
            r = bw["best"][s]
            lines.append(f"| {s} | {r['bq']} | {r['bk']} | "
                         f"{_fmt(r['ms'], 3)} |")

    for stage in ("step_breakdown", "step_breakdown_b32"):
        sb = res(stage)
        if sb.get("attribution_ms"):
            a = sb["attribution_ms"]
            lines += ["", f"Step attribution ({stage}, batch "
                      f"{sb.get('config', {}).get('batch')}): "
                      + ", ".join(f"{k} {_fmt(v, 2)}"
                                  for k, v in a.items())
                      + f"; full step {_fmt(sb['step_ms']['full'], 2)} ms."]

    retracted = [r for r in rows if r.get("retracted")]
    if retracted:
        lines += ["", "Retracted rows (kept for the audit trail):"]
        for r in retracted:
            lines.append(f"- {r.get('stage')} ({r.get('ts', '?')}): "
                         f"{_truncate_words(r.get('reason', 'retracted'))}")
    return "\n".join(lines)


_OBS = None


def _obs_modules():
    """obs.export/detect — the real modules when already imported
    (in-process test use), else file-based under the SAME private root
    as :func:`_perfbench_record` (obs.detect's relative import of
    ``..perfbench.stats`` then resolves to the one already-loaded
    private stats instance)."""
    global _OBS
    if _OBS is not None:
        return _OBS
    real = sys.modules.get("distributed_pytorch_tpu.obs.export")
    if real is not None:
        _OBS = (real,
                sys.modules["distributed_pytorch_tpu.obs.detect"])
        return _OBS
    _, export_mod, detect_mod = _load_private(
        [("perfbench", "stats"), ("obs", "export"), ("obs", "detect")])
    _OBS = (export_mod, detect_mod)
    return _OBS


def render_trace(path: str) -> str:
    """The observability section: per-op per-rank span summary + the
    straggler verdict from one span log (``dpxtrace summarize`` /
    ``stragglers`` as markdown)."""
    export, detect = _obs_modules()
    try:
        records, malformed = export.read_log(path)
    except OSError as e:
        return f"## Trace\n\n(cannot read {path}: {e})\n"
    spans = export.collect_spans(records)
    lines = ["## Trace (dpxtrace)", "",
             f"Source: `{os.path.basename(path)}` — {len(spans)} "
             f"span(s), {len(malformed)} malformed line(s)", ""]
    rows = detect.summarize_ops(spans)
    if not rows:
        lines += ["(no spans recorded — set `DPX_TRACE=1`)", ""]
        return "\n".join(lines)
    lines += ["| op | rank | count | median ms | IQR ms | total ms |",
              "|---|---|---|---|---|---|"]
    for r in rows:
        lines.append(f"| `{r['op']}` | {r['rank']} | {r['count']} | "
                     f"{r['median_ms']} | {r['iqr_ms']} | "
                     f"{r['total_ms']} |")
    lines.append("")
    found = detect.stragglers(spans)
    if not found:
        lines += ["Stragglers: none flagged "
                  f"(k·IQR gate, k={detect.IQR_K})", ""]
    else:
        lines += ["**Stragglers flagged** (per-rank median outside "
                  f"k·IQR, k={detect.IQR_K}):", ""]
        for f in found:
            lines.append(
                f"- `{f['op']}` rank {f['rank']}: {f['median_ms']} ms "
                f"vs world median {f['world_median_ms']} ms "
                f"({f['excess_x']}x, threshold {f['threshold_ms']} ms)")
        lines.append("")
    return "\n".join(lines)


def main(argv):
    path = DEFAULT_LOG
    if "--log" in argv:
        i = argv.index("--log")
        if i + 1 >= len(argv):
            print("usage: report.py [--log FILE]", file=sys.stderr)
            return 2
        path = argv[i + 1]
    rows, malformed = load_rows_checked(path)
    for line_no, reason in malformed:
        print(f"# report: skipping malformed store line {line_no}: "
              f"{reason}", file=sys.stderr)
    md = render(rows)
    print(md)
    if "--trace-log" in argv:
        i = argv.index("--trace-log")
        if i + 1 >= len(argv):
            print("usage: report.py [--trace-log FILE]",
                  file=sys.stderr)
            return 2
        print(render_trace(argv[i + 1]))
    # the JSON summary line is the last stdout line — tooling parses it
    live = latest_per_stage(rows)
    print(json.dumps({"stages_on_file": sorted(live),
                      "n_rows": len(rows),
                      "n_malformed": len(malformed),
                      "n_retracted": sum(bool(r.get("retracted"))
                                         for r in rows)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
