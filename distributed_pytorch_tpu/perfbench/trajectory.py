"""The trusted BENCH trajectory: carry-forward and regression diffing.

Two consumers of the trajectory store (the line-JSON log bench.py
appends to at run time, ``benchmarks/tpu_results.jsonl``) live here:

* :func:`last_good_flagship` — the ``last_good`` carry-forward source:
  the newest non-retracted, *actually measured* on-chip flagship
  record.  Rows whose
  result is itself a carry-forward are excluded — a last_good must never
  launder a previous round's last_good into fresh-looking evidence.
* :func:`diff` — compare a new record's trusted measured metrics against
  the newest trusted measured baseline per metric in the trajectory, and
  flag **statistically significant** regressions: a change is a
  regression only when it exceeds ``max(min_drop, baseline spread, new
  spread)`` in the metric's *worse* direction.  Untrusted sides never
  produce verdicts (they are listed as skipped, with the reason) —
  the spread gate and the regression gate are the same policy applied
  twice.

``tools/benchdiff.py`` is the CLI over :func:`diff`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

from .errors import BenchRegression
from .record import SCHEMA, iter_rows

__all__ = ["FLAGSHIP_METRIC", "last_good_flagship", "metric_series",
           "diff", "RegressionReport"]

#: The headline metric name — the one stage fallback and report join on.
FLAGSHIP_METRIC = "transformer_lm_mfu_single_chip"


def last_good_flagship(path: str) -> dict:
    """Most recent non-retracted on-chip FLAGSHIP-config MFU record from
    the trajectory store.  Only the pinned flagship config qualifies — a
    ``bench_mfu`` row (bench.py's mfu stage) or a composite headline row
    whose metric is the headline metric; the medium-model arm must never
    leak into the headline's fallback, and neither may a row that was
    itself a carry-forward (``provenance: "last_good"``)."""
    best: dict = {}
    rows, _ = iter_rows(path)
    for row in rows:
        if row.get("retracted") or not row.get("ok"):
            continue
        res = row.get("result", {})
        if not isinstance(res, dict):
            continue
        if res.get("provenance") == "last_good":
            continue  # never carry a carry-forward forward
        if res.get("trusted") is False:
            # a record the gates poisoned (roofline-implausible, spread
            # violation) is not evidence — it must never be re-emitted
            # as a trusted headline.  Explicit False only: legacy raw
            # rows carry no trust field at all.
            continue
        if row.get("stage") == "bench_mfu":
            mfu = res.get("mfu")
        elif res.get("metric") == FLAGSHIP_METRIC:
            mfu = res.get("value")
        else:
            continue
        if mfu is not None and 0 < mfu <= 1.0:
            # MFU is a fraction of peak — a value above 1 is physically
            # impossible on ANY chip (the r02 "7.42" dispatch artifact);
            # this stdlib reader can't consult the roofline, but it can
            # enforce the universal bound
            best = {"mfu": mfu, "ts": row.get("ts"),
                    "stage": row.get("stage"),
                    "device": res.get("device"),
                    "tokens_per_sec": res.get("tokens_per_sec"),
                    # the ACTUAL store read, so the carry-forward always
                    # points at a file that contains the cited row
                    "source": path}
    return best


# ---------------------------------------------------------------------------
# regression diffing
# ---------------------------------------------------------------------------

def _trusted_measured(blob: dict) -> bool:
    return (isinstance(blob, dict) and blob.get("trusted") is True
            and blob.get("provenance") == "measured"
            and isinstance(blob.get("value"), (int, float))
            and not isinstance(blob.get("value"), bool)
            # NaN/Inf would make every gate comparison False and land
            # garbage in "unchanged" with exit 0 — skip it instead
            and math.isfinite(blob.get("value")))


def metric_series(rows: Sequence[dict]) -> Dict[str, List[dict]]:
    """Chronological trusted-measured entries per metric name, extracted
    from every non-retracted schema record in trajectory rows.
    Legacy (pre-schema) rows carry no gated metrics and contribute
    nothing — they stay visible as history but cannot anchor a
    regression verdict.  Row-level ``ok`` is deliberately NOT required:
    a record whose *flagship* was unmeasured or carried forward logs
    ``ok: false`` (so it never becomes a ``last_good``), but its
    per-metric blobs carry their own provenance + trust — a trusted
    freshly-measured dp8/baseline metric inside such a record is a
    legitimate regression anchor."""
    series: Dict[str, List[dict]] = {}
    for row in rows:
        if row.get("retracted"):
            continue
        res = row.get("result", {})
        if not isinstance(res, dict) or res.get("schema") != SCHEMA:
            continue
        for name, blob in (res.get("metrics") or {}).items():
            if not _trusted_measured(blob):
                continue
            series.setdefault(name, []).append({
                "value": float(blob["value"]),
                "spread_frac": float(blob.get("spread_frac") or 0.0),
                "direction": blob.get("direction", "higher"),
                "unit": blob.get("unit", ""),
                "ts": row.get("ts") or res.get("ts"),
                "stage": row.get("stage", "?"),
            })
    return series


@dataclasses.dataclass
class RegressionReport:
    """Outcome of diffing one new record against the trajectory."""

    regressions: List[dict]
    improvements: List[dict]
    unchanged: List[dict]
    skipped: List[Tuple[str, str]]   # (metric, reason)

    @property
    def ok(self) -> bool:
        return not self.regressions

    def format(self) -> str:
        lines: List[str] = []
        for r in self.regressions:
            lines.append(
                f"BENCH REGRESSION metric={r['metric']}: "
                f"{r['baseline']:g} -> {r['measured']:g} {r['unit']} "
                f"({-r['change_frac']:+.1%} in the worse direction), "
                f"gate {r['gate_frac']:.0%} (min-drop {r['min_drop']:.0%}"
                f", baseline spread {r['baseline_spread']:.0%}, new "
                f"spread {r['new_spread']:.0%}); baseline "
                f"stage={r['baseline_stage']} ts={r['baseline_ts']}")
        for r in self.improvements:
            lines.append(
                f"bench improvement metric={r['metric']}: "
                f"{r['baseline']:g} -> {r['measured']:g} {r['unit']} "
                f"({r['change_frac']:+.1%})")
        for r in self.unchanged:
            lines.append(
                f"bench unchanged metric={r['metric']}: "
                f"{r['baseline']:g} -> {r['measured']:g} {r['unit']} "
                f"({r['change_frac']:+.1%} within gate "
                f"{r['gate_frac']:.0%})")
        for name, reason in self.skipped:
            lines.append(f"bench skipped metric={name}: {reason}")
        return "\n".join(lines) if lines else "benchdiff: nothing to compare"

    def raise_first(self) -> None:
        """Raise a typed :class:`BenchRegression` for the worst finding
        (largest gated exceedance), for callers that want the PR-2
        style exception instead of an exit code."""
        if not self.regressions:
            return
        worst = max(self.regressions,
                    key=lambda r: -r["change_frac"] - r["gate_frac"])
        raise BenchRegression(
            f"{worst['metric']} regressed {-worst['change_frac']:.1%} "
            f"(gate {worst['gate_frac']:.0%}): {worst['baseline']:g} -> "
            f"{worst['measured']:g} {worst['unit']}",
            metric=worst["metric"], baseline=worst["baseline"],
            measured=worst["measured"],
            drop_frac=-worst["change_frac"])


def diff(new_rec: dict, rows: Sequence[dict], *,
         min_drop: Optional[float] = None) -> RegressionReport:
    """Diff ``new_rec``'s gated metrics against the stored trajectory.

    ``min_drop`` is the sensitivity floor (default
    ``DPX_BENCH_MIN_DROP``): changes smaller than it are never flagged
    even when both spreads are tiny — run-to-run noise below it is not
    worth a red CI.  The effective gate per metric is
    ``max(min_drop, baseline spread, new spread)``.
    """
    if min_drop is None:
        from ..runtime import env
        min_drop = float(env.get("DPX_BENCH_MIN_DROP"))
    base_series = metric_series(rows)
    regressions: List[dict] = []
    improvements: List[dict] = []
    unchanged: List[dict] = []
    skipped: List[Tuple[str, str]] = []

    metrics = (new_rec or {}).get("metrics") or {}
    for name in sorted(metrics):
        blob = metrics[name]
        if not _trusted_measured(blob):
            if not isinstance(blob, dict):
                why = "malformed metric blob (not a dict)"
            elif blob.get("provenance") == "last_good":
                why = "carry-forward (not a fresh measurement)"
            else:
                why = blob.get("untrusted_reason", "untrusted")
            skipped.append((name, f"new side not comparable: {why}"))
            continue
        series = base_series.get(name)
        if not series:
            skipped.append((name, "no trusted measured baseline in "
                            "trajectory"))
            continue
        base = series[-1]
        if base["value"] == 0:
            skipped.append((name, "baseline value is 0 — relative "
                            "change undefined"))
            continue
        new_spread = float(blob.get("spread_frac") or 0.0)
        gate = max(min_drop, base["spread_frac"], new_spread)
        direction = blob.get("direction", "higher")
        # change_frac > 0 means BETTER in the metric's own direction
        delta = (float(blob["value"]) - base["value"]) / base["value"]
        change = delta if direction == "higher" else -delta
        entry = {
            "metric": name, "unit": blob.get("unit", ""),
            "baseline": base["value"], "measured": float(blob["value"]),
            "change_frac": round(change, 4),
            "gate_frac": round(gate, 4), "min_drop": min_drop,
            "baseline_spread": base["spread_frac"],
            "new_spread": new_spread,
            "baseline_stage": base["stage"], "baseline_ts": base["ts"],
        }
        if change < -gate:
            regressions.append(entry)
        elif change > gate:
            improvements.append(entry)
        else:
            unchanged.append(entry)
    return RegressionReport(regressions=regressions,
                            improvements=improvements,
                            unchanged=unchanged, skipped=skipped)
