"""perfbench — the variance-gated benchmark subsystem.

Replaces the ad-hoc statistics scattered through the old 743-line
``bench.py`` with one policy every perf number the repo prints goes
through (ROADMAP item 5 — the gating dependency for every scaling claim
items 2-4 want to make):

* :mod:`.stats` — warmup-discarded repeated trials, median + IQR, a hard
  spread gate, affinity/thread pinning;
* :mod:`.runner` — execution: the backend probe in a child (the parent
  stays off the chip), the parseable-record-no-matter-what subprocess
  contract;
* :mod:`.record` — versioned schema-validated records (a null metric is
  a schema violation; ``vs_baseline`` is structurally withheld with a
  reason when either side fails the gate) appended to the line-JSON
  trajectory store via the thread-safe ``append_event`` path;
* :mod:`.roofline_gate` — the analytic ceilings folded into every
  flagship record as achieved/ceiling, plus the plausibility gate;
* :mod:`.trajectory` — ``last_good`` carry-forward and statistical
  regression diffing (CLI: ``tools/benchdiff.py``);
* :mod:`.errors` — the typed failure vocabulary (PR-2 style).

``bench.py`` is now a thin shim over this package; the serve/ckpt
benches and the CI bench-smoke job all build on it.  Every
module keeps cross-package imports function-scope so ``tools/
benchdiff.py`` can load the subsystem without the heavy package
``__init__`` (the ``tools/dpxlint.py`` contract); docs in
``docs/benchmarking.md``.
"""

from . import errors, record, roofline_gate, runner, stats, trajectory  # noqa: F401
from .errors import BenchError, BenchRegression, RecordInvalid  # noqa: F401
from .record import (append_row, iter_rows, make_metric,  # noqa: F401
                     make_record, validate_record)
from .stats import (TrialStats, gated_ratio, measure,  # noqa: F401
                    measure_until, summarize)
from .trajectory import RegressionReport, diff, last_good_flagship  # noqa: F401

__all__ = [
    "errors", "record", "roofline_gate", "runner", "stats", "trajectory",
    "BenchError", "BenchRegression", "RecordInvalid",
    "append_row", "iter_rows", "make_metric", "make_record",
    "validate_record", "TrialStats", "gated_ratio", "measure",
    "measure_until", "summarize", "RegressionReport", "diff",
    "last_good_flagship",
]
