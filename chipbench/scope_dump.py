"""By hand, after a traced run of a serving cell in this checkout: where
one decode program's device time went, by name stack.

    python chipbench/scope_dump.py --workload <cell> [--top 40]

Reads the trace the run left under ``.chipbench_runs/<cell>/trace/`` and
prints the expert layer's and the residual path's numbers
(``scope_split.readings``: they are no metrics of the manifest yet) and
the ``--top`` name stacks of the decode program by device time a
program (an operation without a name stack under its own short name).
The benchmark's own runs never run this; it is how a reader's scopes are
checked against what the compiler kept of them."""

import argparse
import collections
import json
import os
import sys

from run import HERE, REPO, Cell  # noqa: E402

sys.path.insert(0, REPO)        # run.py took chipbench/ itself off the path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--top", type=int, default=40)
    args = ap.parse_args()
    from chipbench import scope_split

    cell = Cell(REPO, args.workload, 0, 0.0, 1)
    with open(os.path.join(HERE, "peaks.json")) as f:
        cell.peaks = json.load(f)["TPU v5 lite"]
    print(json.dumps(scope_split.readings(cell)))
    ops, runs = scope_split.decode_ops(cell)
    if not ops:
        raise SystemExit("no decode program in the trace")
    by_stack = collections.Counter()
    for short, stack, ns in ops:
        by_stack[stack or f"(no name stack) {short}"] += ns
    total = sum(by_stack.values())
    print(f"{runs} decode programs, {total / runs / 1e6:.3f} ms each")
    for stack, ns in by_stack.most_common(args.top):
        print(f"{ns / runs / 1e6:9.4f} ms  {stack}")


if __name__ == "__main__":
    main()
