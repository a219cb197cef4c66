"""By hand, after a traced run of a ``train_mtp`` cell in this checkout:
where one training step's device time went, by name stack.

    python chipbench/scope_dump_train.py --workload <cell> [--top 40]

Reads the trace the run left under ``.chipbench_runs/<cell>/trace/`` and
prints the by-hand numbers of ``scope_split_train.readings`` (they are no
metrics of the manifest yet) and the ``--top`` name stacks of the step
program by device time a step (an operation without a name stack under
its own short name). The load numbers come from the window means of the
step's own metrics, which the run left beside the trace
(``step_counters.json``). The benchmark's own runs never run this."""

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
    from chipbench import scope_split_train

    cell = Cell(REPO, args.workload, 0, 0.0, 1)
    with open(os.path.join(HERE, "peaks.json")) as f:
        cell.peaks = json.load(f)["TPU v5 lite"]
    counters = {}
    left = os.path.join(cell.out_dir, "step_counters.json")
    if os.path.exists(left):
        with open(left) as f:
            counters = json.load(f)
    print(json.dumps(scope_split_train.readings(cell, counters)))
    ops, runs = scope_split_train.step_ops(cell)
    if not ops:
        raise SystemExit("no step program in the trace")
    by_stack = collections.Counter()
    for short, stack, ns, _ in ops:
        by_stack[stack or f"(no name stack) {short}"] += ns
    total = sum(by_stack.values())
    print(f"{len(runs)} steps, {total / len(runs) / 1e6:.3f} ms each")
    for stack, ns in by_stack.most_common(args.top):
        print(f"{ns / len(runs) / 1e6:9.4f} ms  {stack}")


if __name__ == "__main__":
    main()
