"""By hand, after a traced run of a cell of kind ``serve_blocks`` in this
checkout: where one block-step program's device time went.

    python chipbench/scope_dump_blocks.py --workload <cell> [--top 40]

Reads the trace the run left under ``.chipbench_runs/<cell>/trace/`` and
prints ``scope_split_blocks.readings`` (no metrics of the manifest yet)
and the ``--top`` name stacks of the block-step program by device time a
program. The benchmark's own runs never run this."""

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
    from chipbench import scope_split, scope_split_blocks

    cell = Cell(REPO, args.workload, 0, 0.0, 1)
    with open(os.path.join(HERE, "peaks.json")) as f:
        cell.peaks = json.load(f)["TPU v5 lite"]
    print(json.dumps(scope_split_blocks.readings(cell)))
    ops, runs = scope_split.decode_ops(cell)
    if not ops:
        raise SystemExit("no block-step program in the trace")
    by_stack = collections.Counter()
    for short, stack, ns in ops:
        by_stack[stack or f"(no name stack) {short}"] += ns
    total = sum(by_stack.values())
    print(f"{runs} block-step programs, {total / runs / 1e6:.3f} ms each")
    for stack, ns in by_stack.most_common(args.top):
        print(f"{ns / runs / 1e6:9.4f} ms  {stack}")


if __name__ == "__main__":
    main()
