"""By hand, after a traced run of a cell of kind ``serve_mixers`` in this
checkout: where the decode program's and the largest prefill program's
device time went, linear layers and sparse layers apart.

    python chipbench/scope_dump_mixers.py --workload <cell> [--top 40]

Reads the trace the run left under ``.chipbench_runs/<cell>/trace/`` and
prints ``scope_split_mixers.readings`` (no metrics of the manifest yet) and
the ``--top`` name stacks of the decode program and of the largest prefill
program by device time a program. The benchmark's own runs never run
this."""

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
    from chipbench import program_trace, scope_split_mixers

    cell = Cell(REPO, args.workload, 0, 0.0, 1)
    with open(os.path.join(HERE, "peaks.json")) as f:
        cell.peaks = json.load(f)["TPU v5 lite"]
    print(json.dumps(scope_split_mixers.readings(cell)))
    for what, is_program in (
            ("decode", program_trace.is_decode_program),
            ("largest prefill", scope_split_mixers.largest_prefill(cell))):
        ops, runs = program_trace.ops_of_program(cell, is_program)
        if not ops:
            print(f"no {what} program in the trace")
            continue
        by_stack = collections.Counter()
        for stack, ns in ops:
            by_stack[stack or "(no name stack)"] += ns
        total = sum(by_stack.values())
        print(f"{runs} {what} programs, {total / runs / 1e6:.3f} ms each")
        for stack, ns in by_stack.most_common(args.top):
            print(f"{ns / runs / 1e6:9.4f} ms  {stack}")


if __name__ == "__main__":
    main()
