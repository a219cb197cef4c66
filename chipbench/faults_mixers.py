"""The two seeded faults of a cell of kind ``serve_mixers``, read on the
chip at the cell's own size.

    python chipbench/faults_mixers.py --workload <name> --seeds a,b [--seconds s] [--faults dense_attention,bfloat16_state]

For each seed the cell is run once with each fault in the timed path: a
program that attends densely where it should select
(``dense_attention``), and one that keeps a linear layer's state in
bfloat16 (``bfloat16_state``); ``kinds/serve_mixers.py`` has both. The
numbers ``correct`` compares are printed as each fault reads them; a limit
stands only where the smallest of these lies well above the largest that
sound runs give (PERF.md section 2). The benchmark's own runs never run
this."""

import argparse
import json
import time

from run import REPO, Tracer, open_cell, say  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--faults", default="dense_attention,bfloat16_state")
    args = ap.parse_args()
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        for fault in args.faults.split(","):
            cell, kind, devices = open_cell(REPO, args.workload, seed,
                                            args.seconds, 0, True,
                                            reference=False)
            with kind.FAULTS[fault]():
                run = kind.run(cell, devices, Tracer(cell),
                               time.perf_counter())
            reading = {c["name"]: c["value"] for c in run["checks"]}
            say(f"fault {fault} seed {seed}: {json.dumps(reading)}")
            out.append({"seed": seed, "fault": fault, **reading})
    print(json.dumps(out))


if __name__ == "__main__":
    main()
