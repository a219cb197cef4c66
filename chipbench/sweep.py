"""Find the highest rate a serving cell sustains, once, on the chip.

    python chipbench/sweep.py --workload <name> --rates 2,3,4 --seconds 30

One process, one rate after another, each a whole run of the cell (set-up,
lead-in, window, drain, check) with the mix's ``rate_per_s`` replaced. The
rate found goes into the mix file as a number; cells then offer load at a
fixed share of it and never search. The benchmark's own runs never run this."""

import argparse
import json
import time

from run import REPO, Tracer, open_cell, say  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 77)
    args = ap.parse_args()
    rows = []
    for n, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell, kind, devices = open_cell(REPO, args.workload, args.seed + n,
                                        args.seconds, 0, True)
        cell.traffic["rate_per_s"] = rate
        run = kind.run(cell, devices, Tracer(cell), time.perf_counter())
        row = {"rate_per_s": rate, "attempted": run["attempted"],
               "failed": run["failed"],
               **{k: v for k, v in run["end_to_end"].items()
                  if k != "setup_s"},
               **{k: run["counters"][k] for k in (
                   "queue_depth", "active_slots", "window_tokens_per_s",
                   "ttft_p50_ms", "ttft_p95_ms", "iterations",
                   "tokens_emitted")},
               "checks": {c["name"]: c["value"] for c in run["checks"]}}
        say(f"sweep {json.dumps(row)}")
        rows.append(row)
    print(json.dumps(rows))


if __name__ == "__main__":
    main()
