"""The control of ``correct``, read on the chip at a cell's own size.

    python chipbench/control.py --workload <name> --seeds a,b,c [--seconds s]

For each seed, the reference is put in the program's place and computed
in the precision next below the configuration's (fp8 operands,
chipbench/lowprec.py); the numbers ``correct`` compares are printed as the
control reads them. A limit stands only where the smallest of these lies
well above the largest that sound runs of the program give (PERF.md
section 2). The benchmark's own runs never run this."""

import argparse
import json
import time

from run import REPO, Tracer, open_cell, say  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        cell, kind, devices = open_cell(REPO, args.workload, seed,
                                        args.seconds, 0, True,
                                        reference=False)
        from chipbench import lowprec
        if hasattr(kind, "control"):
            reading = kind.control(cell, devices)
        else:
            run = kind.run(cell, devices, Tracer(cell), time.perf_counter(),
                           control_mm=lowprec.mm_fp8)
            reading = {c["name"]: c["value"] for c in run["checks"]}
        say(f"control seed {seed}: {json.dumps(reading)}")
        out.append({"seed": seed, **reading})
    print(json.dumps(out))


if __name__ == "__main__":
    main()
