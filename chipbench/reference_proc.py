"""The training reference in a process of its own.

    python -m chipbench.reference_proc --root <root> --workload <cell> --seed <n> --out <file>

The float32 follower holds as much state as the program it checks (the
parameters and both moments), plus its own activations, so in one process
with the program the peak memory read would be the reference's. It runs
here first, writes what it found, and exits; only then does ``run.py``
touch the chip (one process may hold it at a time). Its whole time, start
of the process included, is not counted in ``setup_s``."""

import argparse
import json
import sys
import time


def main():
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--cpu", action="store_true",
                    help="the tests' entry: no chip is looked for")
    args = ap.parse_args()

    from chipbench import run, traffic_gen
    cell = run.Cell(args.root, args.workload, args.seed, 0.0, 0)
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", cell.chips)
    run.setup_compile_cache()
    devices = run.find_devices(cell, require_chip=not args.cpu)

    from chipbench.reference import train_steps
    cfg, job = cell.config, cell.traffic
    feed = traffic_gen.TrainFeed(job, cell.seed, cfg["vocab_size"],
                                 len(devices))
    ref = train_steps.follow(
        cfg, cell.seed, [feed.batch(i) for i in range(job["check_steps"])],
        job["optimizer"], job["reference_row_block"], devices=devices)
    to_float = lambda t: jax.tree_util.tree_map(float, t)
    ref = {"losses": ref["losses"], "grad_norms": to_float(ref["grad_norms"]),
           "delta_norms": to_float(ref["delta_norms"]),
           "peak_bytes": run.device_record(devices)["memory_peak_bytes"],
           "seconds": time.perf_counter() - t0}
    with open(args.out, "w") as f:
        json.dump(ref, f)


if __name__ == "__main__":
    sys.exit(main())
