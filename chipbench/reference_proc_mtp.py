"""The reference of a ``train_mtp`` job in a process of its own, as
``reference_proc.py`` is for ``train``:

    python -m chipbench.reference_proc_mtp --root <root> --workload <cell> --seed <n> --out <file>

It holds as much float32 state as the program it checks, so it runs
first, writes what it found, and exits before ``run.py`` touches the chip.
Its whole time is not counted in ``setup_s``."""

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
    run.setup_compile_cache()
    devices = run.find_devices(cell, require_chip=not args.cpu)

    from chipbench.reference import train_steps_mtp
    cfg, job = cell.config, cell.traffic
    feed = traffic_gen.TrainFeed(job, cell.seed, cfg["vocab_size"],
                                 len(devices))
    ref = train_steps_mtp.follow(
        cfg, cell.seed, [feed.batch(i) for i in range(job["check_steps"])],
        job, devices=devices)
    to_float = lambda t: jax.tree_util.tree_map(float, t)
    ref = {**ref, "grad_norms": to_float(ref["grad_norms"]),
           "delta_norms": to_float(ref["delta_norms"]),
           "peak_bytes": run.device_record(devices)["memory_peak_bytes"],
           "seconds": time.perf_counter() - t0}
    with open(args.out, "w") as f:
        json.dump(ref, f)


if __name__ == "__main__":
    sys.exit(main())
