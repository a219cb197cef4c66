"""The seeded faults of a cell of kind ``train_mtp``, read on the chip at
the cell's own size.

    python chipbench/faults_train.py --workload <name> --seeds a,b,c [--seconds s] [--faults constant_rate,half_batch]

``constant_rate`` runs the cell with the program built at the job's
constant rate where the job names a warm-up (the optimizer the job built
before it named ``warmup_steps``, the schedule ignored), the reference as
every run has it. ``half_batch`` runs no program: the fault is planted in
the reference put in the program's place, which follows the first step on
the first half of every row's positions (half of the batch left out, the
mean taken over the rest) and is compared with the reference of the
whole. A step that returns its state unchanged is not here: it reads 1 in
``param_change_worst_leaf`` by the measure itself and needs no run (and a
step that donates its state cannot hand it back: the tests plant that
fault at a tiny size with donation off). The numbers ``correct`` compares
are printed as each fault reads them; a limit stands only where the
smallest of these lies well above the largest that sound runs give
(PERF.md section 2). The readings need no long window (``--seconds``).
The benchmark's own runs never run this."""

import argparse
import importlib
import json
import os
import sys
import time

from run import REPO, Cell, Tracer, open_cell, say  # noqa: E402

sys.path.insert(0, REPO)        # run.py took chipbench/ itself off the path


def half_batch(cell, kind, devices):
    """The first step's loss and gradient as the reference gives them on
    the first half of every row, against the reference of the whole."""
    from chipbench import traffic_gen
    from chipbench.reference import train_steps_mtp

    cfg, job, ref = cell.config, cell.traffic, cell.reference
    feed = traffic_gen.TrainFeed(job, cell.seed, cfg["vocab_size"],
                                 len(devices))
    half = train_steps_mtp.follow(
        cfg, cell.seed, [feed.batch(0)[:, :job["seq"] // 2 + 1]], job,
        devices=devices)
    return {"loss_rel_gap.step1": abs(half["losses"][0] - ref["losses"][0])
            / abs(ref["losses"][0]),
            "grad_norm_worst_leaf": kind.worst_leaf_gap(
                half["grad_norms"], ref["grad_norms"])}


def constant_rate(cell, kind, devices):
    """The cell's run with the schedule ignored: every number ``correct``
    compares."""
    built = kind.optimizer
    kind.optimizer = lambda o: built(
        {k: v for k, v in o.items() if k != "warmup_steps"})
    try:
        run = kind.run(cell, devices, Tracer(cell), time.perf_counter())
    finally:
        kind.optimizer = built
    return {c["name"]: c["value"] for c in run["checks"]}


FAULTS = {"constant_rate": constant_rate, "half_batch": half_batch}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--faults", default="constant_rate,half_batch")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    # every seed's reference first, each in its process: once this one
    # has touched the chip no child can have it
    refs = {}
    for seed in seeds:
        cell = Cell(REPO, args.workload, seed, args.seconds, 0)
        os.makedirs(cell.out_dir, exist_ok=True)
        kind = importlib.import_module(
            f"chipbench.kinds.{cell.traffic['kind']}")
        kind.before_devices(cell, True)
        refs[seed] = cell.reference
    out = []
    for seed in seeds:
        for fault in args.faults.split(","):
            cell, kind, devices = open_cell(REPO, args.workload, seed,
                                            args.seconds, 0, True,
                                            reference=False)
            cell.reference = refs[seed]
            reading = FAULTS[fault](cell, kind, devices)
            say(f"fault {fault} seed {seed}: {json.dumps(reading)}")
            out.append({"seed": seed, "fault": fault, **reading})
    print(json.dumps(out))


if __name__ == "__main__":
    main()
