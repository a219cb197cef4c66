"""By hand, after a traced run of a serving cell in this checkout: why the
chip waited between two decode programs.

    python chipbench/engine_gap_dump.py --workload <cell>

Reads the trace the run left under ``.chipbench_runs/<cell>/trace/`` and
prints one JSON object: the ten numbers of ``engine_gap.METRICS`` (they
are no metrics of the manifest yet), the passes counted, what
``turnaround`` is made of, the shift of the device's plane, and beside
them what the same trace reads for the pass, the program and the idle
share, so that the sum can be checked:
``decode_gap_ms x passes / window`` is ``device_idle_share.serve`` less
the idle time before the first and after the last decode program. The
benchmark's own runs never run this."""

import argparse
import json
import sys

from run import REPO, Cell, Tracer  # noqa: E402

sys.path.insert(0, REPO)        # run.py took chipbench/ itself off the path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    from chipbench import engine_gap, program_trace, trace_reduce

    cell = Cell(REPO, args.workload, 0, 0.0, 1)
    trace = trace_reduce.load(Tracer(cell).xplane(), cell.chips)
    got = engine_gap.parts(trace, cell)
    if got is None:
        raise SystemExit("no serve.decode.upload or serve.decode.fetch "
                         "span in the trace: nothing to read")
    out = {m: engine_gap.read(trace, cell, m) for m in engine_gap.METRICS}
    out["passes"] = got["passes"]
    out["turnaround_in"] = got["turnaround_in"]
    # what the device's plane was moved later by, and the most it could be
    out["shift_ms"], out["shift_most_ms"] = got["shift"], got["shift_most"]
    out["sum_of_parts_ms"] = sum(got[p] for p in engine_gap.PARTS)
    programs = trace.module_events(program_trace.is_decode_program)
    out["decode_step_device_ms"] = sum(
        e - s for _, _, s, e in programs) / len(programs) / 1e6
    for name, span in (("engine_iter_ms", "serve.iter"),
                       ("engine_decode_dispatch_ms",
                        "serve.decode.dispatch")):
        out[name] = program_trace.span_mean_ms(cell, span)
    out["window_s"] = trace.window_s()
    out["device_idle_share.serve"] = 100.0 * trace.idle_share()
    out["gap_share_of_window"] = \
        got["gap"] * got["passes"] / 10.0 / trace.window_s()
    split = program_trace.engine_idle_parts(trace, cell)
    out.update({f"device_idle_in_{k}.serve": v for k, v in split.items()})
    print(json.dumps(out))


if __name__ == "__main__":
    main()
