"""Time of the collective operations (all-gather, reduce-scatter,
all-reduce, ...) during which no other operation ran on that chip, in
milliseconds a step, averaged over the chips."""

from chipbench import trace_reduce


def read(trace, counters, cell):
    steps = counters.get("traced_steps")
    if not steps or not trace.op_events(trace_reduce.is_collective):
        return None
    return 1e3 * trace.exposed_s(trace_reduce.is_collective) / steps
