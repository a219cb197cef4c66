"""Device time a training step in the backward pass: operations whose name
stack holds JAX's ``transpose(jvp`` and not ``rematted_computation``. In
milliseconds; the split is ``program_trace.step_class``."""

from chipbench import program_trace


def read(trace, counters, cell):
    split = program_trace.step_split_ms(cell)
    return None if split is None else split["bwd"]
