"""Device time a training step in recomputing the forward pass during the
backward one: operations whose name stack holds JAX's
``rematted_computation``. In milliseconds; the split is
``program_trace.step_class``."""

from chipbench import program_trace


def read(trace, counters, cell):
    split = program_trace.step_split_ms(cell)
    return None if split is None else split["remat"]
