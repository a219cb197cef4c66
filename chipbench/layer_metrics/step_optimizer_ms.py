"""Device time a training step in the update: operations under the program's
scopes ``optimizer`` or ``cast`` (the bf16 working copy) and under no
``jvp``. In milliseconds; the split is ``program_trace.step_class``."""

from chipbench import program_trace


def read(trace, counters, cell):
    split = program_trace.step_split_ms(cell)
    return None if split is None else split["optimizer"]
