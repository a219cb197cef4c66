"""Programs the step builder traced and compiled between the start and
the end of the window (``step.compiles`` after - before). Must be 0."""


def read(trace, counters, cell):
    return counters.get("compiles_in_window")
