"""Share of the training step's device time in operations with no scope
of the program's and no ``jvp`` in their name stack, in percent: what the
split into forward, backward, recompute and update cannot place (copies
the compiler added carry no name stack at all)."""

from chipbench import program_trace


def read(trace, counters, cell):
    split = program_trace.step_split_ms(cell)
    if split is None:
        return None
    total = sum(split[k] for k in ("fwd", "bwd", "remat", "optimizer",
                                   "unscoped"))
    return 100.0 * split["unscoped"] / total
