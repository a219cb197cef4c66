"""Share of the decode program's device time in operations under none of
the program's scopes, in percent: what the compiler added on its own
(copies of a donated argument carry the argument's name, not a scope) and
the few index computations before the first layer."""

from chipbench import program_trace


def read(trace, counters, cell):
    split = program_trace.decode_split_ms(cell)
    return None if split is None else \
        100.0 * split["unscoped"] / split["total"]
