"""Share of the traced window in which no operation ran on the chip that no
span of the engine's thread covers, in percent of the window. With its
three siblings it sums to ``device_idle_share.serve``: the same busy union
over the same window."""

from chipbench import program_trace


def read(trace, counters, cell):
    parts = program_trace.engine_idle_parts(trace, cell)
    return None if parts is None else parts["unattributed"]
