"""Share of the traced window in which no operation ran on the chip while the
engine's thread was inside its per-row loop (the program's span
``serve.decode.rows``: a slice, a key upload, a sampler dispatch, a
blocking read and the client's callback for every running row), in percent
of the window. With its three siblings it sums to
``device_idle_share.serve``: the same busy union over the same window."""

from chipbench import program_trace


def read(trace, counters, cell):
    parts = program_trace.engine_idle_parts(trace, cell)
    return None if parts is None else parts["row_loop"]
