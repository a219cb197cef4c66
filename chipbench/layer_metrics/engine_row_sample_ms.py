"""Mean host time of one row's slice, key upload and sampler dispatch (the
program's span ``serve.row.sample``), a row."""

from chipbench import program_trace


def read(trace, counters, cell):
    return program_trace.span_mean_ms(cell, "serve.row.sample")
