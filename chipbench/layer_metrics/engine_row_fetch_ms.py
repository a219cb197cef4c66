"""Mean host time of one row's blocking device-to-host read of its token (the
program's span ``serve.row.fetch``), a row."""

from chipbench import program_trace


def read(trace, counters, cell):
    return program_trace.span_mean_ms(cell, "serve.row.fetch")
