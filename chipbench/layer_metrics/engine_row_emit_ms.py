"""Mean host time of one row's bookkeeping, the client's ``on_token`` and
retirement (the program's span ``serve.row.emit``), a row."""

from chipbench import program_trace


def read(trace, counters, cell):
    return program_trace.span_mean_ms(cell, "serve.row.emit")
