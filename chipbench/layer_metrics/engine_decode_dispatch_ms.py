"""Mean host time of handing the decode program its arguments (the program's
span ``serve.decode.dispatch``: four uploads and the dispatch), an
iteration."""

from chipbench import program_trace


def read(trace, counters, cell):
    return program_trace.span_mean_ms(cell, "serve.decode.dispatch")
