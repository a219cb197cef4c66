"""Mean host time of admitting one request (the program's span
``serve.admit``: prefix lookup, page allocation, uploads, the prefill's
dispatch, and the wait for its first token), a request."""

from chipbench import program_trace


def read(trace, counters, cell):
    return program_trace.span_mean_ms(cell, "serve.admit")
