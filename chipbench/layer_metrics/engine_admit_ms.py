"""Mean host time of one CHUNK of a prompt's prefill (the program's span
``serve.admit``; since PR 31 one span a chunk of at most ``chunk_tokens``
tokens, at most one an engine iteration while rows are running): for a
chunk that is not its prompt's last the uploads and the chunk program's
dispatch alone; for the last, which for a prompt of one chunk is the
whole admission, also the wait for the chunk, the sampler and the first
token's fetch. The prefix lookup and the page allocation (``pool.begin``)
run before the first chunk and outside the span. The mean over the chunks
of the traced part, waited for or not, a chunk. Nothing to read in a
traced part that holds no admission (``trace_admissions`` in the mix
keeps two in it)."""

from chipbench import program_trace


def read(trace, counters, cell):
    return program_trace.span_mean_ms(cell, "serve.admit")
