"""Share of the traced window in which no operation ran on the chip while the
engine's thread was inside the program's span ``serve.decode.rows``: from
the decode (or block-step) dispatch's return to the last row's emit. Since
PR 27 that is ONE blocking read of the iteration's tokens, where the host
waits for the program (``serve.decode.fetch``), the batched samplers where
a row samples, and then for every running row the filing of its token and
the client's callback (for a block generator the blocks' advance); no
longer a slice, a key upload, a sampler dispatch and a read a row. In
percent of the window. With its three siblings it sums to
``device_idle_share.serve``: the same busy union over the same window."""

from chipbench import program_trace


def read(trace, counters, cell):
    parts = program_trace.engine_idle_parts(trace, cell)
    return None if parts is None else parts["row_loop"]
