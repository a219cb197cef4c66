"""Programs XLA built (compiled, or loaded from the persistent cache) in
the traced part: the marks ``dpx:xla.compile`` that
``runtime/compile_cache.py`` drops after each. Must be 0."""

from chipbench import program_trace


def read(trace, counters, cell):
    return program_trace.compile_marks(cell)
