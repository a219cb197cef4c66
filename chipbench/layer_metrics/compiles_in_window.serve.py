"""Decode, prefill and sampler programs the engine compiled between the
start of the window and the end of the drain (``stats()`` after - before).
Must be 0."""


def read(trace, counters, cell):
    return counters.get("compiles_in_window")
