"""Tokens the engine emitted per loop iteration inside the window
(``stats()``: ``tokens_emitted`` / ``iterations``, after - before)."""


def read(trace, counters, cell):
    it = counters.get("iterations")
    return None if not it else counters["tokens_emitted"] / it
