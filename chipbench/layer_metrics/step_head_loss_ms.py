"""Device time a training step in the vocabulary projection and the loss,
forward, backward and recomputed: operations under the program's scope
``head``, or under ``loss`` and none of the model's scopes. An overlay on
the forward, backward and recompute times, not a further part. In
milliseconds; the split is ``program_trace.step_class``."""

from chipbench import program_trace


def read(trace, counters, cell):
    split = program_trace.step_split_ms(cell)
    return None if split is None else split["head_loss"]
