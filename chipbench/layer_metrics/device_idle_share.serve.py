"""Share of the traced window in which no operation ran on the chip
(1 - union of device-op intervals / window), in percent."""


def read(trace, counters, cell):
    share = trace.idle_share()
    return None if share is None else 100.0 * share
