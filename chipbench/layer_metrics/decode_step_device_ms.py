"""Mean device duration of one execution of the decode program, read
from the device plane's program line by the program's name."""


def read(trace, counters, cell):
    evs = trace.module_events(lambda n: "decode" in n.lower())
    if not evs:
        return None
    return sum(e - s for _, _, s, e in evs) / len(evs) / 1e6
