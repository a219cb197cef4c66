"""Mean device duration of one execution of a prefill program, read from the
device plane's program line by the programs' name (``prefill_b<bucket>``,
one a bucket; all buckets together). Since PR 31 an execution is one
CHUNK of a prompt (at most ``chunk_tokens`` tokens, or the largest
bucket), so this is the mean over the chunks of the traced part, not over
prompts; a prompt that fits one chunk is one execution. Nothing to read
in a traced part that holds no admission (``trace_admissions`` in the mix
keeps two in it)."""

from chipbench import program_trace


def read(trace, counters, cell):
    pt = program_trace.of(cell)
    evs = [(s, e) for evs in (pt.modules.values() if pt else ())
           for n, s, e in evs if "prefill_b" in n]
    if not evs:
        return None
    return sum(e - s for s, e in evs) / len(evs) / 1e6
