"""Mean duration of one pass of the engine loop's body (the program's span
``serve.iter``) in the traced part. Prints the span's own time: what none
of its child spans covers."""

from chipbench import program_trace


def read(trace, counters, cell):
    mean = program_trace.span_mean_ms(cell, "serve.iter")
    if mean is not None:
        own = program_trace.self_time_share(cell, "serve.iter")
        print(f"chipbench: engine_iter_ms: {100 * own:.2f} % of serve.iter "
              f"is its own time, under none of its child spans", flush=True)
    return mean
