"""95th percentile of the time from a request's DUE time to its first
streamed token, over the requests due in the window, by the benchmark's
own clock. A per-layer metric and not an end-to-end one in cells whose
window holds too few requests for it to be steady (PERF.md section 2)."""


def read(trace, counters, cell):
    return counters.get("ttft_p95_ms")
