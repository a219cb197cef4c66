"""Device time of the flash-attention (Mosaic) kernels over the device's
busy time, in percent."""

from chipbench import trace_reduce


def read(trace, counters, cell):
    busy = trace.busy_s()
    t = trace.op_seconds(trace_reduce.is_mosaic)
    return None if busy <= 0 or t <= 0 else 100.0 * t / busy
