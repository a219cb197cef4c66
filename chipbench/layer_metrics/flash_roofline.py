"""The flash-attention kernels' share of their roofline: the least time
the chip could take for every traced call (the larger of FLOPs over the
bf16 peak and bytes over the HBM peak, from the call's shapes by
chipbench/flops.py) over the device time the calls took. Each layer makes
three calls a step (forward, dK/dV, dQ) and, under remat ``full``, the
forward a second time; the calls are told apart by order within a layer:
the kinds' times differ, so the count per kind is what is used. Prints
which peak bounds."""

from chipbench import flops, trace_reduce


def read(trace, counters, cell):
    events = trace.op_events(trace_reduce.is_mosaic)
    if not events or not cell.peaks or not counters.get("traced_steps"):
        return None
    cfg = cell.config
    shape = flops.config_shape(cfg, counters["seq"])
    base = dict(batch=counters["rows_per_chip"], n_heads=shape["n_heads"],
                n_kv_heads=shape["n_kv_heads"], seq_q=counters["seq"],
                seq_k=counters["seq"],
                head_dim=shape["dim"] // shape["n_heads"])
    # calls a layer a step: forward (twice under full remat), dK/dV, dQ
    fwd_calls = 2 if cell.traffic.get("remat") == "full" else 1
    per_layer = [("fwd", fwd_calls), ("dkv", 1), ("dq", 1)]
    calls_per_step = shape["n_layers"] * sum(n for _, n in per_layer)
    per_chip = len(events) / len(trace.chips)
    steps = per_chip / calls_per_step
    least = 0.0
    bound = {}
    for kind, n in per_layer:
        f, b = flops.flash_call_cost(kind=kind, **base)
        tf = f / cell.peaks["bf16_flops_per_s"]
        tb = b / cell.peaks["hbm_bytes_per_s"]
        bound[kind] = "flops" if tf >= tb else "bytes"
        least += n * max(tf, tb) * shape["n_layers"] * steps
    took = trace.op_seconds(trace_reduce.is_mosaic)
    print(f"chipbench: flash_roofline: {per_chip:.0f} calls a chip "
          f"({steps:.2f} steps of {calls_per_step}), bound by {bound}, "
          f"least {least:.4f} s, took {took:.4f} s", flush=True)
    return 100.0 * least / took
