"""Device time of one decode program by the scopes PR 28 added
(``moe`` > ``route``, ``dispatch``, ``experts``, ``shared``, ``combine``;
``hc``), the expert layers' counters over the traced part, read from the
two ``dpx:serve.stats`` marks that bracket it, and the five per-layer
numbers made of them (:func:`readings`). None where the program has no
such scope or mark (a parent without them, a CPU run).

These are NOT per-layer metrics of ``BENCHMARK.json`` yet:
``tests/chipbench/test_chipbench_golden.py`` holds every file under
``layer_metrics/`` to ``golden_readings.json`` and asks every serving
metric for a value on the recorded StarCoder2 trace, so a new reader
needs both of those files edited, which only a ``benchmark`` PR may do
(PERF.md section 7). Until then ``chipbench/scope_dump.py`` prints them
by hand after a traced run."""

from chipbench import flops_moe, program_trace

SCOPES = ("moe", "route", "dispatch", "experts", "shared", "combine", "hc")
#: the grouped matmul is XLA's own kernel, and its expansion on the TPU
#: names the two custom calls it is made of after itself
#: (``ragged-dot-none``, ``ragged-dot-metadata``): they carry no name
#: stack, so they are told by that name and counted under ``moe`` >
#: ``experts``, where ``parallel/moe.py`` calls them
GROUPED_MATMUL = "ragged-dot"


def decode_ops(cell):
    """(short name, name stack, nanoseconds) of every leaf op inside an
    execution of the decode program, and the number of executions, first
    chip: ``program_trace.ops_of_program`` with the op's own name kept."""
    import bisect

    pt = program_trace.of(cell)
    if pt is None or not pt.ops:
        return None, 0
    chip = min(pt.ops)
    runs = sorted((s, e) for n, s, e in pt.modules.get(chip, ())
                  if program_trace.is_decode_program(n))
    if not runs:
        return None, 0
    starts = [s for s, _ in runs]
    out = []
    for short, s, e, stack in pt.leaf_ops(chip):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            out.append((short, stack, e - s))
    return out, len(runs)


def decode_scope_ms(cell):
    """``{scope: ms a decode program}`` plus ``total``; ``experts`` and
    the others under ``moe`` count only inside it."""
    pt = program_trace.of(cell)
    if pt is None:
        return None
    if "decode_scope_ms" not in pt.memo:
        ops, runs = decode_ops(cell)
        out = None
        if ops:
            out = dict.fromkeys(SCOPES + ("total",), 0.0)
            for short, stack, ns in ops:
                names = program_trace.scopes(stack)
                if GROUPED_MATMUL in short or GROUPED_MATMUL in stack:
                    names = {"moe", "experts"}
                out["total"] += ns
                if "hc" in names:
                    out["hc"] += ns
                if "moe" in names:
                    out["moe"] += ns
                    for part in SCOPES[1:6]:
                        if part in names:
                            out[part] += ns
            out = {k: v / runs / 1e6 for k, v in out.items()}
        pt.memo["decode_scope_ms"] = out
    return pt.memo["decode_scope_ms"]


def moe_counts(cell):
    """What the expert layers counted between the first and the last
    ``serve.stats`` mark of the trace: ``{steps, layers, routed,
    touched}`` (sums over the steps and layers), or None."""
    pt = program_trace.of(cell)
    marks = pt.spans_named("serve.stats") if pt else []
    if len(marks) < 2:
        return None
    a, b = marks[0][4], marks[-1][4]
    try:
        steps = int(b["moe_decode_steps"]) - int(a["moe_decode_steps"])
        out = {"steps": steps, "layers": int(b["moe_layers"]),
               "routed": int(b["moe_tokens_routed"])
               - int(a["moe_tokens_routed"]),
               "touched": int(b["moe_experts_touched"])
               - int(a["moe_experts_touched"])}
    except (KeyError, TypeError, ValueError):
        return None
    return out if steps > 0 else None


def readings(cell, say=print):
    """``{name: value}`` of the expert layer's and the residual path's
    numbers for this run's trace, as five readers would return them:
    ``moe_device_ms`` (ms a decode program under ``moe``),
    ``moe_dispatch_share`` (% of it under ``route`` + ``dispatch`` +
    ``combine``), ``moe_experts_touched_share`` (% of routed experts x
    expert layers that got a token in a decode step),
    ``moe_experts_roofline`` (% : the least time of the grouped matmuls,
    the larger of the touched experts' weight bytes over the HBM peak and
    the routed pairs' FLOPs over the bf16 peak, over the device time of
    ``moe`` > ``experts``) and ``hc_device_ms``. A number that finds
    nothing to read is left out."""
    out = {}
    split, c = decode_scope_ms(cell), moe_counts(cell)
    if split and split["hc"]:
        out["hc_device_ms"] = split["hc"]
    if split and split["moe"]:
        say("moe_device_ms: " + " ".join(
            f"{k} {split[k]:.3f}" for k in SCOPES[1:6])
            + f" of {split['moe']:.3f} ms, the program "
            f"{split['total']:.3f} ms")
        out["moe_device_ms"] = split["moe"]
        out["moe_dispatch_share"] = 100.0 * (
            split["route"] + split["dispatch"] + split["combine"]) \
            / split["moe"]
    if c is not None:
        per_step = c["touched"] / c["steps"]
        say(f"moe_experts_touched_share: {per_step:.1f} experts and "
            f"{c['routed'] / c['steps']:.1f} (token, expert) pairs a decode "
            f"step over {c['layers']} layers, {c['steps']} steps")
        out["moe_experts_touched_share"] = 100.0 * per_step / (
            cell.config["n_routed_experts"] * c["layers"])
    if c is not None and split and split["experts"] and cell.peaks:
        t_bytes = flops_moe.experts_bytes(cell.config, c["touched"]
                                          / c["steps"]) \
            / cell.peaks["hbm_bytes_per_s"]
        t_flops = flops_moe.experts_flops(cell.config, c["routed"]
                                          / c["steps"]) \
            / cell.peaks["bf16_flops_per_s"]
        least_ms = max(t_bytes, t_flops) * 1e3
        say(f"moe_experts_roofline: bound by "
            f"{'bytes' if t_bytes >= t_flops else 'flops'}, least "
            f"{least_ms:.3f} ms, took {split['experts']:.3f} ms a decode "
            f"program")
        out["moe_experts_roofline"] = 100.0 * least_ms / split["experts"]
    return out
