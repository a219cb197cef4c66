"""Device time of the decode program and of the largest prefill program
of a model that mixes window and global layers (kind ``serve_long``), by
the program's scopes: ``decode_attention`` > ``window_attention`` /
``global_attention``, ``attn/core`` > the same two in a prefill program,
``page_write``, ``moe`` > ``experts``; and the pool's and the expert
layers' counters over the traced part, read from the two
``dpx:serve.stats`` marks that bracket it. None where the program has no
such scope or mark (a parent without them, a CPU run).

These are NOT per-layer metrics of ``BENCHMARK.json`` yet, for the reason
``scope_split.py`` gives (the golden test holds every file under
``layer_metrics/`` to a value on the recorded StarCoder2 trace, which only
a ``benchmark`` PR may edit). Until then ``chipbench/scope_dump_mixed.py``
prints them by hand after a traced run."""

from chipbench import bytes_kv, flops_moe, program_trace, scope_split

PARTS = ("window_attention", "global_attention", "page_write", "moe",
         "experts")


def scope_ms(cell, is_program):
    """``{part: ms a program}`` plus ``total`` for the programs
    ``is_program`` names, or None; ``experts`` counts only inside
    ``moe``."""
    ops, runs = program_trace.ops_of_program(cell, is_program)
    if not ops:
        return None
    out = dict.fromkeys(PARTS + ("total",), 0.0)
    for stack, ns in ops:
        names = program_trace.scopes(stack)
        if scope_split.GROUPED_MATMUL in stack:
            # XLA's own grouped matmul (an expert too large for the Mosaic
            # kernel's slab) carries no name stack: told by its name
            names = {"moe", "experts"}
        out["total"] += ns
        for part in PARTS[:4]:
            if part in names:
                out[part] += ns
        if "moe" in names and "experts" in names:
            out["experts"] += ns
    return {k: v / runs / 1e6 for k, v in out.items()}


def largest_prefill(cell):
    """The name test of the mix's largest prefill program."""
    bucket = max(cell.traffic["engine"]["buckets"])
    return lambda name: f"prefill_b{bucket}" in name


def counts(cell):
    """The pool's and the expert layers' counters from the first and the
    last ``serve.stats`` mark of the trace: the differences of what is
    summed, the means of what is a level (contexts, running rows, bytes
    do not move); or None."""
    pt = program_trace.of(cell)
    marks = pt.spans_named("serve.stats") if pt else []
    if len(marks) < 2:
        return None
    a, b = marks[0][4], marks[-1][4]
    try:
        out = {k: int(b[k]) - int(a[k]) for k in (
            "moe_decode_steps", "moe_tokens_routed", "moe_experts_touched")}
        out["moe_layers"] = int(b["moe_layers"])
        for k in ("context_tokens_mean", "active_slots", "pages_in_use"):
            out[k] = (float(a[k]) + float(b[k])) / 2.0
        for k in ("kv_resident_bytes_window", "kv_resident_bytes_global"):
            out[k] = int(b[k])
    except (KeyError, TypeError, ValueError):
        return None
    return out if out["moe_decode_steps"] > 0 else None


def readings(cell, say=print):
    """``{name: value}`` for this run's trace, as readers would return
    them. Of the decode program: ``window_attention_device_ms``,
    ``global_attention_device_ms`` (ms a program under each scope),
    ``global_attention_roofline`` (%: the resident K/V of the running rows
    in the global layers, ``bytes_kv.resident_kv_bytes`` of the marks' mean
    context times their mean running rows, over the HBM peak, over that
    time), ``moe_device_ms``, ``moe_experts_touched_share`` (% of held
    experts x expert layers that got a token in a decode step) and
    ``moe_experts_roofline`` (as ``scope_split.readings`` counts it). Of
    the largest prefill program: ``prefill_window_attention_device_ms``
    and ``prefill_global_attention_device_ms``. Of the pool:
    ``kv_resident_bytes_window``, ``kv_resident_bytes_global``,
    ``context_tokens_mean``. A number that finds nothing to read is left
    out."""
    out = {}
    dec = scope_ms(cell, program_trace.is_decode_program)
    pre = scope_ms(cell, largest_prefill(cell))
    c = counts(cell)
    if dec and dec["window_attention"] + dec["global_attention"]:
        say("decode program: " + " ".join(f"{k} {dec[k]:.3f}" for k in PARTS)
            + f" of {dec['total']:.3f} ms")
        out["window_attention_device_ms"] = dec["window_attention"]
        out["global_attention_device_ms"] = dec["global_attention"]
        if dec["moe"]:
            out["moe_device_ms"] = dec["moe"]
    if pre and pre["window_attention"] + pre["global_attention"]:
        say("largest prefill program: "
            + " ".join(f"{k} {pre[k]:.3f}" for k in PARTS)
            + f" of {pre['total']:.3f} ms")
        out["prefill_window_attention_device_ms"] = pre["window_attention"]
        out["prefill_global_attention_device_ms"] = pre["global_attention"]
    if c is None:
        return out
    for k in ("kv_resident_bytes_window", "kv_resident_bytes_global",
              "context_tokens_mean"):
        out[k] = c[k]
    steps = c["moe_decode_steps"]
    held = cell.config["num_experts"]
    out["moe_experts_touched_share"] = 100.0 * c["moe_experts_touched"] \
        / steps / (held * c["moe_layers"])
    if not cell.peaks or not dec:
        return out
    n_global = sum(t == "full_attention" for t in cell.config["layer_types"][
        :cell.config["num_hidden_layers"]])
    if dec["global_attention"]:
        tokens = c["context_tokens_mean"] * c["active_slots"]
        least_ms = bytes_kv.resident_kv_bytes(cell.config, tokens, n_global) \
            / cell.peaks["hbm_bytes_per_s"] * 1e3
        say(f"global_attention_roofline: {c['active_slots']:.1f} running "
            f"rows of {c['context_tokens_mean']:.0f} tokens (the marks' "
            f"means), {n_global} global layer(s), least {least_ms:.3f} ms, "
            f"took {dec['global_attention']:.3f} ms a decode program")
        out["global_attention_roofline"] = 100.0 * least_ms \
            / dec["global_attention"]
    if dec["experts"]:
        t_bytes = flops_moe.experts_bytes(
            cell.config, c["moe_experts_touched"] / steps) \
            / cell.peaks["hbm_bytes_per_s"]
        t_flops = flops_moe.experts_flops(
            cell.config, c["moe_tokens_routed"] / steps) \
            / cell.peaks["bf16_flops_per_s"]
        least_ms = max(t_bytes, t_flops) * 1e3
        say(f"moe_experts_roofline: {c['moe_experts_touched'] / steps:.1f} "
            f"experts and {c['moe_tokens_routed'] / steps:.1f} pairs a "
            f"decode program over {c['moe_layers']} layers, bound by "
            f"{'bytes' if t_bytes >= t_flops else 'flops'}, least "
            f"{least_ms:.3f} ms, took {dec['experts']:.3f} ms")
        out["moe_experts_roofline"] = 100.0 * least_ms / dec["experts"]
    return out
