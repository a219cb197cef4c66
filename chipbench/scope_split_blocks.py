"""Device time of one block-step program (a model that generates by
blocks, kind ``serve_blocks``) by the program's scopes: ``moe`` >
``route``, ``dispatch``, ``experts``, ``combine``; ``decode_attention``;
``page_write``; ``head``; ``sample`` > ``denoise`` (the pick); and the
engine's block counters over the traced part, read from the two
``dpx:serve.stats`` marks that bracket it. None where the program has no
such scope or mark (a parent without them, a CPU run).

These are NOT per-layer metrics of ``BENCHMARK.json`` yet, for the reason
``scope_split.py`` gives (the golden test holds every file under
``layer_metrics/`` to a value on the recorded StarCoder2 trace, which only
a ``benchmark`` PR may edit). Until then ``chipbench/scope_dump_blocks.py``
prints them by hand after a traced run."""

from chipbench import flops_moe, program_trace, scope_split

PARTS = ("moe", "route", "dispatch", "experts", "combine",
         "decode_attention", "page_write", "head", "sample", "attn")


def block_scope_ms(cell):
    """``{scope: ms a block-step program}`` plus ``total``; ``experts``
    and the others under ``moe`` count only inside it, ``attn`` is the
    projections round the attention (``attn/qkv``, ``attn/out``)."""
    ops, runs = scope_split.decode_ops(cell)
    if not ops:
        return None
    out = dict.fromkeys(PARTS + ("total",), 0.0)
    for short, stack, ns in ops:
        names = program_trace.scopes(stack)
        if scope_split.GROUPED_MATMUL in short \
                or scope_split.GROUPED_MATMUL in stack:
            names = {"moe", "experts"}
        out["total"] += ns
        for part in ("moe", "decode_attention", "page_write", "head",
                     "sample"):
            if part in names:
                out[part] += ns
        if "moe" in names:
            for part in PARTS[1:5]:
                if part in names:
                    out[part] += ns
        elif "attn" in names and not names & {"decode_attention",
                                               "page_write"}:
            out["attn"] += ns
    return {k: v / runs / 1e6 for k, v in out.items()}


def counts(cell):
    """What the engine and its expert layers counted between the first
    and the last ``serve.stats`` mark of the trace, or None."""
    pt = program_trace.of(cell)
    marks = pt.spans_named("serve.stats") if pt else []
    if len(marks) < 2:
        return None
    a, b = marks[0][4], marks[-1][4]
    keys = ("moe_decode_steps", "moe_tokens_routed", "moe_experts_touched",
            "block_passes", "block_commits", "block_fills", "blocks_emitted",
            "tokens_emitted")
    try:
        out = {k: int(b[k]) - int(a[k]) for k in keys}
        out["moe_layers"] = int(b["moe_layers"])
    except (KeyError, TypeError, ValueError):
        return None
    return out if out["moe_decode_steps"] > 0 else None


def readings(cell, say=print):
    """``{name: value}`` for this run's trace, as readers would return
    them: ``block_passes_per_token`` (row-passes run over tokens
    streamed: 1.25 by the published procedure at four steps a block of
    four), ``block_pick_device_ms`` (confidence and fill, ms a program),
    ``head_device_ms``, ``moe_device_ms``, ``block_attention_share`` (% of
    the program under ``decode_attention``), ``moe_experts_touched_share``
    and ``moe_experts_roofline`` (as ``scope_split.readings`` counts them:
    the touched experts' weight bytes over the HBM peak, or the routed
    pairs' FLOPs over the bf16 peak, over the grouped matmuls' time). A
    number that finds nothing to read is left out."""
    out = {}
    split, c = block_scope_ms(cell), counts(cell)
    if split and split["total"] and split["moe"]:
        say("block step: " + " ".join(f"{k} {split[k]:.3f}" for k in PARTS)
            + f" of {split['total']:.3f} ms a program")
        out["block_pick_device_ms"] = split["sample"]
        out["head_device_ms"] = split["head"]
        out["moe_device_ms"] = split["moe"]
        out["block_attention_share"] = 100.0 * split["decode_attention"] \
            / split["total"]
    if c is not None:
        steps = c["moe_decode_steps"]
        if c["tokens_emitted"]:
            say(f"block_passes_per_token: {c['block_passes']} row-passes, "
                f"{c['block_commits']} of them commits, {c['block_fills']} "
                f"positions filled, {c['tokens_emitted']} tokens streamed in "
                f"{c['blocks_emitted']} blocks over {steps} programs")
            out["block_passes_per_token"] = c["block_passes"] \
                / c["tokens_emitted"]
        n_routed = cell.config["num_experts"]
        out["moe_experts_touched_share"] = 100.0 * c["moe_experts_touched"] \
            / steps / (n_routed * c["moe_layers"])
        if split and split["experts"] and cell.peaks:
            t_bytes = flops_moe.experts_bytes(
                cell.config, c["moe_experts_touched"] / steps) \
                / cell.peaks["hbm_bytes_per_s"]
            t_flops = flops_moe.experts_flops(
                cell.config, c["moe_tokens_routed"] / steps) \
                / cell.peaks["bf16_flops_per_s"]
            least_ms = max(t_bytes, t_flops) * 1e3
            say(f"moe_experts_roofline: {c['moe_experts_touched'] / steps:.1f}"
                f" experts and {c['moe_tokens_routed'] / steps:.1f} pairs a "
                f"program over {c['moe_layers']} layers, bound by "
                f"{'bytes' if t_bytes >= t_flops else 'flops'}, least "
                f"{least_ms:.3f} ms, took {split['experts']:.3f} ms")
            out["moe_experts_roofline"] = 100.0 * least_ms / split["experts"]
    return out
