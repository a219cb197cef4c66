"""Device time of the decode program and of the largest prefill program
of a model of linear- and sparse-attention layers (kind ``serve_mixers``),
by the program's scopes: ``decode_attention`` > ``linear_attention`` (>
``state``) / ``sparse_attention`` (> ``compress``, ``select``,
``attend``), ``attn/core`` > the same in a prefill program (a linear
layer's chunk has ``intra`` beside ``state``), ``page_write``, ``mlp``;
and the pool's counters over the traced part, read from the two
``dpx:serve.stats`` marks that bracket it. None where the program has no
such scope or mark (a parent without them, a CPU run).

These are NOT per-layer metrics of ``BENCHMARK.json`` yet, for the reason
``scope_split.py`` gives (the golden test holds every file under
``layer_metrics/`` to a value on the recorded StarCoder2 trace, which only
a ``benchmark`` PR may edit). Until then ``chipbench/scope_dump_mixers.py``
prints them by hand after a traced run."""

from chipbench import bytes_mixers, program_trace
from chipbench.scope_split_mixed import largest_prefill  # noqa: F401

#: (reading's key, the scopes an operation's name stack has to hold)
PARTS = (("linear_attention", ("linear_attention",)),
         ("linear_state", ("linear_attention", "state")),
         ("linear_intra", ("linear_attention", "intra")),
         ("sparse_attention", ("sparse_attention",)),
         ("sparse_compress", ("sparse_attention", "compress")),
         ("sparse_select", ("sparse_attention", "select")),
         ("sparse_attend", ("sparse_attention", "attend")),
         ("page_write", ("page_write",)), ("mlp", ("mlp",)))


def scope_ms(cell, is_program):
    """``{part: ms a program}`` plus ``total`` for the programs
    ``is_program`` names, or None."""
    ops, runs = program_trace.ops_of_program(cell, is_program)
    if not ops:
        return None
    out = dict.fromkeys([k for k, _ in PARTS] + ["total"], 0.0)
    for stack, ns in ops:
        names = program_trace.scopes(stack)
        out["total"] += ns
        for key, scopes in PARTS:
            if all(s in names for s in scopes):
                out[key] += ns
    return {k: v / runs / 1e6 for k, v in out.items()}


def counts(cell):
    """The pool's counters from the first and the last ``serve.stats``
    mark of the trace: the differences of what is summed, the means of
    what is a level, the last of what does not move; or None."""
    pt = program_trace.of(cell)
    marks = pt.spans_named("serve.stats") if pt else []
    if len(marks) < 2:
        return None
    a, b = marks[0][4], marks[-1][4]
    try:
        out = {k: int(b[k]) - int(a[k]) for k in (
            "sparse_decode_steps", "sparse_blocks_chosen",
            "sparse_blocks_resident", "slots_state_reset")}
        for k in ("context_tokens_mean", "active_slots", "pages_in_use"):
            out[k] = (float(a[k]) + float(b[k])) / 2.0
        for k in ("state_resident_bytes", "compressed_keys_resident_bytes",
                  "kv_resident_bytes_global", "state_layers",
                  "sparse_layers"):
            out[k] = int(b[k])
    except (KeyError, TypeError, ValueError):
        return None
    return out if out["sparse_decode_steps"] > 0 else None


def readings(cell, say=print):
    """``{name: value}`` for this run's trace, as readers would return
    them. Of the decode program: ``linear_attention_device_ms``,
    ``sparse_attention_device_ms``, ``sparse_select_device_ms`` (ms a
    program under each scope), ``sparse_blocks_chosen_share`` (% of the
    resident blocks that the decode steps of the traced part chose),
    ``sparse_attention_roofline`` (%: the compressed keys of the running
    rows' contexts and the pages they chose, ``bytes_mixers``, over the
    HBM peak, over the scope's time) and ``linear_state_roofline`` (%: the
    running rows' states read and written, over the HBM peak, over the
    scope's time). Of the largest prefill program:
    ``prefill_linear_attention_device_ms`` and
    ``prefill_sparse_attention_device_ms``. Of the pool:
    ``state_resident_bytes``, ``compressed_keys_resident_bytes``,
    ``kv_resident_bytes_global``, ``context_tokens_mean``. A number that
    finds nothing to read is left out."""
    out = {}
    dec = scope_ms(cell, program_trace.is_decode_program)
    pre = scope_ms(cell, largest_prefill(cell))
    c = counts(cell)
    line = lambda d: " ".join(f"{k} {d[k]:.3f}" for k, _ in PARTS) \
        + f" of {d['total']:.3f} ms"
    if dec and dec["linear_attention"] + dec["sparse_attention"]:
        say("decode program: " + line(dec))
        out["linear_attention_device_ms"] = dec["linear_attention"]
        out["sparse_attention_device_ms"] = dec["sparse_attention"]
        out["sparse_select_device_ms"] = dec["sparse_select"]
    if pre and pre["linear_attention"] + pre["sparse_attention"]:
        say("largest prefill program: " + line(pre))
        out["prefill_linear_attention_device_ms"] = pre["linear_attention"]
        out["prefill_sparse_attention_device_ms"] = pre["sparse_attention"]
    if c is None:
        return out
    for k in ("state_resident_bytes", "compressed_keys_resident_bytes",
              "kv_resident_bytes_global", "context_tokens_mean"):
        out[k] = c[k]
    out["sparse_blocks_chosen_share"] = 100.0 * c["sparse_blocks_chosen"] \
        / max(c["sparse_blocks_resident"], 1)
    if not cell.peaks or not dec:
        return out
    peak, cfg = cell.peaks["hbm_bytes_per_s"], cell.config
    if dec["sparse_attention"]:
        chosen = c["sparse_blocks_chosen"] / c["sparse_decode_steps"]
        tokens = c["context_tokens_mean"] * c["active_slots"]
        least_ms = (bytes_mixers.chosen_page_bytes(cfg, chosen)
                    + bytes_mixers.compressed_key_bytes(
                        cfg, tokens, c["sparse_layers"])) / peak * 1e3
        say(f"sparse_attention_roofline: {chosen:.0f} chosen blocks a "
            f"decode program (rows x {c['sparse_layers']} layers x KV "
            f"heads) and the compressed keys of {c['active_slots']:.1f} "
            f"running rows of {c['context_tokens_mean']:.0f} tokens (the "
            f"marks' means), least {least_ms:.3f} ms, took "
            f"{dec['sparse_attention']:.3f} ms a decode program")
        out["sparse_attention_roofline"] = 100.0 * least_ms \
            / dec["sparse_attention"]
    if dec["linear_attention"]:
        least_ms = bytes_mixers.state_bytes(
            cfg, c["active_slots"], c["state_layers"]) / peak * 1e3
        say(f"linear_state_roofline: {c['active_slots']:.1f} running rows' "
            f"states read and written in {c['state_layers']} layers, least "
            f"{least_ms:.3f} ms, took {dec['linear_attention']:.3f} ms a "
            f"decode program")
        out["linear_state_roofline"] = 100.0 * least_ms \
            / dec["linear_attention"]
    return out
