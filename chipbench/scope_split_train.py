"""Device time of one training step by the scopes the expert layer and
the prediction module bring (``moe`` > ``route``, ``dispatch``,
``experts``, ``shared``, ``combine``; ``mtp``), forward, recomputed and
backward apart, and the by-hand numbers of the family ``joyai`` made of
them (:func:`readings`): ``train_mfu`` by ``flops_joyai.py``'s count, the
flash kernels' roofline share at their real head width, the expert
layers' and the module's device time, the router's load. None where the
trace has no step program (a CPU run).

These are NOT per-layer metrics of ``BENCHMARK.json``: ``train_mfu`` and
``flash_roofline`` count through ``flops.config_shape``, which knows the
dense block only, and ``tests/chipbench/test_chipbench_golden.py`` holds
every file under ``layer_metrics/`` to ``golden_readings.json``; both
need a ``benchmark`` PR (PERF.md section 7). Until then
``chipbench/scope_dump_train.py`` prints them by hand after a traced
run."""

import bisect

from chipbench import flops, flops_joyai, program_trace, trace_reduce
from chipbench.scope_split import GROUPED_MATMUL

MOE_PARTS = ("route", "dispatch", "experts", "shared", "combine")
PASSES = ("fwd", "remat", "bwd")


def step_ops(cell):
    """(short name, name stack, nanoseconds, is a Mosaic call) of every
    leaf op inside an execution of the step program (the program that
    took most of the device's time), first chip; the executions as
    (start, end) ns. (None, []) without a device plane."""
    pt = program_trace.of(cell)
    if pt is None or not pt.ops or not pt.modules:
        return None, []
    chip = min(pt.ops)
    total = {}
    for n, s, e in pt.modules[chip]:
        total[n.split("(")[0]] = total.get(n.split("(")[0], 0) + e - s
    name = max(total, key=total.get)
    runs = sorted((s, e) for n, s, e in pt.modules[chip]
                  if n.split("(")[0] == name)
    starts = [s for s, _ in runs]
    out = []
    for (short, s, e, stack), st in zip(pt.ops[chip], pt.stats[chip]):
        if trace_reduce.is_container(short):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            out.append((short, stack, e - s,
                        trace_reduce.is_mosaic(short, st)))
    return out, runs


def grouped(short, stack):
    """The grouped matmul's custom calls carry no name stack: they are
    told by their name (``scope_split.GROUPED_MATMUL``)."""
    return GROUPED_MATMUL in short or GROUPED_MATMUL in stack


def step_scope_ms(cell):
    """``{"total", "flash", "grouped_matmul", "moe": {pass: {part: ms}},
    "mtp": {pass: ms}, "steps"}`` a step. ``moe`` > ``experts`` holds the
    grouped matmul's calls, which no pass can claim (no name stack), under
    the pass ``unplaced``; ``mtp`` counts everything under the scope
    ``mtp`` (the module's merge, block and head), its grouped matmuls
    excepted for the same reason."""
    pt = program_trace.of(cell)
    if pt is None:
        return None
    if "step_scope_ms" not in pt.memo:
        ops, runs = step_ops(cell)
        out = None
        if ops:
            moe = {p: dict.fromkeys(MOE_PARTS + ("all",), 0.0)
                   for p in PASSES + ("unplaced",)}
            mtp = dict.fromkeys(PASSES + ("other",), 0.0)
            out = {"total": 0.0, "flash": 0.0, "grouped_matmul": 0.0}
            for short, stack, ns, mosaic in ops:
                names = program_trace.scopes(stack)
                kind = program_trace.step_class(stack)
                out["total"] += ns
                if grouped(short, stack):
                    out["grouped_matmul"] += ns
                    moe["unplaced"]["experts"] += ns
                    moe["unplaced"]["all"] += ns
                    continue
                if mosaic:
                    out["flash"] += ns
                if "moe" in names and kind in PASSES:
                    moe[kind]["all"] += ns
                    for part in MOE_PARTS:
                        if part in names:
                            moe[kind][part] += ns
                if "mtp" in names:
                    mtp[kind if kind in PASSES else "other"] += ns
            n = len(runs)
            scale = lambda d: {k: v / n / 1e6 for k, v in d.items()}
            out = {**scale(out), "moe": {p: scale(v) for p, v in moe.items()},
                   "mtp": scale(mtp), "steps": n,
                   "step_period_ms": (runs[-1][0] - runs[0][0])
                   / max(n - 1, 1) / 1e6}
        pt.memo["step_scope_ms"] = out
    return pt.memo["step_scope_ms"]


def readings(cell, counters=None, say=print):
    """``{name: value}`` for this run's trace. ``counters``: the run's
    own (``moe_pairs_here``, ``moe_load_max``, ``moe_load_mean``,
    ``moe_pairs_routed``), where the caller has them. A number that finds
    nothing to read is left out."""
    out, split = {}, step_scope_ms(cell)
    if not split:
        return out
    cfg, job = cell.config, cell.traffic
    tokens = job["rows_per_chip"] * job["seq"]
    n_expert = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] \
        + cfg["num_nextn_predict_layers"]
    moe = split["moe"]
    moe_ms = sum(moe[p]["all"] for p in moe)
    for p in PASSES + ("unplaced",):
        say(f"moe_train_device_ms {p}: " + " ".join(
            f"{k} {moe[p][k]:.3f}" for k in MOE_PARTS)
            + f" of {moe[p]['all']:.3f} ms")
    mtp_ms = sum(split["mtp"].values())
    say(f"mtp_device_ms: " + " ".join(
        f"{k} {v:.3f}" for k, v in split["mtp"].items())
        + f" of {split['total']:.3f} ms a step ({split['steps']} steps, "
        f"one every {split['step_period_ms']:.3f} ms)")
    out.update(step_device_ms=split["total"], moe_train_device_ms=moe_ms,
               moe_share_of_step=100.0 * moe_ms / split["total"],
               moe_grouped_matmul_ms=split["grouped_matmul"],
               mtp_device_ms=mtp_ms,
               mtp_share_of_step=100.0 * mtp_ms / split["total"],
               flash_device_ms=split["flash"],
               flash_only_share_of_step=100.0 * split["flash"]
               / split["total"])
    if cell.peaks and split["steps"] > 1:
        pairs = (counters or {}).get("moe_pairs_here")
        kw = {} if pairs is None else {
            "pairs_here_per_token": pairs / n_expert / tokens}
        tps = tokens / (split["step_period_ms"] / 1e3)
        for name, padded in (("train_mfu", False),
                             ("train_mfu_as_run_padded", True)):
            out[name] = 100.0 * flops_joyai.train_flops_per_token(
                cfg, job["seq"], padded_values=padded, **kw) * tps \
                / cell.peaks["bf16_flops_per_s"]
        say(f"train_mfu: {tps:.1f} tokens/s from the step program's own "
            f"starts, {flops_joyai.forward_flops_per_token(cfg, job['seq'], **kw) / 1e6:.1f}"
            f" MFLOP a token forward")
    if cell.peaks and split["flash"]:
        h = cfg["num_attention_heads"]
        base = dict(batch=job["rows_per_chip"], n_heads=h, n_kv_heads=h,
                    seq_q=job["seq"], seq_k=job["seq"],
                    head_dim=cfg["qk_nope_head_dim"]
                    + cfg["qk_rope_head_dim"])
        layers = cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
        fwd = 2 if job.get("remat") == "full" else 1
        least = 0.0
        for kind, n in (("fwd", fwd), ("dkv", 1), ("dq", 1)):
            f, b = flops.flash_call_cost(kind=kind, **base)
            least += n * layers * max(f / cell.peaks["bf16_flops_per_s"],
                                      b / cell.peaks["hbm_bytes_per_s"])
        say(f"flash_roofline: {layers * (fwd + 2)} calls a step at head "
            f"{base['head_dim']}, least {least * 1e3:.3f} ms, took "
            f"{split['flash']:.3f} ms")
        out["flash_roofline"] = 100.0 * least * 1e3 / split["flash"]
    c = counters or {}
    if c.get("moe_load_mean"):
        out["moe_load_max_over_mean"] = c["moe_load_max"] / c["moe_load_mean"]
    if c.get("moe_pairs_routed"):
        out["moe_pairs_here_share"] = 100.0 * c["moe_pairs_here"] \
            / c["moe_pairs_routed"] / n_expert
    return out
