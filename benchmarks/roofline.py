"""Analytic roofline for the MFU benchmark configs (v5e single chip).

Where would the remaining gap to MFU 1.0 live? This is the analysis,
executable: for each benchmark config it derives

- the **compute floor**: analytic model FLOPs / peak bf16 FLOP/s (the
  step time at MFU 1.0 — same FLOP accounting as mfu_transformer.py, so
  the two agree by construction);
- the **HBM floor**: an itemized per-step traffic model (params, grads,
  optimizer moments, activations, logits) / peak HBM bandwidth;
- the implied **MFU ceiling** = compute_floor / max(compute, hbm) — what
  a perfectly overlapped execution could reach; and
- against the newest measured row in the results log bench.py appends
  to (when present), the **efficiency gap**: measured_step / max(floor) — the factor that
  is kernel/overlap inefficiency rather than physics.

The conclusion this model supports: at flagship scale
(135M params, batch 8, seq 1024) the step is COMPUTE-dominated on paper
(HBM floor ~1/3 of the compute floor), so a sub-0.9 MFU is NOT
"memory-bound and irreducible" — the gap lives in kernel efficiency and
is attackable (fused-CE removes the largest single HBM item, the f32
logits; the no-remat large-batch arms amortize per-step overheads).

Usage: python benchmarks/roofline.py            (table + one JSON line)
"""

from __future__ import annotations

import json
import os
import sys
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.mfu_transformer import (  # noqa: E402
    FLAGSHIP, LONGCTX, MEDIUM, PEAK_BF16, model_flops_per_token)

#: The chip this model is drawn for, under the name JAX reports for it:
#: a v5e's ``device_kind`` is "TPU v5 lite" (chip_smoke.py on the chip,
#: PR 21). A measured record is analyzed against ITS device kind.
TARGET_KIND = "TPU v5 lite"

# Public per-chip HBM specs (same sourcing rule as PEAK_BF16: only the
# generation we can run on is judged; others best-effort). Key set
# MIRRORS PEAK_BF16 exactly — analyze() indexes both with one
# device_kind.
HBM_GBPS = {
    "TPU v2": 700e9,
    "TPU v3": 900e9,
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,           # v5p, mirroring PEAK_BF16's aliasing
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,      # Trillium / v6e
    "TPU v6e": 1640e9,
}
assert set(HBM_GBPS) == set(PEAK_BF16), \
    "HBM_GBPS and PEAK_BF16 must stay key-identical (analyze() indexes both)"
# Activation tensors written in forward and re-read in backward, per
# layer, in units of (batch*seq*dim) elements. Transformer block with
# flash attention (no S^2 materialization): ln1 out, qkv out (3x), attn
# out, proj out, ln2 out, mlp hidden (4x), mlp out ~= 12 B*S*d tensors.
# bf16. Remat reduces the stored set to the block boundary (~2) at the
# price of recomputing the forward (uncounted by model-FLOPs MFU).
_ACT_UNITS_PER_LAYER = 12.0
_ACT_UNITS_PER_LAYER_REMAT = 2.0


def remat_enabled(remat) -> bool:
    """Normalize a remat flag OR named policy to the binary question
    the activation-traffic model asks: are per-layer activations
    rematerialized? The ONE rule — ``hbm_bytes_per_step`` and
    ``analyze`` both use it, so a config carrying the new policy
    strings (``none|full|dots_saveable``, models/transformer.py) can
    never read as remat-enabled through one entry point and disabled
    through the other. ``dots_saveable`` stores strictly less than
    "none"; the two-unit block-boundary estimate is the conservative
    lower bound for both remat policies."""
    return remat not in (False, None, "none")


def count_params(cfg) -> int:
    d, L, V = cfg["dim"], cfg["n_layers"], cfg["vocab"]
    per_layer = 12 * d * d  # qkv 3d^2 + proj d^2 + mlp 8d^2 (r=4)
    return V * d + L * per_layer + V * d  # emb + blocks + untied head


def hbm_bytes_per_step(cfg, *, fused_ce: Optional[bool] = None,
                       remat: Optional[bool] = None,
                       master_f32: Optional[bool] = None) -> dict:
    """Itemized HBM traffic for one train step, bytes.

    A deliberate lower-bound model: each item counted once at its
    minimum unavoidable traffic (e.g. params read once for forward and
    once for backward, moments read+written once). Real executions
    re-stream tiles; that inefficiency is what the measured gap shows.

    Arm flags left as None default from the config dict itself (the
    FLAGSHIP identity carries them), same contract as :func:`analyze`.
    """
    fused_ce = cfg.get("fused_ce", False) if fused_ce is None else fused_ce
    remat = remat_enabled(cfg.get("remat", False) if remat is None
                          else remat)
    master_f32 = (cfg.get("master_f32", False) if master_f32 is None
                  else master_f32)
    P = count_params(cfg)
    B, S, d, L, V = (cfg["batch"], cfg["seq"], cfg["dim"],
                     cfg["n_layers"], cfg["vocab"])
    tok = B * S
    p_bytes = 4 if master_f32 else 2
    items = {
        # bf16 working params read by fwd and again by bwd
        "params_fwd+bwd_read": 2 * P * 2,
        # bf16 grads written by bwd, read by the update
        "grads_write+read": 2 * P * 2,
        # adamw f32 moments m,v: read + write each
        "adamw_moments_rw": 4 * P * 4,
        # updated params written (+ f32 master copy rw when enabled)
        "params_update_write": P * p_bytes
        + (2 * P * 4 if master_f32 else 0),
        # stored activations: fwd write + bwd read, bf16
        "activations_fwd_write+bwd_read":
            int(2 * (_ACT_UNITS_PER_LAYER_REMAT if remat
                     else _ACT_UNITS_PER_LAYER) * L * tok * d * 2),
        # f32 logits (B,S,V): write + CE read + bwd read — absent
        # entirely under fused-CE (losses.fused_linear_cross_entropy
        # streams the vocab projection chunkwise)
        "logits_f32": 0 if fused_ce else 3 * tok * V * 4,
    }
    items["total"] = sum(items.values())
    return items


def analyze(cfg, *, device_kind: str = TARGET_KIND,
            fused_ce: Optional[bool] = None, remat=None,
            master_f32: Optional[bool] = None,
            peak_flops: Optional[float] = None,
            mem_bytes_per_s: Optional[float] = None) -> dict:
    # arm flags default from the config dict itself (FLAGSHIP carries
    # its arm flags as part of the flagship identity) so a flagship
    # promotion propagates here without touching call sites
    fused_ce = cfg.get("fused_ce", False) if fused_ce is None else fused_ce
    # named remat policies normalize through the shared rule
    remat = remat_enabled(cfg.get("remat", False) if remat is None
                          else remat)
    master_f32 = (cfg.get("master_f32", False) if master_f32 is None
                  else master_f32)
    if peak_flops is not None and mem_bytes_per_s is not None:
        # CALIBRATED specs (benchmarks/mfu_transformer.calibrate_host):
        # hosts without a spec-sheet row anchor their ceilings to their
        # own measured matmul/memcpy peaks — same math, honest inputs
        peak, bw = peak_flops, mem_bytes_per_s
    else:
        if device_kind not in PEAK_BF16 or device_kind not in HBM_GBPS:
            raise ValueError(
                f"unsupported device_kind {device_kind!r}: roofline "
                f"specs exist for {sorted(PEAK_BF16)} (or pass measured "
                f"peak_flops + mem_bytes_per_s overrides)")
        peak = PEAK_BF16[device_kind]
        bw = HBM_GBPS[device_kind]
    tok = cfg["batch"] * cfg["seq"]
    flops = 3 * model_flops_per_token(
        cfg["dim"], cfg["n_layers"], cfg["vocab"], cfg["seq"]) * tok
    traffic = hbm_bytes_per_step(cfg, fused_ce=fused_ce, remat=remat,
                                 master_f32=master_f32)
    t_compute = flops / peak
    t_hbm = traffic["total"] / bw
    floor = max(t_compute, t_hbm)
    return {
        "n_params": count_params(cfg),
        "model_tflops_per_step": round(flops / 1e12, 3),
        "hbm_gb_per_step": round(traffic["total"] / 1e9, 3),
        "hbm_items_gb": {k: round(v / 1e9, 3)
                         for k, v in traffic.items() if k != "total"},
        "compute_floor_ms": round(t_compute * 1e3, 2),
        "hbm_floor_ms": round(t_hbm * 1e3, 2),
        "bound": "compute" if t_compute >= t_hbm else "hbm",
        # perfect compute/memory overlap (the optimistic extreme) ...
        "mfu_ceiling": round(t_compute / floor, 4),
        # ... and zero overlap (the pessimistic extreme): real
        # executions land between the two
        "mfu_ceiling_no_overlap": round(t_compute / (t_compute + t_hbm),
                                        4),
    }


#: Wire bytes per gradient element of the comm-ceiling arms: f32, the
#: block-q8 wire (one f32 scale per 1024-block), and the nibble-packed
#: q4 wire (comm/wire.py's widths — ~3.98x / ~7.9x less than f32).
WIRE_BYTES_PER_ELEM = {32: 4.0, 8: 1.0 + 4 / 1024, 4: 0.5 + 4 / 1024}


def dp_comm_bytes_per_step(cfg, world: int, wire_bits: int = 32) -> int:
    """Bytes ONE chip puts on the interconnect for a data-parallel
    gradient ring allreduce of the model's params: ``2*(W-1)/W * P``
    elements at the wire width (the bandwidth-optimal ring's per-rank
    traffic; the quantized widths carry their per-block scale tax)."""
    if world <= 1:
        return 0
    per_elem = WIRE_BYTES_PER_ELEM[wire_bits]
    return int(2 * (world - 1) / world * count_params(cfg) * per_elem)


def comm_ceilings(analysis: dict, cfg, *, dp_world: int,
                  net_gbps: float, wire_bits: int = 8) -> dict:
    """Fold a data-parallel gradient-allreduce comm floor into an
    :func:`analyze` result — the distributed-step extension of the
    overlap story. Adds ``comm_floor_ms`` plus the two MFU ceilings
    that bracket real distributed executions:

    * ``mfu_ceiling_comm_overlap`` — comm fully hidden behind compute
      (what the double-buffered chunk pipeline + bucketed backward
      overlap drive toward; ``t_compute / max(t_compute, t_hbm,
      t_comm)``);
    * ``mfu_ceiling_comm_exposed`` — comm strictly serialized after the
      backward (the no-overlap floor, ``t_compute / (t_compute + t_hbm
      + t_comm)``).

    The gap between the two IS the overlap win the dp8 bench's
    ``exposed_ms`` measures; the plausibility gate keeps using the
    OVERLAPPED ceiling (nothing real exceeds the optimistic extreme).
    """
    t_c = analysis["compute_floor_ms"] / 1e3
    t_h = analysis["hbm_floor_ms"] / 1e3
    t_comm = dp_comm_bytes_per_step(cfg, dp_world, wire_bits) \
        / (net_gbps * 1e9)
    analysis["comm_floor_ms"] = round(t_comm * 1e3, 3)
    analysis["comm_wire_bits"] = wire_bits
    analysis["comm_dp_world"] = dp_world
    analysis["mfu_ceiling_comm_overlap"] = round(
        t_c / max(t_c, t_h, t_comm), 4)
    analysis["mfu_ceiling_comm_exposed"] = round(
        t_c / (t_c + t_h + t_comm), 4)
    return analysis


def attach_measured(analysis: dict, meas_ms) -> dict:
    """Join a measured step time onto an analyze() result: records
    measured_step_ms and the efficiency gap vs the binding floor. The
    ONE definition of the join rule — bench.attach_roofline and main()
    both use it, so the headline record and the roofline report can
    never disagree about the same measurement."""
    if meas_ms:
        analysis["measured_step_ms"] = meas_ms
        analysis["efficiency_gap_x"] = round(
            meas_ms / max(analysis["compute_floor_ms"],
                          analysis["hbm_floor_ms"]), 2)
    return analysis


def measured_step_ms(rows, stage: str):
    """The NEWEST ok non-retracted row's step_ms_median for a stage —
    None when that row lacks one (no silent fallback to a stale older
    measurement; keeps this join consistent with report.latest_per_stage
    so the two BASELINE-facing outputs agree on what is current)."""
    newest = None
    for r in rows:
        if r.get("stage") == stage and r.get("ok") \
                and not r.get("retracted"):
            newest = r
    if newest is None:
        return None
    return newest.get("result", {}).get("step_ms_median")


def main(argv):
    from benchmarks.report import load_rows
    log = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "tpu_results.jsonl")
    rows = load_rows(log)

    configs = [
        ("flagship", FLAGSHIP, {}, "bench_mfu"),
        ("flagship+fused_ce", FLAGSHIP, {"fused_ce": True}, None),
        ("medium", MEDIUM, {}, "bench_mfu_medium"),
        ("long(seq4096,remat+fce)", LONGCTX,
         {"remat": True, "fused_ce": True}, "mfu_long"),
    ]
    out = {"device": TARGET_KIND,
           "peak_bf16_tflops": PEAK_BF16[TARGET_KIND] / 1e12,
           "hbm_gbps": HBM_GBPS[TARGET_KIND] / 1e9,
           "configs": {}}
    print("# config | params | TF/step | HBM GB/step | compute floor | "
          "HBM floor | bound | MFU ceiling (overlap/none) | measured | "
          "gap")
    for name, cfg, arm, stage in configs:
        a = analyze(cfg, **arm)
        meas = measured_step_ms(rows, stage) if stage else None
        attach_measured(a, meas)
        gap = a.get("efficiency_gap_x")
        out["configs"][name] = a
        print(f"# {name}: {a['n_params']/1e6:.0f}M | "
              f"{a['model_tflops_per_step']} | {a['hbm_gb_per_step']} | "
              f"{a['compute_floor_ms']} ms | {a['hbm_floor_ms']} ms | "
              f"{a['bound']} | {a['mfu_ceiling']}/"
              f"{a['mfu_ceiling_no_overlap']} | "
              f"{meas if meas is not None else '-'} ms | "
              f"{gap if gap is not None else '-'}", flush=True)
    # the distributed extension: what a dp8 flagship could reach over a
    # 100 Gb/s-class DCN hop per wire width, with and without comm
    # overlap — the analytic bracket behind the dp8_hier bench arm's
    # measured exposed_ms
    print("# dp8 comm ceilings (flagship, 12.5 GB/s interconnect): "
          "wire | comm floor | MFU ceiling overlapped/exposed")
    dp = {}
    for bits in (32, 8, 4):
        a = comm_ceilings(dict(analyze(FLAGSHIP)), FLAGSHIP, dp_world=8,
                          net_gbps=12.5, wire_bits=bits)
        dp[f"q{bits}" if bits != 32 else "f32"] = {
            k: a[k] for k in ("comm_floor_ms",
                              "mfu_ceiling_comm_overlap",
                              "mfu_ceiling_comm_exposed")}
        print(f"#   {'f32' if bits == 32 else f'q{bits}'} | "
              f"{a['comm_floor_ms']} ms | "
              f"{a['mfu_ceiling_comm_overlap']}/"
              f"{a['mfu_ceiling_comm_exposed']}", flush=True)
    out["dp8_comm_ceilings"] = dp
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
