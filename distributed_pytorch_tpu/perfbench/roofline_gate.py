"""Roofline anchoring: every flagship record answers "is this number
physics-bound or attackable?" — and implausible numbers get caught.

Folds ``benchmarks/roofline.py``'s analytic ceilings into a record as
``roofline_flagship`` (floors, overlap/no-overlap MFU ceilings, the
efficiency gap when a step time was measured) and adds the two things
the old best-effort attach never did:

* ``achieved_over_ceiling_no_overlap`` — measured MFU divided by the
  no-overlap ceiling (r05's roofline: the flagship is compute-bound,
  ceiling **0.70** without overlap; this module prints it with every
  flagship record);
* a **plausibility gate**: an MFU above the overlapped ceiling is
  physically impossible on the modeled chip (the r02 dispatch-rate
  artifact measured "7.42 MFU"), so the record is marked ``untrusted``
  with the roofline as the attributed reason instead of entering the
  trajectory as evidence.

Heavy imports (``benchmarks.roofline`` pulls jax via mfu_transformer)
stay function-scope: attaching is best-effort and must never block a
record from being emitted.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["attach_flagship", "ROOFLINE_KEYS"]

#: The analyze()/attach_measured()/comm_ceilings() fields that travel
#: with the record (comm_* appear on distributed arms only).
ROOFLINE_KEYS = ("compute_floor_ms", "hbm_floor_ms", "bound",
                 "mfu_ceiling", "mfu_ceiling_no_overlap",
                 "comm_floor_ms", "comm_wire_bits", "comm_dp_world",
                 "mfu_ceiling_comm_overlap", "mfu_ceiling_comm_exposed",
                 "measured_step_ms", "efficiency_gap_x")


def attach_flagship(rec: dict, *, announce: bool = True) -> dict:
    """Fold the flagship roofline into ``rec`` (best-effort — a roofline
    failure becomes a warning, never a blocked record), join the
    measured MFU against the ceilings, and apply the plausibility gate.
    """
    try:
        from benchmarks.mfu_transformer import FLAGSHIP
        from benchmarks.roofline import analyze, attach_measured
        det = rec.get("mfu_detail") or {}
        cal = det.get("calibration")
        cfg_src = det.get("config") or {}
        dims = ("dim", "n_layers", "n_heads", "vocab", "seq", "batch")
        if cal and all(k in cfg_src for k in dims):
            # calibrated-host record (no spec-sheet row for the device):
            # analyze the config that actually ran against the MEASURED
            # peaks it was normalized by — the ceilings and the MFU then
            # share one denominator, so the plausibility gate stays
            # meaningful off-TPU (docs/compute.md)
            analysis = analyze(
                {k: cfg_src[k] for k in dims},
                device_kind=det.get("device", "host"),
                fused_ce=bool(cfg_src.get("fused_ce")),
                remat=cfg_src.get("remat"),
                master_f32=bool(cfg_src.get("master_f32"))
                or cfg_src.get("mp") == "bf16",
                peak_flops=cal["peak_flops"],
                mem_bytes_per_s=cal["mem_bytes_per_s"])
            analysis["specs_source"] = "calibrated_host"
        elif det.get("platform") == "tpu":
            # a chip record is analyzed against the chip it ran on: an
            # unknown device_kind raises (and lands as a warning) instead
            # of borrowing another chip's ceilings
            analysis = analyze(FLAGSHIP, device_kind=det["device"])
        else:
            analysis = analyze(FLAGSHIP)
        rl = attach_measured(analysis, det.get("step_ms_median"))
        out = {k: rl[k] for k in ROOFLINE_KEYS if k in rl}
        if "specs_source" in rl:
            out["specs_source"] = rl["specs_source"]
        rec["roofline_flagship"] = out
    except Exception as e:  # noqa: BLE001 — attach must never block
        rec.setdefault("warnings", []).append(
            f"roofline attach failed: {type(e).__name__}: {e}")
        return rec

    value = rec.get("value")
    ceiling = out.get("mfu_ceiling")
    no_overlap = out.get("mfu_ceiling_no_overlap")
    achieved: Optional[float] = None
    if isinstance(value, (int, float)) and not isinstance(value, bool) \
            and no_overlap:
        achieved = round(float(value) / no_overlap, 4)
        out["achieved_over_ceiling_no_overlap"] = achieved
        if ceiling:
            # the record reports achieved against BOTH extremes: the
            # no-overlap floor (real executions should beat it once
            # comm/memory hide behind compute) and the perfectly
            # overlapped ceiling (nothing real exceeds it — which is
            # exactly why the plausibility gate below uses THIS one)
            out["achieved_over_ceiling_overlapped"] = round(
                float(value) / ceiling, 4)
        if ceiling is not None and float(value) > ceiling:
            # an MFU above the overlapped ceiling cannot have been a real
            # chip measurement — poison it structurally, keep the value
            # visible with its reason (the r02 "7.42 MFU" artifact class)
            rec["trusted"] = False
            rec["untrusted_reason"] = (
                f"mfu {value:g} exceeds the roofline ceiling "
                f"{ceiling:g} ({out.get('bound', '?')}-bound flagship) — "
                "physically impossible; likely a dispatch-rate artifact")
    if announce:
        # ROOFLINE_KEYS are copied if-present, so either ceiling may be
        # absent here — formatting must not be the thing that crashes
        # main() after the record survived everything else
        def g(v):
            return (f"{v:g}" if isinstance(v, (int, float))
                    and not isinstance(v, bool) else "?")

        msg = (f"roofline: flagship is {out.get('bound', '?')}-bound; "
               f"MFU ceiling {g(ceiling)} overlapped / "
               f"{g(no_overlap)} no-overlap")
        if achieved is not None:
            msg += f"; achieved/ceiling(no-overlap) = {achieved:g}"
        print(f"# {msg}", flush=True)
    return rec
