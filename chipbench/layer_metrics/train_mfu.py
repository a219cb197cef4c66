"""Model FLOP/s utilization: the operations forward and backward need a
token (chipbench/flops.py; recomputation not counted) times the tokens a
second of the traced part of the window, over chips times the bf16 peak."""

from chipbench import flops


def read(trace, counters, cell):
    tps = counters.get("traced_tokens_per_s")
    if not tps or not cell.peaks:
        return None
    per_token = flops.train_flops_per_token(
        **flops.config_shape(cell.config, counters["seq"]))
    return 100.0 * per_token * tps / (
        cell.chips * cell.peaks["bf16_flops_per_s"])
