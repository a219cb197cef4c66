"""A tiny copy of the grid for the CPU tests: the same harness, kinds and
readers, on configurations and mixes small enough for a test run. Written
into a temporary root that holds its own ``BENCHMARK.json``."""

import json
import os

GPT2 = {"name": "tiny-gpt2", "family": "gpt2", "vocab_size": 211,
        "n_positions": 32, "n_embd": 48, "n_layer": 2, "n_head": 3,
        "layer_norm_epsilon": 1e-05, "initializer_range": 0.02}
STARCODER2 = {"name": "tiny-starcoder2", "family": "starcoder2",
              "vocab_size": 211, "hidden_size": 48, "intermediate_size": 192,
              "num_hidden_layers": 2, "num_attention_heads": 6,
              "num_key_value_heads": 2, "max_position_embeddings": 64,
              "sliding_window": 4096, "rope_theta": 999999.44,
              "norm_epsilon": 1e-05, "initializer_range": 0.02}
TRAIN = {"kind": "train", "seq": 32, "rows_per_chip": 4,
         "zipf_exponent": 1.0,
         "optimizer": {"name": "adamw", "lr": 0.0003, "b1": 0.9, "b2": 0.999,
                       "eps": 1e-08, "weight_decay": 0.01},
         "mixed_precision": "bf16", "remat": "full", "attention": "flash",
         "donate": False, "sharding": "zero3_over_dp", "check_steps": 3,
         "reference_row_block": 2, "trace_seconds": 1}
SERVE = {"kind": "serve", "rate_per_s": 12.0, "lead_in_s": 0.5,
         "drain_limit_s": 30, "schedule_seed": 1,
         "prompt_tokens": {"min": 4, "max": 16, "law": "log_uniform"},
         "answer_tokens": {"min": 4, "max": 12, "law": "log_uniform",
                           "distinct": 4},
         "engine": {"paged": True, "n_slots": 4, "max_len": 64,
                    "buckets": [8, 16], "max_queue": 256},
         "check_requests": 4, "trace_seconds": 1, "trace_iterations": 32,
         "trace_admissions": 1}


# at this size a leaf has few elements, so bfloat16's noise averages out
# less than at the cells' own size: the tiny cells bring their own limits
LIMITS = {
    "tiny-train-cell": {"loss_rel_gap": 0.002, "grad_norm_worst_leaf": 0.02,
                        "param_change_worst_leaf": 0.5},
    "tiny-train4-cell": {"loss_rel_gap": 0.002, "grad_norm_worst_leaf": 0.02,
                         "param_change_worst_leaf": 0.5},
    "tiny-serve-cell": {"served_logit_gap_max": 0.25}}


def write_root(root, real_manifest, serve=None):
    """``root``/BENCHMARK.json with two tiny cells that report the same
    metrics as the real train and serve cells. ``serve`` replaces fields
    of the serving mix."""
    bench = os.path.join(root, "chipbench")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    for cfg in (GPT2, STARCODER2):
        with open(os.path.join(bench, "configs", cfg["name"] + ".json"),
                  "w") as f:
            json.dump(cfg, f)
    for name, mix in (("tiny-train", TRAIN),
                      ("tiny-serve", dict(SERVE, **(serve or {})))):
        with open(os.path.join(bench, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    for cell, limits in LIMITS.items():
        with open(os.path.join(bench, "limits", cell + ".json"), "w") as f:
            json.dump({k: {"limit": v} for k, v in limits.items()}, f)
    train_cell = next(w["name"] for w in real_manifest["workloads"]
                      if w["traffic"].startswith("pretrain"))
    serve_cell = next(w["name"] for w in real_manifest["workloads"]
                      if w["config"].startswith("starcoder2"))
    rename = {train_cell: ["tiny-train-cell", "tiny-train4-cell"],
              serve_cell: ["tiny-serve-cell"]}

    def cells(m):
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = sorted({t for w in m["workloads"]
                                     for t in rename.get(w, [])})
        return m

    manifest = dict(real_manifest)
    manifest["configs"] = [
        {"name": c["name"], "source": "test", "reduced": [], "why": "test",
         "file": f"chipbench/configs/{c['name']}.json"}
        for c in (GPT2, STARCODER2)]
    manifest["workloads"] = [
        {"name": "tiny-train-cell", "config": "tiny-gpt2",
         "traffic": "tiny-train", "chips": 1, "why": "test"},
        {"name": "tiny-serve-cell", "config": "tiny-starcoder2",
         "traffic": "tiny-serve", "chips": 1, "why": "test"},
        {"name": "tiny-train4-cell", "config": "tiny-gpt2",
         "traffic": "tiny-train", "chips": 4, "why": "test"}]
    manifest["end_to_end"] = [cells(m) for m in real_manifest["end_to_end"]]
    manifest["per_layer"] = [cells(m) for m in real_manifest["per_layer"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
