"""A tiny cell of the family ``joyai`` for the CPU tests, beside
``tiny.py`` and ``tiny_xing4.py`` (which stay as they are): the same
harness, the kind ``train_mtp`` and the training readers on a
configuration small enough for a test run (hidden 64, 4 heads, latents
32/16, rope 8, a router 16 wide with 4 a token of which experts [4, 8) are
held, 1 dense + 1 expert layer, the prediction module, vocabulary 211).
Written into a temporary root with its own ``BENCHMARK.json``."""

import json
import os

JOYAI = {
    "name": "tiny-joyai", "family": "joyai", "vocab_size": 211,
    "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 2,
    "n_layer": 1, "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
    "router_width": 16, "experts_held_first": 4, "num_experts_per_tok": 4,
    "n_shared_experts": 1, "moe_intermediate_size": 32,
    "routed_scaling_factor": 2.5, "num_nextn_predict_layers": 1,
    "rms_norm_eps": 1e-06, "rope_theta": 32000000, "rope_scaling": None,
    "max_position_embeddings": 4096, "tie_word_embeddings": False,
    "init_embed_std": 1.0, "init_matrix_gain": 1.0, "init_down_gain": 0.4,
    "init_expert_down_gain": 0.08, "init_shared_down_gain": 0.3,
    "init_norm_gain_std": 0.1, "init_router_bias_std": 0.01}
TRAIN = {"kind": "train_mtp", "seq": 32, "rows_per_chip": 2,
         "zipf_exponent": 1.0,
         "optimizer": {"name": "adamw", "lr": 0.0003, "b1": 0.9, "b2": 0.999,
                       "eps": 1e-08, "weight_decay": 0.01},
         "mtp_weight": 0.3, "bias_update_speed": 0.001,
         "mixed_precision": "bf16", "remat": "full", "attention": "flash",
         "donate": False, "check_steps": 3, "reference_row_block": 1,
         "trace_seconds": 1, "rate_metric": "train_tokens_per_s.moe"}
# the same job under a schedule: a linear warm-up of a few steps to the
# same peak, so that the three check steps run at 1/4, 2/4 and 3/4 of it
TRAIN_WARMUP = dict(TRAIN, optimizer=dict(TRAIN["optimizer"],
                                          warmup_steps=4))
CELL = "tiny-joyai-cell"
# at this size a leaf has few elements and an expert few tokens, so
# bfloat16's noise averages out less than at the cell's own size, and one
# rerouted token is a large part of an expert's gradient: the tiny cell
# brings its own limits (tests/chipbench/test_chipbench_joyai.py prints
# what sound runs and the fp8 control read)
LIMITS = {"loss_rel_gap": 0.004, "grad_norm_worst_leaf": 0.015,
          "param_change_worst_leaf": 0.5,
          "router_pairs_elsewhere_share": 0.03}


def write_root(root, real_manifest, train=TRAIN):
    """``root``/BENCHMARK.json with one tiny cell that reports what the
    real cell of the family reports. ``train`` is its job."""
    bench = os.path.join(root, "chipbench")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    with open(os.path.join(bench, "configs", "tiny-joyai.json"), "w") as f:
        json.dump(JOYAI, f)
    with open(os.path.join(bench, "traffic", "tiny-mtp.json"), "w") as f:
        json.dump(train, f)
    with open(os.path.join(bench, "limits", CELL + ".json"), "w") as f:
        json.dump({k: {"limit": v} for k, v in LIMITS.items()}, f)
    real_cell = next(w["name"] for w in real_manifest["workloads"]
                     if w["config"].startswith("joyai"))

    def cells(m):
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = [CELL] if real_cell in m["workloads"] else []
        return m

    manifest = dict(real_manifest)
    manifest["configs"] = [{"name": "tiny-joyai", "source": "test",
                            "reduced": [], "why": "test",
                            "file": "chipbench/configs/tiny-joyai.json"}]
    manifest["workloads"] = [{"name": CELL, "config": "tiny-joyai",
                              "traffic": "tiny-mtp", "chips": 1,
                              "why": "test"}]
    manifest["end_to_end"] = [cells(m) for m in real_manifest["end_to_end"]]
    manifest["per_layer"] = [cells(m) for m in real_manifest["per_layer"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
