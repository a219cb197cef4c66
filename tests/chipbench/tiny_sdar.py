"""A tiny cell of the family ``sdar`` for the CPU tests, beside ``tiny.py``
and ``tiny_xing4.py`` (which stay as they are): the same harness, the kind
``serve_blocks`` and the readers on a configuration small enough for a
test run (hidden 64, 8 query heads and 2 KV heads of 16, 16 experts top 4
of width 32, 2 layers, blocks of 4 positions, vocabulary 211 with the
mask id its last). Written into a temporary root with its own
``BENCHMARK.json``."""

import json
import os

SDAR = {
    "name": "tiny-sdar", "family": "sdar", "vocab_size": 211,
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 16,
    "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "intermediate_size": 160, "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "rope_scaling": None, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "norm_topk_prob": True, "attention_bias": False,
    "tie_word_embeddings": False, "max_position_embeddings": 4096,
    "block_length": 4, "denoising_steps": 4, "mask_token_id": 210,
    "init_embed_std": 1.0, "init_matrix_gain": 1.0,
    "init_expert_down_gain": 0.4, "init_norm_gain_std": 0.1}
SERVE = {"kind": "serve_blocks", "rate_per_s": 10.0, "lead_in_s": 0.5,
         "drain_limit_s": 60, "schedule_seed": 1, "block_length": 4,
         "denoise_steps": 4,
         "prompt_tokens": {"min": 4, "max": 16, "law": "log_uniform"},
         "answer_tokens": {"min": 4, "max": 12, "law": "log_uniform",
                           "distinct": 4},
         "engine": {"paged": True, "n_slots": 4, "max_len": 64,
                    "buckets": [8, 16], "max_queue": 256, "page_len": 4},
         "check_requests": 4, "trace_seconds": 1, "trace_iterations": 32,
         "trace_admissions": 1}
CELL = "tiny-sdar-cell"
# at this size a run checks a few dozen filled positions. The bfloat16
# program against the float32 reference reads a logit gap of 0 to 0.02 and
# a mean confidence gap of 0 to 0.005 on the seeds the tests use, the fp8
# control 0.17 and 0.03 over 12 x 12 positions, the two faults 4.3 and
# 0.13 (the printed readings of
# tests/chipbench/test_chipbench_sdar.py); the real cell's limits are set
# from runs at its own size (chipbench/limits/serve-sdar-chat-blocks.json)
LIMITS = {"served_logit_gap_max": 0.08, "served_confidence_gap_mean": 0.02}


def write_root(root, real_manifest):
    """``root``/BENCHMARK.json with one tiny cell that reports what the
    real cell of the family reports."""
    bench = os.path.join(root, "chipbench")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    with open(os.path.join(bench, "configs", "tiny-sdar.json"), "w") as f:
        json.dump(SDAR, f)
    with open(os.path.join(bench, "traffic", "tiny-blocks.json"), "w") as f:
        json.dump(SERVE, f)
    with open(os.path.join(bench, "limits", CELL + ".json"), "w") as f:
        json.dump({k: {"limit": v} for k, v in LIMITS.items()}, f)
    real_cell = next(w["name"] for w in real_manifest["workloads"]
                     if w["config"].startswith("sdar"))

    def cells(m):
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = [CELL] if real_cell in m["workloads"] else []
        return m

    manifest = dict(real_manifest)
    manifest["configs"] = [{"name": "tiny-sdar", "source": "test",
                            "reduced": [], "why": "test",
                            "file": "chipbench/configs/tiny-sdar.json"}]
    manifest["workloads"] = [{"name": CELL, "config": "tiny-sdar",
                              "traffic": "tiny-blocks", "chips": 1,
                              "why": "test"}]
    manifest["end_to_end"] = [cells(m) for m in real_manifest["end_to_end"]]
    manifest["per_layer"] = [cells(m) for m in real_manifest["per_layer"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
