"""A tiny cell of the family ``xing4`` for the CPU tests, beside
``tiny.py`` (which stays as it is): the same harness, kind and readers on
a configuration small enough for a test run (hidden 64, 4 heads, latents
32/16, rope 8, 8 experts top 2, 1 dense + 2 expert layers, 4 streams,
vocabulary 211). Written into a temporary root with its own
``BENCHMARK.json``."""

import json
import os

XING4 = {
    "name": "tiny-xing4", "family": "xing4", "vocab_size": 211,
    "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 3,
    "n_layer": 2, "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "moe_intermediate_size": 32, "routed_scaling_factor": 2, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "max_position_embeddings": 4096, "tie_word_embeddings": False,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "init_embed_std": 1.0, "init_matrix_gain": 1.0, "init_down_gain": 0.4,
    "init_expert_down_gain": 0.08, "init_shared_down_gain": 0.3,
    "init_norm_gain_std": 0.1,
    "init_router_bias_std": 0.1, "init_hc_scale_std": 0.1,
    "init_hc_bias_std": 0.5, "init_hc_res_gain": 0.7,
    "init_hc_res_bias_std": 0.7}
SERVE = {"kind": "serve", "rate_per_s": 12.0, "lead_in_s": 0.5,
         "drain_limit_s": 60, "schedule_seed": 1,
         "prompt_tokens": {"min": 4, "max": 16, "law": "log_uniform"},
         "answer_tokens": {"min": 4, "max": 12, "law": "log_uniform",
                           "distinct": 4},
         "engine": {"paged": True, "n_slots": 4, "max_len": 64,
                    "buckets": [8, 16], "max_queue": 256, "page_len": 4},
         "check_requests": 4, "trace_seconds": 1, "trace_iterations": 32,
         "trace_admissions": 1}
CELL = "tiny-xing4-cell"
# at this size a run checks a few dozen served tokens: the seeds the tests
# use read 0 and 0, the fp8 control 0.22 to 0.25 over 8 x 48 positions
# (tests/chipbench/test_chipbench_xing4.py prints it). With 8 experts of
# width 32 one rerouted token is a large change (one seed in six read
# 0.39), which is why the real cell's limit is set from runs at its own
# size (chipbench/limits/serve-xing4-chat-decode.json)
LIMIT = 0.1


def write_root(root, real_manifest):
    """``root``/BENCHMARK.json with one tiny cell that reports what the
    real cell of the family reports."""
    bench = os.path.join(root, "chipbench")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    with open(os.path.join(bench, "configs", "tiny-xing4.json"), "w") as f:
        json.dump(XING4, f)
    with open(os.path.join(bench, "traffic", "tiny-chat.json"), "w") as f:
        json.dump(SERVE, f)
    with open(os.path.join(bench, "limits", CELL + ".json"), "w") as f:
        json.dump({"served_logit_gap_max": {"limit": LIMIT}}, f)
    real_cell = next(w["name"] for w in real_manifest["workloads"]
                     if w["config"].startswith("xing4"))

    def cells(m):
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = [CELL] if real_cell in m["workloads"] else []
        return m

    manifest = dict(real_manifest)
    manifest["configs"] = [{"name": "tiny-xing4", "source": "test",
                            "reduced": [], "why": "test",
                            "file": "chipbench/configs/tiny-xing4.json"}]
    manifest["workloads"] = [{"name": CELL, "config": "tiny-xing4",
                              "traffic": "tiny-chat", "chips": 1,
                              "why": "test"}]
    manifest["end_to_end"] = [cells(m) for m in real_manifest["end_to_end"]]
    manifest["per_layer"] = [cells(m) for m in real_manifest["per_layer"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
