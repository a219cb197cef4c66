"""A tiny cell of the family ``kexaone`` for the CPU tests, beside
``tiny.py`` (which stays as it is): the same harness, kind
(``serve_long``) and readers on a configuration small enough for a test
run (hidden 64, 8 query heads over 2 KV heads of 8, window 8, layers
sliding, sliding, sliding, full, sliding; 1 dense + 4 expert layers, 16
experts top 4 of which 4 are held, vocabulary 211; pages of 4, so a ring
of 12 entries). Written into a temporary root with its own
``BENCHMARK.json``."""

import json
import os

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
KEXAONE = {
    "name": "tiny-kexaone", "family": "kexaone", "vocab_size": 211,
    "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 5,
    "n_layer": 4, "first_k_dense_replace": 1, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 8, "num_experts": 4,
    "router_width": 16, "experts_held_first": 0, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "moe_intermediate_size": 32,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "rms_norm_eps": 1e-05, "sliding_window": 8,
    "layer_types": PERIOD * 2, "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
    "max_position_embeddings": 4096, "tie_word_embeddings": False,
    "init_embed_std": 1.0, "init_matrix_gain": 1.0, "init_down_gain": 0.4,
    "init_expert_down_gain": 0.4, "init_shared_down_gain": 0.3,
    "init_norm_gain_std": 0.1, "init_router_bias_std": 0.1}
SERVE = {"kind": "serve_long", "rate_per_s": 8.0, "lead_in_s": 0.5,
         "drain_limit_s": 60, "schedule_seed": 1,
         "prompt_tokens": {"min": 4, "max": 60, "law": "log_uniform"},
         "answer_tokens": {"min": 4, "max": 12, "law": "log_uniform",
                           "distinct": 4},
         "engine": {"paged": True, "n_slots": 4, "max_len": 72,
                    "buckets": [8, 16], "max_queue": 256, "page_len": 4,
                    "prefix_share": False},
         "check_requests": 4, "trace_seconds": 1, "trace_iterations": 32,
         "trace_admissions": 1}
CELL = "tiny-kexaone-cell"
# at this size a run checks a few dozen served tokens: the seeds the tests
# use read 0, the fp8 control several tenths
# (tests/chipbench/test_chipbench_kexaone.py prints it). The real cell's
# limit is set from runs at its own size
# (chipbench/limits/serve-kexaone-longdoc-mixed.json)
LIMIT = 0.1


def write_root(root, real_manifest):
    """``root``/BENCHMARK.json with one tiny cell that reports what the
    real cell of the family reports."""
    bench = os.path.join(root, "chipbench")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    with open(os.path.join(bench, "configs", "tiny-kexaone.json"), "w") as f:
        json.dump(KEXAONE, f)
    with open(os.path.join(bench, "traffic", "tiny-longdoc.json"), "w") as f:
        json.dump(SERVE, f)
    with open(os.path.join(bench, "limits", CELL + ".json"), "w") as f:
        json.dump({"served_logit_gap_max": {"limit": LIMIT}}, f)
    real_cell = next(w["name"] for w in real_manifest["workloads"]
                     if w["config"].startswith("kexaone"))

    def cells(m):
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = [CELL] if real_cell in m["workloads"] else []
        return m

    manifest = dict(real_manifest)
    manifest["configs"] = [{"name": "tiny-kexaone", "source": "test",
                            "reduced": [], "why": "test",
                            "file": "chipbench/configs/tiny-kexaone.json"}]
    manifest["workloads"] = [{"name": CELL, "config": "tiny-kexaone",
                              "traffic": "tiny-longdoc", "chips": 1,
                              "why": "test"}]
    manifest["end_to_end"] = [cells(m) for m in real_manifest["end_to_end"]]
    manifest["per_layer"] = [cells(m) for m in real_manifest["per_layer"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
