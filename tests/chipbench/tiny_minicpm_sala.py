"""A tiny cell of the family ``minicpm_sala`` for the CPU tests, beside
``tiny.py`` and ``tiny_kexaone.py`` (which stay as they are): the same
harness, kind (``serve_mixers``) and readers on a configuration small
enough for a test run (hidden 64, 8 query heads over 2 KV heads of 8, as
many lightning heads; layers minicpm4, lightning-attn, lightning-attn,
minicpm4; compressed keys over windows of 4 keys 2 apart, blocks of 4 =
pages of 4, the 2 best blocks beside the first and those of the last 8
positions, dense below 24; vocabulary 211). Written into a temporary root
with its own ``BENCHMARK.json``."""

import json
import os

SALA = {
    "name": "tiny-minicpm-sala", "family": "minicpm_sala", "vocab_size": 211,
    "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 4,
    "num_hidden_layers_published": 32, "n_layer": 2, "n_sparse_layer": 2,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 8,
    "lightning_nh": 8, "lightning_nkv": 8, "lightning_head_dim": 8,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "attn_use_rope": False, "qk_norm": True, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True,
    "attention_bias": False, "hidden_act": "silu", "rms_norm_eps": 1e-06,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
    "dim_model_base": 4, "tie_word_embeddings": False,
    "max_position_embeddings": 4096,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "minicpm4"],
    "sparse_config": {"kernel_size": 4, "kernel_stride": 2, "block_size": 4,
                      "topk": 2, "init_blocks": 1, "window_size": 8,
                      "dense_len": 24},
    "init_embed_std": 0.0833, "init_matrix_gain": 1.0, "init_down_gain": 0.4,
    "init_sparse_out_gain": 2.0, "init_linear_out_gain": 1.0,
    "init_sparse_qk_gain": 1.7, "init_linear_qk_gain": 1.0,
    "init_head_gain": 16.0, "init_norm_gain_std": 0.1}
SERVE = {"kind": "serve_mixers", "rate_per_s": 8.0, "lead_in_s": 0.5,
         "drain_limit_s": 60, "schedule_seed": 1,
         "prompt_tokens": {"min": 8, "max": 60, "law": "log_uniform"},
         "answer_tokens": {"min": 4, "max": 12, "law": "log_uniform",
                           "distinct": 4},
         "engine": {"paged": True, "n_slots": 4, "max_len": 72,
                    "buckets": [8, 16], "max_queue": 256, "page_len": 4,
                    "prefix_share": False},
         "check_requests": 4, "trace_seconds": 1, "trace_iterations": 32,
         "trace_admissions": 1, "state_probe": {"prompt_tokens": 8, "answer_tokens": 60}}
CELL = "tiny-minicpm-sala-cell"
# at this size a run checks a few dozen served tokens: the seeds the tests
# use read 0, the dense fault 0.4 to 0.6 and the fp8 control 0.19; the
# probe request's slowest state reads 0.003 to 0.006 off the reference's,
# 0.010 to 0.011 when it is kept in bfloat16 and 0.064 in the control
# (tests/chipbench/test_chipbench_minicpm_sala.py prints them). The real
# cell's limits are set from runs at its own size
# (chipbench/limits/serve-minicpm-sala-longctx.json)
LIMIT = 0.05
STATE_LIMIT = 0.008
#: of the probe's 2 x 64 state values a float32 sum leaves none that
#: bfloat16 holds exactly; a state kept in bfloat16 all
BF16_SHARE_LIMIT = 0.004


def write_root(root, real_manifest):
    """``root``/BENCHMARK.json with one tiny cell that reports what the
    real cell of the family reports."""
    bench = os.path.join(root, "chipbench")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    with open(os.path.join(bench, "configs", SALA["name"] + ".json"),
              "w") as f:
        json.dump(SALA, f)
    with open(os.path.join(bench, "traffic", "tiny-longctx.json"), "w") as f:
        json.dump(SERVE, f)
    with open(os.path.join(bench, "limits", CELL + ".json"), "w") as f:
        json.dump({"served_logit_gap_max": {"limit": LIMIT},
                   "served_state_gap_max": {"limit": STATE_LIMIT},
                   "served_state_bfloat16_share":
                       {"limit": BF16_SHARE_LIMIT}}, f)
    real_cell = next(w["name"] for w in real_manifest["workloads"]
                     if w["config"].startswith("minicpm-sala"))

    def cells(m):
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = [CELL] if real_cell in m["workloads"] else []
        return m

    manifest = dict(real_manifest)
    manifest["configs"] = [{"name": SALA["name"], "source": "test",
                            "reduced": [], "why": "test",
                            "file": f"chipbench/configs/{SALA['name']}.json"}]
    manifest["workloads"] = [{"name": CELL, "config": SALA["name"],
                              "traffic": "tiny-longctx", "chips": 1,
                              "why": "test"}]
    manifest["end_to_end"] = [cells(m) for m in real_manifest["end_to_end"]]
    manifest["per_layer"] = [cells(m) for m in real_manifest["per_layer"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
