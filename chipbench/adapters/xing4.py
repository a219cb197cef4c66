"""Xing4.0 through the program's public model class: the keyword
arguments that build it from parts (``TransformerLM`` ``block_kinds``,
``attention="latent"``, ``norm="rms"``, ``hyper_connections``), and the
benchmark's leaves re-nested under the program's names, copying nothing.
The leading dense layer's leaves come out of ``globals`` (prefix ``d_``,
see reference/xing4.py), the walked expert layers follow it."""

from chipbench.adapters.transformer_lm import _put

GLOBALS = {"wte": ("tok", "emb"), "lnf_g": ("ln_f", "scale"),
           "w_head": ("head", "w")}
ATTN = {"ln1_g": ("ln1", "scale"), "w_qa": ("attn", "q_a", "w"),
        "qa_g": ("attn", "q_norm", "scale"), "w_qb": ("attn", "q_b", "w"),
        "w_kva": ("attn", "kv_a", "w"), "kva_g": ("attn", "kv_norm", "scale"),
        "w_kvb": ("attn", "kv_b", "w"), "w_o": ("attn", "out", "w"),
        "ln2_g": ("ln2", "scale")}
HC = {f"hc{i}_{leaf}": (f"hc{i}", leaf) for i in (1, 2)
      for leaf in ("p_pre", "p_post", "p_res", "a_pre", "a_post", "a_res",
                   "b_pre", "b_post", "b_res")}
DENSE = {**ATTN, **HC, "w_gate": ("ffn", "gate", "w"),
         "w_up": ("ffn", "up", "w"), "w_down": ("ffn", "down", "w")}
EXPERT = {**ATTN, **HC, "w_router": ("ffn", "router", "w"),
          "b_router": ("ffn", "router", "bias"),
          "we_gate": ("ffn", "experts", "gate"),
          "we_up": ("ffn", "experts", "up"),
          "we_down": ("ffn", "experts", "down"),
          "ws_gate": ("ffn", "shared", "gate", "w"),
          "ws_up": ("ffn", "shared", "up", "w"),
          "ws_down": ("ffn", "shared", "down", "w")}


def model_kwargs(cfg, max_len=None):
    dense, walked = cfg["first_k_dense_replace"], cfg["n_layer"]
    if dense != 1 or dense + walked != cfg["num_hidden_layers"]:
        raise ValueError("the reference runs ONE leading dense layer inside "
                         "embed and walks n_layer expert layers after it")
    return dict(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        max_seq=max_len or cfg["max_position_embeddings"], pos="none",
        rope_base=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        block_kinds=("dense",) * dense + ("moe",) * walked,
        attention="latent",
        latent=dict(q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
                    nope_dim=cfg["qk_nope_head_dim"],
                    rope_dim=cfg["qk_rope_head_dim"],
                    v_dim=cfg["v_head_dim"], yarn=cfg["rope_scaling"],
                    norm_eps=cfg["rms_norm_eps"]),
        norm="rms", norm_eps=cfg["rms_norm_eps"],
        ffn_dim=cfg["intermediate_size"],
        moe=dict(n_routed=cfg["n_routed_experts"],
                 width=cfg["moe_intermediate_size"],
                 top_k=cfg["num_experts_per_tok"],
                 n_shared=cfg["n_shared_experts"],
                 scale=cfg["routed_scaling_factor"]),
        hyper_connections=cfg["hc_mult"],
        hc=dict(sinkhorn_iters=cfg["hc_sinkhorn_iters"], eps=cfg["hc_eps"],
                clamp=(cfg["mhc_h_res_clamp_min"],
                       cfg["mhc_h_res_clamp_max"])))


def to_program(weights):
    tree = {"blocks": [{}]}
    for name, x in weights["globals"].items():
        if name in GLOBALS:
            _put(tree, GLOBALS[name], x)
        else:
            _put(tree["blocks"][0], DENSE[name[len("d_"):]], x)
    for layer in weights["layers"]:
        blk = {}
        for name, x in layer.items():
            _put(blk, EXPERT[name], x)
        tree["blocks"].append(blk)
    return tree
