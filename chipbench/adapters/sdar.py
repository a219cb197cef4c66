"""SDAR through the program's public model class: the keyword arguments
that build it from parts (``TransformerLM`` ``block_kinds``, ``norm="rms"``,
``head_dim``, ``attn_bias=False``, ``qk_norm``, ``moe=dict(score="softmax",
n_shared=0)``) and declare that it generates by blocks (``gen_block``,
``mask_id``), and the benchmark's leaves re-nested under the program's
names, copying nothing."""

from chipbench.adapters.transformer_lm import _put

GLOBALS = {"wte": ("tok", "emb"), "lnf_g": ("ln_f", "scale"),
           "w_head": ("head", "w")}
LAYER = {"ln1_g": ("ln1", "scale"), "w_qkv": ("attn", "qkv", "w"),
         "q_g": ("attn", "q_norm", "scale"),
         "k_g": ("attn", "k_norm", "scale"), "w_o": ("attn", "out", "w"),
         "ln2_g": ("ln2", "scale"), "w_router": ("ffn", "router", "w"),
         "we_gate": ("ffn", "experts", "gate"),
         "we_up": ("ffn", "experts", "up"),
         "we_down": ("ffn", "experts", "down")}


def model_kwargs(cfg, max_len=None):
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"] \
            or not cfg["norm_topk_prob"] or cfg["rope_scaling"] is not None:
        raise ValueError("the reference has every layer sparse, the chosen "
                         "probabilities renormalised and plain RoPE")
    return dict(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        attn_bias=cfg["attention_bias"], qk_norm=cfg["rms_norm_eps"],
        max_seq=max_len or cfg["max_position_embeddings"], pos="rope",
        rope_base=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        block_kinds=("moe",) * cfg["num_hidden_layers"],
        norm="rms", norm_eps=cfg["rms_norm_eps"],
        moe=dict(n_routed=cfg["num_experts"],
                 width=cfg["moe_intermediate_size"],
                 top_k=cfg["num_experts_per_tok"], n_shared=0,
                 score="softmax"),
        gen_block=cfg["block_length"], mask_id=cfg["mask_token_id"])


def to_program(weights):
    tree = {"blocks": []}
    for name, x in weights["globals"].items():
        _put(tree, GLOBALS[name], x)
    for layer in weights["layers"]:
        blk = {}
        for name, x in layer.items():
            _put(blk, LAYER[name], x)
        tree["blocks"].append(blk)
    return tree
