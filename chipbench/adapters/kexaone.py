"""K-EXAONE through the program's public model class: the keyword
arguments that build it from parts (``TransformerLM`` ``block_kinds``,
``norm="rms"``, ``head_dim``, ``attn_bias=False``, ``qk_norm``,
``moe=dict(held=...)``) and say which layers slide a window and rotate
(``layer_windows``, ``layer_rope``), and the benchmark's leaves re-nested
under the program's names, copying nothing. The leading dense layer's
leaves come out of ``globals`` (prefix ``d_``, see reference/kexaone.py),
the walked expert layers follow it."""

from chipbench.adapters.transformer_lm import _put
from chipbench.reference.kexaone import window_of

GLOBALS = {"wte": ("tok", "emb"), "lnf_g": ("ln_f", "scale"),
           "w_head": ("head", "w")}
ATTN = {"ln1_g": ("ln1", "scale"), "w_qkv": ("attn", "qkv", "w"),
        "q_g": ("attn", "q_norm", "scale"),
        "k_g": ("attn", "k_norm", "scale"), "w_o": ("attn", "out", "w"),
        "ln2_g": ("ln2", "scale")}
DENSE = {**ATTN, "w_gate": ("ffn", "gate", "w"), "w_up": ("ffn", "up", "w"),
         "w_down": ("ffn", "down", "w")}
EXPERT = {**ATTN, "w_router": ("ffn", "router", "w"),
          "b_router": ("ffn", "router", "bias"),
          "we_gate": ("ffn", "experts", "gate"),
          "we_up": ("ffn", "experts", "up"),
          "we_down": ("ffn", "experts", "down"),
          "ws_gate": ("ffn", "shared", "gate", "w"),
          "ws_up": ("ffn", "shared", "up", "w"),
          "ws_down": ("ffn", "shared", "down", "w")}


def model_kwargs(cfg, max_len=None):
    n, walked = cfg["num_hidden_layers"], cfg["n_layer"]
    kinds = cfg["mlp_layer_types"][:n]
    if kinds != ["dense"] + ["sparse"] * walked or n != 1 + walked:
        raise ValueError("the reference runs ONE leading dense layer and "
                         "walks n_layer sparse layers after it")
    if (cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"]
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1
            or cfg["rope_parameters"]["rope_type"] != "default"):
        raise ValueError("the reference has sigmoid scores, the chosen "
                         "renormalised, no group limit and plain RoPE")
    windows = tuple(window_of(cfg, layer) for layer in range(n))
    return dict(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=n,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        attn_bias=False, qk_norm=cfg["rms_norm_eps"],
        max_seq=max_len or cfg["max_position_embeddings"], pos="rope",
        rope_base=cfg["rope_parameters"]["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        block_kinds=("dense",) + ("moe",) * walked,
        norm="rms", norm_eps=cfg["rms_norm_eps"],
        ffn_dim=cfg["intermediate_size"],
        moe=dict(n_routed=cfg["router_width"],
                 width=cfg["moe_intermediate_size"],
                 top_k=cfg["num_experts_per_tok"],
                 n_shared=cfg["num_shared_experts"],
                 scale=cfg["routed_scaling_factor"],
                 held=(cfg["experts_held_first"], cfg["num_experts"])),
        # a window layer rotates q and k, a global layer nothing
        layer_windows=windows,
        layer_rope=tuple(w is not None for w in windows))


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
