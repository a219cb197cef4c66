"""JoyAI-LLM-Flash through the program's public model class: the keyword
arguments that build it from parts (``TransformerLM`` ``block_kinds``,
``attention="latent"``, ``norm="rms"``, ``moe=dict(held=...)``,
``mtp=1``), and the benchmark's leaves re-nested under the program's
names and back, copying nothing. The leading dense layer's leaves (prefix
``d_``) and the prediction module's (prefix ``m_``) come out of
``globals`` (reference/joyai.py); the walked expert layers follow the
dense one."""

from chipbench.adapters.transformer_lm import _get, _put

GLOBALS = {"wte": ("tok", "emb"), "lnf_g": ("ln_f", "scale"),
           "w_head": ("head", "w")}
ATTN = {"ln1_g": ("ln1", "scale"), "w_qa": ("attn", "q_a", "w"),
        "qa_g": ("attn", "q_norm", "scale"), "w_qb": ("attn", "q_b", "w"),
        "w_kva": ("attn", "kv_a", "w"), "kva_g": ("attn", "kv_norm", "scale"),
        "w_kvb": ("attn", "kv_b", "w"), "w_o": ("attn", "out", "w"),
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
MODULE = {"ne_g": ("norm_e", "scale"), "nh_g": ("norm_h", "scale"),
          "w_eh": ("proj", "w"), "lnf_g": ("norm", "scale"),
          **{n: ("block",) + p for n, p in EXPERT.items()}}


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
        moe=dict(n_routed=cfg["router_width"],
                 width=cfg["moe_intermediate_size"],
                 top_k=cfg["num_experts_per_tok"],
                 n_shared=cfg["n_shared_experts"],
                 scale=cfg["routed_scaling_factor"],
                 held=(cfg["experts_held_first"], cfg["n_routed_experts"])),
        mtp=cfg["num_nextn_predict_layers"])


def to_program(weights):
    tree = {"blocks": [{}], "mtp": {}}
    for name, x in weights["globals"].items():
        if name in GLOBALS:
            _put(tree, GLOBALS[name], x)
        elif name.startswith("d_"):
            _put(tree["blocks"][0], DENSE[name[2:]], x)
        else:
            _put(tree["mtp"], MODULE[name[2:]], x)
    for layer in weights["layers"]:
        blk = {}
        for name, x in layer.items():
            _put(blk, EXPERT[name], x)
        tree["blocks"].append(blk)
    return tree


def from_program(tree):
    """The inverse, for any tree shaped like the parameters (moments,
    per-leaf norms). A leaf the tree holds None at, as the optimizer's
    moments do at the router biases, is left out."""
    def named(sub, names, pre=""):
        got = {pre + n: _get(sub, p) for n, p in names.items()}
        return {n: x for n, x in got.items() if x is not None}

    return {"globals": {**named(tree, GLOBALS),
                        **named(tree["blocks"][0], DENSE, "d_"),
                        **named(tree["mtp"], MODULE, "m_")},
            "layers": [named(blk, EXPERT) for blk in tree["blocks"][1:]]}
