"""MiniCPM-SALA through the program's public model class: the keyword
arguments that say which layer mixes by linear attention and which by
sparse attention (``TransformerLM`` ``layer_mixers``, ``linear``,
``sparse``), the muP scalings (``emb_scale``, ``branch_scale``,
``logit_scale``), and the benchmark's leaves re-nested under the program's
names, copying nothing but the q/k norms' scales (128 numbers each, times
the configuration's ``init_*_qk_gain``: the weights' maker knows gains of
mean 1 alone). The ``minicpm4`` layers' leaves come out of ``globals``
(prefixes ``s0_``, ``s1_``, ...: reference/minicpm_sala.py), the walked
``lightning-attn`` layers stand between them as ``mixer_types`` says."""

from chipbench.adapters.transformer_lm import _put
from chipbench.reference.minicpm_sala import (LINEAR, SPARSE, branch_scale,
                                              mixers)

GLOBALS = {"wte": ("tok", "emb"), "lnf_g": ("ln_f", "scale"),
           "w_head": ("head", "w")}
LAYER = {"ln1_g": ("ln1", "scale"), "w_qkv": ("attn", "qkv", "w"),
         "q_g": ("attn", "q_norm", "scale"),
         "k_g": ("attn", "k_norm", "scale"),
         "o_g": ("attn", "o_norm", "scale"), "w_g": ("attn", "gate", "w"),
         "w_o": ("attn", "out", "w"), "ln2_g": ("ln2", "scale"),
         "w_gate": ("ffn", "gate", "w"), "w_up": ("ffn", "up", "w"),
         "w_down": ("ffn", "down", "w")}
NAMES = {LINEAR: "linear", SPARSE: "sparse"}


def model_kwargs(cfg, max_len=None):
    switches = dict(
        qk_norm=True, attn_use_rope=False, lightning_use_rope=True,
        use_output_gate=True, use_output_norm=True,
        attn_use_output_gate=True, attention_bias=False,
        tie_word_embeddings=False, hidden_act="silu",
        lightning_scale="1/sqrt(d)")
    off = {k: cfg[k] for k, v in switches.items() if cfg[k] != v}
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    if off or (cfg["lightning_nh"], cfg["lightning_nkv"],
               cfg["lightning_head_dim"]) != (h, h, dh):
        raise ValueError(
            f"the reference implements the published switches {switches} "
            f"and lightning heads like the query heads; this config has "
            f"{off or 'other lightning heads'}")
    sc = cfg["sparse_config"]
    return dict(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=h,
        n_kv_heads=cfg["num_key_value_heads"], head_dim=dh,
        attn_bias=False, qk_norm=cfg["rms_norm_eps"],
        max_seq=max_len or cfg["max_position_embeddings"], pos="rope",
        rope_base=cfg["rope_theta"], tie_embeddings=False,
        norm="rms", norm_eps=cfg["rms_norm_eps"],
        ffn_dim=cfg["intermediate_size"],
        layer_mixers=tuple(NAMES[m] for m in mixers(cfg)),
        linear=dict(rope=True, out_norm=cfg["rms_norm_eps"], out_gate=True),
        sparse=dict(kernel=sc["kernel_size"], stride=sc["kernel_stride"],
                    block=sc["block_size"], topk=sc["topk"],
                    init_blocks=sc["init_blocks"], window=sc["window_size"],
                    dense_len=sc["dense_len"], rope=False, out_gate=True),
        emb_scale=cfg["scale_emb"], branch_scale=branch_scale(cfg),
        logit_scale=cfg["hidden_size"] / cfg["dim_model_base"])


def to_program(weights, cfg):
    tree = {"blocks": []}
    held = {}
    for name, x in weights["globals"].items():
        if name in GLOBALS:
            _put(tree, GLOBALS[name], x)
        else:
            pre, leaf = name.split("_", 1)            # "s0", "w_qkv"
            held.setdefault(pre, {})[leaf] = x
    walked = iter(weights["layers"])
    n_sparse = 0
    for mixer in mixers(cfg):
        if mixer == SPARSE:
            leaves, gain = held[f"s{n_sparse}"], cfg["init_sparse_qk_gain"]
            n_sparse += 1
        else:
            leaves, gain = next(walked), cfg["init_linear_qk_gain"]
        blk = {}
        for name, x in leaves.items():
            _put(blk, LAYER[name], x * gain if name in ("q_g", "k_g") else x)
        tree["blocks"].append(blk)
    return tree
