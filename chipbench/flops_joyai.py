"""Operations a token of the family ``joyai`` needs, forward, from the
configuration's own keys (``flops.config_shape`` knows the dense block
only). Kept with the benchmark so that no later PR can change how a
utilization is counted. The table in ISSUE.md (PR 32) is this module's
test: at 8192 positions, 16 of 256 experts held, an eighth of the
vocabulary, 1233 MFLOP a token as the program runs it (the values padded
from 128 to the keys' 192 for the kernel) and 1133 as the algorithm needs
it."""


def parts(cfg, seq, *, pairs_here_per_token=None, padded_values=False):
    """Matmul FLOPs of one token's forward pass by part, summed over the
    layers that have the part (the prediction module's block counts among
    the attention and the expert layers). Embedding lookups are gathers
    and are not counted. ``pairs_here_per_token``: the (token, expert)
    pairs a token sends to the experts held here, a layer; by default
    what a uniform router sends, ``k * held / router_width``."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    n_mtp = cfg["num_nextn_predict_layers"]
    n_dense = cfg["first_k_dense_replace"]
    n_expert = cfg["num_hidden_layers"] - n_dense + n_mtp
    n_attn = cfg["num_hidden_layers"] + n_mtp
    expert = 3 * 2 * d * cfg["moe_intermediate_size"]
    if pairs_here_per_token is None:
        pairs_here_per_token = cfg["num_experts_per_tok"] \
            * cfg["n_routed_experts"] / cfg["router_width"]
    v_width = dn + dr if padded_values else dv
    return {
        "attn_projections": n_attn * 2 * (
            d * qr + qr * h * (dn + dr) + d * (kr + dr)
            + kr * h * (dn + dv) + h * dv * d),
        # causal: half of QK^T at nope + rope and of PV at the values'
        "attn_core": n_attn * 0.5 * h * 2 * seq * ((dn + dr) + v_width),
        "dense_mlp": n_dense * 3 * 2 * d * cfg["intermediate_size"],
        "router": n_expert * 2 * d * cfg["router_width"],
        "shared_experts": n_expert * cfg["n_shared_experts"] * expert,
        "routed_here": n_expert * pairs_here_per_token * expert,
        "heads": (1 + n_mtp) * 2 * d * cfg["vocab_size"],
        "mtp_merge": n_mtp * 2 * (2 * d) * d,
    }


def forward_flops_per_token(cfg, seq, **kw):
    return sum(parts(cfg, seq, **kw).values())


def train_flops_per_token(cfg, seq, **kw):
    """Forward and backward: three times the forward. Recomputed
    operations (remat) are not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq, **kw)
