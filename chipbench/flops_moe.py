"""Operations and bytes a dropless expert layer's grouped matmuls need,
from shapes and from what the router did. Kept with the benchmark so that
no later PR can change how ``moe_experts_roofline`` is counted.

An expert is three matrices of ``hidden x width`` (gate, up, down). A
decode step has to read every expert that got at least one token, once,
and nothing of the others; it multiplies every routed (token, expert)
pair through the three. Activations are not counted among the bytes
(a few hundred rows against 22 MB an expert), so the least time is an
underestimate and the share can only read low, never over 100 %."""


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def experts_bytes(cfg, experts_touched, bytes_per_el=2):
    """HBM bytes of the weights of ``experts_touched`` experts."""
    return experts_touched * expert_params(cfg) * bytes_per_el


def experts_flops(cfg, pairs_routed):
    """Matmul FLOPs of ``pairs_routed`` (token, expert) pairs: two a
    multiply-add, through gate, up and down."""
    return pairs_routed * 2 * expert_params(cfg)
