"""Operations and bytes the algorithm needs, from shapes alone. Kept with
the benchmark so that no later PR can change how a utilization is counted.

``model_flops_per_token`` is ``benchmarks/mfu_transformer.py``'s count
(per layer ``(8 + 4r) d^2``, causal attention ``2 S d``, head ``2 d V``),
generalised to grouped-query attention: with ``h_kv`` of ``h`` heads the
key and value projections shrink to ``2 * 2 d^2 * h_kv / h``."""


def model_flops_per_token(*, dim, n_layers, vocab, seq, mlp_dim,
                          n_heads, n_kv_heads=None, causal=True):
    """Matmul FLOPs of one token's forward pass at sequence length
    ``seq``. Embedding lookups are gathers and are not counted."""
    n_kv_heads = n_kv_heads or n_heads
    q_and_out = 2 * dim * dim * 2
    k_and_v = 2 * dim * (dim * n_kv_heads // n_heads) * 2
    mlp = 2 * dim * mlp_dim * 2
    attn = 4 * seq * dim * (0.5 if causal else 1.0)
    return n_layers * (q_and_out + k_and_v + mlp + attn) + 2 * dim * vocab


def train_flops_per_token(**shape):
    """Forward and backward: three times the forward. Recomputed
    operations (remat) are not counted."""
    return 3.0 * model_flops_per_token(**shape)


def config_shape(cfg, seq):
    """The keyword arguments above from a configuration file of either
    family's key names."""
    dim = cfg.get("n_embd", cfg.get("hidden_size"))
    heads = cfg.get("n_head", cfg.get("num_attention_heads"))
    return dict(dim=dim, n_layers=cfg.get("n_layer",
                                          cfg.get("num_hidden_layers")),
                vocab=cfg["vocab_size"], seq=seq,
                mlp_dim=cfg.get("intermediate_size", 4 * dim),
                n_heads=heads,
                n_kv_heads=cfg.get("num_key_value_heads", heads))


def param_count(cfg):
    """Parameters of the dense pre-norm block both families share (biased
    projections, two LayerNorms a block, one final, tied head)."""
    s = config_shape(cfg, 0)
    d, f = s["dim"], s["mlp_dim"]
    kv = d * s["n_kv_heads"] // s["n_heads"]
    block = d * (d + 2 * kv) + (d + 2 * kv) + d * d + d \
        + 2 * d * f + f + d + 4 * d
    table = s["vocab"] * d + cfg.get("n_positions", 0) * d
    return s["n_layers"] * block + table + 2 * d


def flash_call_cost(*, batch, n_heads, n_kv_heads, seq_q, seq_k, head_dim,
                    kind, causal=True, bytes_per_el=2):
    """(FLOPs, HBM bytes) one flash-attention call has to do.

    ``kind`` is ``fwd`` (QK^T and PV: 4 S_q S_k Dh a head), ``dkv`` or
    ``dq`` (the two backward kernels; each recomputes the scores, so
    ``dkv`` does QK^T, dV = P^T dO, dP = dO V^T, dK = dS^T Q and ``dq``
    does QK^T, dP, dQ = dS K: 8 and 6 S_q S_k Dh). Causal halves them.
    Bytes: every operand read once and every result written once."""
    mm = {"fwd": 2, "dkv": 4, "dq": 3}[kind]
    flops = batch * n_heads * mm * 2 * seq_q * seq_k * head_dim
    if causal:
        flops *= 0.5
    q = batch * n_heads * seq_q * head_dim
    kv = batch * n_kv_heads * seq_k * head_dim
    stats = batch * n_heads * seq_q * 4          # row log-sum-exp, f32
    els = {"fwd": q + 2 * kv + q,                # q k v -> o
           "dkv": q + 2 * kv + 2 * q + 2 * kv,   # q k v o/do -> dk dv
           "dq": q + 2 * kv + 2 * q + q}[kind]   # q k v o/do -> dq
    return flops, els * bytes_per_el + stats
