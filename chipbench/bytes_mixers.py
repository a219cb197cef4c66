"""Bytes a decode step of a model of linear- and sparse-attention layers
has to move, from shapes and from what the engine counted. Kept with the
benchmark so that no later PR can change how ``sparse_attention_roofline``
and ``linear_state_roofline`` are counted.

A sparse layer's step reads, for each running row and KV head, the
compressed keys of the windows its context has closed (one ``head_dim``
vector a window, ``kernel_stride`` positions apart) and the K and V of
every position of the pages it CHOSE; a linear layer's step reads each
running row's state and writes it back. Queries, outputs, tables, the
step's own key and a page's dead tail are not counted, so the least time
is an underestimate and a share can only read low, never over 100 %."""


def chosen_page_bytes(cfg, blocks, bytes_per_el=2):
    """K and V of ``blocks`` chosen blocks (summed over rows, layers and
    KV heads: each is one KV head's ``block_size`` positions)."""
    return blocks * cfg["sparse_config"]["block_size"] * 2 \
        * cfg["head_dim"] * bytes_per_el


def compressed_key_bytes(cfg, context_tokens, layers=1, bytes_per_el=2):
    """The compressed keys of ``context_tokens`` resident positions
    (summed over the running rows) in ``layers`` sparse layers."""
    return context_tokens / cfg["sparse_config"]["kernel_stride"] * layers \
        * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_el


def state_bytes(cfg, rows, layers=1):
    """``rows`` float32 states read and written in ``layers`` linear
    layers."""
    return 2 * rows * layers * cfg["lightning_nh"] \
        * cfg["lightning_head_dim"] ** 2 * 4
