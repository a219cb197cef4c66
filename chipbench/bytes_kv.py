"""Bytes of resident keys and values a decode step's attention has to
read, from shapes and from the contexts the engine held. Kept with the
benchmark so that no later PR can change how ``global_attention_roofline``
is counted.

A layer that sees every earlier position reads, for each running row, the
K and the V entry of every position of the row's context: ``2 x KV heads x
head size`` values a token. Queries, the output and the page table are not
counted (64 x 128 values a row against megabytes of pages), and a page's
dead tail is not either, so the least time is an underestimate and the
share can only read low, never over 100 %."""


def kv_bytes_a_token(cfg, bytes_per_el=2):
    """K and V of one position of one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_el


def resident_kv_bytes(cfg, context_tokens, layers=1, bytes_per_el=2):
    """HBM bytes of ``context_tokens`` resident positions (summed over the
    running rows) in ``layers`` layers."""
    return context_tokens * layers * kv_bytes_a_token(cfg, bytes_per_el)
