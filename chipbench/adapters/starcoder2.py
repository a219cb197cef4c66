"""StarCoder2 through the program's public model class. No window: the
paged pool refuses one, and the cells keep every context inside the
published 4096 (the configuration file says so; the reference checks it)."""

from chipbench.adapters.transformer_lm import from_program, to_program  # noqa: F401


def model_kwargs(cfg, max_len=None):
    if cfg["intermediate_size"] % cfg["hidden_size"]:
        raise ValueError("the program's MLP width is a whole multiple of dim")
    return dict(vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                max_seq=max_len or cfg["max_position_embeddings"],
                mlp_ratio=cfg["intermediate_size"] // cfg["hidden_size"],
                pos="rope", rope_base=cfg["rope_theta"], tie_embeddings=True)
