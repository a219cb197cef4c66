"""GPT-2 through the program's public model class."""

from chipbench.adapters.transformer_lm import from_program, to_program  # noqa: F401


def model_kwargs(cfg, max_len=None):
    return dict(vocab=cfg["vocab_size"], dim=cfg["n_embd"],
                n_layers=cfg["n_layer"], n_heads=cfg["n_head"],
                max_seq=cfg["n_positions"], mlp_ratio=4, pos="learned",
                tie_embeddings=True)
