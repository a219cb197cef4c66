"""The one place that knows how the program's ``TransformerLM`` names its
parameters. The benchmark makes the weights (chipbench/weights.py) under
the reference's names; this re-nests the same arrays, copying nothing."""

GLOBALS = {"wte": ("tok", "emb"), "wpe": ("pos", "emb"),
           "lnf_g": ("ln_f", "scale"), "lnf_b": ("ln_f", "bias")}
LAYER = {"ln1_g": ("ln1", "scale"), "ln1_b": ("ln1", "bias"),
         "w_qkv": ("attn", "qkv", "w"), "b_qkv": ("attn", "qkv", "b"),
         "w_o": ("attn", "out", "w"), "b_o": ("attn", "out", "b"),
         "ln2_g": ("ln2", "scale"), "ln2_b": ("ln2", "bias"),
         "w_fc": ("fc1", "w"), "b_fc": ("fc1", "b"),
         "w_proj": ("fc2", "w"), "b_proj": ("fc2", "b")}


def _put(tree, path, x):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = x


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def to_program(weights):
    tree = {"blocks": []}
    for name, x in weights["globals"].items():
        _put(tree, GLOBALS[name], x)
    for layer in weights["layers"]:
        blk = {}
        for name, x in layer.items():
            _put(blk, LAYER[name], x)
        tree["blocks"].append(blk)
    return tree


def from_program(tree):
    """The inverse, for any tree shaped like the parameters (moments,
    per-leaf norms)."""
    return {"globals": {n: _get(tree, p) for n, p in GLOBALS.items()
                        if p[0] in tree},
            "layers": [{n: _get(blk, p) for n, p in LAYER.items()}
                       for blk in tree["blocks"]]}
