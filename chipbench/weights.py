"""Weights from ``--seed``, made on the device in the type they are
served or trained in. Every leaf has a key of its own (seed, layer, leaf),
so one layer can be made again alone, which is how the serving reference
walks a model that does not fit beside its float32 copy."""

import functools
import importlib

import jax
import jax.numpy as jnp


def family(cfg):
    """The plain reference module of the configuration's architecture."""
    return importlib.import_module(f"chipbench.reference.{cfg['family']}")


def base_key(seed: int):
    # seeds run past 2**31; a PRNGKey takes 32 bits at a time
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _group(key, specs, dtype):
    out = {}
    for n, (name, shape, init, std) in enumerate(specs):
        x = std * jax.random.normal(jax.random.fold_in(key, n), shape,
                                    jnp.float32)
        if init == "gain":
            x = 1.0 + x
        elif init != "normal":
            raise ValueError(f"unknown init {init!r} for {name}")
        out[name] = x.astype(dtype)
    return out


@functools.lru_cache(maxsize=None)
def _makers(family_name, cfg_items, dtype):
    cfg = dict(cfg_items)
    specs = importlib.import_module(
        f"chipbench.reference.{family_name}").leaf_specs(cfg)
    g = jax.jit(lambda key: _group(jax.random.fold_in(key, 0),
                                   specs["globals"], dtype))
    layer = jax.jit(lambda key, i: _group(jax.random.fold_in(key, 1 + i),
                                          specs["layer"], dtype))
    return g, layer


def _cfg_items(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def n_layers(cfg):
    return cfg.get("n_layer", cfg.get("num_hidden_layers"))


def make_globals(seed, cfg, dtype):
    g, _ = _makers(cfg["family"], _cfg_items(cfg), jnp.dtype(dtype))
    return g(base_key(seed))


def make_layer(seed, cfg, i, dtype):
    _, layer = _makers(cfg["family"], _cfg_items(cfg), jnp.dtype(dtype))
    return layer(base_key(seed), jnp.int32(i))


def make(seed, cfg, dtype):
    """The whole model: ``{"globals": {...}, "layers": [{...}, ...]}``.
    Two compiled programs, the second called once a layer."""
    return {"globals": make_globals(seed, cfg, dtype),
            "layers": [make_layer(seed, cfg, i, dtype)
                       for i in range(n_layers(cfg))]}
