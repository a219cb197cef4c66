"""Where XLA's persistent compilation cache lives.

A cold start on the chip compiles for minutes; the persistent cache
turns the second start into a file read. The directory is part of the
deployment, not of the code:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it at import and nothing
  here touches the directory: the cache is wherever the operator put it.
* unset — one fixed path inside the checkout (``<repo>/.jax_cache``,
  git-ignored). Fixed on purpose: the directory is part of the cache
  key, so a temp name, pid or timestamp would never hit.

:func:`enable` is idempotent and cheap; every entry point that compiles
(``launch``, ``make_step``, ``InferenceEngine``) calls it before its
first compile.

Donation and the cache: on jax 0.4.37 a deserialized CPU executable lost
its input-output aliasing and a donated train step diverged. Under the
installed jax 0.9.0 a warm run (every program a cache hit) reproduces
the cold run's losses bit for bit with donation on — CPU, 1 and 8
devices, and the TPU v5e (chip_smoke.py run twice against one directory,
PR 21) — so the cache is on everywhere.
"""

from __future__ import annotations

import os

import jax

from . import env

#: The in-checkout default (three levels up: runtime/ -> package -> repo).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Make sure the persistent compilation cache has a directory and
    return it. Obeys ``JAX_COMPILATION_CACHE_DIR`` when set (JAX's own
    handling places the cache; no directory is set from code), otherwise
    points JAX at :data:`DEFAULT_DIR`."""
    if env.raw("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    if jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
