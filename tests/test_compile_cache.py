"""Where the persistent compilation cache lives
(runtime/compile_cache.py): JAX's own handling of
JAX_COMPILATION_CACHE_DIR when it is set, one fixed in-checkout path
when it is not."""

import os
import subprocess
import sys

import jax

from distributed_pytorch_tpu.runtime import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_unset_uses_the_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache")
        # fixed: a second call, or another process, names the same path
        assert compile_cache.enable() == compile_cache.DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_set_variable_places_the_cache_and_code_sets_nothing(tmp_path):
    """With the variable set, the cache lands where it says — through
    JAX's own reading of it — and nowhere else: not even a late
    ``enable()`` moves it."""
    code = (
        "import os, jax, jax.numpy as jnp\n"
        "from distributed_pytorch_tpu.runtime import compile_cache\n"
        "want = os.environ['JAX_COMPILATION_CACHE_DIR']\n"
        "assert jax.config.jax_compilation_cache_dir == want\n"
        "assert compile_cache.enable() == want\n"
        "assert jax.config.jax_compilation_cache_dir == want\n"
        "jax.jit(lambda x: (x @ x).sum())(jnp.ones((64, 64)))"
        ".block_until_ready()\n"
        "print(len(os.listdir(want)))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               # cache even this sub-second compile
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
               PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert int(out.stdout.strip().splitlines()[-1]) > 0
