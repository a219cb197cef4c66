"""Compile-only: the flash-attention kernel at the cells' real shapes, for
a described (not attached) ``v5e:2x2``, so that what the chip's compiler
refuses costs no chip time. The topology is described inside a fixture,
never at import: only one process may load the TPU's library, and every
test worker imports this file."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache but cannot be
    # read back without a chip: keep these tests silent
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def shapes(b, h, h_kv, s, d, sharding):
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((b, h_kv, s, d), jnp.bfloat16,
                              sharding=sharding)
    return q, kv, kv


# (batch, heads, kv heads, keys, head size, backward too)
CASES = [
    pytest.param(8, 25, 25, 1024, 64, True, id="gpt2xl-train-1024"),
    pytest.param(1, 24, 2, 512, 128, False, id="starcoder2-prefill-512"),
    pytest.param(1, 24, 2, 2048, 128, False, id="starcoder2-prefill-2048"),
]


@pytest.mark.parametrize("b,h,h_kv,s,d,backward", CASES)
def test_flash_compiles_for_v5e(one_chip, b, h, h_kv, s, d, backward):
    from distributed_pytorch_tpu.ops import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    compiled = jax.jit(fn).lower(*shapes(b, h, h_kv, s, d, one_chip)).compile()
    n = compiled.as_text().count("tpu_custom_call")
    assert n == (3 if backward else 1), n
