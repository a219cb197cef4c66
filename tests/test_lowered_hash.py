"""``tools/lowered_hash.py``: the hash of a lowered program follows what
the program computes and nothing else. Tiny programs, lowered on the CPU;
the Mosaic kernel is lowered FOR the TPU platform (no chip, no topology:
the kernel's serialized body is made by the lowering alone)."""

import jax
import jax.numpy as jnp
import pytest

from tools.lowered_hash import normalize, program_hash

X = jax.ShapeDtypeStruct((8, 128), jnp.float32)

PLAIN = """
import jax.numpy as jnp
def {name}(x):
    y = jnp.tanh(x) * {c}
    return y @ y.T
"""

KERNEL = """
import jax
from jax.experimental import pallas as pl
def body(x_ref, o_ref):
    o_ref[...] = x_ref[...] * {c}
def {name}(x):
    return pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
"""


def lowered(source, *, name="f", c=2.0, down=0, tpu=False):
    """``source`` compiled as a file of its own, ``down`` lines further
    down in it, and its function lowered."""
    scope = {}
    code = compile("\n" * down + source.format(name=name, c=c),
                   f"/somewhere/{name}_{down}.py", "exec")
    exec(code, scope)
    traced = jax.jit(scope[name]).trace(X)
    return traced.lower(lowering_platforms=("tpu",)) if tpu \
        else traced.lower()


@pytest.mark.parametrize("source,tpu", [(PLAIN, False), (KERNEL, True)],
                         ids=["plain", "mosaic-kernel"])
class TestWhatTheHashFollows:
    def test_two_lowerings_of_one_program_agree(self, source, tpu):
        a, b = (lowered(source, tpu=tpu) for _ in range(2))
        assert program_hash(a) == program_hash(b)

    def test_a_changed_constant_disagrees(self, source, tpu):
        assert program_hash(lowered(source, c=2.0, tpu=tpu)) \
            != program_hash(lowered(source, c=3.0, tpu=tpu))

    def test_a_moved_source_line_does_not(self, source, tpu):
        a, b = lowered(source, tpu=tpu), lowered(source, down=7, tpu=tpu)
        # the texts do differ: by their locations, and a kernel's body
        # (which carries its own) with them
        assert a.as_text(debug_info=True) != b.as_text(debug_info=True)
        if tpu:
            assert a.as_text() != b.as_text()
        assert program_hash(a) == program_hash(b)

    def test_a_renamed_program_does_not(self, source, tpu):
        a, b = lowered(source, tpu=tpu), lowered(source, name="g", tpu=tpu)
        assert a.as_text().split("\n")[0] != b.as_text().split("\n")[0]
        assert program_hash(a) == program_hash(b)


def test_normalize_leaves_no_path_and_no_location():
    text = normalize(lowered(KERNEL, down=3, tpu=True).as_text(
        debug_info=True))
    assert "/somewhere/" not in text and "loc(" not in text
    assert "#loc" not in text and text.startswith("module @_ ")
    assert "tpu_custom_call" in text        # the call itself stays
