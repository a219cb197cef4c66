"""The control's arithmetic: a matrix product whose operands are rounded
to fp8 (e4m3, each tensor scaled to the format's range), the precision
next below the bfloat16 the configurations state. A later PR tempted to
serve or train in fp8 would compute this."""

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


def round_fp8(x):
    """``x`` rounded to e4m3 after scaling its largest magnitude to the
    format's range. Straight-through for gradients: a cotangent cast to
    e4m3 unscaled would flush to zero, which is a broken step and not a
    lower precision."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = E4M3_MAX / amax
    rounded = (x * s).astype(jnp.float8_e4m3fn).astype(x.dtype) / s
    return x + jax.lax.stop_gradient(rounded - x)


def mm_fp8(a, b):
    return jnp.matmul(round_fp8(a), round_fp8(b))
