"""The arithmetic a reference runs in.

``f32``: float32 operands, ``highest`` precision (on a TPU a float32 product
otherwise runs in one bfloat16 pass).  ``fp8``: the control of "How correct is
decided": the nearest precision below the bfloat16 compute both configurations
state.  Each operand of every product is scaled to its largest magnitude and
rounded to ``float8_e4m3fn`` on the way in (the backward pass sees the
rounding as the identity), the way an fp8 training path would do it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


class Numerics:
    def __init__(self, mode: str = "f32"):
        if mode not in ("f32", "fp8"):
            raise ValueError(f"unknown numerics {mode!r}")
        self.mode = mode

    def operand(self, x):
        x = x.astype(jnp.float32)
        return _fp8(x) if self.mode == "fp8" else x

    def einsum(self, eq: str, a, b):
        return jnp.einsum(eq, self.operand(a), self.operand(b),
                          precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    def conv(self, x, w, stride: int, padding):
        return jax.lax.conv_general_dilated(
            self.operand(x), self.operand(w), (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
            preferred_element_type=jnp.float32)
