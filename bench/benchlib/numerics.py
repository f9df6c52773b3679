"""Matrix products for the plain references, at a stated precision.

``highest``  float32 operands, products at full float32 precision
             (`lax.Precision.HIGHEST`).  The references run here.
``bf16x3``   the control: each float32 operand split into a high part and
             a low part of 8 significant bits each (bfloat16's), and the
             three products hi*hi + hi*lo + lo*hi accumulated in float32.
             This is what `Precision.HIGH` runs on a TPU, the nearest
             precision below the configurations' float32 at HIGHEST;
             spelled out here so that it means the same on every platform.
             The parts are rounded by integer arithmetic, not by a round trip through
             bfloat16, which XLA may fold away (excess precision); each
             part is exact in bfloat16, so every product is exact in one
             MXU pass and on the CPU alike.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("highest", "bf16x3")
_CONV_DIMS = ("NHWC", "HWIO", "NHWC")


def key(seed: int):
    """A JAX random key from any non-negative seed, however large."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _top8(x):
    """x rounded (to nearest, ties to even) to bfloat16's 8 significant
    bits, kept in float32."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _split(x):
    hi = _top8(x)
    return hi, _top8(x - hi)


def _three_pass(f, a, b):
    ah, al = _split(a)
    bh, bl = _split(b)
    return f(ah, bh) + (f(ah, bl) + f(al, bh))


def conv(x, w, *, stride: int, pad: int, mode: str):
    """NHWC input, HWIO weight, symmetric padding."""
    def f(x, w, precision=None):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=_CONV_DIMS, precision=precision,
            preferred_element_type=jnp.float32)
    if mode == "highest":
        return f(x, w, jax.lax.Precision.HIGHEST)
    if mode != "bf16x3":
        raise ValueError(f"unknown precision mode {mode!r}; one of {MODES}")
    return _three_pass(lambda a, b: f(a, b, jax.lax.Precision.DEFAULT), x, w)
