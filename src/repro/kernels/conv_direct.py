"""Direct (implicit-GEMM) convolution kernel.

The Darknet path lowers conv as materialized im2col + GEMM — that is what
the paper's framework does, and it multiplies input HBM traffic by
KH·KW.  This kernel is the TPU-native upgrade: the im2col never exists —
an input row-band is staged in VMEM once and every (kh, kw) tap reads it
as a shifted static window feeding the MXU:

    grid = (B, OH/TH); x band (TH+KH-1, W, Cin) staged in VMEM;
    y[oh, ow, co] = Σ_{kh,kw} dot(x[oh+kh, ow+kw, :], w[kh, kw, :, co])

Taps are a python-unrolled loop of static slices — the same "operand
window streams past a resident accumulator" structure as the GEMM engine.
Stride 1, 'VALID' on a pre-padded input (ops wrapper pads).
Validated against jax.lax.conv in interpret mode (tests/test_kernels_conv.py).

FORWARD-ONLY: this kernel carries no custom VJP (differentiating it dies
inside pallas_call).  Training conv goes through the im2col GEMM path
(kernels/common.py im2col + kernels/gemm.py — both custom-VJP'd), which
is what the built-in pallas backend registers.  A backend registering
THIS kernel as its conv2d must exclude "conv2d" from `differentiable` so
the engine's guard raises the clear capability error instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret


def _conv_kernel(x_ref, w_ref, o_ref, *, kh: int, kw: int, th: int,
                 ow: int):
    # x_ref: (1, th+kh-1, W, Cin); w_ref: (kh, kw, Cin, Cout)
    # o_ref: (1, th, ow, Cout)
    cin = x_ref.shape[-1]
    cout = w_ref.shape[-1]
    acc = jnp.zeros((th * ow, cout), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            # shifted window: rows i..i+th, cols j..j+ow
            win = x_ref[0, i:i + th, j:j + ow, :].astype(jnp.float32)
            acc += jax.lax.dot_general(
                win.reshape(th * ow, cin), w_ref[i, j].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[0] = acc.reshape(th, ow, cout).astype(o_ref.dtype)


def _band_kernel(x_ref, w_ref, o_ref, *, kh: int, kw: int, th: int,
                 ow: int):
    # x_ref: (1, 1, th+kh-1, W, Cin) halo band; o_ref: (1, 1, th, ow, Cout)
    cin = x_ref.shape[-1]
    cout = w_ref.shape[-1]
    acc = jnp.zeros((th * ow, cout), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            win = x_ref[0, 0, i:i + th, j:j + ow, :].astype(jnp.float32)
            acc += jax.lax.dot_general(
                win.reshape(th * ow, cin), w_ref[i, j].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[0, 0] = acc.reshape(th, ow, cout).astype(o_ref.dtype)


def conv2d_direct(x, w, *, th: int = 8, interpret: bool | None = None):
    """x: (B, H, W, Cin) pre-padded; w: (KH, KW, Cin, Cout).

    VALID conv, stride 1 -> (B, H-KH+1, W-KW+1, Cout).

    Overlapping VMEM bands are not expressible as portable BlockSpecs, so
    the wrapper materializes halo'd row bands once (duplication factor
    (th+KH-1)/th ≈ 1.25 for 3x3/th=8 — vs im2col's KH·KW = 9x).  The
    kernel then sees clean non-overlapping blocks.
    """
    B, H, W, Cin = x.shape
    KH, KW, _, Cout = w.shape
    OH, OW = H - KH + 1, W - KW + 1
    th = min(th, OH)
    n_bands = -(-OH // th)
    OH_pad = n_bands * th
    if OH_pad != OH:  # pad rows so every band is full; sliced off below
        x = jnp.pad(x, ((0, 0), (0, OH_pad - OH), (0, 0), (0, 0)))
    bands = jnp.stack(
        [jax.lax.dynamic_slice_in_dim(x, i * th, th + KH - 1, axis=1)
         for i in range(n_bands)], axis=1)   # (B, n_bands, th+KH-1, W, Cin)
    grid = (B, n_bands)
    kernel = functools.partial(_band_kernel, kh=KH, kw=KW, th=th, ow=OW)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, th + KH - 1, W, Cin),
                         lambda b, i: (b, i, 0, 0, 0)),
            pl.BlockSpec((KH, KW, Cin, Cout), lambda b, i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, th, OW, Cout),
                               lambda b, i: (b, i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_bands, th, OW, Cout), x.dtype),
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(bands, w)
    return out.reshape(B, OH_pad, OW, Cout)[:, :OH]
