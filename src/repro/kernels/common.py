"""Shared pieces for the compute-engine kernels.

The paper's HLS engine fuses the activation stage into the streaming GEMM
pipeline (data leaves the PE array already activated).  We mirror that with a
fused epilogue applied while the output tile is still in VMEM:

    y = act(acc * scale + shift)

``scale``/``shift`` are per-output-column vectors.  This one form covers all
Darknet layer needs: plain bias (scale=1, shift=bias), folded batch-norm
(scale=gamma/sqrt(var+eps), shift=beta-mean*scale [+bias]), and bare GEMM
(scale=None, shift=None).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def default_interpret() -> bool:
    """Whether Pallas kernels run in the interpreter, derived from the
    platform: True on the CPU backend (tests), False on a TPU.  Any other
    platform raises RuntimeError — no kernel mode is guessed for it."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels have no execution mode for platform "
                       f"{platform!r} (supported: cpu, tpu)")


def resolve_interpret(interpret: bool | None) -> bool:
    """An explicit `interpret` wins (compile tests pass False on a CPU host
    against a described chip); None derives it from the platform."""
    return default_interpret() if interpret is None else bool(interpret)


# Activations supported by the fused epilogue.  Darknet's default conv
# activation is leaky ReLU with slope 0.1; YOLOv4's backbone uses mish;
# LM blocks use silu/gelu.
_LEAKY_SLOPE = 0.1
# Darknet's softplus threshold inside mish (MISH_THRESHOLD).
_MISH_THRESHOLD = 20.0


def _mish_parts(x):
    """(e, sp) of mish as darknet computes it: e = exp(min(x, 20)) and
    softplus(x) = x above 20, exp(x) below -20, log1p(exp(x)) between
    (darknet's CUDA kernel; its C code writes log(exp(x) + 1)).  The
    exponent is capped so that no branch `where` leaves out overflows and
    turns the gradient into NaN."""
    e = jnp.exp(jnp.minimum(x, _MISH_THRESHOLD))
    sp = jnp.where(x > _MISH_THRESHOLD, x,
                   jnp.where(x < -_MISH_THRESHOLD, e, jnp.log1p(e)))
    return e, sp


def apply_act(x, act: str):
    if act == "linear":
        return x
    if act == "relu":
        # `where` (not jnp.maximum) so autodiff's subgradient at exactly 0
        # is 0 on every backend — matching `act_deriv`'s kernel residual
        # (maximum splits ties 0.5/0.5).
        return jnp.where(x > 0, x, 0.0)
    if act == "leaky":
        return jnp.where(x > 0, x, _LEAKY_SLOPE * x)
    if act == "silu":
        # jax.nn.sigmoid (logistic): same values as 1/(1+exp(-x)), but its
        # autodiff is overflow-safe — the naive form's gradient is
        # inf/inf = NaN once exp(-x) overflows (|x| > ~88 in fp32).
        return x * jax.nn.sigmoid(x)
    if act == "gelu":
        # tanh approximation, matches jax.nn.gelu(approximate=True)
        c = jnp.sqrt(2.0 / jnp.pi).astype(x.dtype)
        return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x**3)))
    if act == "mish":
        # x * tanh(softplus(x)) (Misra 2019), darknet's thresholds.
        return x * jnp.tanh(_mish_parts(x)[1])
    raise ValueError(f"unknown activation: {act!r}")


ACTIVATIONS = ("linear", "relu", "leaky", "silu", "gelu", "mish")


def act_deriv(x, act: str):
    """d act(x) / dx, elementwise — the `act'(pre-act)` residual the fused
    GEMM's custom VJP emits from its forward kernel (docs/engine_api.md,
    "residual layout contract").  Subgradient at relu/leaky kinks follows
    `apply_act`'s `where` branches (0 resp. slope at exactly 0), so the
    kernel backward matches jax.grad of the jnp formulation bit-for-bit."""
    if act == "linear":
        return jnp.ones_like(x)
    if act == "relu":
        return jnp.where(x > 0, 1.0, 0.0).astype(x.dtype)
    if act == "leaky":
        return jnp.where(x > 0, 1.0, _LEAKY_SLOPE).astype(x.dtype)
    if act == "silu":
        s = 1.0 / (1.0 + jnp.exp(-x))
        return s * (1.0 + x * (1.0 - s))
    if act == "gelu":
        c = jnp.sqrt(2.0 / jnp.pi).astype(x.dtype)
        inner = c * (x + 0.044715 * x**3)
        t = jnp.tanh(inner)
        return (0.5 * (1.0 + t)
                + 0.5 * x * (1.0 - t**2) * c * (1.0 + 3 * 0.044715 * x**2))
    if act == "mish":
        # tanh(sp) + x * (1 - tanh(sp)^2) * sp'.  sp' is e / (1 + e) in
        # all three branches to float32's precision: 1 above 20, exp(x)
        # below -20 (darknet's backward writes it 1 - exp(-sp)).
        e, sp = _mish_parts(x)
        t = jnp.tanh(sp)
        return t + x * (1.0 - t * t) * (e / (1.0 + e))
    raise ValueError(f"unknown activation: {act!r}")


def epilogue(acc, scale, shift, act: str):
    """acc: (bm, bn) fp32 tile; scale/shift: (1, bn) or None."""
    y = acc
    if scale is not None:
        y = y * scale
    if shift is not None:
        y = y + shift
    return apply_act(y, act)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def im2col(x, kh: int, kw: int, stride: int, pad: int):
    """x: (B, H, W, C) -> patches (B, OH, OW, kh*kw*C).

    The canonical Darknet conv lowering: materialize patches, GEMM on the
    engine.  Shared by every backend's im2col-based conv2d op.  Patches are
    slices of the padded input — exact copies on every platform (a patches
    convolution would run on the TPU's MXU at the default precision and
    round every input to bf16).  At stride 1 each tap is a window of the
    padded input.  At stride s > 1 the padded input is split into its
    s x s phases (every s-th row and column, from each of the s offsets),
    and tap (ki, kj) is a unit-stride window of phase (ki % s, kj % s) at
    offset (ki // s, kj // s): a strided slice on the TPU's tiled W axis
    lowers to a gather, the phase split to a reshape, a transpose and
    plain slices.  Both give the same (kh, kw, C)-ordered patches, bit for
    bit.

    Carries a custom VJP whose backward is a col2im scatter-add (the
    `deconv2d` idiom) accumulated in fp32: patch cotangents accumulate back
    onto the input positions each tap read.
    """
    return _im2col_fwd_impl(x, kh, kw, stride, pad)


def _im2col_fwd_impl(x, kh, kw, stride, pad):
    b, h, w, c = x.shape
    s = stride
    oh = (h + 2 * pad - kh) // s + 1
    ow = (w + 2 * pad - kw) // s + 1
    if s == 1:
        xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        taps = [xp[:, ki:ki + oh, kj:kj + ow, :]
                for ki in range(kh) for kj in range(kw)]
    else:
        # Pad (or crop) to the (s*hq, s*wq) rows and columns the taps
        # read; the extra ones sit at the bottom and right, never read.
        hq, wq = oh + (kh - 1) // s, ow + (kw - 1) // s
        xp = jax.lax.pad(x, jnp.zeros((), x.dtype),
                         ((0, 0, 0), (pad, s * hq - h - pad, 0),
                          (pad, s * wq - w - pad, 0), (0, 0, 0)))
        # (B, s, s, hq, wq, C): phase (p, q) holds xp[:, p::s, q::s].
        ph = xp.reshape(b, hq, s, wq, s, c).transpose(0, 2, 4, 1, 3, 5)
        taps = [ph[:, ki % s, kj % s, ki // s:ki // s + oh,
                   kj // s:kj // s + ow, :]
                for ki in range(kh) for kj in range(kw)]
    # Tap-major, channel-minor: (kh, kw, C) order, the HWIO weight layout.
    return jnp.concatenate(taps, axis=-1)


def col2im(g, x_shape: tuple, kh: int, kw: int, stride: int, pad: int):
    """Transpose of `im2col`: scatter patch cotangents g (B, OH, OW,
    kh*kw*C) back onto dx (B, H, W, C).  Static python loop over the
    (kh, kw) taps, each a strided slice-add — every output position
    (i, j) of tap (ki, kj) read padded-input position (i*stride + ki,
    j*stride + kj), so its cotangent accumulates back there."""
    b, h, w, c = x_shape
    _, oh, ow, _ = g.shape
    g = g.reshape(b, oh, ow, kh, kw, c).astype(jnp.float32)
    dx = jnp.zeros((b, h + 2 * pad, w + 2 * pad, c), jnp.float32)
    for ki in range(kh):
        for kj in range(kw):
            dx = dx.at[:, ki:ki + oh * stride:stride,
                       kj:kj + ow * stride:stride, :].add(g[:, :, :, ki, kj])
    return dx[:, pad:pad + h, pad:pad + w, :]


def _im2col_vjp_fwd(x, kh, kw, stride, pad):
    # Residual: a zero-size array whose STATIC shape/dtype carry what the
    # backward needs (residual pytrees may only hold arrays, not dtypes).
    ref = jnp.zeros((0,) + x.shape[1:], x.dtype)
    return _im2col_fwd_impl(x, kh, kw, stride, pad), ref


def _im2col_vjp_bwd(kh, kw, stride, pad, ref, g):
    x_shape = (g.shape[0],) + ref.shape[1:]
    return (col2im(g, x_shape, kh, kw, stride, pad).astype(ref.dtype),)


im2col.defvjp(_im2col_vjp_fwd, _im2col_vjp_bwd)
