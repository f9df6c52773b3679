"""Darknet layer library, lowered onto the compute engine.

All tensors are NHWC.  Convolution follows Darknet's canonical decomposition:
im2col -> GEMM on the engine -> reshape, with batch-norm folded into the
engine's fused (scale, shift) epilogue so a conv+BN+activation layer is ONE
engine invocation — the paper's stream-fused pipeline.

Deconvolution (transpose conv) is GEMM + col2im, same engine.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ComputeEngine
from repro.kernels.common import apply_act, im2col  # noqa: F401  (re-export)

_BN_EPS = 1e-5
# The named scope of each glue layer (route, shortcut, upsample, yolo) in a
# lowered network, beside the engine ops' "repro.op.<op>".
LAYER_SCOPE_PREFIX = "repro.layer."


def _scoped(fn):
    """Run a glue layer under ``jax.named_scope("repro.layer.<name>")``."""
    scope = LAYER_SCOPE_PREFIX + fn.__name__

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.named_scope(scope):
            return fn(*args, **kwargs)
    return wrapped


def fold_batchnorm(gamma, beta, mean, var, bias=None):
    """Returns (scale, shift) for the engine epilogue: y = conv*scale+shift."""
    scale = gamma / jnp.sqrt(var + _BN_EPS)
    shift = beta - mean * scale
    if bias is not None:
        shift = shift + bias * scale
    return scale, shift


# ----------------------------------------------------------------- layers ---

def conv2d(engine: ComputeEngine, params: dict, x, *, size: int, stride: int,
           pad: int, act: str, batch_normalize: bool):
    """Darknet [convolutional]: ONE fused engine conv2d op (the registry
    backend lowers it — im2col+GEMM on pallas/xla, or a direct kernel)."""
    w = params["w"]                       # (kh*kw*Cin, Cout)
    if batch_normalize:
        scale, shift = fold_batchnorm(params["gamma"], params["beta"],
                                      params["mean"], params["var"])
    else:
        scale, shift = None, params["b"]
    return engine.conv2d(x, w, scale=scale, shift=shift, size=size,
                         stride=stride, pad=pad, act=act, out_dtype=x.dtype)


def deconv2d(engine: ComputeEngine, params: dict, x, *, size: int,
             stride: int, pad: int, act: str, batch_normalize: bool):
    """Darknet [deconvolutional]: engine GEMM + col2im (scatter-add).

    x: (B, H, W, Cin); w: (Cin, kh*kw*Cout).  Output spatial size follows
    conv_transpose: OH = (H-1)*stride + size - 2*pad.
    """
    w = params["w"]
    b, h, wd, cin = x.shape
    khkw_cout = w.shape[1]
    cout = khkw_cout // (size * size)
    cols = engine.matmul(x.reshape(b * h * wd, cin), w, out_dtype=jnp.float32)
    cols = cols.reshape(b, h, wd, size, size, cout)
    oh = (h - 1) * stride + size - 2 * pad
    ow = (wd - 1) * stride + size - 2 * pad
    # col2im: scatter-add each kernel tap; static python loop over (kh, kw).
    out = jnp.zeros((b, oh + 2 * pad, ow + 2 * pad, cout), jnp.float32)
    for ki in range(size):
        for kj in range(size):
            out = out.at[:, ki:ki + h * stride:stride,
                         kj:kj + wd * stride:stride, :].add(cols[:, :, :, ki, kj, :])
    out = out[:, pad:pad + oh, pad:pad + ow, :]
    if batch_normalize:
        scale, shift = fold_batchnorm(params["gamma"], params["beta"],
                                      params["mean"], params["var"])
        out = out * scale + shift
    elif "b" in params:
        out = out + params["b"]
    return apply_act(out, act).astype(x.dtype)


def maxpool(x, *, size: int, stride: int, pad: int = 0):
    if pad:
        x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                    constant_values=-jnp.inf)
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, size, size, 1), (1, stride, stride, 1),
        "VALID")


def avgpool_global(x):
    return x.mean(axis=(1, 2))  # darknet [avgpool] is global


@_scoped
def upsample(x, *, stride: int):
    return jnp.repeat(jnp.repeat(x, stride, axis=1), stride, axis=2)


@_scoped
def shortcut(x, other, *, act: str = "linear"):
    return apply_act(x + other, act)


@_scoped
def route(tensors):
    return jnp.concatenate(tensors, axis=-1)


@_scoped
def yolo(x, *, classes: int):
    """Darknet [yolo] at inference (``forward_yolo_layer``): of each
    anchor's ``5 + classes`` entries, anchor-major on the channel axis,
    the logistic on 0-1 (x, y) and 4.. (objectness, classes); 2-3 (w, h)
    stay linear."""
    entry = np.arange(x.shape[-1]) % (5 + classes)
    return jnp.where((entry < 2) | (entry >= 4), jax.nn.sigmoid(x), x)


def connected(engine: ComputeEngine, params: dict, x, *, act: str):
    b = x.shape[0]
    return engine.matmul(x.reshape(b, -1), params["w"], shift=params["b"],
                         act=act, out_dtype=x.dtype)


def softmax(x):
    return jax.nn.softmax(x, axis=-1)


# ------------------------------------------------------------------- init ---

def init_conv(key, size, cin, cout, batch_normalize, dtype=jnp.float32):
    fan_in = size * size * cin
    w = jax.random.normal(key, (size * size * cin, cout), dtype) * np.sqrt(
        2.0 / fan_in)
    p = {"w": w}
    if batch_normalize:
        p.update(gamma=jnp.ones((cout,), dtype), beta=jnp.zeros((cout,), dtype),
                 mean=jnp.zeros((cout,), dtype), var=jnp.ones((cout,), dtype))
    else:
        p["b"] = jnp.zeros((cout,), dtype)
    return p


def init_deconv(key, size, cin, cout, batch_normalize, dtype=jnp.float32):
    fan_in = cin
    w = jax.random.normal(key, (cin, size * size * cout), dtype) * np.sqrt(
        2.0 / fan_in)
    p = {"w": w}
    if batch_normalize:
        p.update(gamma=jnp.ones((cout,), dtype), beta=jnp.zeros((cout,), dtype),
                 mean=jnp.zeros((cout,), dtype), var=jnp.ones((cout,), dtype))
    else:
        p["b"] = jnp.zeros((cout,), dtype)
    return p


def init_connected(key, nin, nout, dtype=jnp.float32):
    w = jax.random.normal(key, (nin, nout), dtype) * np.sqrt(2.0 / nin)
    return {"w": w, "b": jnp.zeros((nout,), dtype)}
