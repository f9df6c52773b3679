"""im2col's patches, the `im2col_phased` counter, and the `conv_implicit`
counter of the convolutions that form their patches inside the GEMM.

At stride s > 1 `kernels.common.im2col` reads each tap as a unit-stride
window of one of the padded input's s x s phases; the oracle here is the
strided-slice formulation it replaced, which it must equal bit for bit
(the GEMM, the weights and the custom VJP's col2im backward are shared).
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.darknet_ref import DARKNET19_CFG, YOLOV3_CFG, yolov3_cfg
from repro.core import backends, make_engine
from repro.core.darknet.network import Network
from repro.kernels.common import im2col


def _strided_im2col(x, kh, kw, stride, pad):
    """Each tap a strided slice of the padded input, (kh, kw, C) order."""
    _, h, w, _ = x.shape
    oh = (h + 2 * pad - kh) // stride + 1
    ow = (w + 2 * pad - kw) // stride + 1
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    return jnp.concatenate(
        [xp[:, ki:ki + (oh - 1) * stride + 1:stride,
            kj:kj + (ow - 1) * stride + 1:stride, :]
         for ki in range(kh) for kj in range(kw)], axis=-1)


# Odd (9, 11) and even (8, 10) extents: with no padding, some of them leave
# rows and columns past the last tap (11 at stride 2, kernel 2), which the
# phase split crops; others fall short of whole phases and are padded.
@pytest.mark.parametrize(
    "stride, k, pad, hw, c, b",
    list(itertools.product((2, 3), (1, 2, 3), (0, 1), ((9, 11), (8, 10)),
                           (3, 32), (1, 2))))
def test_phase_im2col_equals_strided_slices(stride, k, pad, hw, c, b):
    h, w = hw
    x = jax.random.normal(jax.random.PRNGKey(h * 31 + c + b), (b, h, w, c))
    got = jax.jit(im2col, static_argnums=(1, 2, 3, 4))(x, k, k, stride, pad)
    want = _strided_im2col(x, k, k, stride, pad)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _traced_phased(cfg):
    """`backends.im2col_phased` of one abstract trace of the network."""
    net = Network(cfg, make_engine("xla"))
    h, w, c = net.in_shape
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    mark = backends.dispatch_log_size()
    jax.eval_shape(net.apply, params,
                   jax.ShapeDtypeStruct((1, h, w, c), jnp.float32))
    return backends.im2col_phased(backends.dispatch_log()[mark:])


@pytest.mark.parametrize("cfg, want", [
    (YOLOV3_CFG, {"convs": 75, "phased": 5}),     # the five downsamples
    (DARKNET19_CFG, {"convs": 19, "phased": 0}),  # max-pools downsample
], ids=["yolov3_416", "darknet19"])
def test_im2col_phased_counts_published_networks(cfg, want):
    assert _traced_phased(cfg) == want


@pytest.mark.parametrize("cfg, want", [
    # YOLOv3's layer pattern at 64x64, widths / 16.
    (yolov3_cfg(size=64, width_div=16), {"convs": 75, "phased": 5}),
    (DARKNET19_CFG.replace("height=224", "height=32")
     .replace("width=224", "width=32"), {"convs": 19, "phased": 0}),
], ids=["yolov3", "darknet19"])
def test_profile_and_stats_report_im2col_phased(cfg, want):
    net = Network(cfg, make_engine("xla"))
    params = net.init(jax.random.PRNGKey(0))
    cache = net.compile_cache(params, buckets=(1,))
    assert cache.get(1).profile(reps=1)["im2col_phased"] == want
    assert cache.stats()["im2col_phased"] == want


def _traced_log(cfg, batch):
    """The dispatch log of one abstract trace on the pallas engine."""
    net = Network(cfg, make_engine("pallas"))
    h, w, c = net.in_shape
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    mark = backends.dispatch_log_size()
    jax.eval_shape(net.apply, params,
                   jax.ShapeDtypeStruct((batch, h, w, c), jnp.float32))
    return backends.dispatch_log()[mark:]


PUBLISHED = pytest.mark.parametrize("cfg, batch, want", [
    # 32 stride-1 3x3 convolutions past the stem; 37 1x1, 5 stride-2 and
    # the stem keep im2col.
    (YOLOV3_CFG, 1, {"convs": 75, "implicit": 32}),
    # 11 stride-1 3x3 with Cin >= 32; the stem and the 7 1x1 keep im2col.
    (DARKNET19_CFG, 1, {"convs": 19, "implicit": 11}),
    # At batch 8 the three 7x7 (63 flat rows an image) keep im2col too.
    (DARKNET19_CFG, 8, {"convs": 19, "implicit": 8}),
], ids=["yolov3_416_b1", "darknet19_b1", "darknet19_b8"])


@PUBLISHED
def test_conv_implicit_counts_published_networks(cfg, batch, want):
    assert backends.conv_implicit(_traced_log(cfg, batch)) == want


@PUBLISHED
def test_gemm_padded_counts_only_im2col_gemms(cfg, batch, want):
    """A convolution that forms its patches in VMEM runs no tiled GEMM:
    `gemm_padded` counts the other convolutions (and no head GEMM here)."""
    gemms = backends.gemm_padded(_traced_log(cfg, batch))
    assert gemms["gemms"] == want["convs"] - want["implicit"]


@pytest.mark.parametrize("backend, want", [
    # At batch 1 every stride-1 3x3 past the stem, down to 1x1 images.
    ("pallas", {"convs": 19, "implicit": 11}),
    ("xla", {"convs": 19, "implicit": 0}),      # every conv on im2col
], ids=["pallas", "xla"])
def test_profile_and_stats_report_conv_implicit(backend, want):
    cfg = (DARKNET19_CFG.replace("height=224", "height=32")
           .replace("width=224", "width=32"))
    net = Network(cfg, make_engine(backend))
    params = net.init(jax.random.PRNGKey(0))
    cache = net.compile_cache(params, buckets=(1,))
    assert cache.get(1).profile(reps=1)["conv_implicit"] == want
    assert cache.stats()["conv_implicit"] == want
