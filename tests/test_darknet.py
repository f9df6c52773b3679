"""Darknet substrate: parser round-trip, conv/deconv vs XLA oracles,
end-to-end network inference, engine backend equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dep — property tests skip without it
    from hypothesis_stub import given, settings, st

from repro.configs.darknet_ref import (DARKNET_SMALL_CFG, SEGNET_SMALL_CFG)
from repro.core.darknet import cfg as cfg_mod
from repro.core.darknet import layers as L
from repro.core.darknet.network import Network
from repro.core import make_engine


# ------------------------------------------------------------------ parser

def test_parse_small_cfg():
    secs = cfg_mod.parse_cfg(DARKNET_SMALL_CFG)
    assert secs[0].type == "net"
    types = [s.type for s in secs[1:]]
    assert types == ["convolutional", "maxpool", "convolutional", "maxpool",
                     "convolutional", "shortcut", "avgpool", "connected",
                     "softmax"]
    assert secs[1].get("filters") == 16


def test_parse_roundtrip():
    secs = cfg_mod.parse_cfg(SEGNET_SMALL_CFG)
    again = cfg_mod.parse_cfg(cfg_mod.dump_cfg(secs))
    assert [s.type for s in secs] == [s.type for s in again]
    assert [s.options for s in secs] == [s.options for s in again]


def test_parse_rejects_unknown_section():
    with pytest.raises(ValueError):
        cfg_mod.parse_cfg("[net]\nheight=8\nwidth=8\nchannels=1\n[crnn]\n")


def test_conv_pad_rule():
    """Single source of truth for darknet's pad/padding rule."""
    assert cfg_mod.conv_pad({"pad": 1}, 3) == 1          # same-ish conv
    assert cfg_mod.conv_pad({"pad": 1}, 5) == 2
    assert cfg_mod.conv_pad({"pad": 1, "padding": 7}, 3) == 1  # pad wins
    assert cfg_mod.conv_pad({"pad": 0, "padding": 2}, 3) == 2  # explicit
    assert cfg_mod.conv_pad({"padding": 2}, 3) == 2
    assert cfg_mod.conv_pad({}, 3) == 0                  # default
    assert cfg_mod.conv_pad({"pad": 1}, 1) == 0          # 1x1: size//2 == 0
    # Section objects work too (plan path uses them)
    sec = cfg_mod.Section("convolutional", {"pad": 1, "size": 3})
    assert cfg_mod.conv_pad(sec, 3) == 1


# ------------------------------------------------------- conv/deconv oracle

@pytest.mark.parametrize("size,stride,pad,cin,cout",
                         [(3, 1, 1, 3, 8), (1, 1, 0, 4, 4), (3, 2, 1, 3, 6),
                          (5, 1, 2, 2, 4), (2, 2, 0, 3, 5)])
def test_conv2d_matches_lax(size, stride, pad, cin, cout):
    eng = make_engine("xla")
    key = jax.random.PRNGKey(size * 7 + stride)
    x = jax.random.normal(key, (2, 13, 11, cin), jnp.float32)
    p = L.init_conv(jax.random.PRNGKey(1), size, cin, cout,
                    batch_normalize=False)
    got = L.conv2d(eng, p, x, size=size, stride=stride, pad=pad,
                   act="linear", batch_normalize=False)
    w_hwio = p["w"].reshape(size, size, cin, cout)
    want = jax.lax.conv_general_dilated(
        x, w_hwio, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_conv2d_bn_fold_matches_unfused():
    eng = make_engine("xla")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 3), jnp.float32)
    p = L.init_conv(jax.random.PRNGKey(1), 3, 3, 8, batch_normalize=True)
    p = dict(p, gamma=p["gamma"] * 1.3 + 0.1,
             mean=jnp.full((8,), 0.2), var=jnp.full((8,), 2.0))
    got = L.conv2d(eng, p, x, size=3, stride=1, pad=1, act="leaky",
                   batch_normalize=True)
    w_hwio = p["w"].reshape(3, 3, 3, 8)
    conv = jax.lax.conv_general_dilated(
        x, w_hwio, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    bn = (conv - p["mean"]) / jnp.sqrt(p["var"] + 1e-5) * p["gamma"] + p["beta"]
    want = jnp.where(bn > 0, bn, 0.1 * bn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("size,stride,pad", [(2, 2, 0), (4, 2, 1), (3, 1, 1)])
def test_deconv2d_matches_conv_transpose(size, stride, pad):
    eng = make_engine("xla")
    cin, cout = 4, 6
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 7, 9, cin), jnp.float32)
    p = L.init_deconv(jax.random.PRNGKey(3), size, cin, cout,
                      batch_normalize=False)
    got = L.deconv2d(eng, p, x, size=size, stride=stride, pad=pad,
                     act="linear", batch_normalize=False)
    # oracle: standard deconv (PyTorch ConvTranspose2d semantics) ==
    # lhs-dilated VALID conv with spatially-flipped kernel and per-side
    # padding (k - 1 - p).
    w = p["w"].reshape(cin, size, size, cout).transpose(1, 2, 0, 3)  # HWIO
    w_flip = w[::-1, ::-1, :, :]
    want = jax.lax.conv_general_dilated(
        x, w_flip, (1, 1),
        [(size - 1 - pad, size - 1 - pad)] * 2,
        lhs_dilation=(stride, stride),
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(h=st.integers(4, 12), w=st.integers(4, 12), c=st.integers(1, 4),
       size=st.sampled_from([1, 2, 3]), stride=st.sampled_from([1, 2]))
def test_im2col_property_patch_content(h, w, c, size, stride):
    """Every im2col patch equals the corresponding input window."""
    if size > h or size > w:
        return
    x = jax.random.normal(jax.random.PRNGKey(h * 13 + w), (1, h, w, c))
    cols = L.im2col(x, size, size, stride, 0)
    oh = (h - size) // stride + 1
    ow = (w - size) // stride + 1
    assert cols.shape == (1, oh, ow, size * size * c)
    win = np.asarray(x[0, :size, :size, :]).reshape(-1)
    np.testing.assert_allclose(np.asarray(cols[0, 0, 0]), win, rtol=1e-6)


# --------------------------------------------------------------- end-to-end

def test_network_forward_small():
    net = Network(DARKNET_SMALL_CFG, make_engine("xla"))
    params = net.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 28, 28, 3), jnp.float32)
    y = jax.jit(net.apply)(params, x)
    assert y.shape == (4, 10)
    np.testing.assert_allclose(np.asarray(y.sum(-1)), 1.0, rtol=1e-5)
    assert not np.any(np.isnan(np.asarray(y)))


def test_network_forward_segnet_deconv():
    net = Network(SEGNET_SMALL_CFG, make_engine("xla"))
    params = net.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3), jnp.float32)
    y = jax.jit(net.apply)(params, x)
    assert y.shape == (2, 32, 32, 4)
    assert not np.any(np.isnan(np.asarray(y)))


def test_engine_backends_agree_on_network():
    """pallas(interpret) and xla backends produce the same network output."""
    net_x = Network(DARKNET_SMALL_CFG, make_engine("xla"))
    net_p = Network(DARKNET_SMALL_CFG, make_engine("pallas"))
    params = net_x.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 28, 28, 3), jnp.float32)
    yx = net_x.apply(params, x)
    yp = net_p.apply(params, x)
    np.testing.assert_allclose(np.asarray(yx), np.asarray(yp),
                               rtol=2e-4, atol=2e-4)
