"""YOLOv3 and YOLOv4 through the Darknet path: the [yolo] section with
`scale_x_y`, darknet's [maxpool] padding, mish, multi-output `Network` /
`CompileCache` / `CNNServingEngine`, against a plain reference.

The reference below is the detectors' forward in straightforward
`jax.numpy` and `lax.conv_general_dilated` under
``jax.default_matmul_precision("highest")`` over its own reading of the
cfg text; it imports nothing of the program.  It follows darknet's C code
(`forward_maxpool_layer`, `activate_array_mish`, `forward_yolo_layer` with
new_coords=0), with these departures: batch-norm's eps inside the root
(darknet adds 1e-6 to sqrt(var)); softplus inside mish as log1p(exp(x))
between the thresholds (darknet's CUDA kernel; its C code writes
log(exp(x) + 1)); the shift -(s - 1) / 2 of [yolo]'s x, y rounded to
float32 from float64 (darknet computes it in float32); box decoding and NMS, which darknet
runs on the host after the forward, are left out here as in the program.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.darknet_ref import (DARKNET_SMALL_CFG, YOLOV3_CFG,
                                       YOLOV3_SMALL_CFG, YOLOV4_CFG,
                                       YOLOV4_SMALL_CFG)
from repro.core import backends, make_engine
from repro.core.darknet import cfg as cfg_mod
from repro.core.darknet import layers as L
from repro.core.darknet.network import Network
from repro.serve import frontend as fe

SMALL_HW, SMALL_CLASSES = 64, 2
V4_HW = 256
EPS, SLOPE, MISH_THRESHOLD = 1e-5, 0.1, 20.0


# --------------------------------------------------------------- reference

def _sections(text):
    """[(type, {key: text value})] of a darknet cfg, [net] left out."""
    out = []
    for line in text.splitlines():
        line = line.split("#", 1)[0].strip()
        if line.startswith("["):
            out.append((line.strip("[]"), {}))
        elif "=" in line:
            k, v = line.split("=", 1)
            out[-1][1][k.strip()] = v.strip()
    return out[1:]


def _ints(v):
    return [int(s) for s in v.split(",")]


def _mish(x):
    """darknet's mish: x * tanh(softplus(x)), softplus x above 20, exp(x)
    below -20."""
    sp = jnp.where(x > MISH_THRESHOLD, x,
                   jnp.where(x < -MISH_THRESHOLD, jnp.exp(x),
                             jnp.log1p(jnp.exp(x))))
    return x * jnp.tanh(sp)


def reference(text, params, x):
    """(B, H, W, 3) -> tuple of the [yolo] heads' outputs."""
    outs, heads = [], []
    with jax.default_matmul_precision("highest"):
        for i, (t, o) in enumerate(_sections(text)):
            if t == "convolutional":
                p = params[f"l{i}"]
                k, s = int(o["size"]), int(o["stride"])
                pad = k // 2 if int(o.get("pad", 0)) else 0
                w = p["w"].reshape(k, k, x.shape[-1], -1)
                x = jax.lax.conv_general_dilated(
                    x, w, (s, s), [(pad, pad)] * 2,
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
                if int(o.get("batch_normalize", 0)):
                    x = ((x - p["mean"]) / jnp.sqrt(p["var"] + EPS)
                         * p["gamma"] + p["beta"])
                else:
                    x = x + p["b"]
                if o["activation"] == "leaky":
                    x = jnp.where(x > 0, x, SLOPE * x)
                elif o["activation"] == "mish":
                    x = _mish(x)
            elif t == "maxpool":
                k, s = int(o["size"]), int(o["stride"])
                x = _darknet_maxpool(x, k, s, int(o.get("padding", k - 1)))
            elif t == "shortcut":
                x = x + outs[i + int(o["from"])]
            elif t == "route":
                x = jnp.concatenate([outs[j if j >= 0 else i + j]
                                     for j in _ints(o["layers"])], axis=-1)
            elif t == "upsample":
                s = int(o["stride"])
                x = jnp.repeat(jnp.repeat(x, s, axis=1), s, axis=2)
            elif t == "yolo":
                entry = np.arange(x.shape[-1]) % (5 + int(o["classes"]))
                x = jnp.where((entry < 2) | (entry >= 4), jax.nn.sigmoid(x), x)
                sxy = float(o.get("scale_x_y", 1))
                x = jnp.where(entry < 2, x * sxy - 0.5 * (sxy - 1), x)
                heads.append(x)
            else:
                raise ValueError(t)
            outs.append(x)
    return tuple(heads)


def _darknet_maxpool(x, size, stride, pad):
    """darknet's [maxpool] with total padding `pad`: a -inf border of
    pad // 2 rows and columns before the input and pad - pad // 2 after,
    then every size x size window at `stride`."""
    lo, hi = pad // 2, pad - pad // 2
    x = jnp.pad(x, ((0, 0), (lo, hi), (lo, hi), (0, 0)),
                constant_values=-jnp.inf)
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, size, size, 1), (1, stride, stride, 1),
                                 "VALID")


def _params(net, seed=0, residual_gamma=0.2):
    """Seeded random weights and batch-norm statistics; the convolution
    that ends each residual branch has its gamma times `residual_gamma`,
    so the heads stay off the logistic's flat ends."""
    params = net.init(jax.random.PRNGKey(seed))
    ends = {net.plans[i - 1].index for i, p in enumerate(net.plans)
            if p.type == "shortcut"}
    rng = np.random.default_rng(seed)
    for name, p in params.items():
        n = p["w"].shape[1]
        if "gamma" in p:
            g = residual_gamma if int(name[1:]) in ends else 1.0
            p.update(gamma=jnp.asarray(g * rng.uniform(0.9, 1.1, n), jnp.float32),
                     beta=jnp.asarray(0.05 * rng.standard_normal(n), jnp.float32),
                     mean=jnp.asarray(0.05 * rng.standard_normal(n), jnp.float32),
                     var=jnp.asarray(rng.uniform(0.9, 1.1, n), jnp.float32))
        else:
            p["b"] = jnp.asarray(0.1 * rng.standard_normal(n), jnp.float32)
    return params


def _images(n, seed=1):
    return np.random.default_rng(seed).standard_normal(
        (n, SMALL_HW, SMALL_HW, 3)).astype(np.float32)


def _small(backend="xla"):
    net = Network(YOLOV3_SMALL_CFG, make_engine(backend, "fp32_strict"))
    return net, _params(net)


def _assert_heads_equal(got, want):
    assert len(got) == len(want) == 3
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ------------------------------------------------------------------ parser

def test_parse_yolo_list_keys():
    secs = cfg_mod.parse_cfg(
        "[net]\nheight=8\nwidth=8\nchannels=3\n[yolo]\nmask = 3,4,5\n"
        "anchors = 10,13,  16,30, 1.5,2.25\nclasses=80\nnum=9\njitter=.3\n"
        "ignore_thresh = .7\ntruth_thresh = 1\nrandom=1\n")
    o = secs[1].options
    assert o["mask"] == [3, 4, 5]
    assert o["anchors"] == [10, 13, 16, 30, 1.5, 2.25]
    assert o["classes"] == 80 and o["num"] == 9 and o["random"] == 1
    assert o["jitter"] == 0.3 and o["ignore_thresh"] == 0.7
    assert o["truth_thresh"] == 1


def test_parse_roundtrip_yolov3():
    secs = cfg_mod.parse_cfg(YOLOV3_CFG)
    again = cfg_mod.parse_cfg(cfg_mod.dump_cfg(secs))
    assert [(s.type, s.options) for s in secs] == \
        [(s.type, s.options) for s in again]
    assert [s.options["layers"] for s in secs if s.type == "route"] == \
        [[-4], [-1, 61], [-4], [-1, 36]]


def test_yolov3_416_plan():
    """yolov3.cfg at 416: 107 layers, 75 convolutions, 62,001,757
    parameters, heads at 13, 26 and 52 with 3 * (5 + 80) channels."""
    net = Network(YOLOV3_CFG)
    assert len(net.plans) == 107
    assert net.layer_counts == {"convolutional": 75, "shortcut": 23,
                                "route": 4, "upsample": 2, "yolo": 3}
    assert [p.out_shape for p in net.plans if p.type == "yolo"] == \
        [(13, 13, 255), (26, 26, 255), (52, 52, 255)]
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) == \
        62_001_757


def test_yolo_channels_must_match_anchors_and_classes():
    bad = YOLOV3_SMALL_CFG.replace("classes=2", "classes=3", 1)
    with pytest.raises(ValueError, match=r"\[yolo\]"):
        Network(bad)


# ------------------------------------------------------ against reference

@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_yolo_small_matches_reference(backend):
    net, params = _small(backend)
    x = jnp.asarray(_images(2))
    got = jax.jit(net.apply)(params, x)
    want = reference(YOLOV3_SMALL_CFG, params, x)
    assert [g.shape for g in got] == [(2, 2, 2, 21), (2, 4, 4, 21),
                                      (2, 8, 8, 21)]
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        # Both sides compute in float32 at full precision; only the order
        # of the sums differs (im2col GEMM against XLA's convolution),
        # which after 39 convolutions moves a head value by ~1.1e-6 of the
        # head's largest |value| (both backends, on the CPU).  Products in
        # bf16x3, the next precision down, read ~3.5e-5 at this size.
        scale = np.max(np.abs(w))
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-5 * scale)
        entry = np.arange(21) % (5 + SMALL_CLASSES)
        logistic = (entry < 2) | (entry >= 4)
        assert np.all((g[..., logistic] > 0) & (g[..., logistic] < 1))


def test_yolo_layer_is_darknets_logistic_on_xy_objectness_classes():
    x = jnp.linspace(-4.0, 4.0, 2 * 21).reshape(1, 1, 2, 21)
    net = Network("[net]\nheight=1\nwidth=2\nchannels=21\n[yolo]\n"
                  "mask=0,1,2\nclasses=2\n")
    (y,) = net.apply({}, x)
    y, x = np.asarray(y).reshape(-1, 7), np.asarray(x).reshape(-1, 7)
    np.testing.assert_allclose(y[:, [2, 3]], x[:, [2, 3]])
    for e in (0, 1, 4, 5, 6):
        np.testing.assert_allclose(y[:, e], 1 / (1 + np.exp(-x[:, e])),
                                   rtol=1e-6)


# --------------------------------------------------- cache and serving

@pytest.mark.parametrize("n", [1, 2, 3])
def test_compile_cache_ragged_multi_output(n):
    """Ragged batches, one over the top bucket (3 on buckets (1, 2)): each
    head's real rows equal an exact-batch compiled call's."""
    net, params = _small("xla")
    cache = net.compile_cache(params, buckets=(1, 2))
    x = jnp.asarray(_images(n))
    got = cache.run(x)
    assert isinstance(got, tuple)
    want = net.compile(params, batch_size=n)(x)
    _assert_heads_equal(got, want)


def test_serving_engine_returns_each_request_its_rows_of_every_head():
    """5 requests on top bucket 4: a step of 4, then one of 1; each request
    gets its own row of each head of its step's dispatch."""
    net, params = _small("xla")
    cache = net.compile_cache(params, buckets=(1, 2, 4))
    eng = fe.CNNServingEngine(cache)
    imgs = _images(5, seed=3)
    reqs = [fe.ImageRequest(rid=i, image=im) for i, im in enumerate(imgs)]
    eng.run(reqs)
    assert eng.stats()["steps"] == 2
    want = [np.concatenate(heads) for heads in zip(
        cache.run(jnp.asarray(imgs[:4])), cache.run(jnp.asarray(imgs[4:])))]
    for i, r in enumerate(reqs):
        assert r.done and isinstance(r.result, tuple)
        _assert_heads_equal(r.result, [w[i] for w in want])


def test_outputs_and_layers_counters():
    """`outputs` and `layers`, fixed at compile: one array of 10 float32
    per image for the small classifier, three heads of 21 channels at 2,
    4 and 8 for the small detector."""
    net, params = _small("xla")
    cache = net.compile_cache(params, buckets=(1,))
    assert cache.stats()["outputs"] is None
    cache.warmup()
    want = {"arrays": 3, "bytes_per_item": 4 * 21 * (4 + 16 + 64)}
    assert cache.stats()["outputs"] == want
    assert cache.stats()["layers"] == net.layer_counts
    prof = cache.get(1).profile(reps=1)
    assert prof["outputs"] == want
    assert prof["layers"]["yolo"] == 3
    clf = Network(DARKNET_SMALL_CFG, make_engine("xla"))
    cn = clf.compile(clf.init(jax.random.PRNGKey(0)), batch_size=2)
    assert cn.profile(reps=1)["outputs"] == {"arrays": 1,
                                             "bytes_per_item": 40}


# ------------------------------------------------------------------ YOLOv4

def _small_v4(backend="xla"):
    net = Network(YOLOV4_SMALL_CFG, make_engine(backend, "fp32_strict"))
    return net, _params(net)


def _v4_images(n, seed=1):
    return np.random.default_rng(seed).standard_normal(
        (n, V4_HW, V4_HW, 3)).astype(np.float32)


def _assert_near_reference(got, want, classes=SMALL_CLASSES):
    """Both sides compute in float32 at full precision; only the order of
    the sums differs (the program's GEMMs against XLA's convolution),
    which after YOLOv4-small's 74 convolutions moves a head value by up
    to 2.3e-6 of the head's largest |value| (both backends, on the CPU).
    Products in bf16x3, the next precision down, read 3.5e-5 to 4.9e-5."""
    assert len(got) == len(want) == 3
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        scale = np.max(np.abs(w))
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-5 * scale)
        entry = np.arange(w.shape[-1]) % (5 + classes)
        logistic = entry >= 4
        assert np.all((g[..., logistic] > 0) & (g[..., logistic] < 1))


def test_yolov4_608_plan():
    """yolov4.cfg at 608: 162 layers (110 convolutions, 23 shortcuts, 21
    routes, 3 max-pools, 2 upsamples, 3 [yolo]), 64,429,405 parameters,
    the SPP pools keep the 19x19 grid, heads at 76, 38 and 19 with
    3 * (5 + 80) channels and their scale_x_y, the finest first."""
    net = Network(YOLOV4_CFG)
    assert len(net.plans) == 162
    assert net.layer_counts == {"convolutional": 110, "shortcut": 23,
                                "route": 21, "maxpool": 3, "upsample": 2,
                                "yolo": 3}
    assert [p.out_shape for p in net.plans if p.type == "maxpool"] == \
        [(19, 19, 512)] * 3
    assert net.plans[113].out_shape == (19, 19, 2048)
    yolos = [p for p in net.plans if p.type == "yolo"]
    assert [p.out_shape for p in yolos] == [(76, 76, 255), (38, 38, 255),
                                            (19, 19, 255)]
    assert [p.options["scale_x_y"] for p in yolos] == [1.2, 1.1, 1.05]
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) == \
        64_429_405
    secs = cfg_mod.parse_cfg(YOLOV4_CFG)
    assert [(s.type, s.options) for s in secs] == \
        [(s.type, s.options) for s in cfg_mod.parse_cfg(
            cfg_mod.dump_cfg(secs))]


def test_yolov4_608_conv_act_counter():
    """`conv_act` of YOLOv4-608's lowering, read from the dispatch log of
    one trace (shapes only, nothing compiled): 72 mish, 35 leaky, 3
    linear; and the activations YOLOv3-416 fuses."""
    for cfg, want in ((YOLOV4_CFG, {"mish": 72, "leaky": 35, "linear": 3}),
                      (YOLOV3_CFG, {"leaky": 72, "linear": 3})):
        net = Network(cfg, make_engine("xla", "fp32_strict"))
        params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
        mark = backends.dispatch_log_size()
        jax.eval_shape(net.apply, params, jax.ShapeDtypeStruct(
            (1,) + net.in_shape, jnp.float32))
        assert backends.conv_act(backends.dispatch_log()[mark:]) == want


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_yolov4_small_matches_reference(backend):
    net, params = _small_v4(backend)
    x = jnp.asarray(_v4_images(2))
    got = jax.jit(net.apply)(params, x)
    want = reference(YOLOV4_SMALL_CFG, params, x)
    assert [g.shape for g in got] == [(2, 32, 32, 21), (2, 16, 16, 21),
                                      (2, 8, 8, 21)]
    _assert_near_reference(got, want)


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_yolov4_small_served_matches_reference(backend):
    """Three requests through `CompileCache` (buckets 1, 2) and
    `CNNServingEngine`: each gets its rows of every head, near the
    reference; `stats()` and `profile()` count the convolutions by fused
    activation."""
    net, params = _small_v4(backend)
    cache = net.compile_cache(params, buckets=(1, 2))
    eng = fe.CNNServingEngine(cache)
    imgs = _v4_images(3, seed=5)
    reqs = [fe.ImageRequest(rid=i, image=im) for i, im in enumerate(imgs)]
    eng.run(reqs)
    want = reference(YOLOV4_SMALL_CFG, params, jnp.asarray(imgs))
    for i, r in enumerate(reqs):
        assert r.done
        _assert_near_reference([h[None] for h in r.result],
                               [w[i:i + 1] for w in want])
    acts = {"mish": 36, "leaky": 35, "linear": 3}
    assert cache.get(1).profile(reps=1)["conv_act"] == acts
    assert cache.stats()["conv_act"] == {k: 2 * v for k, v in acts.items()}


@pytest.mark.parametrize("h, size, stride", [
    (19, 5, 1), (19, 9, 1), (19, 13, 1), (8, 9, 1), (8, 13, 1),
    (224, 2, 2), (13, 2, 2), (13, 3, 2), (12, 3, 2), (7, 2, 1)])
def test_maxpool_follows_darknet_padding(h, size, stride):
    """[maxpool] with darknet's default padding (size - 1): the planned
    shape, and the values against a plain reduce_window over a -inf
    border of pad // 2 before and pad - pad // 2 after."""
    cfg = (f"[net]\nheight={h}\nwidth={h + 1}\nchannels=4\n"
           f"[maxpool]\nsize={size}\nstride={stride}\n")
    net = Network(cfg)
    x = jax.random.normal(jax.random.PRNGKey(h * size), (2, h, h + 1, 4))
    got = net.apply({}, x)
    want = _darknet_maxpool(x, size, stride, size - 1)
    assert got.shape == want.shape == (2,) + net.out_shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.all(np.isfinite(np.asarray(got)))


def test_maxpool_explicit_padding_and_scope():
    """An explicit ``padding`` wins over darknet's default, and the pool
    runs under the `repro.layer.maxpool` scope."""
    assert cfg_mod.maxpool_pad({"padding": 0}, 3) == 0
    assert cfg_mod.maxpool_pad({}, 3) == 2
    net = Network("[net]\nheight=9\nwidth=9\nchannels=2\n"
                  "[maxpool]\nsize=3\nstride=2\npadding=0\n")
    assert net.out_shape == (4, 4, 2)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 9, 2))
    np.testing.assert_array_equal(np.asarray(net.apply({}, x)),
                                  np.asarray(_darknet_maxpool(x, 3, 2, 0)))
    hlo = jax.jit(net.apply).lower({}, x).as_text(debug_info=True)
    assert L.LAYER_SCOPE_PREFIX + "maxpool" in hlo


def test_yolo_scale_x_y():
    """x, y = σ * s - (s - 1) / 2; the rest as at s = 1, whose program is
    the logistic alone, as before `scale_x_y`."""
    x = jnp.linspace(-4.0, 4.0, 2 * 21).reshape(1, 1, 2, 21)
    y = np.asarray(L.yolo(x, classes=2, scale_x_y=1.2)).reshape(-1, 7)
    base = np.asarray(L.yolo(x, classes=2)).reshape(-1, 7)
    sig = 1 / (1 + np.exp(-np.asarray(x, np.float64).reshape(-1, 7)))
    np.testing.assert_allclose(y[:, :2], sig[:, :2] * 1.2 - 0.1,
                               rtol=0, atol=1e-7)
    np.testing.assert_array_equal(y[:, 2:], base[:, 2:])

    def logistic_only(x):
        entry = np.arange(x.shape[-1]) % 7
        return jnp.where((entry < 2) | (entry >= 4), jax.nn.sigmoid(x), x)
    assert str(jax.make_jaxpr(
        lambda x: L.yolo(x, classes=2, scale_x_y=1.0))(x)) == \
        str(jax.make_jaxpr(logistic_only)(x))
