"""YOLOv3 through the Darknet path: the [yolo] section, multi-output
`Network` / `CompileCache` / `CNNServingEngine`, against a plain reference.

The reference below is YOLOv3's forward in straightforward `jax.numpy` and
`lax.conv_general_dilated` under ``jax.default_matmul_precision("highest")``
over its own reading of the cfg text; it imports nothing of the program.
Departures from darknet's C code: batch-norm's eps inside the root
(darknet adds 1e-6 to sqrt(var)); box decoding and NMS, which darknet runs
on the host after the forward, are left out here as in the program.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.darknet_ref import (DARKNET_SMALL_CFG, YOLOV3_CFG,
                                       YOLOV3_SMALL_CFG)
from repro.core import make_engine
from repro.core.darknet import cfg as cfg_mod
from repro.core.darknet.network import Network
from repro.serve import frontend as fe

SMALL_HW, SMALL_CLASSES = 64, 2
EPS, SLOPE = 1e-5, 0.1


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
                heads.append(x)
            else:
                raise ValueError(t)
            outs.append(x)
    return tuple(heads)


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
