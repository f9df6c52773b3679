"""YOLOv4-608 (yolov4.cfg; Bochkovskiy, Wang, Liao, YOLOv4: Optimal Speed
and Accuracy of Object Detection, 2020).

Builds the served system from `yolov4.json` as `yolov3.py` does: weights
and batch-norm statistics made on the device from the seed in one jitted
call, the program's `Network` on the cell's engine, a `CompileCache` over
the cell's buckets and a `CNNServingEngine` in front of it.  The network
answers each image with its three [yolo] heads (76x76, 38x38, 19x19, 255
channels each, the finest first).  Holds the plain reference
(`lax.conv_general_dilated` + batch-norm + mish, leaky or linear + bias,
darknet's max-pool, shortcut add, route concat, nearest 2x upsample, the
[yolo] logistic with `scale_x_y`), which imports nothing of the program,
and the operation and byte counts of each convolution.

Departures from darknet's C code: batch-norm's eps inside the root (as in
`darknet19.json`); softplus inside mish as log1p(exp(x)) between the
thresholds (darknet's CUDA kernel; its C code writes log(exp(x) + 1));
box decoding and NMS, which darknet does on the host after the forward,
are left out, as the program leaves them out.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import numerics
from configs import darknet19, yolov3

F32 = 4  # bytes


def pool_padding(h: int, size: int, stride: int, pad: int) -> tuple[int, int]:
    """darknet's [maxpool] (`forward_maxpool_layer`) along an axis of
    extent `h`: ``pad`` (``padding``, default size - 1) in total, windows
    starting ``pad // 2`` before the input, ``(h + pad - size) // stride
    + 1`` of them; returns the (low, high) -inf border the windows read."""
    lo = pad // 2
    out = (h + pad - size) // stride + 1
    return lo, max(0, (out - 1) * stride + size - lo - h)


def pool_pad(layer: dict) -> int:
    return layer.get("padding", layer["size"] - 1)


def plan(conf: dict) -> list[dict]:
    """Each layer with its input and output shapes (H, W, C)."""
    h, w, c = conf["height"], conf["width"], conf["channels"]
    out = []
    for i, layer in enumerate(conf["layers"]):
        t = layer["type"]
        shape_in = (h, w, c)
        if t == "convolutional":
            k, s, p = layer["size"], layer["stride"], darknet19.pad_of(layer)
            h, w, c = ((h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1,
                       layer["filters"])
        elif t == "maxpool":
            k, s, p = layer["size"], layer["stride"], pool_pad(layer)
            h, w = (h + p - k) // s + 1, (w + p - k) // s + 1
        elif t == "upsample":
            h, w = h * layer["stride"], w * layer["stride"]
        elif t == "route":
            src = [j if j >= 0 else i + j for j in layer["layers"]]
            h, w, _ = out[src[0]]["shape_out"]
            c = sum(out[j]["shape_out"][2] for j in src)
        elif t not in ("shortcut", "yolo"):
            raise ValueError(f"layer {i}: {t!r} is not a YOLOv4 layer")
        out.append(dict(layer, index=i, shape_in=shape_in, shape_out=(h, w, c)))
    return out


def convs(conf: dict) -> list[dict]:
    return [p for p in plan(conf) if p["type"] == "convolutional"]


def heads(conf: dict) -> list[dict]:
    return [p for p in plan(conf) if p["type"] == "yolo"]


def conv_work(conf: dict, batch: int) -> list[tuple[float, float]]:
    """(FLOPs, bytes) of every convolution of one call at `batch` images,
    counted as in `darknet19.conv_work`."""
    work = []
    for p in convs(conf):
        (h, w, cin), (oh, ow, cout) = p["shape_in"], p["shape_out"]
        k = p["size"]
        flops = 2.0 * batch * oh * ow * cout * k * k * cin
        nbytes = F32 * (batch * h * w * cin + k * k * cin * cout + 2 * cout
                        + batch * oh * ow * cout)
        work.append((flops, nbytes))
    return work


def flops_per_image(conf: dict) -> float:
    return sum(f for f, _ in conv_work(conf, 1))


def param_count(conf: dict) -> int:
    n = 0
    for p in convs(conf):
        k, cin, cout = p["size"], p["shape_in"][2], p["filters"]
        n += k * k * cin * cout + (4 if p.get("batch_normalize") else 1) * cout
    return n


# ----------------------------------------------------------------- weights ---

def make_params(conf: dict, seed: int) -> dict:
    """The program's param tree, made on the device in one jitted call, as
    `yolov3.make_params` makes it: the convolution that ends each
    residual branch has its gamma times `assumed.residual_gamma`."""
    ps = plan(conf)
    ends = {ps[i - 1]["index"] for i, p in enumerate(ps)
            if p["type"] == "shortcut"}
    res_gamma = conf["assumed"]["residual_gamma"]
    layers = convs(conf)

    def gen(key):
        params = {}
        for p, k in zip(layers, jax.random.split(key, len(layers))):
            ks = jax.random.split(k, 5)
            size, cin, cout = p["size"], p["shape_in"][2], p["filters"]
            fan_in = size * size * cin
            layer = {"w": jax.random.normal(ks[0], (fan_in, cout), jnp.float32)
                     * math.sqrt(2.0 / fan_in)}
            if p.get("batch_normalize"):
                g = res_gamma if p["index"] in ends else 1.0
                layer.update(
                    gamma=g * jax.random.uniform(ks[1], (cout,), jnp.float32,
                                                 0.9, 1.1),
                    beta=0.05 * jax.random.normal(ks[2], (cout,), jnp.float32),
                    mean=0.05 * jax.random.normal(ks[3], (cout,), jnp.float32),
                    var=jax.random.uniform(ks[4], (cout,), jnp.float32, 0.9, 1.1))
            else:
                layer["b"] = 0.1 * jax.random.normal(ks[1], (cout,), jnp.float32)
            params[f"l{p['index']}"] = layer
        return params

    return jax.jit(gen)(numerics.key(seed))


make_images = yolov3.make_images


# --------------------------------------------------------------- reference ---

def mish(x, threshold: float):
    """darknet's mish: x * tanh(softplus(x)), softplus x above `threshold`,
    exp(x) below -threshold, log1p(exp(x)) between."""
    e = jnp.exp(jnp.minimum(x, threshold))
    sp = jnp.where(x > threshold, x,
                   jnp.where(x < -threshold, e, jnp.log1p(e)))
    return x * jnp.tanh(sp)


def xy_affine(scale: float) -> tuple[np.float32, np.float32]:
    """(s, shift) of [yolo]'s x, y: σ * s + shift, shift = -0.5 * (s - 1)
    in float32, as darknet's `scal_add_cpu` computes it."""
    s = np.float32(scale)
    return s, np.float32(-0.5) * (s - np.float32(1))


def yolo_head(x, classes: int, scale: float):
    """[yolo] at inference: the logistic on entries 0-1 and 4.., then x and
    y scaled by `scale` about 0.5."""
    logistic = yolov3.logistic_entries(classes, x.shape[-1])
    x = jnp.where(logistic, jax.nn.sigmoid(x), x)
    s, shift = xy_affine(scale)
    entry = np.arange(x.shape[-1]) % (5 + classes)
    return jnp.where(entry < 2, x * s + shift, x)


def reference(conf: dict, params: dict, x, mode: str = "highest"):
    """The plain forward of YOLOv4: (B, H, W, C) -> the tuple of the
    [yolo] heads' (B, h, w, 3 * (5 + classes)) outputs, every product at
    `mode` (benchlib.numerics)."""
    a = conf["assumed"]
    outs, found = [], []
    for p in plan(conf):
        t = p["type"]
        if t == "convolutional":
            lp = params[f"l{p['index']}"]
            k, cin = p["size"], p["shape_in"][2]
            w = lp["w"].reshape(k, k, cin, p["filters"])
            x = numerics.conv(x, w, stride=p["stride"],
                              pad=darknet19.pad_of(p), mode=mode)
            if p.get("batch_normalize"):
                x = ((x - lp["mean"]) / jnp.sqrt(lp["var"] + a["batchnorm_eps"])
                     * lp["gamma"] + lp["beta"])
            else:
                x = x + lp["b"]
            if p["activation"] == "mish":
                x = mish(x, a["mish_threshold"])
            elif p["activation"] == "leaky":
                x = jnp.where(x > 0, x, a["leaky_slope"] * x)
            elif p["activation"] != "linear":
                raise ValueError(f"activation {p['activation']!r}")
        elif t == "maxpool":
            k, s = p["size"], p["stride"]
            h, w, _ = p["shape_in"]
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, s, s, 1),
                ((0, 0), pool_padding(h, k, s, pool_pad(p)),
                 pool_padding(w, k, s, pool_pad(p)), (0, 0)))
        elif t == "shortcut":
            x = x + outs[p["index"] + p["from"]]
        elif t == "route":
            x = jnp.concatenate([outs[j if j >= 0 else p["index"] + j]
                                 for j in p["layers"]], axis=-1)
        elif t == "upsample":
            s = p["stride"]
            x = jnp.repeat(jnp.repeat(x, s, axis=1), s, axis=2)
        elif t == "yolo":
            x = yolo_head(x, p["classes"], p.get("scale_x_y", 1.0))
            found.append(x)
        outs.append(x)
    return tuple(found)


cfg_text = yolov3.cfg_text


# ------------------------------------------------------------------- check ---

def det_err(got, want, classes: int, lo: float, hi: float,
            scales) -> float:
    """`yolov3.det_err` after undoing each head's x, y affine (`scales`,
    one `scale_x_y` per head, in head order) on both sides, in float64:
    x and y then compare as the logits of their σ."""
    undone = []
    for side in (got, want):
        heads_ = []
        for v, scale in zip(side, scales):
            v = np.asarray(v, np.float64)
            s, shift = (np.float64(a) for a in xy_affine(scale))
            entry = np.arange(v.shape[-1]) % (5 + classes)
            heads_.append(np.where(entry < 2, (v - shift) / s, v))
        undone.append(heads_)
    return yolov3.det_err(undone[0], undone[1], classes, lo, hi)


# ------------------------------------------------------------------ system ---

class System(yolov3.System):
    """YOLOv4-608 served by `CNNServingEngine` over a `CompileCache`; each
    request's result is the tuple of its rows of the three heads, held
    until the check as `yolov3.System` holds them."""

    def __init__(self, conf: dict, cell: dict, spec: dict, seed: int):
        from repro.core import make_engine
        from repro.core.darknet.network import Network
        from repro.serve import frontend

        self.conf, self.cell, self.seed = conf, cell, seed
        self._frontend = frontend
        self.rejected = (frontend.RejectedRequest,)
        self.params = make_params(conf, seed)
        self.images = make_images(conf, seed, spec["pool"])
        net = Network(cfg_text(conf),
                      make_engine(cell["backend"], cell["policy"]))
        self.cache = net.compile_cache(self.params, buckets=cell["buckets"])
        self.server = frontend.CNNServingEngine(self.cache)
        self._pool_of: dict = {}     # rid -> pool index
        self._first: dict = {}       # pool index -> its first answer
        self._scales = [h.get("scale_x_y", 1.0) for h in heads(conf)]

    def flops_per_item(self) -> float:
        return flops_per_image(self.conf)

    def op_calls(self, steps) -> dict:
        """{"conv2d": [(FLOPs, bytes, calls), ...]} over `steps`."""
        calls = []
        for s in steps:
            for bucket, n in s.obs.items():
                calls += [(f, b, n) for f, b in conv_work(self.conf, bucket)]
        return {"conv2d": calls}

    def _outputs(self, window, mode):
        idx = sorted({r.item.pool_index for r in window.records if r.req.done})
        fn = jax.jit(lambda p, x: reference(self.conf, p, x, mode))
        out = {}
        block = self.cell.get("check_block", 8)
        for i in range(0, len(idx), block):
            part = idx[i:i + block]
            x = self.images[part]
            if len(part) < block:   # one shape for every block
                x = np.concatenate([x, np.zeros((block - len(part),)
                                                + x.shape[1:], x.dtype)])
            ys = [np.asarray(y) for y in fn(self.params, jnp.asarray(x))]
            out.update((j, tuple(y[n] for y in ys))
                       for n, j in enumerate(part))
        return out

    def _err(self, got, want) -> float:
        a = self.conf["assumed"]["det_err"]
        return det_err(got, want, self.conf["classes"], a["p_min"],
                       a["p_max"], self._scales)


def build(conf: dict, cell: dict, spec: dict, seed: int) -> System:
    return System(conf, cell, spec, seed)
