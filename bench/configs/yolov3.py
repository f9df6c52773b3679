"""YOLOv3-416 (yolov3.cfg; Redmon & Farhadi, YOLOv3: An Incremental
Improvement, 2018).

Builds the served system from `yolov3.json` as `darknet19.py` does: weights
and batch-norm statistics made on the device from the seed in one jitted
call, the program's `Network` on the cell's engine, a `CompileCache` over
the cell's buckets and a `CNNServingEngine` in front of it.  The network
answers each image with its three [yolo] heads (13x13, 26x26, 52x52, 255
channels each).  Holds the plain reference (`lax.conv_general_dilated` +
batch-norm + leaky or linear + bias, shortcut add, route concat, nearest
2x upsample, the [yolo] logistic), which imports nothing of the program,
and the operation and byte counts of each convolution.

Departures from darknet's C code: batch-norm's eps inside the root (as in
`darknet19.json`); box decoding and NMS, which darknet does on the host
after the forward, are left out, as the program leaves them out.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import numerics, traffic
from configs import darknet19

F32 = 4  # bytes


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
        elif t == "upsample":
            h, w = h * layer["stride"], w * layer["stride"]
        elif t == "route":
            src = [j if j >= 0 else i + j for j in layer["layers"]]
            h, w, _ = out[src[0]]["shape_out"]
            c = sum(out[j]["shape_out"][2] for j in src)
        elif t not in ("shortcut", "yolo"):
            raise ValueError(f"layer {i}: {t!r} is not a YOLOv3 layer")
        out.append(dict(layer, index=i, shape_in=shape_in, shape_out=(h, w, c)))
    return out


def convs(conf: dict) -> list[dict]:
    return [p for p in plan(conf) if p["type"] == "convolutional"]


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


def heads(conf: dict) -> list[dict]:
    return [p for p in plan(conf) if p["type"] == "yolo"]


# ----------------------------------------------------------------- weights ---

def make_params(conf: dict, seed: int) -> dict:
    """The program's param tree, made on the device in one jitted call, as
    `darknet19.make_params` makes it (He-normal weights, batch-norm gamma
    and var in [0.9, 1.1), beta and mean N(0, 0.05^2), head biases
    N(0, 0.1^2)), except that the convolution that ends each residual
    branch (the one a [shortcut] adds) has its gamma times
    `assumed.residual_gamma`: at full gamma each of the 23 residual adds
    roughly doubles the variance, and the heads saturate."""
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


def make_images(conf: dict, seed: int, n: int) -> np.ndarray:
    shape = (n, conf["height"], conf["width"], conf["channels"])
    return traffic.rng_for(seed, 6).standard_normal(shape, dtype=np.float32)


# --------------------------------------------------------------- reference ---

def logistic_entries(classes: int, channels: int) -> np.ndarray:
    """Which of a head's channels [yolo] puts through the logistic: entries
    0-1 (x, y) and 4.. (objectness, classes) of each anchor, anchor-major;
    2-3 (w, h) stay linear."""
    entry = np.arange(channels) % (5 + classes)
    return (entry < 2) | (entry >= 4)


def reference(conf: dict, params: dict, x, mode: str = "highest"):
    """The plain forward of YOLOv3: (B, H, W, C) -> the tuple of the
    [yolo] heads' (B, h, w, 3 * (5 + classes)) outputs, every product at
    `mode` (benchlib.numerics)."""
    eps = conf["assumed"]["batchnorm_eps"]
    slope = conf["assumed"]["leaky_slope"]
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
                x = ((x - lp["mean"]) / jnp.sqrt(lp["var"] + eps)
                     * lp["gamma"] + lp["beta"])
            else:
                x = x + lp["b"]
            if p["activation"] == "leaky":
                x = jnp.where(x > 0, x, slope * x)
            elif p["activation"] != "linear":
                raise ValueError(f"activation {p['activation']!r}")
        elif t == "shortcut":
            x = x + outs[p["index"] + p["from"]]
        elif t == "route":
            x = jnp.concatenate([outs[j if j >= 0 else p["index"] + j]
                                 for j in p["layers"]], axis=-1)
        elif t == "upsample":
            s = p["stride"]
            x = jnp.repeat(jnp.repeat(x, s, axis=1), s, axis=2)
        elif t == "yolo":
            x = jnp.where(logistic_entries(p["classes"], x.shape[-1]),
                          jax.nn.sigmoid(x), x)
            found.append(x)
        outs.append(x)
    return tuple(found)


def cfg_text(conf: dict) -> str:
    """The configuration as a darknet .cfg, which the program parses."""
    lines = ["[net]", f"height={conf['height']}", f"width={conf['width']}",
             f"channels={conf['channels']}"]
    for layer in conf["layers"]:
        lines += ["", f"[{layer['type']}]"]
        for k, v in layer.items():
            if k != "type":
                lines.append(f"{k}=" + (",".join(map(str, v))
                                        if isinstance(v, list) else f"{v}"))
        if layer["type"] == "yolo":
            lines += ["anchors=" + ",".join(map(str, conf["anchors"])),
                      f"num={len(conf['anchors']) // 2}"]
    return "\n".join(lines) + "\n"


# ------------------------------------------------------------------- check ---

def det_err(got, want, classes: int, lo: float, hi: float) -> float:
    """The largest |served - reference| over every head, each relative to
    the reference's largest |value| in that head: the logistic entries as
    logits, log p - log(1 - p), the rest raw, in float64.  A logistic
    entry counts where the reference's p lies in [lo, hi]: outside it
    float32 cannot hold p finely enough to give its logit back (see
    `yolov3.json` `assumed.det_err`)."""
    worst = 0.0
    for g, w in zip(got, want):
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        logistic = logistic_entries(classes, w.shape[-1])
        keep = ~logistic | ((w >= lo) & (w <= hi))
        with np.errstate(divide="ignore", invalid="ignore"):
            g = np.where(logistic, np.log(g) - np.log1p(-g), g)[keep]
            w = np.where(logistic, np.log(w) - np.log1p(-w), w)[keep]
        scale = np.max(np.abs(w))
        err = np.max(np.abs(g - w)) / scale
        worst = max(worst, float(err) if np.isfinite(err) else math.inf)
    return worst


# ------------------------------------------------------------------ system ---

class System(darknet19.System):
    """YOLOv3-416 served by `CNNServingEngine` over a `CompileCache`; each
    request's result is the tuple of its rows of the three heads.  Every
    request's answer is held until the check, once per distinct answer:
    one that equals, bit for bit, the first answer to the same pooled
    image is replaced by that one (a 51 s window serves thousands of
    3.6 MB answers)."""

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

    def request(self, item, rid):
        self._pool_of[rid] = item.pool_index
        return super().request(item, rid)

    def done(self, req) -> bool:
        if req.done and req.rid >= 0:
            first = self._first.setdefault(self._pool_of[req.rid], req.result)
            if first is not req.result and all(
                    np.array_equal(a, b) for a, b in zip(first, req.result)):
                req.result = first
        return req.done

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
        return det_err(got, want, self.conf["classes"], a["p_min"], a["p_max"])

    def check(self, window, seed) -> list[dict]:
        """Every image served in the window, and in the drain after it,
        against the reference (`det_err`), each distinct answer once; and
        the requests that never got a result (`unanswered`, limit 0)."""
        ref = self._outputs(window, "highest")
        errs = {}
        for r in window.records:
            if r.req.done and id(r.req.result) not in errs:
                errs[id(r.req.result)] = self._err(r.req.result,
                                                   ref[r.item.pool_index])
        self._ref = ref
        return [{"name": "det_err", "value": max(errs.values(), default=0.0),
                 "limit": self.cell["limits"]["det_err"]},
                {"name": "unanswered", "value": window.unanswered,
                 "limit": 0}]

    def control(self, window, seed) -> dict:
        """The reference in bf16x3 in the program's place, compared with
        the reference as `check` compares the program."""
        ctl = self._outputs(window, "bf16x3")
        return {"det_err": max(self._err(ctl[i], self._ref[i]) for i in ctl)}


def build(conf: dict, cell: dict, spec: dict, seed: int) -> System:
    return System(conf, cell, spec, seed)
