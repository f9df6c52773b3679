"""Darknet-19 @224 (darknet19.cfg; Redmon & Farhadi, YOLO9000, 2017).

Builds the served system from `darknet19.json`: weights and batch-norm
statistics made on the device from the seed in one jitted call, the
program's `Network` on the cell's engine, a `CompileCache` over the cell's
buckets and a `CNNServingEngine` in front of it.  Holds the plain
reference (`lax.conv_general_dilated` + batch-norm + leaky + max-pool,
global average pool, softmax), which imports nothing of the program, and
the operation and byte counts of each convolution, computed from its
shapes: input, weights and output once each.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import numerics, traffic

F32 = 4  # bytes


def pad_of(layer: dict) -> int:
    """darknet: ``pad=1`` means size // 2, else ``padding`` (default 0)."""
    if layer.get("pad", 0):
        return layer.get("size", 1) // 2
    return layer.get("padding", 0)


def plan(conf: dict) -> list[dict]:
    """Each layer with its input and output shapes (H, W, C)."""
    h, w, c = conf["height"], conf["width"], conf["channels"]
    out = []
    for i, layer in enumerate(conf["layers"]):
        t = layer["type"]
        shape_in = (h, w, c)
        if t == "convolutional":
            k, s, p = layer.get("size", 1), layer.get("stride", 1), pad_of(layer)
            h, w, c = ((h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1,
                       layer["filters"])
        elif t == "maxpool":
            k, s = layer.get("size", 2), layer.get("stride", 2)
            h, w = (h - k) // s + 1, (w - k) // s + 1
        elif t == "avgpool":
            h, w = 1, 1
        elif t != "softmax":
            raise ValueError(f"layer {i}: {t!r} is not a Darknet-19 layer")
        out.append(dict(layer, index=i, shape_in=shape_in, shape_out=(h, w, c)))
    return out


def conv_work(conf: dict, batch: int) -> list[tuple[float, float]]:
    """(FLOPs, bytes) of every convolution of one call at `batch` images:
    2*OH*OW*Cout*k*k*Cin FLOPs per image, and the input, weights,
    epilogue vectors and output read or written once."""
    work = []
    for p in plan(conf):
        if p["type"] != "convolutional":
            continue
        (h, w, cin), (oh, ow, cout) = p["shape_in"], p["shape_out"]
        k = p.get("size", 1)
        flops = 2.0 * batch * oh * ow * cout * k * k * cin
        nbytes = F32 * (batch * h * w * cin + k * k * cin * cout + 2 * cout
                        + batch * oh * ow * cout)
        work.append((flops, nbytes))
    return work


def flops_per_image(conf: dict) -> float:
    return sum(f for f, _ in conv_work(conf, 1))


def param_count(conf: dict) -> int:
    n = 0
    for p in plan(conf):
        if p["type"] == "convolutional":
            k, cin, cout = p.get("size", 1), p["shape_in"][2], p["filters"]
            n += k * k * cin * cout + (4 if p.get("batch_normalize") else 1) * cout
    return n


# ----------------------------------------------------------------- weights ---

def make_params(conf: dict, seed: int) -> dict:
    """The program's param tree ({"l<i>": {"w": (k*k*Cin, Cout), ...}}),
    made on the device in one jitted call: He-normal weights, batch-norm
    gamma and var in [0.9, 1.1), beta and mean N(0, 0.05^2) (wider ranges
    let activations grow through the 18 layers until the softmax
    saturates)."""
    convs = [p for p in plan(conf) if p["type"] == "convolutional"]

    def gen(key):
        params = {}
        for p, k in zip(convs, jax.random.split(key, len(convs))):
            ks = jax.random.split(k, 5)
            size, cin, cout = p.get("size", 1), p["shape_in"][2], p["filters"]
            fan_in = size * size * cin
            layer = {"w": jax.random.normal(ks[0], (fan_in, cout), jnp.float32)
                     * math.sqrt(2.0 / fan_in)}
            if p.get("batch_normalize"):
                layer.update(
                    gamma=jax.random.uniform(ks[1], (cout,), jnp.float32, 0.9, 1.1),
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

def reference(conf: dict, params: dict, x, mode: str = "highest"):
    """The plain forward of Darknet-19: (B, H, W, C) -> (B, classes)
    probabilities, every product at `mode` (benchlib.numerics)."""
    eps = conf["assumed"]["batchnorm_eps"]
    slope = conf["assumed"]["leaky_slope"]
    for p in plan(conf):
        t = p["type"]
        if t == "convolutional":
            lp = params[f"l{p['index']}"]
            k, cin = p.get("size", 1), p["shape_in"][2]
            w = lp["w"].reshape(k, k, cin, p["filters"])
            x = numerics.conv(x, w, stride=p.get("stride", 1), pad=pad_of(p),
                              mode=mode)
            if p.get("batch_normalize"):
                x = ((x - lp["mean"]) / jnp.sqrt(lp["var"] + eps)
                     * lp["gamma"] + lp["beta"])
            else:
                x = x + lp["b"]
            if p["activation"] == "leaky":
                x = jnp.where(x > 0, x, slope * x)
            elif p["activation"] != "linear":
                raise ValueError(f"activation {p['activation']!r}")
        elif t == "maxpool":
            k, s = p.get("size", 2), p.get("stride", 2)
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, k, k, 1), (1, s, s, 1), "VALID")
        elif t == "avgpool":
            x = jnp.mean(x, axis=(1, 2))
        elif t == "softmax":
            x = jax.nn.softmax(x, axis=-1)
    return x


def cfg_text(conf: dict) -> str:
    """The configuration as a darknet .cfg, which the program parses."""
    lines = ["[net]", f"height={conf['height']}", f"width={conf['width']}",
             f"channels={conf['channels']}"]
    for layer in conf["layers"]:
        lines += ["", f"[{layer['type']}]"]
        lines += [f"{k}={v}" for k, v in layer.items() if k != "type"]
    return "\n".join(lines) + "\n"


# ------------------------------------------------------------------ system ---

class System:
    """Darknet-19 served by `CNNServingEngine` over a `CompileCache`."""

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
        self.results: list = []   # (pool index, served output) per request

    # -- driving
    def request(self, item, rid):
        return self._frontend.ImageRequest(
            rid=rid, image=self.images[item.pool_index])

    def done(self, req) -> bool:
        return req.done

    def warmup(self, items) -> None:
        """Compile every bucket, then serve a few rounds of each bucket's
        batch through the frontend, so the window finds everything built."""
        self.cache.warmup()
        for bucket in self.cache.buckets:
            for r in range(3):
                for i in range(bucket):
                    self.server.submit(self.request(items[(r + i) % len(items)],
                                                    -1))
                while self.server.step():
                    pass
        self._dispatches = dict(self.cache.stats()["dispatches"])

    def before_step(self):
        return None

    def after_step(self, _):
        """Dispatches by bucket made by this step."""
        now = self.cache.stats()["dispatches"]
        made = {b: n - self._dispatches.get(b, 0) for b, n in now.items()
                if n != self._dispatches.get(b, 0)}
        self._dispatches = dict(now)
        return made

    # -- counts for the per-layer readers
    def flops_per_item(self) -> float:
        return flops_per_image(self.conf)

    def op_calls(self, steps) -> dict:
        """{"conv2d": [(FLOPs, bytes, calls), ...]} over `steps`."""
        calls = []
        for s in steps:
            for bucket, n in s.obs.items():
                calls += [(f, b, n) for f, b in conv_work(self.conf, bucket)]
        return {"conv2d": calls}

    # -- after the window
    def release(self) -> None:
        """Free the program's state (served results stay with the
        requests; the weights are the benchmark's own)."""
        del self.server, self.cache

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
            y = np.asarray(fn(self.params, jnp.asarray(x)))
            out.update(zip(part, y))
        return out

    def check(self, window, seed) -> list[dict]:
        """Every image served in the window, and in the drain after it,
        against the reference, in log space: the largest |log p_served -
        log p_reference| over the classes both put above 1e-30, relative
        to the reference's largest |log p| there (`logprob_err`).
        Probabilities alone say little once the softmax saturates: one
        class at 1.0 hides every logit's error.  And the requests that
        never got a result (`unanswered`, limit 0)."""
        ref = self._outputs(window, "highest")
        worst = max((logprob_err(r.req.result, ref[r.item.pool_index])
                     for r in window.records if r.req.done), default=0.0)
        self._ref = ref
        return [{"name": "logprob_err", "value": worst,
                 "limit": self.cell["limits"]["logprob_err"]},
                {"name": "unanswered", "value": window.unanswered,
                 "limit": 0}]

    def control(self, window, seed) -> dict:
        """The reference in bf16x3 in the program's place, compared with
        the reference as `check` compares the program."""
        ctl = self._outputs(window, "bf16x3")
        return {"logprob_err": max(logprob_err(ctl[i], self._ref[i])
                                   for i in ctl)}


def logprob_err(got, want, floor: float = 1e-30) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    keep = (got > floor) & (want > floor)
    lw = np.log(want[keep])
    scale = np.max(np.abs(lw)) if lw.size else 0.0
    if scale == 0.0:
        return 0.0 if np.array_equal(got, want) else float("inf")
    return float(np.max(np.abs(np.log(got[keep]) - lw)) / scale)


def build(conf: dict, cell: dict, spec: dict, seed: int) -> System:
    return System(conf, cell, spec, seed)
