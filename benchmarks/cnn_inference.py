"""End-to-end CNN inference on the Darknet framework (the paper's use-case).

Measures: (a) darknet-19-style classifier and (b) the deconv encoder-decoder,
with the engine's fused conv+BN+activation path vs an unfused reference
(separate conv, BN, activation) — the paper's stream-fusion claim at network
scale; plus (c) the serving path: a ragged request stream through the
bucketed `CNNServingEngine` vs naive per-request-shape compilation.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.darknet_ref import (DARKNET19_CFG, DARKNET_SMALL_CFG,
                                       SEGNET_SMALL_CFG)
from repro.core.darknet.network import Network
from repro.core import make_engine
from repro.serve.frontend import CNNServingEngine, ImageRequest


def _time(fn, reps=3):
    fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def _conv_flops(net: Network) -> float:
    """Analytic MACs×2 for conv/deconv/connected layers."""
    total = 0.0
    h, w, c = net.in_shape
    prev_c = c
    for p in net.plans:
        o = p.options
        oh, ow, oc = (p.out_shape + (1,))[:3] if len(p.out_shape) == 3 \
            else (1, 1, p.out_shape[0])
        if p.type == "convolutional":
            size = o.get("size", 3)
            total += 2.0 * oh * ow * oc * size * size * prev_c
        elif p.type == "deconvolutional":
            size = o.get("size", 3)
            total += 2.0 * oh * ow * oc * size * size * prev_c
        elif p.type == "connected":
            total += 2.0 * oc * prev_c  # flattened input approximated
        prev_c = oc if len(p.out_shape) == 3 else p.out_shape[0]
    return total


def run() -> list[tuple[str, float, str]]:
    rows = []
    for name, cfg_text, bhw in [
        ("darknet19_224", DARKNET19_CFG, (1, 224, 224, 3)),
        ("segnet_deconv_32", SEGNET_SMALL_CFG, (8, 32, 32, 3)),
    ]:
        # Compile-once deployment: one jit trace at compile, every timed
        # call a straight executable invocation (tests assert the single
        # trace; see tests/test_backends.py).
        net = Network(cfg_text, make_engine("xla", "fp32_strict"))
        params = net.init(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            bhw).astype(np.float32))
        compiled = net.compile(params, batch_size=bhw[0]).warmup()
        prof = compiled.profile(x, reps=3)
        t = prof["per_call_s"]
        gf = _conv_flops(net) * bhw[0] / t / 1e9
        op_plan = "+".join(f"{op}x{n}" for (_, op), n in
                           sorted(prof["op_counts"].items()))
        rows.append((f"cnn/{name}", t * 1e6,
                     f"GFLOPS={gf:.1f} traces={prof['trace_count']} "
                     f"ops={op_plan}"))

    # fused vs unfused epilogue on the SAME conv algorithm (im2col+GEMM),
    # isolating the paper's stream-fusion claim; the native-XLA conv row is
    # the backend reference (on TPU, the pallas backend forms a stride-1
    # 3x3 conv's patches inside the GEMM instead: ops.conv2d_implicit).
    from repro.core.darknet import layers as L
    eng = make_engine("xla", "fp32_strict")
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (8, 56, 56, 128)).astype(np.float32))
    p = L.init_conv(jax.random.PRNGKey(1), 3, 128, 256, batch_normalize=True)

    fused = jax.jit(lambda pp, xx: L.conv2d(eng, pp, xx, size=3, stride=1,
                                            pad=1, act="leaky",
                                            batch_normalize=True))

    def unfused_fn(pp, xx):  # im2col+GEMM, then separate BN and activation
        cols = L.im2col(xx, 3, 3, 1, 1)
        b, oh, ow, _ = cols.shape
        y = eng.matmul(cols.reshape(b * oh * ow, -1),
                       pp["w"]).reshape(b, oh, ow, -1)
        y = (y - pp["mean"]) / jnp.sqrt(pp["var"] + 1e-5)
        y = y * pp["gamma"] + pp["beta"]
        return jnp.where(y > 0, y, 0.1 * y)

    def native_fn(pp, xx):
        w = pp["w"].reshape(3, 3, 128, 256)
        y = jax.lax.conv_general_dilated(
            xx, w, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)
        y = (y - pp["mean"]) / jnp.sqrt(pp["var"] + 1e-5)
        y = y * pp["gamma"] + pp["beta"]
        return jnp.where(y > 0, y, 0.1 * y)

    unfused = jax.jit(unfused_fn)
    native = jax.jit(native_fn)
    tf = _time(lambda: jax.block_until_ready(fused(p, x)))
    tu = _time(lambda: jax.block_until_ready(unfused(p, x)))
    tn = _time(lambda: jax.block_until_ready(native(p, x)))
    rows.append(("cnn/conv_bn_act_fused_im2col_gemm", tf * 1e6, ""))
    rows.append(("cnn/conv_bn_act_unfused_im2col_gemm", tu * 1e6,
                 f"fused_speedup={tu / tf:.2f}x"))
    rows.append(("cnn/conv_bn_act_xla_native_ref", tn * 1e6,
                 "backend reference (pallas: implicit-patch GEMM)"))
    rows.extend(_serving_sweep())
    return rows


def _serving_sweep() -> list[tuple[str, float, str]]:
    """Ragged request stream: bucketed CompileCache serving vs compiling a
    fresh executable for every request batch shape (the naive deployment)."""
    ragged = [1, 3, 8, 2, 9, 4, 1, 5]                # arrival burst sizes
    rng = np.random.default_rng(0)
    bursts = [rng.standard_normal((b, 28, 28, 3)).astype(np.float32)
              for b in ragged]
    n_images = sum(ragged)

    def fresh_net():
        net = Network(DARKNET_SMALL_CFG, make_engine("xla", "fp32_strict"))
        return net, net.init(jax.random.PRNGKey(0))

    # bucketed serving frontend (compile cache pre-warmed: steady state)
    net, params = fresh_net()
    eng = CNNServingEngine(net.compile_cache(params,
                                             buckets=(1, 2, 4, 8)).warmup())
    t0 = time.perf_counter()
    rid = 0
    for xs in bursts:
        reqs = []
        for im in xs:
            reqs.append(ImageRequest(rid=rid, image=np.asarray(im)))
            rid += 1
        eng.run(reqs)
    t_served = time.perf_counter() - t0
    st = eng.stats()
    rows = [("cnn/serve_bucketed_stream", t_served / n_images * 1e6,
             f"img/s={n_images / t_served:.1f} "
             f"traces={st['cache']['traces']} "
             f"pad_waste={st['cache']['pad_waste'] * 100:.0f}% "
             f"lat_avg_ms={st['latency_s']['avg'] * 1e3:.1f}")]

    # naive baseline: every request batch compiles its own executable
    net, params = fresh_net()
    t0 = time.perf_counter()
    traces = 0
    for xs in bursts:
        cn = net.compile(params, batch_size=xs.shape[0])
        traces += cn.trace_count
        jax.block_until_ready(cn(jnp.asarray(xs)))
    t_naive = time.perf_counter() - t0
    rows.append(("cnn/serve_naive_per_request_compile",
                 t_naive / n_images * 1e6,
                 f"img/s={n_images / t_naive:.1f} traces={traces} "
                 f"bucketed_speedup={t_naive / t_served:.1f}x"))
    return rows
