"""Any-shape flexibility sweep (paper §IV: dims chosen away from the sweet
spot; 'results with different dimensions are fully in line').

Measures the XLA-backend engine on CPU across shapes and validates the
Pallas-backend engine against it at every shape (both resolve through the
backend registry); derives modeled v5e times and reports the tile-plan
cache behaviour across the sweep.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backends, make_engine

SHAPES = [
    (2048, 4096, 16384),   # the paper's headline
    (512, 512, 512),
    (1000, 777, 333),      # ragged
    (4096, 1024, 1024),
    (128, 8192, 128),      # skinny
]


def _time(fn, reps=3):
    fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def run() -> list[tuple[str, float, str]]:
    rows = []
    eng = make_engine("xla", "fp32_strict")
    eng_pallas = make_engine("pallas", "fp32_strict")
    rng = np.random.default_rng(1)
    stats0 = backends.cache_stats()
    for (m, k, n) in SHAPES:
        a = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
        f = jax.jit(lambda x, y: eng.matmul(x, y, act="leaky"))
        t = _time(lambda: jax.block_until_ready(f(a, b)))
        gf = 2.0 * m * k * n / t / 1e9
        # kernel correctness at this shape (subsampled for big shapes):
        # pallas-backend engine vs xla-backend engine, both via registry.
        ms, ks, ns = min(m, 256), min(k, 512), min(n, 512)
        got = eng_pallas.matmul(a[:ms, :ks], b[:ks, :ns], act="leaky")
        want = eng.matmul(a[:ms, :ks], b[:ks, :ns], act="leaky")
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        rows.append((f"engine_sweep/{m}x{k}x{n}", t * 1e6,
                     f"GFLOPS={gf:.1f} kernel_err={err:.1e}"))
    stats = backends.cache_stats()
    rows.append(("engine_sweep/tile_plan_cache", 0.0,
                 f"hits={stats['hits'] - stats0['hits']} "
                 f"misses={stats['misses'] - stats0['misses']}"))
    return rows
