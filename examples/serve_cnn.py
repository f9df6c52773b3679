"""Serve a compiled Darknet CNN behind the unified serving frontend.

    PYTHONPATH=src python examples/serve_cnn.py

The paper's deployment shape end to end: compile the network once per
batch bucket (`Network.compile_cache`), stand up the micro-batching
`CNNServingEngine`, and push a ragged request stream through it — padded
bucket dispatch, per-request latency, aggregate images/sec.

Doubles as the CI serving smoke: exits non-zero if any bucket retraces
(trace count must equal the number of compiled buckets) or if traffic
does not complete.
"""
import jax
import numpy as np

from repro.configs.darknet_ref import DARKNET_SMALL_CFG
from repro.core import enable_persistent_cache, make_engine
from repro.core.darknet.network import Network
from repro.serve.frontend import CNNServingEngine, ImageRequest

BUCKETS = (1, 2, 4, 8)


def main():
    enable_persistent_cache()
    net = Network(DARKNET_SMALL_CFG, make_engine("xla", "fp32_strict"))
    params = net.init(jax.random.PRNGKey(0))
    cache = net.compile_cache(params, buckets=BUCKETS)
    engine = CNNServingEngine(cache)

    # ragged arrival pattern: bursts of 1..9 images
    rng = np.random.default_rng(0)
    h, w, c = net.in_shape
    rid = 0
    for burst in (1, 3, 8, 2, 9, 4, 1, 5):
        reqs = []
        for _ in range(burst):
            reqs.append(ImageRequest(
                rid=rid,
                image=rng.standard_normal((h, w, c)).astype(np.float32)))
            rid += 1
        engine.run(reqs)
        assert all(r.done and r.result is not None for r in reqs)

    st = engine.stats()
    cs = st["cache"]
    print(f"[serve_cnn] served {st['requests']['completed']} requests in "
          f"{st['steps']} micro-batches: {st['throughput']:.1f} img/s, "
          f"avg latency {st['latency_s']['avg'] * 1e3:.1f} ms")
    print(f"[serve_cnn] buckets={cs['buckets']} compiled={cs['compiled']} "
          f"traces={cs['traces']} dispatches={cs['dispatches']}")
    print(f"[serve_cnn] pad waste {cs['pad_waste'] * 100:.1f}% "
          f"({cs['rows_padded']} padded / "
          f"{cs['rows_real'] + cs['rows_padded']} dispatched rows)")

    # retrace-count regression guard (CI smoke).  `misses` counts every
    # compile the cache ever performed (a recompiled bucket replaces its
    # dict entry, so `traces` alone can't see it) — exactly one compile per
    # bucket means misses == compiled buckets.
    if cs["misses"] != len(cs["compiled"]) or cs["traces"] != len(
            cs["compiled"]):
        raise SystemExit(f"retrace regression: {cs['misses']} compiles / "
                         f"{cs['traces']} traces for "
                         f"{len(cs['compiled'])} compiled buckets")
    if st["requests"]["completed"] != rid:
        raise SystemExit(f"dropped traffic: {st['requests']['completed']} "
                         f"of {rid} requests completed")
    print("[serve_cnn] OK: one trace per bucket, all traffic served")


if __name__ == "__main__":
    main()
