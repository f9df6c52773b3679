"""Serve a small LM through the continuous-batching paged-pool frontend.

    PYTHONPATH=src python examples/serve_lm.py

The default LM serving path: a ragged batch of greedy-decode requests runs
through `PagedServingEngine` — chunked prefill interleaved with decode over
a shared pool of fixed-size KV blocks, dispatched through a bounded set of
compiled shape buckets (docs/serving.md).  The fixed-slot `ServingEngine`
remains as the baseline; `benchmarks/lm_serving.py` runs the two
head-to-head at equal KV memory.
"""
import time

import jax
import numpy as np

from repro.configs.base import get_arch, reduced
from repro.core import enable_persistent_cache, make_engine
from repro.models import transformer as tfm
from repro.serve.engine import Request
from repro.serve.scheduler import PagedServingEngine


def main():
    enable_persistent_cache()
    cfg = reduced(get_arch("qwen2-0.5b"))
    engine = make_engine("xla", "fp32_strict")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)

    rng = np.random.default_rng(1)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        int(rng.integers(4, 24))).tolist(),
                    max_new=int(rng.integers(4, 13)))
            for i in range(8)]

    frontend = PagedServingEngine(
        cfg, params, engine=engine, kv_blocks=16, block_size=16,
        max_len=64, chunk=8, prefill_budget=32)

    t0 = time.perf_counter()
    frontend.run(reqs)
    wall = time.perf_counter() - t0

    st = frontend.stats()
    lat = st["latency_s"]
    print(f"[serve_lm] {st['requests']['completed']}/{len(reqs)} requests, "
          f"{st['tokens']} tokens in {wall:.2f}s "
          f"({st['tokens'] / wall:.1f} tok/s)")
    print(f"[serve_lm] latency p50={lat['p50'] * 1e3:.0f}ms "
          f"p95={lat['p95'] * 1e3:.0f}ms p99={lat['p99'] * 1e3:.0f}ms")
    print(f"[serve_lm] peak concurrency={st['peak_active']} "
          f"pool peak={st['pool']['peak_used']}/{st['pool']['n_blocks']} "
          f"blocks, traces={st['compile']['traces']}/{st['trace_bound']}")
    print("[serve_lm] sample generations (token ids):")
    for r in reqs[:4]:
        print(f"  req{r.rid}: prompt[{len(r.prompt)}] -> {r.out}")


if __name__ == "__main__":
    main()
