"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  figure3_gemm     paper Fig. 3 (FP32 GEMM perf + energy efficiency)
  engine_sweep     paper §IV any-shape flexibility claim
  cnn_inference    paper's CNN use-case end-to-end (+ fusion ablation)
  lm_step          substrate: LM train/decode steps per family
  roofline_report  §Roofline table from dry-run artifacts
"""
from __future__ import annotations

import sys


def main() -> None:
    from repro.core import enable_persistent_cache
    enable_persistent_cache()
    mods = sys.argv[1:] or ["figure3_gemm", "engine_sweep", "cnn_inference",
                            "lm_step", "roofline_report"]
    print("name,us_per_call,derived")
    for name in mods:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        for row, us, derived in mod.run():
            print(f"{row},{us:.1f},{derived}")
        sys.stdout.flush()


if __name__ == "__main__":
    main()
