"""End-to-end driver: train a ~100M-param qwen2-family model for a few
hundred steps with checkpointing, restart-on-failure and straggler watchdog.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

This exercises the full production loop (data pipeline -> microbatched
train step -> AdamW -> atomic checkpoints).  ~100M params: 12L d=512.
"""
import argparse
import dataclasses

from repro.configs.base import get_arch
from repro.core import enable_persistent_cache
from repro.launch.train import train_loop


def main():
    enable_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    # ~100M-param member of the qwen2 family (vocab dominates).
    cfg = dataclasses.replace(
        get_arch("qwen2-0.5b"),
        name="qwen2-100m", n_layers=12, d_model=512, n_heads=8,
        n_kv_heads=2, head_dim=64, d_ff=1536, vocab_size=65536,
    )
    from repro.models.transformer import param_counts
    total, _ = param_counts(cfg)
    print(f"[train_lm] params: {total/1e6:.1f}M")
    train_loop(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
               ckpt_dir=args.ckpt_dir, ckpt_every=100, lr=3e-4,
               num_microbatches=2, log_every=10)


if __name__ == "__main__":
    main()
