"""Reduced-config LM step timings on CPU: train / prefill / decode per arch
family — the substrate-level benchmark (one row per model family) — plus a
grouped-vs-broadcast GQA prefill head-to-head and a kernel-vs-blockwise
TRAIN-STEP head-to-head.

The prefill head-to-head times the SAME attention math two ways through
the registry `attention` op: the grouped-KV native dispatch (compact
(B, S, KV, hd) K/V, the shipped path) against a caller-side
``jnp.repeat`` H-broadcast (the pre-ISSUE-4 path), and reports the
wall-clock ratio alongside the K/V bytes each variant materializes
(`kvcache.kv_broadcast_bytes`) and, where the backend exposes it, the
compiled executable's peak temp memory delta.

The train head-to-head differentiates the SAME loss two ways: through the
registry op (the kernel path — now the training default, since the flash
kernel carries a custom VJP) and through the retired blockwise-jnp
fallback (``kernel_attention=False``), interleaved-median timed, with the
max relative gradient error between the two reported alongside.

    PYTHONPATH=src python benchmarks/lm_step.py            # full rows
    PYTHONPATH=src python benchmarks/lm_step.py --smoke    # CI: head-to-heads
                                                           # + dispatch/
                                                           # kernel-VJP gates
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_arch, reduced
from repro.core import backends, make_engine
from repro.models import transformer as tfm
from repro.serve import kvcache
from repro.serve.serve_step import make_decode_step, make_prefill_step
from repro.train import optimizer as opt
from repro.train.train_step import make_train_step

ARCHS = ["qwen2-0.5b", "deepseek-v2-lite-16b", "mamba2-1.3b", "zamba2-7b",
         "hubert-xlarge"]


def _time(fn, reps=3):
    fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def _interleaved_median(fns: dict, reps=7) -> dict:
    """Median seconds per call, with the variants interleaved round-robin
    so machine-load drift hits all of them equally (head-to-heads on
    shared CI boxes are meaningless without this)."""
    import statistics
    for f in fns.values():
        f()                                    # warmup / compile
    t = {n: [] for n in fns}
    for _ in range(reps):
        for n, f in fns.items():
            t0 = time.perf_counter()
            f()
            t[n].append(time.perf_counter() - t0)
    return {n: statistics.median(v) for n, v in t.items()}


def _peak_temp_bytes(fn, *args) -> int | None:
    """Compiled executable's temp-allocation estimate, when the backend
    reports one (CPU/TPU expose memory_analysis; interpret-mode fallbacks
    may not)."""
    try:
        ma = jax.jit(fn).lower(*args).compile().memory_analysis()
        return int(ma.temp_size_in_bytes)
    except Exception:
        return None


def gqa_prefill_headtohead(*, B=2, S=256, n_layers=2, reps=3
                           ) -> list[tuple[str, float, str]]:
    """Grouped vs broadcast prefill on a G=8 GQA model (8 query heads per
    kv head — the ratio class of qwen2-style configs)."""
    cfg = dataclasses.replace(reduced(get_arch("qwen2-0.5b")),
                              n_heads=8, n_kv_heads=1, head_dim=32,
                              n_layers=n_layers)
    eng = make_engine("xla", "fp32_strict")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)

    def grouped(p, t):
        return make_prefill_step(eng, cfg)(p, {"tokens": t})[0]

    # The pre-ISSUE-4 formulation: same registry op, but K/V pre-broadcast
    # to all H query heads before dispatch (G x the KV traffic).
    from repro.models import attention as attn
    real_forward = attn.gqa_forward

    def broadcast_forward(engine, p, x, cos, sin, c, **kw):
        kw.pop("kernel_attention", None)
        return _gqa_forward_broadcast(engine, p, x, cos, sin, c, **kw)

    def _gqa_forward_broadcast(engine, p, x, cos, sin, c, *,
                               shard_mode="seq", n_q_chunks=8,
                               return_kv=False):
        from repro.models.common import rope_apply
        Bx, Sx, _ = x.shape
        H, KV, hd = c.n_heads, c.n_kv_heads, c.head_dim
        q = engine.matmul(x, p["wq"], shift=p.get("bq")).reshape(
            Bx, Sx, H, hd)
        k = engine.matmul(x, p["wk"], shift=p.get("bk")).reshape(
            Bx, Sx, KV, hd)
        v = engine.matmul(x, p["wv"], shift=p.get("bv")).reshape(
            Bx, Sx, KV, hd)
        if cos is not None:
            q, k = rope_apply(q, cos, sin), rope_apply(k, cos, sin)
        kb = jnp.repeat(k, H // KV, axis=2)
        vb = jnp.repeat(v, H // KV, axis=2)
        y = engine.attention(q, kb, vb, causal=c.causal)
        out = engine.matmul(y.reshape(Bx, Sx, H * hd), p["wo"])
        return (out, {"k": k, "v": v}) if return_kv else out

    def broadcast(p, t):
        attn.gqa_forward = broadcast_forward
        try:
            return make_prefill_step(eng, cfg)(p, {"tokens": t})[0]
        finally:
            attn.gqa_forward = real_forward

    g_jit, b_jit = jax.jit(grouped), jax.jit(broadcast)
    med = _interleaved_median(
        {"g": lambda: jax.block_until_ready(g_jit(params, toks)),
         "b": lambda: jax.block_until_ready(b_jit(params, toks))},
        reps=max(reps, 5))
    t_g, t_b = med["g"], med["b"]
    compact, broad = kvcache.kv_broadcast_bytes(cfg, B, S)
    mem_g = _peak_temp_bytes(grouped, params, toks)
    mem_b = _peak_temp_bytes(broadcast, params, toks)
    mem = (f" peak_temp_delta={(mem_b - mem_g) / 1e6:.2f}MB"
           if mem_g is not None and mem_b is not None else "")
    rows = [
        ("lm_step/gqa_prefill_grouped", t_g * 1e6,
         f"B={B} S={S} H=8 KV=1 kv_bytes={compact / 1e6:.2f}MB"),
        ("lm_step/gqa_prefill_broadcast", t_b * 1e6,
         f"B={B} S={S} H=8 KV=8(broadcast) kv_bytes={broad / 1e6:.2f}MB"
         f" grouped_speedup={t_b / t_g:.2f}x"
         f" kv_bytes_saved={(broad - compact) / 1e6:.2f}MB{mem}"),
    ]
    return rows


def train_grad_headtohead(*, B=2, S=64, n_layers=2, reps=5
                          ) -> tuple[list[tuple[str, float, str]], float]:
    """Kernel-path vs blockwise-fallback training gradients: same loss,
    same engine, the only difference is which attention formulation the
    differentiated trace runs.  Reports wall-clock (interleaved median)
    and the max relative error between the two gradient trees."""
    cfg = dataclasses.replace(reduced(get_arch("qwen2-0.5b")),
                              n_layers=n_layers)
    eng = make_engine("xla", "fp32_strict")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}

    def loss(p, kernel_attention):
        return tfm.loss_fn(eng, cfg, p, batch, ce_chunk=32, n_q_chunks=4,
                           kernel_attention=kernel_attention)

    g_kern = jax.jit(jax.value_and_grad(lambda p: loss(p, True)))
    g_block = jax.jit(jax.value_and_grad(lambda p: loss(p, False)))
    med = _interleaved_median(
        {"k": lambda: jax.block_until_ready(g_kern(params)[0]),
         "b": lambda: jax.block_until_ready(g_block(params)[0])},
        reps=max(reps, 5))
    _, gk = g_kern(params)
    _, gb = g_block(params)
    rel = max(jax.tree_util.tree_leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)), gk, gb)))
    return [
        ("lm_step/train_grad_kernel", med["k"] * 1e6,
         f"B={B} S={S} layers={n_layers} registry-op path"),
        ("lm_step/train_grad_blockwise", med["b"] * 1e6,
         f"B={B} S={S} layers={n_layers}"
         f" kernel_speedup={med['b'] / med['k']:.2f}x"
         f" grad_max_rel_err={rel:.2e}"),
    ], rel


def run() -> list[tuple[str, float, str]]:
    rows = []
    eng = make_engine("xla", "fp32_strict")
    for arch in ARCHS:
        cfg = reduced(get_arch(arch))
        params = tfm.init_params(jax.random.PRNGKey(0), cfg)
        B, S = 2, 64
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        batch = {"labels": jax.random.randint(ks[2], (B, S), 0,
                                              cfg.vocab_size)}
        if cfg.frontend == "audio":
            batch["frames"] = jax.random.normal(ks[0],
                                                (B, S, cfg.frontend_dim))
        else:
            n_text = S - (cfg.frontend_tokens
                          if cfg.frontend == "vision" else 0)
            batch["tokens"] = jax.random.randint(ks[0], (B, n_text), 0,
                                                 cfg.vocab_size)
            if cfg.frontend == "vision":
                batch["patch_embeds"] = jax.random.normal(
                    ks[1], (B, cfg.frontend_tokens, cfg.frontend_dim))
        ocfg = opt.AdamWConfig()
        step = jax.jit(make_train_step(eng, cfg, ocfg, ce_chunk=32,
                                       n_q_chunks=4))
        st = opt.adamw_init(params)
        t = _time(lambda: jax.block_until_ready(
            step(params, st, batch)[2]["loss"]))
        rows.append((f"lm_step/{arch}/train", t * 1e6, f"B={B} S={S}"))

        if not cfg.is_encoder:
            caches = kvcache.cache_init(cfg, B, S)
            dec = jax.jit(make_decode_step(eng, cfg))
            tok = jnp.zeros((B, 1), jnp.int32)
            pos = jnp.array(0, jnp.int32)
            t = _time(lambda: jax.block_until_ready(
                dec(params, caches, tok, pos)[0]))
            rows.append((f"lm_step/{arch}/decode", t * 1e6, f"B={B}"))
    rows.extend(gqa_prefill_headtohead())
    rows.extend(train_grad_headtohead()[0])
    return rows


def smoke() -> list[tuple[str, float, str]]:
    """CI smoke: the grouped-vs-broadcast and kernel-vs-blockwise
    head-to-heads at a small size, one grouped prefill step asserted to
    dispatch the registry op with compact KV (no jnp.repeat in the
    dispatch path), the DIFFERENTIATED train trace asserted to dispatch
    the registry attention op with matching gradients, and one train step
    through the pallas flash kernel's custom VJP asserted finite."""
    rows = gqa_prefill_headtohead(B=1, S=64, n_layers=1, reps=1)
    cfg = reduced(get_arch("qwen2-0.5b"))
    eng = make_engine("xla", "fp32_strict")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((2, 32), jnp.int32)
    snap = backends.dispatch_counts()
    logits, caches = jax.jit(make_prefill_step(eng, cfg))(
        params, {"tokens": toks})
    jax.block_until_ready(logits)
    n_att = backends.counts_since(snap).get(("xla", "attention"), 0)
    # scan-over-layers traces the layer body once: one dispatch per stack.
    if n_att != 1:
        raise SystemExit(f"FAIL: grouped prefill dispatched {n_att} "
                         f"attention ops, expected 1 (scanned stack)")
    # cache leaves are layer-stacked: (n_layers, B, S, KV, hd)
    kv_shapes = {tuple(l.shape[-4:]) for entry in caches
                 for l in jax.tree_util.tree_leaves(entry)}
    want = (2, 32, cfg.n_kv_heads, cfg.head_dim)
    if kv_shapes != {want}:
        raise SystemExit(f"FAIL: prefill caches are not compact grouped KV: "
                         f"{kv_shapes} != {{{want}}}")
    rows.append(("lm_step/smoke_grouped_prefill", 0.0,
                 f"attention_dispatches={n_att} kv_cache_shape={want}"))

    # The DIFFERENTIATED trace dispatches the registry attention op (the
    # kernel path — kernel_attention=False is retired) and its gradients
    # match the blockwise formulation.
    hh_rows, rel = train_grad_headtohead(B=1, S=32, n_layers=1, reps=1)
    rows.extend(hh_rows)
    snap = backends.dispatch_counts()
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    step = jax.jit(make_train_step(eng, cfg, opt.AdamWConfig(), ce_chunk=32,
                                   n_q_chunks=4))
    _, _, metrics = step(params, opt.adamw_init(params), batch)
    loss = float(jax.block_until_ready(metrics["loss"]))
    n_att = backends.counts_since(snap).get(("xla", "attention"), 0)
    if n_att < 1:
        raise SystemExit("FAIL: differentiated train trace dispatched no "
                         "registry attention op (blockwise fallback?)")
    if rel > 1e-5:
        raise SystemExit(f"FAIL: kernel-vs-blockwise gradient parity "
                         f"{rel:.2e} > 1e-5")
    rows.append(("lm_step/smoke_train_dispatches_kernel_op", 0.0,
                 f"attention_dispatches={n_att} loss={loss:.4f} "
                 f"grad_max_rel_err={rel:.2e}"))

    # And the literal pallas flash kernel trains: a hybrid backend (xla
    # GEMMs + the pallas attention impl with its custom-VJP backward
    # kernels) runs one full train step off-mesh.
    pallas = backends.get_backend("pallas")
    xla = backends.get_backend("xla")
    from repro.core import register_backend
    register_backend("train-flash",
                     dict(xla.ops, attention=pallas.op("attention")),
                     tile_picker=pallas.tile_picker, overwrite=True)
    try:
        feng = make_engine("train-flash", "fp32_strict")
        snap = backends.dispatch_counts()
        step = jax.jit(make_train_step(feng, cfg, opt.AdamWConfig(),
                                       ce_chunk=32, n_q_chunks=4))
        _, _, metrics = step(params, opt.adamw_init(params), batch)
        floss = float(jax.block_until_ready(metrics["loss"]))
        n_att = backends.counts_since(snap).get(("train-flash", "attention"),
                                                0)
        if n_att < 1 or not jnp.isfinite(floss):
            raise SystemExit(
                f"FAIL: flash-kernel train step dispatched {n_att} "
                f"attention ops, loss={floss}")
        if abs(floss - loss) > 1e-3:
            raise SystemExit(f"FAIL: flash-kernel train loss {floss} != "
                             f"registry-op train loss {loss}")
    finally:
        backends.unregister_backend("train-flash")
    rows.append(("lm_step/smoke_train_flash_kernel_vjp", 0.0,
                 f"attention_dispatches={n_att} loss={floss:.4f}"))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small grouped-vs-broadcast head-to-head + one "
                         "grouped prefill step with compact-KV asserts "
                         "(CI gate)")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    for row, us, derived in (smoke() if args.smoke else run()):
        print(f"{row},{us:.1f},{derived}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
