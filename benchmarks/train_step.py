"""Pallas-vs-xla TRAINING benchmark: per-layer backward head-to-heads over
the darknet_ref layer zoo, and a full-train-step smoke gate.

Every registry op now carries a custom VJP on the pallas backend (GEMM
backward kernels under lazily-resolved "gemm_bwd" tile-plan keys — see
docs/engine_api.md), so the SAME differentiated trace can run either
backend end to end.  `run()` times jax.grad of each darknet_ref layer on
pallas against xla (interleaved median) and reports the max relative
gradient error between the two.  `--smoke` is the CI gate: one full
darknet_ref CNN train step and one reduced-LM train step through the
literal pallas VJPs, asserted to dispatch pallas kernels forward AND
backward (lazy gemm_bwd keys registered, loss + grads matching xla at
1e-5).

    PYTHONPATH=src python benchmarks/train_step.py            # full rows
    PYTHONPATH=src python benchmarks/train_step.py --smoke    # CI gate
"""
from __future__ import annotations

import argparse
import dataclasses
import statistics
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_arch, reduced
from repro.configs.darknet_ref import DARKNET_SMALL_CFG
from repro.core import backends, make_engine
from repro.core.darknet.network import Network
from repro.models import transformer as tfm
from repro.train import optimizer as opt
from repro.train.train_step import (cnn_loss_fn, make_cnn_train_step,
                                    make_train_step)

# The darknet_ref (DARKNET_SMALL_CFG) dense-layer zoo as engine problems:
# (name, B, H, W, Cin, Cout, size, stride, pad) for the conv layers, plus
# the connected head as a matmul.
CONV_LAYERS = [
    ("conv1_28x28x3_16", 4, 28, 28, 3, 16, 3, 1, 1),
    ("conv2_14x14x16_32", 4, 14, 14, 16, 32, 3, 1, 1),
    ("conv3_7x7x32_64", 4, 7, 7, 32, 64, 3, 1, 1),
]
FC_LAYERS = [
    ("connected_64_10", 4, 64, 10),
]


def _interleaved_median(fns: dict, reps=7) -> dict:
    """Median seconds per call, variants interleaved round-robin so
    machine-load drift hits all of them equally (same discipline as
    benchmarks/lm_step.py)."""
    for f in fns.values():
        f()                                    # warmup / compile
    t = {n: [] for n in fns}
    for _ in range(reps):
        for n, f in fns.items():
            t0 = time.perf_counter()
            f()
            t[n].append(time.perf_counter() - t0)
    return {n: statistics.median(v) for n, v in t.items()}


def _tree_max_rel(a, b) -> float:
    return max(jax.tree_util.tree_leaves(jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(x - y))
                           / (jnp.max(jnp.abs(y)) + 1e-12)), a, b)))


def layer_backward_headtohead(reps=5) -> list[tuple[str, float, str]]:
    """jax.grad of each darknet_ref layer, pallas vs xla: same loss, same
    operands, the only difference is which backend's kernels the
    differentiated trace dispatches (forward kernel + custom-VJP backward
    kernels on pallas; fused dot_generals on xla)."""
    engines = {n: make_engine(n, "fp32_strict") for n in ("pallas", "xla")}
    rows = []
    for name, b, h, w, cin, cout, size, stride, pad in CONV_LAYERS:
        key = jax.random.PRNGKey(hash(name) % 2**31)
        ks = jax.random.split(key, 4)
        x = jax.random.normal(ks[0], (b, h, w, cin), jnp.float32)
        wt = jax.random.normal(ks[1], (size * size * cin, cout)) * 0.1
        sc = jnp.abs(jax.random.normal(ks[2], (cout,))) + 0.5
        sh = jax.random.normal(ks[3], (cout,)) * 0.1

        grads, fns = {}, {}
        for n, eng in engines.items():
            def loss(x, wt, sc, sh, eng=eng):
                y = eng.conv2d(x, wt, scale=sc, shift=sh, size=size,
                               stride=stride, pad=pad, act="leaky")
                return (y.astype(jnp.float32) ** 2).sum()
            g = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
            grads[n] = g(x, wt, sc, sh)
            fns[n] = (lambda g=g: jax.block_until_ready(
                g(x, wt, sc, sh)[0]))
        med = _interleaved_median(fns, reps=reps)
        rel = _tree_max_rel(grads["pallas"], grads["xla"])
        rows.append((
            f"train_step/bwd_{name}_pallas", med["pallas"] * 1e6,
            f"B={b} {h}x{w}x{cin}->{cout} s{stride}p{pad}"))
        rows.append((
            f"train_step/bwd_{name}_xla", med["xla"] * 1e6,
            f"xla_speedup={med['pallas'] / med['xla']:.2f}x "
            f"grad_max_rel_err={rel:.2e}"))
    for name, b, nin, nout in FC_LAYERS:
        key = jax.random.PRNGKey(hash(name) % 2**31)
        ks = jax.random.split(key, 3)
        x = jax.random.normal(ks[0], (b, nin), jnp.float32)
        wt = jax.random.normal(ks[1], (nin, nout)) * 0.1
        bi = jax.random.normal(ks[2], (nout,)) * 0.1
        grads, fns = {}, {}
        for n, eng in engines.items():
            def loss(x, wt, bi, eng=eng):
                y = eng.matmul(x, wt, shift=bi, act="linear")
                return (y.astype(jnp.float32) ** 2).sum()
            g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            grads[n] = g(x, wt, bi)
            fns[n] = (lambda g=g: jax.block_until_ready(g(x, wt, bi)[0]))
        med = _interleaved_median(fns, reps=reps)
        rel = _tree_max_rel(grads["pallas"], grads["xla"])
        rows.append((
            f"train_step/bwd_{name}_pallas", med["pallas"] * 1e6,
            f"B={b} {nin}->{nout}"))
        rows.append((
            f"train_step/bwd_{name}_xla", med["xla"] * 1e6,
            f"xla_speedup={med['pallas'] / med['xla']:.2f}x "
            f"grad_max_rel_err={rel:.2e}"))
    return rows


def cnn_step_headtohead(*, batch=4, reps=3
                        ) -> tuple[list[tuple[str, float, str]], dict]:
    """One FULL darknet_ref CNN train step (cross-entropy + AdamW) per
    backend, identical params/batch.  Returns timing rows plus the parity
    and dispatch evidence the smoke gate asserts on."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    images = jax.random.normal(ks[1], (batch, 28, 28, 3), jnp.float32)
    labels = jax.random.randint(ks[2], (batch,), 0, 10)
    nets = {n: Network(DARKNET_SMALL_CFG, make_engine(n, "fp32_strict"))
            for n in ("pallas", "xla")}
    params = nets["pallas"].init(ks[0])
    ocfg = opt.AdamWConfig()

    evidence: dict = {}
    out, fns = {}, {}
    tuned0 = set(backends.tile_plans())
    for n, net in nets.items():
        step = jax.jit(make_cnn_train_step(net, ocfg))
        snap = backends.dispatch_counts()
        grads = jax.jit(jax.grad(
            lambda p: cnn_loss_fn(net, p, images, labels)))(params)
        p2, st2, metrics = step(params, opt.adamw_init(params),
                                (images, labels))
        jax.block_until_ready(metrics["loss"])
        out[n] = {"loss": float(metrics["loss"]), "grads": grads,
                  "params": p2,
                  "counts": backends.counts_since(snap)}
        fns[n] = (lambda step=step, st=opt.adamw_init(params):
                  jax.block_until_ready(
                      step(params, st, (images, labels))[2]["loss"]))
    med = _interleaved_median(fns, reps=reps)
    evidence["loss"] = {n: out[n]["loss"] for n in out}
    evidence["grad_rel"] = _tree_max_rel(out["pallas"]["grads"],
                                         out["xla"]["grads"])
    evidence["param_rel"] = _tree_max_rel(out["pallas"]["params"],
                                          out["xla"]["params"])
    evidence["pallas_counts"] = {
        op: c for (be, op), c in out["pallas"]["counts"].items()
        if be == "pallas"}
    evidence["gemm_bwd_keys"] = [
        k for k in backends.tile_plans()
        if k not in tuned0 and k[0] == "gemm_bwd"]
    rows = [
        ("train_step/cnn_full_step_pallas", med["pallas"] * 1e6,
         f"B={batch} loss={out['pallas']['loss']:.4f} "
         f"pallas_dispatches={evidence['pallas_counts']}"),
        ("train_step/cnn_full_step_xla", med["xla"] * 1e6,
         f"B={batch} loss={out['xla']['loss']:.4f} "
         f"xla_speedup={med['pallas'] / med['xla']:.2f}x "
         f"grad_max_rel_err={evidence['grad_rel']:.2e}"),
    ]
    return rows, evidence


def run() -> list[tuple[str, float, str]]:
    rows = layer_backward_headtohead()
    rows.extend(cnn_step_headtohead()[0])
    return rows


def smoke() -> list[tuple[str, float, str]]:
    """CI gate: the full CNN train step through literal pallas VJPs
    matches xla loss + grads at 1e-5, dispatches pallas kernels for every
    dense layer in the differentiated trace, and registers the lazy
    gemm_bwd backward keys; then one reduced-LM train step on the
    all-pallas engine is asserted finite with kernel dispatches."""
    rows, ev = cnn_step_headtohead(batch=2, reps=1)

    # Every dense layer dispatched the pallas kernels in the grad trace:
    # 3 conv layers + the connected head (value_and_grad traces the
    # forward once; the custom-VJP backward kernels ride those dispatches).
    want = {"conv2d": 3, "matmul": 1}
    got = {op: ev["pallas_counts"].get(op, 0) // 2 for op in want}
    # // 2: the harness traces grad-only and the full step (2 forwards).
    if any(got[op] < n for op, n in want.items()):
        raise SystemExit(f"FAIL: pallas train trace dispatched {got}, "
                         f"expected at least {want}")
    if not ev["gemm_bwd_keys"]:
        raise SystemExit("FAIL: no gemm_bwd tile-plan keys were resolved — "
                         "the backward ran off the pallas kernel path")
    if ev["grad_rel"] > 1e-5:
        raise SystemExit(f"FAIL: pallas-vs-xla CNN gradient parity "
                         f"{ev['grad_rel']:.2e} > 1e-5")
    if abs(ev["loss"]["pallas"] - ev["loss"]["xla"]) > 1e-5:
        raise SystemExit(f"FAIL: CNN loss mismatch {ev['loss']}")
    if ev["param_rel"] > 1e-4:
        raise SystemExit(f"FAIL: post-AdamW param parity "
                         f"{ev['param_rel']:.2e} > 1e-4")
    rows.append(("train_step/smoke_cnn_pallas_vjp", 0.0,
                 f"dispatches={ev['pallas_counts']} "
                 f"gemm_bwd_keys={len(ev['gemm_bwd_keys'])} "
                 f"grad_max_rel_err={ev['grad_rel']:.2e}"))

    # Reduced-LM train step on the ALL-pallas engine: GEMMs, bmm and
    # attention all run their custom-VJP kernels.
    cfg = dataclasses.replace(reduced(get_arch("qwen2-0.5b")), n_layers=1)
    eng = make_engine("pallas", "fp32_strict")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((1, 16), jnp.int32)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    snap = backends.dispatch_counts()
    step = jax.jit(make_train_step(eng, cfg, opt.AdamWConfig(),
                                   ce_chunk=16, n_q_chunks=2))
    _, _, metrics = step(params, opt.adamw_init(params), batch)
    loss = float(jax.block_until_ready(metrics["loss"]))
    counts = {op: c for (be, op), c in backends.counts_since(snap).items()
              if be == "pallas"}
    if counts.get("matmul", 0) < 1 or counts.get("attention", 0) < 1:
        raise SystemExit(f"FAIL: all-pallas LM train step dispatched "
                         f"{counts}; expected matmul + attention kernels")
    if not jnp.isfinite(loss):
        raise SystemExit(f"FAIL: all-pallas LM train loss {loss}")
    rows.append(("train_step/smoke_lm_pallas_vjp", 0.0,
                 f"dispatches={counts} loss={loss:.4f}"))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="full CNN + reduced-LM train steps through the "
                         "pallas VJPs with parity/dispatch asserts "
                         "(CI gate)")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    for row, us, derived in (smoke() if args.smoke else run()):
        print(f"{row},{us:.1f},{derived}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
