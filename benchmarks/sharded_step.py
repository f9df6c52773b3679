"""1-vs-8-virtual-device head-to-head for the `sharded_pallas` backend:
prefill, decode and train step through the SAME kernel set, single-device
pallas vs shard_map-distributed over an 8-device data mesh — plus the
sharded serving gates.

Must run with the host-platform device count forced BEFORE jax initializes:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python benchmarks/sharded_step.py           # rows
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python benchmarks/sharded_step.py --smoke   # CI gate

The --smoke gate asserts, in order:
  * per-SHARD tile-plan keys: the sharded prefill resolves block plans
    from the LOCAL shard shapes (batch 1), never the global batch-8
    problem;
  * fp32 parity <= 1e-5 against the single-device pallas backend for
    prefill logits, decode logits and train-step loss + gradients;
  * greedy token streams through the slot AND paged serving engines
    bit-identical to the unsharded run;
  * collective audit (analysis/diagnose.py): the batch-sharded attention
    trace emits ZERO collectives, and no sharded attention trace —
    including the sequence-split decode path, whose (o, lse) partials DO
    all-gather — contains an all-gather as large as the full K/V.

When the device-count flag didn't take, the benchmark prints a skip row
and exits 0 (the flag only applies before jax init — see
tests/test_dryrun_integration.py for the same guard).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.analysis import diagnose
from repro.configs.base import get_arch, reduced
from repro.core import backends, make_engine
from repro.kernels import sharded
from repro.models import transformer as tfm
from repro.serve import kvcache
from repro.serve.engine import Request, ServingEngine
from repro.serve.scheduler import PagedServingEngine
from repro.serve.serve_step import make_decode_step, make_prefill_step
from repro.sharding import hints

B, S = 8, 64               # global batch (divides the 8-device data axis)
DECODE_LEN = 512           # cache depth: per-shard decode-shaped (Skv >= 256)
TOL = 1e-5


def _gate(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"FAIL: {msg}")


def _median(fns: dict, reps: int = 5) -> dict:
    """Interleaved-median seconds per call (same rationale as lm_step)."""
    for f in fns.values():
        f()                                     # warmup / compile
    t = {n: [] for n in fns}
    for _ in range(reps):
        for n, f in fns.items():
            t0 = time.perf_counter()
            f()
            t[n].append(time.perf_counter() - t0)
    return {n: statistics.median(v) for n, v in t.items()}


def data_mesh() -> Mesh:
    return Mesh(np.array(jax.devices()[:8]), ("data",))


def _setup():
    cfg = reduced(get_arch("qwen2-0.5b"))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    e1 = make_engine("pallas", "fp32_strict")
    e8 = make_engine("sharded_pallas", "fp32_strict")
    return cfg, params, toks, e1, e8


def _maxdiff(a, b) -> float:
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def parity_rows(mesh, reps: int = 3) -> list[tuple[str, float, str]]:
    """Prefill / decode / train head-to-head with parity gates."""
    cfg, params, toks, e1, e8 = _setup()
    rows = []

    pre1 = jax.jit(make_prefill_step(e1, cfg))
    pre8 = jax.jit(make_prefill_step(e8, cfg))
    l1, _ = pre1(params, {"tokens": toks})
    with hints.use_mesh(mesh):
        l8, _ = pre8(params, {"tokens": toks})
    d = _maxdiff(l1, l8)
    _gate(d <= TOL, f"sharded prefill logits diverge: {d:.2e} > {TOL}")
    med = _median(
        {"1": lambda: jax.block_until_ready(pre1(params, {"tokens": toks})[0]),
         "8": lambda: jax.block_until_ready(
             pre8(params, {"tokens": toks})[0])},
        reps=reps)
    rows.append(("sharded_step/prefill_1dev", med["1"] * 1e6,
                 f"B={B} S={S}"))
    rows.append(("sharded_step/prefill_8dev", med["8"] * 1e6,
                 f"B={B} S={S} maxdiff={d:.2e} "
                 f"speedup={med['1'] / med['8']:.2f}x"))

    dec1 = jax.jit(make_decode_step(e1, cfg))
    dec8 = jax.jit(make_decode_step(e8, cfg))
    caches = kvcache.cache_init(cfg, B, DECODE_LEN)
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.full((B,), 300, jnp.int32)
    dl1, _ = dec1(params, caches, tok, pos)
    with hints.use_mesh(mesh):
        dl8, _ = dec8(params, caches, tok, pos)
    d = _maxdiff(dl1, dl8)
    _gate(d <= TOL, f"sharded decode logits diverge: {d:.2e} > {TOL}")
    med = _median(
        {"1": lambda: jax.block_until_ready(dec1(params, caches, tok,
                                                 pos)[0]),
         "8": lambda: jax.block_until_ready(dec8(params, caches, tok,
                                                 pos)[0])},
        reps=reps)
    rows.append(("sharded_step/decode_1dev", med["1"] * 1e6,
                 f"B={B} cache={DECODE_LEN}"))
    rows.append(("sharded_step/decode_8dev", med["8"] * 1e6,
                 f"B={B} cache={DECODE_LEN} maxdiff={d:.2e} "
                 f"speedup={med['1'] / med['8']:.2f}x"))

    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}

    def loss(eng):
        return lambda p: tfm.loss_fn(eng, cfg, p, batch, ce_chunk=32,
                                     n_q_chunks=4)

    g1 = jax.jit(jax.value_and_grad(loss(e1)))
    g8 = jax.jit(jax.value_and_grad(loss(e8)))
    v1, gr1 = g1(params)
    with hints.use_mesh(mesh):
        v8, gr8 = g8(params)
    dl = abs(float(v1) - float(v8))
    dg = max(jax.tree_util.tree_leaves(jax.tree.map(_maxdiff, gr1, gr8)))
    _gate(dl <= TOL and dg <= TOL,
          f"sharded train diverges: loss diff {dl:.2e}, "
          f"grad maxdiff {dg:.2e} (tol {TOL})")
    med = _median(
        {"1": lambda: jax.block_until_ready(g1(params)[0]),
         "8": lambda: jax.block_until_ready(g8(params)[0])},
        reps=reps)
    rows.append(("sharded_step/train_grad_1dev", med["1"] * 1e6,
                 f"B={B} S={S}"))
    rows.append(("sharded_step/train_grad_8dev", med["8"] * 1e6,
                 f"B={B} S={S} loss_diff={dl:.2e} grad_maxdiff={dg:.2e} "
                 f"speedup={med['1'] / med['8']:.2f}x"))
    return rows


# ---------------------------------------------------------------- serving ---

def _requests():
    rng = np.random.default_rng(7)
    return [Request(rid=i, prompt=list(map(int, rng.integers(1, 500, 4 + i))),
                    max_new=6) for i in range(6)]


def _slot_stream(mesh, backend: str) -> list[tuple[int, ...]]:
    cfg, params, _, _, _ = _setup()
    eng = make_engine(backend, "fp32_strict")
    se = ServingEngine(cfg, params, engine=eng, slots=8, max_len=64,
                       mesh=mesh)
    reqs = _requests()
    for r in reqs:
        se.submit(r)
    for _ in range(200):
        if all(r.done for r in reqs):
            break
        se.step()
    _gate(all(r.done for r in reqs), f"slot engine ({backend}) stalled")
    return [tuple(r.out) for r in reqs]


def _paged_stream(mesh, backend: str) -> list[tuple[int, ...]]:
    cfg, params, _, _, _ = _setup()
    eng = make_engine(backend, "fp32_strict")
    pe = PagedServingEngine(cfg, params, engine=eng, kv_blocks=64,
                            block_size=16, max_len=64, chunk=16,
                            mesh=mesh)
    reqs = _requests()
    for r in reqs:
        pe.submit(r)
    for _ in range(200):
        if all(r.done for r in reqs):
            break
        pe.step()
    _gate(all(r.done for r in reqs), f"paged engine ({backend}) stalled")
    return [tuple(r.out) for r in reqs]


def serving_rows(mesh) -> list[tuple[str, float, str]]:
    """Greedy token streams, slot AND paged engines: sharded_pallas under
    the mesh must be BIT-IDENTICAL to single-device pallas."""
    rows = []
    t0 = time.perf_counter()
    s1 = _slot_stream(None, "pallas")
    s8 = _slot_stream(mesh, "sharded_pallas")
    _gate(s1 == s8, f"slot greedy streams differ: {s1} != {s8}")
    rows.append(("sharded_step/serve_slot_bitwise",
                 (time.perf_counter() - t0) * 1e6,
                 f"requests={len(s1)} tokens={sum(map(len, s1))} "
                 f"bit_identical=True"))
    t0 = time.perf_counter()
    p1 = _paged_stream(None, "pallas")
    p8 = _paged_stream(mesh, "sharded_pallas")
    _gate(p1 == p8, f"paged greedy streams differ: {p1} != {p8}")
    _gate(s1 == p1, "slot and paged streams disagree on the same requests")
    rows.append(("sharded_step/serve_paged_bitwise",
                 (time.perf_counter() - t0) * 1e6,
                 f"requests={len(p1)} tokens={sum(map(len, p1))} "
                 f"bit_identical=True"))
    return rows


# ------------------------------------------------------- collective audit ---

def collective_rows(mesh) -> list[tuple[str, float, str]]:
    """Lower the two sharded attention formulations and audit collectives
    (analysis/diagnose.count_collectives / full_kv_gathers)."""
    rows = []
    ks = jax.random.split(jax.random.PRNGKey(2), 3)

    # batch-sharded prefill attention: zero collectives expected.
    q = jax.random.normal(ks[0], (B, S, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, 2, 32), jnp.float32)

    def att(q, k, v):
        return sharded.attention(q, k, v, None, None, causal=True)

    with hints.use_mesh(mesh):
        text = jax.jit(att).lower(q, k, v).compile().as_text()
    counts = diagnose.count_collectives(text)
    _gate(not counts,
          f"batch-sharded attention emitted collectives: {counts}")
    rows.append(("sharded_step/collectives_batch_sharded", 0.0,
                 f"counts={json.dumps(counts)} (zero expected)"))

    # seq-split decode attention: the (o, lse) partial all-gather is
    # expected — but it must be Sq-sized, never full-KV-sized.
    bq, sq, skv = 2, 1, DECODE_LEN
    q2 = jax.random.normal(ks[0], (bq, sq, 4, 32), jnp.float32)
    k2 = jax.random.normal(ks[1], (bq, skv, 2, 32), jnp.float32)
    v2 = jax.random.normal(ks[2], (bq, skv, 2, 32), jnp.float32)

    def att2(q, k, v):
        return sharded.attention(q, k, v, jnp.full((bq,), 300, jnp.int32),
                                 None, causal=True)

    with hints.use_mesh(mesh):
        text2 = jax.jit(att2).lower(q2, k2, v2).compile().as_text()
    counts2 = diagnose.count_collectives(text2)
    _gate(counts2.get("all-gather", 0) >= 1,
          f"seq-split attention lost its partial merge: {counts2}")
    kv_elems = bq * skv * 2 * 32
    bad = diagnose.full_kv_gathers(text2, kv_elems)
    bad += diagnose.full_kv_gathers(text, B * S * 2 * 32)
    _gate(not bad, "full-KV all-gather in a sharded attention trace:\n"
          + "\n".join(bad))
    rows.append(("sharded_step/collectives_seq_split", 0.0,
                 f"counts={json.dumps(counts2)} "
                 f"full_kv_gathers=0 (kv_elems={kv_elems})"))
    return rows


# ----------------------------------------------------- per-shard tile plans ---

def tile_plan_rows(mesh) -> list[tuple[str, float, str]]:
    """The sharded prefill must resolve attention block plans from the
    PER-SHARD shapes (batch 1), never the global batch-8 problem."""
    cfg, params, toks, _, e8 = _setup()
    backends.clear_tile_cache()
    pre8 = jax.jit(make_prefill_step(e8, cfg))
    with hints.use_mesh(mesh):
        jax.block_until_ready(pre8(params, {"tokens": toks})[0])
    att_keys = [key for key in backends.tile_plans()
                if key[0] == "attention"]
    shard_batches = {key[1][0][0] for key in att_keys}
    _gate(bool(att_keys), "sharded prefill resolved no attention tile keys")
    _gate(shard_batches == {B // 8},
          f"attention tile keys are not per-shard: batches {shard_batches} "
          f"!= {{{B // 8}}} (global batch {B} leaked into a key)")
    return [("sharded_step/per_shard_tile_plan_keys", 0.0,
             f"attention_keys={len(att_keys)} "
             f"per_shard_batch={sorted(shard_batches)}")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="parity + serving-bitwise + collective-audit + "
                         "per-shard tile-plan gates (CI)")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    if jax.device_count() < 8:
        print(f"sharded_step/skipped,0.0,device count didn't take "
              f"(found {jax.device_count()}; set XLA_FLAGS="
              f"--xla_force_host_platform_device_count=8 before jax init)")
        return 0
    mesh = data_mesh()
    rows = []
    rows += tile_plan_rows(mesh)       # first: needs a clean plan cache
    rows += parity_rows(mesh, reps=1 if args.smoke else 3)
    rows += collective_rows(mesh)
    rows += serving_rows(mesh)
    for row, us, derived in rows:
        print(f"{row},{us:.1f},{derived}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
