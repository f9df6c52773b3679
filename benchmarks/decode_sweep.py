"""Split-KV vs einsum decode head-to-head (the decode formulation gate).

Times the SAME decode-shaped attention problem (Sq <= 8 against a deep KV
cache) three ways: the xla einsum formulation (softmax materialized per
step — the pre-split baseline), the one-pass forward flash kernel (one
program per batch*head streaming the whole extent), and the split-KV
flash-decoding kernel (kernels/flash_decode.py: n_splits partial (o, lse)
programs per batch*head + logsumexp merge).  On a real accelerator the
split formulation is the only one that saturates the chip at long kv_len;
in CPU interpret mode the wall-clock ratio is reported informationally
(interpret-mode Pallas emulation is not representative) while the
--smoke gate asserts the properties that ARE machine-independent:

  * registry dispatch: a decode-shaped `engine.attention` on the pallas
    backend selects the split-KV formulation and resolves its
    (bk_split, n_splits) tiles under the lazy "attention_decode"
    tile-plan key;
  * numerical parity of all three formulations on the same problem;
  * greedy token BIT-parity: the fixed-slot serving engine (whose decode
    cache extent >= 256 rows puts every decode step on the split path)
    and the paged engine replay the same request stream through a hybrid
    backend (xla GEMMs + the pallas attention op) and must emit exactly
    the tokens the all-xla engines emit.

    PYTHONPATH=src python benchmarks/decode_sweep.py           # full rows
    PYTHONPATH=src python benchmarks/decode_sweep.py --smoke   # CI gate
"""
from __future__ import annotations

import argparse
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_arch, reduced
from repro.core import backends, make_engine, register_backend
from repro.kernels import ops as kernel_ops
from repro.models import transformer as tfm
from repro.serve.engine import ServingEngine
from repro.serve.scheduler import PagedServingEngine

from lm_serving import BLOCK_SIZE, make_stream

# Decode problems (b, sq, skv, h, kv, d): GQA one-token and chunked decode
# at deepening caches, plus the MLA absorbed-latent MQA shape
# (deepseek-v2-lite: one shared kv "head" of width lora + rope_d = 576).
PROBLEMS = [
    (4, 1, 512, 8, 2, 64),
    (4, 1, 2048, 8, 2, 64),
    (2, 4, 1024, 8, 2, 64),
    (2, 1, 1024, 16, 1, 576),
]


def _interleaved_median(fns: dict, reps: int = 5) -> dict:
    for f in fns.values():
        f()                                    # warmup / compile
    t = {n: [] for n in fns}
    for _ in range(reps):
        for n, f in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(f())
            t[n].append(time.perf_counter() - t0)
    return {n: statistics.median(v) for n, v in t.items()}


def _mk(b, sq, skv, h, kv, d, seed=0):
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (b, sq, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, skv, kv, d), jnp.float32)
    v = jax.random.normal(kv_, (b, skv, kv, d), jnp.float32)
    return q, k, v


def formulation_headtohead(reps: int = 5):
    """Rows + max cross-formulation error per decode problem."""
    rows = []
    worst = 0.0
    xla_eng = make_engine("xla", "fp32_strict")
    for b, sq, skv, h, kv, d in PROBLEMS:
        q, k, v = _mk(b, sq, skv, h, kv, d, seed=skv + h)
        kvl = jnp.full((b,), skv, jnp.int32)
        bk, ns = kernel_ops.default_attention_decode_blocks(
            b, sq, skv, h, kv, d, jnp.float32)
        einsum = jax.jit(lambda q, k, v, kvl: xla_eng.attention(
            q, k, v, causal=True, kv_len=kvl))
        onepass = lambda: kernel_ops.attention(q, k, v, kvl, causal=True,
                                               bq=8, bk=bk)
        split = lambda: kernel_ops.attention_decode(
            q, k, v, kvl, causal=True, bk_split=bk, n_splits=ns)
        t = _interleaved_median(
            {"einsum": lambda: einsum(q, k, v, kvl),
             "onepass": onepass, "split": split}, reps=reps)
        err = float(jnp.max(jnp.abs(split() - einsum(q, k, v, kvl))))
        worst = max(worst, err)
        rows.append((
            f"decode_sweep/b{b}q{sq}_kv{skv}_h{h}g{h // kv}_d{d}",
            t["split"] * 1e6,
            f"tiles={bk}x{ns} einsum={t['einsum'] * 1e6:.0f}us "
            f"onepass={t['onepass'] * 1e6:.0f}us "
            f"split={t['split'] * 1e6:.0f}us "
            f"einsum/split={t['einsum'] / t['split']:.2f}x "
            f"onepass/split={t['onepass'] / t['split']:.2f}x "
            f"max_err={err:.1e}"))
    return rows, worst


def run():
    rows, _ = formulation_headtohead()
    return rows


def _hybrid_backend(name: str):
    """xla GEMMs + the pallas attention op: serving traffic rides the
    kernel formulations while everything else stays compiled XLA (the
    lm_step train-flash idiom)."""
    pallas = backends.get_backend("pallas")
    xla = backends.get_backend("xla")
    register_backend(name, dict(xla.ops, attention=pallas.op("attention")),
                     tile_picker=pallas.tile_picker, overwrite=True)


def smoke():
    """CI gate: split-formulation dispatch + parity + greedy token
    bit-parity through both serving engines."""
    # -- registry selection: decode-shaped dispatch resolves the lazy
    # attention_decode key and matches the einsum formulation.
    b, sq, skv, h, kv, d = PROBLEMS[0]
    q, k, v = _mk(b, sq, skv, h, kv, d, seed=1)
    kvl = jnp.full((b,), skv - 5, jnp.int32)
    snap = backends.dispatch_counts()
    got = make_engine("pallas", "fp32_strict").attention(
        q, k, v, causal=True, kv_len=kvl)
    n_att = backends.counts_since(snap).get(("pallas", "attention"), 0)
    if n_att != 1:
        raise SystemExit(f"FAIL: decode dispatch count {n_att} != 1")
    dec_keys = [k2 for k2 in backends.tile_plans()
                if k2[0] == "attention_decode"]
    if not dec_keys:
        raise SystemExit("FAIL: decode-shaped pallas dispatch resolved no "
                         "attention_decode tile-plan key (split-KV "
                         "formulation not selected)")
    want = make_engine("xla", "fp32_strict").attention(
        q, k, v, causal=True, kv_len=kvl)
    err = float(jnp.max(jnp.abs(got - want)))
    if not np.isfinite(err) or err > 2e-4:
        raise SystemExit(f"FAIL: split-vs-einsum parity {err:.2e} > 2e-4")
    rows, ferr = formulation_headtohead(reps=1)
    if ferr > 2e-4:
        raise SystemExit(f"FAIL: formulation head-to-head parity "
                         f"{ferr:.2e} > 2e-4")
    rows.append(("decode_sweep/smoke_registry_selection", 0.0,
                 f"dispatches={n_att} decode_keys={len(dec_keys)} "
                 f"max_err={err:.1e}"))

    # -- greedy token bit-parity: the slot engine's static cache extent
    # (max_len >= 256 rows) puts EVERY decode step on the split path; the
    # paged engine replays the same stream.  Hybrid tokens must equal the
    # all-xla tokens bit-for-bit.
    cfg = reduced(get_arch("qwen2-0.5b"))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    slots, max_len, n_req = 2, 272, 4
    assert max_len >= kernel_ops.DECODE_MIN_SKV and max_len % BLOCK_SIZE == 0
    stream_kw = dict(vocab=cfg.vocab_size, prompt_lo=2, prompt_hi=10,
                     new_lo=3, new_hi=8)

    reqs_ref = make_stream(n_req, **stream_kw)
    ServingEngine(cfg, params, engine=make_engine("xla", "fp32_strict"),
                  slots=slots, max_len=max_len).run(reqs_ref)

    _hybrid_backend("decode-flash")
    try:
        feng = make_engine("decode-flash", "fp32_strict")
        snap = backends.dispatch_counts()
        reqs_slot = make_stream(n_req, **stream_kw)
        ServingEngine(cfg, params, engine=feng, slots=slots,
                      max_len=max_len).run(reqs_slot)
        n_att = backends.counts_since(snap).get(
            ("decode-flash", "attention"), 0)
        if n_att < 1:
            raise SystemExit("FAIL: hybrid slot engine dispatched no "
                             "attention op")
        for a, b_ in zip(reqs_ref, reqs_slot):
            if a.out != b_.out:
                raise SystemExit(
                    f"FAIL: slot token stream diverged on rid={a.rid}: "
                    f"xla={a.out} split-kv={b_.out}")
        reqs_paged = make_stream(n_req, **stream_kw)
        PagedServingEngine(
            cfg, params, engine=feng,
            kv_blocks=slots * max_len // BLOCK_SIZE,
            block_size=BLOCK_SIZE, max_len=max_len, chunk=8,
            prefill_budget=32).run(reqs_paged)
        for a, b_ in zip(reqs_ref, reqs_paged):
            if a.out != b_.out:
                raise SystemExit(
                    f"FAIL: paged token stream diverged on rid={a.rid}: "
                    f"xla={a.out} split-kv={b_.out}")
    finally:
        backends.unregister_backend("decode-flash")
    rows.append(("decode_sweep/smoke_token_parity", 0.0,
                 f"slot+paged bit-parity reqs={n_req} slots={slots} "
                 f"max_len={max_len} attention_dispatches={n_att}"))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="registry-selection, parity and serving "
                         "token-bit-parity asserts (CI gate)")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    for row, us, derived in (smoke() if args.smoke else run()):
        print(f"{row},{us:.1f},{derived}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
