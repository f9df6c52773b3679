"""Run the system's main paths once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: the two phases below
    python chip_smoke.py --chips 4   # four chips: sharded LM serving only

One chip, two phases, each through the entry points a user calls:

  cnn  Darknet-19 @224 (20.9M params, published widths) on the pallas
       engine under fp32_strict, served by `CNNServingEngine` over a
       bucketed `CompileCache` (1, 2, 4, 8): a seeded ragged stream of 24
       images in bursts of 1 to 9.  Every result is compared with the
       plain `xla`-backend forward of the same params at highest matmul
       precision; each bucket must trace once and dispatch 19 pallas conv2d.
  lm   qwen2-0.5b at its published widths (24 layers, d 896, 14/2 heads,
       d_ff 4864, vocab 151936; random weights from the seed) on the pallas
       engine, served by `PagedServingEngine`: 8 seeded prompts of 16-512
       tokens, 16 new tokens each, so that chunked prefill (flash kernel
       with per-sequence kv_len) and split-KV decode both dispatch.  Each
       request's prefill last-token logits from the pallas forward are
       compared with the `xla` forward, and every served token must be
       within the tolerance of the reference's best logit at its position.

`--chips 4` runs only qwen2-0.5b through `PagedServingEngine(mesh=...)` on
the `sharded_pallas` backend over a 4-chip ("data",) mesh, compared with
the same requests served on one chip in this process: the token streams
must be equal and the sharded dispatch must see the mesh.

Everything runs in this one process.  Earlier lines report compile seconds,
steady wall seconds and each comparison; the last line of stdout is one
JSON object, ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Without a TPU, or when any check fails, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Comparisons against the reference are relative to the reference's
# largest magnitude.  fp32 accumulation in a different order stays orders
# of magnitude below this; a single bf16 pass (2^-8 per product) does not.
RTOL = 1e-3
CNN_BUCKETS = (1, 2, 4, 8)
CNN_REQUESTS = 24
LM_REQUESTS = 8
LM_NEW_TOKENS = 16
LM_MAX_PROMPT = 512


def _log(msg: str) -> None:
    print(msg, flush=True)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ------------------------------------------------------------------ cnn ---

def cnn_phase(seed: int) -> None:
    from repro.configs.darknet_ref import DARKNET19_CFG
    from repro.core import make_engine
    from repro.core.darknet.network import Network
    from repro.serve.frontend import CNNServingEngine, ImageRequest

    net = Network(DARKNET19_CFG, make_engine("pallas", "fp32_strict"))
    params = net.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    images = rng.standard_normal((CNN_REQUESTS,) + net.in_shape,
                                 dtype=np.float32)
    bursts = []
    while sum(bursts) < CNN_REQUESTS:
        bursts.append(min(int(rng.integers(1, 10)),
                          CNN_REQUESTS - sum(bursts)))

    t0 = time.perf_counter()
    cache = net.compile_cache(params, buckets=CNN_BUCKETS).warmup()
    compile_s = time.perf_counter() - t0
    server = CNNServingEngine(cache)
    reqs = [ImageRequest(rid=i, image=images[i]) for i in range(CNN_REQUESTS)]
    t0 = time.perf_counter()
    it = iter(reqs)
    for n in bursts:
        for _ in range(n):
            server.submit(next(it))
        while server.step():
            pass
    steady_s = time.perf_counter() - t0

    _check(all(r.done for r in reqs), "cnn: not every request completed")
    got = np.stack([r.result for r in reqs])
    _check(bool(np.isfinite(got).all()), "cnn: non-finite outputs")
    stats = cache.stats()
    _check(stats["traces"] == len(CNN_BUCKETS),
           f"cnn: {stats['traces']} traces for {len(CNN_BUCKETS)} buckets")
    for b in CNN_BUCKETS:
        cn = cache.get(b)
        _check(cn.trace_count == 1, f"cnn: bucket {b} traced "
               f"{cn.trace_count} times")
        _check(cn.op_counts == {("pallas", "conv2d"): 19},
               f"cnn: bucket {b} dispatched {cn.op_counts}")

    ref_net = Network(DARKNET19_CFG, make_engine("xla", "fp32_strict"))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(ref_net.apply)(params, images))
    _check(got.shape == ref.shape, f"cnn: shape {got.shape} != {ref.shape}")
    err = float(np.max(np.abs(got - ref)))
    tol = RTOL * float(np.max(np.abs(ref)))
    _log(f"cnn: darknet19 {net.in_shape} pallas fp32_strict, "
         f"{CNN_REQUESTS} requests "
         f"in bursts {bursts}; compile_s={compile_s:.3f} "
         f"(buckets {CNN_BUCKETS}, warm call included) "
         f"steady_wall_s={steady_s:.3f}; dispatches {stats['dispatches']}")
    _log(f"cnn: vs xla reference (highest precision): max_abs_err={err:.3e} "
         f"tol={tol:.3e} (rtol {RTOL} of max|ref|={np.max(np.abs(ref)):.3e})")
    _check(err <= tol, f"cnn: max error {err} above tolerance {tol}")


# ------------------------------------------------------------------- lm ---

def _lm_setup(seed: int):
    from repro.configs.base import get_arch
    from repro.models import transformer as tfm

    cfg = get_arch("qwen2-0.5b")
    params = jax.jit(tfm.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed + 1)
    lens = [16, LM_MAX_PROMPT] + [int(n) for n in rng.integers(
        17, LM_MAX_PROMPT, size=LM_REQUESTS - 2)]
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, size=n)]
               for n in lens]
    return cfg, params, prompts


def _serve(cfg, params, prompts, engine, mesh=None):
    """Serve every prompt through a fresh `PagedServingEngine`, twice: the
    first pass compiles, the second is the steady pass.  Returns (token
    streams, first-pass seconds, second-pass seconds, server)."""
    from repro.serve.engine import Request
    from repro.serve.scheduler import PagedServingEngine

    chunk, block = 64, 16
    max_len = LM_MAX_PROMPT + LM_NEW_TOKENS
    # Two block-table widths: 256 rows (short requests) and the longest
    # request's chunk-rounded extent.  Both reach the split-KV decode.
    top = -(-max_len // chunk) * chunk // block
    server = PagedServingEngine(
        cfg, params, engine=engine, kv_blocks=LM_REQUESTS * top + 1,
        block_size=block, max_len=max_len, chunk=chunk, prefill_budget=512,
        batch_buckets=(LM_REQUESTS,), block_buckets=(256 // block, top),
        mesh=mesh)
    streams, walls = [], []
    for _ in range(2):
        reqs = [Request(rid=i, prompt=p, max_new=LM_NEW_TOKENS)
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        server.run(reqs)
        walls.append(time.perf_counter() - t0)
        _check(all(r.done and len(r.out) == LM_NEW_TOKENS for r in reqs),
               "lm: not every request completed with all its tokens")
        streams.append([list(r.out) for r in reqs])
    _check(streams[0] == streams[1], "lm: a second serving pass of the "
           "same requests gave different tokens")
    return streams[0], walls[0], walls[1], server


def _teacher_forced_logits(engine, cfg, params, prompts, streams):
    """Logits at each generated position (the prompt's last token and the
    first LM_NEW_TOKENS - 1 generated ones) from one full-sequence forward
    over prompt + generated tokens, right-padded to a common length
    (causal: padding never reaches an earlier position)."""
    from repro.models import transformer as tfm
    from repro.models.common import lm_head_logits

    width = LM_MAX_PROMPT + LM_NEW_TOKENS
    toks = np.zeros((len(prompts), width), np.int32)
    pos = np.zeros((len(prompts), LM_NEW_TOKENS), np.int32)
    for i, (p, out) in enumerate(zip(prompts, streams)):
        seq = p + out[:-1]
        toks[i, :len(seq)] = seq
        pos[i] = np.arange(len(p) - 1, len(p) - 1 + LM_NEW_TOKENS)

    def fwd(params, toks, pos):
        h, _ = tfm.forward_hidden(engine, cfg, params, tokens=toks,
                                  remat=False)
        h = jnp.take_along_axis(h, pos[:, :, None], axis=1)
        return lm_head_logits(engine, h, tfm.head_weight(params, cfg),
                              vocab_real=cfg.vocab_size)

    return np.asarray(jax.jit(fwd)(params, toks, pos))[..., :cfg.vocab_size]


def lm_phase(seed: int) -> None:
    from repro.core import backends, make_engine
    from repro.kernels import ops

    cfg, params, prompts = _lm_setup(seed)
    engine = make_engine("pallas", "fp32_strict")
    log_mark = backends.dispatch_log_size()
    streams, first_s, steady_s, server = _serve(cfg, params, prompts, engine)
    shapes = [r["shapes"] for r in backends.dispatch_log()[log_mark:]
              if r["op"] == "attention" and r["backend"] == "pallas"]
    decode = [s for s in shapes
              if ops.use_decode_formulation(s[0][1], s[1][1])]
    _check(decode and len(decode) < len(shapes),
           f"lm: attention dispatches {shapes} lack a chunked-prefill or a "
           f"split-KV decode shape")
    tokens = LM_REQUESTS * LM_NEW_TOKENS
    _log(f"lm: qwen2-0.5b pallas fp32_strict paged serving, prompt lengths "
         f"{[len(p) for p in prompts]}, {LM_NEW_TOKENS} new tokens each; "
         f"first_pass_s={first_s:.3f} (compiles included) "
         f"steady_wall_s={steady_s:.3f} for {tokens} tokens; "
         f"traces={server.stats()['compile']['traces']}; attention shapes "
         f"prefill={sorted(set(shapes) - set(decode))} "
         f"split-kv decode={sorted(set(decode))}")

    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = _teacher_forced_logits(make_engine("xla", "fp32_strict"), cfg,
                                     params, prompts, streams)
    got = _teacher_forced_logits(engine, cfg, params, prompts, streams)
    _log(f"lm: reference forwards (xla and pallas, {len(prompts)}x"
         f"{LM_MAX_PROMPT + LM_NEW_TOKENS} tokens) took "
         f"{time.perf_counter() - t0:.3f}s")
    for i, out in enumerate(streams):
        scale = float(np.max(np.abs(ref[i, 0])))
        tol = RTOL * scale
        err = float(np.max(np.abs(got[i, 0] - ref[i, 0])))
        # A served token must be the reference's best, up to the tolerance:
        # a near-tie may break either way between two fp32 formulations.
        best = ref[i].max(axis=-1)
        picked = ref[i, np.arange(LM_NEW_TOKENS), out]
        gap = float(np.max(best - picked))
        _log(f"lm: request {i} (prompt {len(prompts[i])}): prefill "
             f"last-token logits max_abs_err={err:.3e} tol={tol:.3e}; "
             f"served tokens' worst gap to the reference best logit "
             f"{gap:.3e}")
        _check(bool(np.isfinite(got[i]).all()), f"lm: request {i} non-finite")
        _check(err <= tol, f"lm: request {i} logits error {err} > {tol}")
        _check(gap <= tol, f"lm: request {i} served a token {gap} below "
               f"the reference best logit (tolerance {tol})")


# ---------------------------------------------------------- four chips ---

def sharded_lm_phase(seed: int) -> None:
    from repro.core import make_engine
    from repro.launch.mesh import make_mesh
    from repro.sharding import hints

    n = len(jax.devices())
    _check(n == 4, f"--chips 4 needs 4 devices, JAX sees {n}")
    cfg, params, prompts = _lm_setup(seed)
    engine = make_engine("sharded_pallas", "fp32_strict")
    mesh = make_mesh((4,), ("data",))

    seen = []
    physical_mesh = hints.physical_mesh

    def recording_physical_mesh():
        m = physical_mesh()
        seen.append(None if m is None else hints.mesh_topology(m))
        return m

    hints.physical_mesh = recording_physical_mesh
    try:
        sharded, first4, steady4, _ = _serve(cfg, params, prompts, engine,
                                             mesh=mesh)
    finally:
        hints.physical_mesh = physical_mesh
    _check(bool(seen) and all(t == (("data", 4),) for t in seen),
           f"lm x4: sharded dispatch saw meshes {sorted(set(seen))}")
    single, first1, steady1, _ = _serve(cfg, params, prompts, engine)
    _log(f"lm x4: qwen2-0.5b sharded_pallas on mesh {hints.mesh_topology(mesh)}"
         f": first_pass_s={first4:.3f} steady_wall_s={steady4:.3f}; one "
         f"chip: first_pass_s={first1:.3f} steady_wall_s={steady1:.3f}; "
         f"mesh seen in {len(seen)} dispatch traces")
    same = sum(a == b for a, b in zip(sharded, single))
    _log(f"lm x4: token streams equal to one chip for {same}/{len(single)} "
         f"requests")
    _check(sharded == single, "lm x4: sharded token streams differ from "
           "the one-chip streams")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 1
    from repro.core import enable_persistent_cache
    _log(f"compile cache: {enable_persistent_cache()}")
    _log(f"device: {devices[0].device_kind} x{len(devices)}, "
         f"jax {jax.__version__}")
    if args.chips == 4:
        sharded_lm_phase(args.seed)
    else:
        cnn_phase(args.seed)
        lm_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
