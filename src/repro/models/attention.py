"""Attention: GQA and MLA (DeepSeek), dispatching the registry op at every
scale.

Every path — train, prefill, decode, MLA absorbed decode — dispatches the
registry `attention` op UNCONDITIONALLY; distribution is the backend's
job, not this module's (the `sharded_pallas` backend shard_maps the
kernels over the installed mesh, see core/shard_backend.py and
kernels/sharded.py; plain `xla` remains the GSPMD formulation a 512-device
abstract-mesh dry-run lowers).  The op is grouped-KV native: the compact
(B, S, KV, hd) K/V is the operand and the kernel reads the shared kv-head
per query-head group, so no H-broadcast is ever materialized.  MLA
absorbed decode rides the same op as multi-query attention over the
latent cache.  Decode-shaped dispatches (short query, deep KV) select the
split-KV flash-decoding formulation inside the backend
(kernels/flash_decode.py).

`blockwise_attention` — the streaming-softmax jnp formulation that never
materializes the S×S score matrix (the same "operands stream through
on-chip memory, accumulator never leaves" structure as the paper's GEMM
engine) — survives as the A/B ORACLE: ``kernel_attention=False`` forces it
for baseline comparisons in tests/benchmarks; no model path requires it.

Sharding modes (chosen per arch by sharding/policy.py) apply to that
oracle formulation:
  heads : KV-head-parallel — zero attention comm, used when n_kv_heads
          divides the TP axis.
  seq   : query-sequence-parallel — uniform utilization for small-KV GQA
          (kv=2..10), costs one K/V all-gather per layer (GSPMD inserts it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ComputeEngine
from repro.models.common import norm_init, rope_apply
from repro.sharding import hints

_NEG = -1e30


# ------------------------------------------------- blockwise core (no S²) ---

def blockwise_attention(engine: ComputeEngine, q, k, v, *, causal: bool,
                        n_q_chunks: int = 8, kv_chunk: int = 1024,
                        shard_mode: str = "seq"):
    """q: (B, Sq, KV, G, Dh); k, v: (B, Skv, KV, Dh) -> (B, Sq, KV, G, Dh).

    Outer loop: static (unrolled) query chunks, each with a *statically
    trimmed* causal KV extent — compiled FLOPs ≈ (1/2 + 1/2n)·S² instead of
    S² (exactness of the useful-FLOPs ratio matters for §Roofline).
    Inner loop: lax.scan over KV blocks carrying (m, l, acc) in fp32.
    """
    B, Sq, KV, G, Dh = q.shape
    Skv = k.shape[1]
    Dv = v.shape[-1]  # may differ from Dh (MLA: qk 192, v 128)
    q_offset = Skv - Sq  # right-aligned (prefill continuation safe)
    qc = max(Sq // n_q_chunks, 1)
    n_q = Sq // qc
    assert n_q * qc == Sq, (Sq, qc)
    sm = 1.0 / (Dh ** 0.5)

    def q_shard(x):
        if shard_mode == "heads":
            return hints.shard(x, "dp", None, "model", None, None)
        return hints.shard(x, "dp", "model", None, None, None)

    def kv_shard(x):
        if shard_mode == "heads":
            return hints.shard(x, "dp", None, "model", None)
        return hints.shard(x, "dp", None, None, None)  # replicated KV

    q = q_shard(q)
    k = kv_shard(k)
    v = kv_shard(v)

    outs = []
    for i in range(n_q):
        qi = jax.lax.slice_in_dim(q, i * qc, (i + 1) * qc, axis=1)
        extent = q_offset + (i + 1) * qc if causal else Skv
        # A negative q_offset (Sq > Skv) can drive early chunks' causal
        # extent to <= 0: no keys are live.  Clamp the SLICE geometry to one
        # key and let the `k_idx < extent` mask (against the raw extent)
        # invalidate everything, so those rows come out exact 0 below.
        kvc = min(kv_chunk, max(extent, 1))
        n_kv = max(-(-extent // kvc), 1)          # ceil, >= 1

        def body(carry, j, qi=qi, kvc=kvc, i=i, extent=extent):
            m, l, acc = carry
            # dynamic_slice clamps an out-of-range start into
            # [0, Skv - kvc]; mirror that clamp when deriving key
            # positions, or the final partial chunk scores its keys at
            # the unclamped indices (wrong mask, keys attended twice).
            start = jnp.minimum(j * kvc, Skv - kvc)
            kj = jax.lax.dynamic_slice_in_dim(k, start, kvc, axis=1)
            vj = jax.lax.dynamic_slice_in_dim(v, start, kvc, axis=1)
            s = engine.einsum("bqhgd,bkhd->bhgqk", qi, kj,
                              out_dtype=jnp.float32) * sm
            q_idx = (q_offset + i * qc
                     + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3))
            k_idx = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 4)
            # A clamped final chunk re-reads keys the previous chunk
            # already scored; the lower bound keeps each key attributed to
            # exactly one logical window [j*kvc, (j+1)*kvc).
            valid = (k_idx >= j * kvc) & (k_idx < extent)
            if causal:
                valid = valid & (k_idx <= q_idx)
            s = jnp.where(valid, s, _NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            # Fully-masked rows have m_new == _NEG, where exp(s - m_new)
            # would be 1 at every masked position; zero them so l stays 0
            # and the final normalization emits exact 0 rows.
            p = jnp.where(s > _NEG * 0.5, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + engine.einsum(
                "bhgqk,bkhd->bhgqd", p, vj, out_dtype=jnp.float32)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((B, KV, G, qc), _NEG, jnp.float32),
                jnp.zeros((B, KV, G, qc), jnp.float32),
                jnp.zeros((B, KV, G, qc, Dv), jnp.float32))
        if n_kv == 1:
            (m, l, acc), _ = body(init, 0)
        else:
            (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(n_kv))
        out = (acc / jnp.maximum(l, 1e-37)[..., None])
        outs.append(out.transpose(0, 3, 1, 2, 4))     # (B, qc, KV, G, Dh)
    y = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return q_shard(y).astype(q.dtype)


# ------------------------------------------------------------- GQA layer ---

def gqa_init(key, cfg):
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    sd = lambda fan_in: 1.0 / (fan_in ** 0.5)
    p = {
        "wq": jax.random.normal(ks[0], (D, H * hd), jnp.float32) * sd(D),
        "wk": jax.random.normal(ks[1], (D, KV * hd), jnp.float32) * sd(D),
        "wv": jax.random.normal(ks[2], (D, KV * hd), jnp.float32) * sd(D),
        "wo": jax.random.normal(ks[3], (H * hd, D), jnp.float32) * sd(H * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), jnp.float32)
        p["bk"] = jnp.zeros((KV * hd,), jnp.float32)
        p["bv"] = jnp.zeros((KV * hd,), jnp.float32)
    return p


def gqa_forward(engine: ComputeEngine, p, x, cos, sin, cfg, *,
                shard_mode: str = "seq", n_q_chunks: int = 8,
                return_kv: bool = False, kernel_attention: bool = True):
    """x: (B, S, D) -> (B, S, D).  Full-sequence (train / prefill).

    With ``kernel_attention`` (the default), attention dispatches the
    registry `attention` op at EVERY scale — the kernel-backed path, for
    training AND inference: the flash kernel carries a custom VJP, so
    jax.grad flows through the same numerics serving runs, and the backend
    decides distribution (`sharded_pallas` shard_maps over the installed
    mesh).  ``kernel_attention=False`` forces the blockwise jnp
    formulation (the A/B oracle).
    """
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = engine.matmul(x, p["wq"], shift=p.get("bq"))
    k = engine.matmul(x, p["wk"], shift=p.get("bk"))
    v = engine.matmul(x, p["wv"], shift=p.get("bv"))
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cos is not None:
        q = rope_apply(q, cos, sin)
        k = rope_apply(k, cos, sin)
    if kernel_attention:
        # The kernel-backed registry `attention` op, grouped-KV native: the
        # compact (B, S, KV, hd) K/V go straight to the op, which reads the
        # shared kv-head per query-head group (same kv*G+g head order as
        # the grouped reshape below).  No H-broadcast anywhere; the
        # backend decides distribution.
        y = engine.attention(q, k, v, causal=cfg.causal)
    else:
        # The blockwise jnp A/B oracle (heads- or sequence-parallel under
        # GSPMD per shard_mode).
        qg = q.reshape(B, S, KV, H // KV, hd)
        y = blockwise_attention(engine, qg, k, v, causal=cfg.causal,
                                n_q_chunks=n_q_chunks, shard_mode=shard_mode)
    y = y.reshape(B, S, H * hd)
    y = hints.shard(y, "dp", None, "model")
    out = engine.matmul(y, p["wo"])
    if return_kv:
        return out, {"k": k, "v": v}
    return out


def cache_write(cache, new, pos, axis: int = 1):
    """Write a one-token entry at pos; pos may be scalar or per-batch (B,)
    (continuous batching: each slot at its own position)."""
    new = new.astype(cache.dtype)
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache, new, pos,
                                                   axis=axis)
    return jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(
            c, n, p, axis=axis - 1))(cache, new, pos)


def gqa_decode(engine: ComputeEngine, p, x, cache, pos, cos, sin, cfg):
    """Decode a chunk of C new tokens against a sequence-sharded KV cache
    (C == 1 is plain one-token decode; C > 1 is a chunked-prefill step).

    x: (B, C, D); cache: {"k","v"}: (B, S_max, KV, hd) with S_max sharded
    over 'model'; pos: scalar int, or (B,) per-slot/sequence START
    positions — the chunk's tokens occupy [pos, pos + C).
    Returns (y, cache').

    Attention dispatches the grouped registry `attention` op at every
    scale (compact KV operand, ``kv_len = pos + C`` masks unwritten cache
    rows; for C > 1 causal right-alignment against that live extent keeps
    causality between the chunk's own tokens — the PR-4 chunked-prefill
    semantics).  The backend decides distribution: `sharded_pallas`
    batch-shards decode or sequence-splits a deep cache into per-device
    partial (o, lse) spans merged by the flash-decoding combine
    (kernels/sharded.py); the plain `xla` formulation lowers to partial
    reductions + all-reduce under a GSPMD mesh.
    """
    B, C, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = engine.matmul(x, p["wq"], shift=p.get("bq")).reshape(B, C, H, hd)
    k = engine.matmul(x, p["wk"], shift=p.get("bk")).reshape(B, C, KV, hd)
    v = engine.matmul(x, p["wv"], shift=p.get("bv")).reshape(B, C, KV, hd)
    if cos is not None:
        q = rope_apply(q, cos, sin)
        k = rope_apply(k, cos, sin)
    ck = cache_write(cache["k"], k, pos)
    cv = cache_write(cache["v"], v, pos)
    ck = hints.shard(ck, "dp", "model", None, None)
    cv = hints.shard(cv, "dp", "model", None, None)
    y = engine.attention(q.astype(ck.dtype), ck, cv, causal=C > 1,
                         kv_len=pos + C)
    y = y.reshape(B, C, H * hd).astype(x.dtype)
    return engine.matmul(y, p["wo"]), {"k": ck, "v": cv}


# ------------------------------------------------------------- MLA layer ---

def mla_init(key, cfg):
    D, H = cfg.d_model, cfg.n_heads
    nope, rope_d = cfg.qk_nope_dim, cfg.qk_rope_dim
    lora, vd = cfg.kv_lora_rank, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    sd = lambda fan_in: 1.0 / (fan_in ** 0.5)
    return {
        "wq": jax.random.normal(ks[0], (D, H * (nope + rope_d)),
                                jnp.float32) * sd(D),
        "w_dkv": jax.random.normal(ks[1], (D, lora + rope_d),
                                   jnp.float32) * sd(D),
        "kv_norm": norm_init("rms", lora),
        "w_uk": jax.random.normal(ks[2], (lora, H * nope),
                                  jnp.float32) * sd(lora),
        "w_uv": jax.random.normal(ks[3], (lora, H * vd),
                                  jnp.float32) * sd(lora),
        "wo": jax.random.normal(ks[4], (H * vd, D), jnp.float32) * sd(H * vd),
    }


def _mla_split(cfg):
    return (cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.kv_lora_rank,
            cfg.v_head_dim, cfg.n_heads)


def mla_forward(engine: ComputeEngine, p, x, cos, sin, cfg, *,
                n_q_chunks: int = 8, return_cache: bool = False,
                kernel_attention: bool = True):
    """MLA prefill/train: materialize per-head K/V from the latent.

    With ``kernel_attention`` (the default) the materialized-KV attention
    dispatches the registry `attention` op in the MHA layout (KV == H,
    G == 1).  The op requires matching K/V head widths and MLA's value
    width (v_head_dim) is narrower than its qk width (nope + rope_d):
    zero-padding V's trailing columns is exact — softmax weights times
    zero columns — and the pad is sliced off after the op.
    ``kernel_attention=False`` keeps the blockwise jnp oracle, which
    supports Dv != Dh natively (the A/B baseline).
    """
    from repro.models.common import rmsnorm
    B, S, D = x.shape
    nope, rope_d, lora, vd, H = _mla_split(cfg)
    q = engine.matmul(x, p["wq"]).reshape(B, S, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rope_apply(q_rope, cos, sin)
    dkv = engine.matmul(x, p["w_dkv"])
    c_kv = rmsnorm(dkv[..., :lora], p["kv_norm"]["scale"], cfg.norm_eps)
    k_rope = rope_apply(dkv[..., lora:][:, :, None, :], cos, sin)
    k_nope = engine.matmul(c_kv, p["w_uk"]).reshape(B, S, H, nope)
    v = engine.matmul(c_kv, p["w_uv"]).reshape(B, S, H, vd)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, rope_d))], axis=-1)
    if kernel_attention:
        v_pad = jnp.concatenate(
            [v, jnp.zeros((B, S, H, nope + rope_d - vd), v.dtype)], axis=-1)
        y = engine.attention(q_full, k_full, v_pad,
                             causal=True)[..., :vd]
    else:
        qg = q_full.reshape(B, S, H, 1, nope + rope_d)
        y = blockwise_attention(engine, qg, k_full, v, causal=True,
                                n_q_chunks=n_q_chunks, shard_mode="heads")
    y = y.reshape(B, S, H * vd)
    y = hints.shard(y, "dp", None, "model")
    out = engine.matmul(y, p["wo"])
    if return_cache:
        return out, {"c_kv": c_kv, "k_rope": k_rope[:, :, 0, :]}
    return out


def mla_decode(engine: ComputeEngine, p, x, cache, pos, cos, sin, cfg):
    """Absorbed-matmul MLA decode (DeepSeek's inference form).

    x: (B, C, D) — C == 1 for one-token decode; C > 1 writes a chunk at
    [pos, pos + C) with right-aligned causality between the chunk's
    tokens (chunked prefill).  Cache holds only (c_kv: (B, S, lora),
    k_rope: (B, S, rope_d)) — 576
    floats/token/layer — sequence-sharded.  W_uk is absorbed into the query
    (q_nope @ W_uk per head) and W_uv applied after attention, so per-step
    FLOPs are O(S·(lora+rope)·H) instead of O(S·H·(nope+vd)·lora).

    The absorbed attention dispatches the registry `attention` op at
    every scale, as multi-query attention over the latent (one shared kv
    "head" of width lora + rope_d, values = the c_kv rows) — at deep
    caches the backend selects the split-KV decode formulation, and the
    `sharded_pallas` backend distributes it over the installed mesh.
    """
    from repro.models.common import rmsnorm
    B, C, D = x.shape
    nope, rope_d, lora, vd, H = _mla_split(cfg)
    q = engine.matmul(x, p["wq"]).reshape(B, C, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rope_apply(q_rope, cos, sin)
    dkv = engine.matmul(x, p["w_dkv"])
    c_kv = rmsnorm(dkv[..., :lora], p["kv_norm"]["scale"], cfg.norm_eps)
    k_rope = rope_apply(dkv[..., lora:][:, :, None, :], cos, sin)[:, :, 0, :]
    cc = cache_write(cache["c_kv"], c_kv, pos)
    cr = cache_write(cache["k_rope"], k_rope, pos)
    cc = hints.shard(cc, "dp", "model", None)
    cr = hints.shard(cr, "dp", "model", None)
    # absorb: q_abs[b,h,r] = sum_n q_nope[b,h,n] * W_uk[r, h, n]
    w_uk = p["w_uk"].reshape(lora, H, nope)
    q_abs = engine.einsum("bqhn,rhn->bqhr", q_nope, w_uk,
                          out_dtype=jnp.float32)
    # Absorbed MLA decode IS multi-query attention over the latent: every
    # head shares ONE kv "head" — the cache row concat(c_kv, k_rope)
    # (lora + rope_d wide) — and the value is c_kv itself.  Route it
    # through the registry `attention` op so the decode formulation
    # (split-KV kernel), tile plans, and mesh distribution all apply.  The
    # op requires matching K/V widths; zero-padding V's trailing rope_d
    # columns is exact (softmax weights times zero columns) and the pad
    # is sliced off below.
    q_cat = jnp.concatenate(
        [q_abs, q_rope.astype(jnp.float32)], axis=-1)   # (B,C,H,lo+ro)
    kv_cat = jnp.concatenate([cc, cr], axis=-1)[:, :, None, :]
    v_pad = jnp.concatenate([cc, jnp.zeros_like(cr)],
                            axis=-1)[:, :, None, :]
    ctx = engine.attention(
        q_cat.astype(kv_cat.dtype), kv_cat, v_pad, causal=C > 1,
        sm_scale=1.0 / ((nope + rope_d) ** 0.5),
        kv_len=pos + C)[..., :lora]                     # (B, C, H, lora)
    w_uv = p["w_uv"].reshape(lora, H, vd)
    y = engine.einsum("bqhr,rhv->bqhv", ctx, w_uv, out_dtype=jnp.float32)
    y = y.reshape(B, C, H * vd).astype(x.dtype)
    return engine.matmul(y, p["wo"]), {"c_kv": cc, "k_rope": cr}
