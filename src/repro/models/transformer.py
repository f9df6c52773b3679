"""Generic LM assembled per ArchConfig: dense / MoE / MLA / SSM / hybrid /
encoder-only, with scan-over-layers (+remat) so HLO size is O(1) in depth.

The layer "program" is STATIC, derived from the config:
  dense|vlm|audio : [("dense", L)]
  deepseek        : [("mla_dense", 1), ("mla_moe", L-1)]
  llama4          : [("gqa_moe", L)]
  mamba2          : [("mamba", L)]
  zamba2          : [("zamba_super", 13×6)] + [("mamba", 3)]   (81 layers)
Params hold one stacked tree per program entry (leading dim = #layers),
initialized with vmap'd per-layer inits — this also works under
jax.eval_shape, which is how the dry-run builds full-scale parameter specs
without allocating.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ComputeEngine
from repro.models import attention as attn
from repro.models import frontend as fe
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (chunked_cross_entropy, embed_init,
                                 embed_lookup, norm_apply, norm_init,
                                 rope_table)
from repro.models.mlp import mlp_forward, mlp_init
from repro.sharding import hints

ZAMBA_TAIL = None  # computed from cfg: n_layers - 13*attn_every


# ----------------------------------------------------------- the program ---

def stack_program(cfg) -> list[tuple[str, int]]:
    if cfg.family in ("dense", "vlm", "audio"):
        return [("dense", cfg.n_layers)]
    if cfg.family == "moe":
        if cfg.is_mla:
            prog = []
            if cfg.first_dense_layers:
                prog.append(("mla_dense", cfg.first_dense_layers))
            prog.append(("mla_moe", cfg.n_layers - cfg.first_dense_layers))
            return prog
        return [("gqa_moe", cfg.n_layers)]
    if cfg.family == "ssm":
        return [("mamba", cfg.n_layers)]
    if cfg.family == "hybrid":
        n_super = cfg.n_layers // cfg.attn_every
        tail = cfg.n_layers - n_super * cfg.attn_every
        prog = [("zamba_super", n_super)]
        if tail:
            prog.append(("mamba", tail))
        return prog
    raise ValueError(cfg.family)


def attn_shard_mode(cfg) -> str:
    """'heads' when kv heads divide the TP axis (zero attention comm),
    else 'seq' (query-sequence parallel; GSPMD all-gathers KV)."""
    if cfg.is_mla:
        return "heads"
    # No 'model' axis: size 1, modes identical (hints are no-ops).
    return "heads" if cfg.n_kv_heads % hints.axis_size("model") == 0 else "seq"


# ------------------------------------------------------------------- init ---

def _layer_init(kind: str, key, cfg):
    if kind == "dense":
        k1, k2 = jax.random.split(key)
        return {"norm1": norm_init(cfg.norm, cfg.d_model),
                "attn": attn.gqa_init(k1, cfg),
                "norm2": norm_init(cfg.norm, cfg.d_model),
                "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.act)}
    if kind == "mla_dense":
        k1, k2 = jax.random.split(key)
        return {"norm1": norm_init(cfg.norm, cfg.d_model),
                "attn": attn.mla_init(k1, cfg),
                "norm2": norm_init(cfg.norm, cfg.d_model),
                "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.act)}
    if kind == "mla_moe":
        k1, k2 = jax.random.split(key)
        return {"norm1": norm_init(cfg.norm, cfg.d_model),
                "attn": attn.mla_init(k1, cfg),
                "norm2": norm_init(cfg.norm, cfg.d_model),
                "moe": moe_mod.moe_init(k2, cfg)}
    if kind == "gqa_moe":
        k1, k2 = jax.random.split(key)
        return {"norm1": norm_init(cfg.norm, cfg.d_model),
                "attn": attn.gqa_init(k1, cfg),
                "norm2": norm_init(cfg.norm, cfg.d_model),
                "moe": moe_mod.moe_init(k2, cfg)}
    if kind == "mamba":
        return {"norm": norm_init(cfg.norm, cfg.d_model),
                "mixer": ssm_mod.ssm_init(key, cfg)}
    raise ValueError(kind)


def _shared_block_init(key, cfg):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "norm_in": norm_init("rms", 2 * d),
        "win": jax.random.normal(ks[0], (2 * d, d), jnp.float32)
        / (2 * d) ** 0.5,
        "norm1": norm_init(cfg.norm, d),
        "attn": attn.gqa_init(ks[1], cfg),
        "norm2": norm_init(cfg.norm, d),
        "mlp": mlp_init(ks[2], d, cfg.d_ff, cfg.act),
        "wout": jax.random.normal(ks[3], (d, d), jnp.float32) / d ** 0.5,
    }


def init_params(key, cfg):
    keys = jax.random.split(key, 8)
    params = {"embed": embed_init(keys[0], cfg.vocab_padded, cfg.d_model),
              "final_norm": norm_init(cfg.norm, cfg.d_model)}
    if cfg.frontend != "none":
        params["frontend"] = fe.frontend_init(keys[1], cfg)
    stacks = []
    prog = stack_program(cfg)
    for si, (kind, n) in enumerate(prog):
        kkey = jax.random.fold_in(keys[2], si)
        if kind == "zamba_super":
            inner = cfg.attn_every
            lkeys = jax.random.split(kkey, n * inner).reshape(n, inner, 2)
            stacked = jax.vmap(jax.vmap(
                lambda k: _layer_init("mamba", k, cfg)))(lkeys)
        else:
            lkeys = jax.random.split(kkey, n)
            stacked = jax.vmap(lambda k: _layer_init(kind, k, cfg))(lkeys)
        stacks.append(stacked)
    params["stacks"] = stacks
    if cfg.family == "hybrid":
        params["shared"] = _shared_block_init(keys[3], cfg)
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": jax.random.normal(keys[4], (cfg.d_model, cfg.vocab_padded),
                                   jnp.float32) / cfg.d_model ** 0.5}
    return params


def head_weight(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"]["tokens"].T
    return params["lm_head"]["w"]


def param_counts(cfg) -> tuple[int, int]:
    """(total, active) parameter counts.  active subtracts non-activated
    routed-expert weights (MoE): per token only top_k of E experts run."""
    import math
    tree = jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    total = sum(math.prod(l.shape) for l in jax.tree_util.tree_leaves(tree))
    active = total
    if cfg.is_moe:
        E, K, D, F = (cfg.n_routed_experts, cfg.top_k, cfg.d_model,
                      cfg.moe_d_ff)
        n_moe_layers = cfg.n_layers - cfg.first_dense_layers
        active -= n_moe_layers * (E - K) * 3 * D * F
    return total, active


# ---------------------------------------------------------------- forward ---

def _embed_inputs(engine, cfg, params, tokens=None, patch_embeds=None,
                  frames=None):
    dt = engine.precision.compute_dtype
    if cfg.frontend == "audio":
        h = fe.frontend_apply(engine, params["frontend"], frames.astype(dt),
                              cfg)
    else:
        h = embed_lookup(params["embed"], tokens, dt)
        if cfg.frontend == "vision":
            v = fe.frontend_apply(engine, params["frontend"],
                                  patch_embeds.astype(dt), cfg)
            h = jnp.concatenate([v, h], axis=1)
    return hints.shard(h, "dp", None, None)


def _dense_layer(engine, cfg, lp, h, cos, sin, shard_mode, n_q_chunks,
                 kernel_attention=True):
    a = attn.gqa_forward(engine, lp["attn"],
                         norm_apply(cfg.norm, lp["norm1"], h, cfg.norm_eps),
                         cos, sin, cfg, shard_mode=shard_mode,
                         n_q_chunks=n_q_chunks,
                         kernel_attention=kernel_attention)
    h = h + a
    m = mlp_forward(engine, lp["mlp"],
                    norm_apply(cfg.norm, lp["norm2"], h, cfg.norm_eps),
                    cfg.act)
    return h + m, jnp.zeros((), jnp.float32)


def _mla_layer(engine, cfg, lp, h, cos, sin, n_q_chunks, use_moe,
               kernel_attention=True):
    a = attn.mla_forward(engine, lp["attn"],
                         norm_apply(cfg.norm, lp["norm1"], h, cfg.norm_eps),
                         cos, sin, cfg, n_q_chunks=n_q_chunks,
                         kernel_attention=kernel_attention)
    h = h + a
    x = norm_apply(cfg.norm, lp["norm2"], h, cfg.norm_eps)
    if use_moe:
        m, aux = moe_mod.moe_forward(engine, lp["moe"], x, cfg)
    else:
        m, aux = mlp_forward(engine, lp["mlp"], x, cfg.act), jnp.zeros(
            (), jnp.float32)
    return h + m, aux


def _gqa_moe_layer(engine, cfg, lp, h, cos, sin, shard_mode, n_q_chunks,
                   kernel_attention=True):
    a = attn.gqa_forward(engine, lp["attn"],
                         norm_apply(cfg.norm, lp["norm1"], h, cfg.norm_eps),
                         cos, sin, cfg, shard_mode=shard_mode,
                         n_q_chunks=n_q_chunks,
                         kernel_attention=kernel_attention)
    h = h + a
    m, aux = moe_mod.moe_forward(
        engine, lp["moe"],
        norm_apply(cfg.norm, lp["norm2"], h, cfg.norm_eps), cfg)
    return h + m, aux


def _mamba_layer(engine, cfg, lp, h):
    m = ssm_mod.ssm_forward(
        engine, lp["mixer"],
        norm_apply(cfg.norm, lp["norm"], h, cfg.norm_eps), cfg)
    return h + m, jnp.zeros((), jnp.float32)


def _shared_block(engine, cfg, sp, h, emb0, cos, sin, shard_mode,
                  n_q_chunks, kernel_attention=True):
    """Zamba2 shared attention+MLP block (weights reused per invocation)."""
    from repro.models.common import rmsnorm
    x = jnp.concatenate([h, emb0], axis=-1)
    x = rmsnorm(x, sp["norm_in"]["scale"], cfg.norm_eps)
    x = engine.matmul(x, sp["win"])
    a = attn.gqa_forward(engine, sp["attn"],
                         norm_apply(cfg.norm, sp["norm1"], x, cfg.norm_eps),
                         cos, sin, cfg, shard_mode=shard_mode,
                         n_q_chunks=n_q_chunks,
                         kernel_attention=kernel_attention)
    x = x + a
    m = mlp_forward(engine, sp["mlp"],
                    norm_apply(cfg.norm, sp["norm2"], x, cfg.norm_eps),
                    cfg.act)
    x = x + m
    return h + engine.matmul(x, sp["wout"])


def forward_hidden(engine: ComputeEngine, cfg, params, *, tokens=None,
                   patch_embeds=None, frames=None, remat: bool = True,
                   n_q_chunks: int = 8, kernel_attention: bool = True):
    """Full-sequence forward to final hidden states (B, S, D).

    Off-mesh, GQA attention dispatches the registry `attention` op under
    training AND inference alike — the flash kernel carries a custom VJP
    (kernels/flash_attention.py), so loss_fn differentiates straight
    through the kernel path and train/serve numerics agree.
    ``kernel_attention=False`` forces the blockwise jnp formulation (the
    A/B baseline; under a mesh the blockwise GSPMD path engages
    regardless).
    """
    h = _embed_inputs(engine, cfg, params, tokens, patch_embeds, frames)
    S = h.shape[1]
    shard_mode = attn_shard_mode(cfg)
    if cfg.n_heads:
        rd = cfg.qk_rope_dim if cfg.is_mla else cfg.head_dim
        cos, sin = rope_table(jnp.arange(S), rd, cfg.rope_theta)
    else:
        cos = sin = None
    emb0 = h
    aux_total = jnp.zeros((), jnp.float32)

    for (kind, n), stacked in zip(stack_program(cfg), params["stacks"]):
        if kind == "zamba_super":
            def super_body(carry, lps):
                hh, aux = carry

                def inner(c, lp):
                    hh2, aux2 = _mamba_layer(engine, cfg, lp, c[0])
                    return (hh2, c[1] + aux2), None

                (hh, aux), _ = jax.lax.scan(inner, (hh, aux), lps)
                hh = _shared_block(engine, cfg, params["shared"], hh, emb0,
                                   cos, sin, shard_mode, n_q_chunks,
                                   kernel_attention)
                return (hh, aux), None

            body = jax.checkpoint(super_body) if remat else super_body
            (h, aux_total), _ = jax.lax.scan(body, (h, aux_total), stacked)
            continue

        def layer_body(carry, lp, kind=kind):
            hh, aux = carry
            if kind == "dense":
                hh, a = _dense_layer(engine, cfg, lp, hh, cos, sin,
                                     shard_mode, n_q_chunks,
                                     kernel_attention)
            elif kind == "mla_dense":
                hh, a = _mla_layer(engine, cfg, lp, hh, cos, sin,
                                   n_q_chunks, use_moe=False,
                                   kernel_attention=kernel_attention)
            elif kind == "mla_moe":
                hh, a = _mla_layer(engine, cfg, lp, hh, cos, sin,
                                   n_q_chunks, use_moe=True,
                                   kernel_attention=kernel_attention)
            elif kind == "gqa_moe":
                hh, a = _gqa_moe_layer(engine, cfg, lp, hh, cos, sin,
                                       shard_mode, n_q_chunks,
                                       kernel_attention)
            elif kind == "mamba":
                hh, a = _mamba_layer(engine, cfg, lp, hh)
            else:
                raise ValueError(kind)
            return (hh, aux + a), None

        body = jax.checkpoint(layer_body) if remat else layer_body
        (h, aux_total), _ = jax.lax.scan(body, (h, aux_total), stacked)

    h = norm_apply(cfg.norm, params["final_norm"], h, cfg.norm_eps)
    return h, aux_total


# ------------------------------------------------------ prefill / decode ---

def forward_prefill(engine: ComputeEngine, cfg, params, *, tokens=None,
                    patch_embeds=None, frames=None, n_q_chunks: int = 8,
                    kernel_attention: bool = True):
    """Full-sequence forward that also collects per-layer caches.

    Off-mesh with ``kernel_attention`` (the default), GQA attention
    dispatches the grouped registry `attention` op — compact (B, S, KV, hd)
    K/V, no H-broadcast.  Returns (hidden (B, S, D), caches: list aligned
    with stack_program).
    """
    h = _embed_inputs(engine, cfg, params, tokens, patch_embeds, frames)
    S = h.shape[1]
    shard_mode = attn_shard_mode(cfg)
    if cfg.n_heads:
        rd = cfg.qk_rope_dim if cfg.is_mla else cfg.head_dim
        cos, sin = rope_table(jnp.arange(S), rd, cfg.rope_theta)
    else:
        cos = sin = None
    emb0 = h
    caches = []

    for (kind, n), stacked in zip(stack_program(cfg), params["stacks"]):
        if kind == "zamba_super":
            def super_body(hh, lps):
                def inner(c, lp):
                    x = norm_apply(cfg.norm, lp["norm"], c, cfg.norm_eps)
                    m, mc = ssm_mod.ssm_forward(engine, lp["mixer"], x, cfg,
                                                return_cache=True)
                    return c + m, mc

                hh, mcaches = jax.lax.scan(inner, hh, lps)
                from repro.models.common import rmsnorm
                sp = params["shared"]
                x = jnp.concatenate([hh, emb0], axis=-1)
                x = rmsnorm(x, sp["norm_in"]["scale"], cfg.norm_eps)
                x = engine.matmul(x, sp["win"])
                a, kv = attn.gqa_forward(
                    engine, sp["attn"],
                    norm_apply(cfg.norm, sp["norm1"], x, cfg.norm_eps),
                    cos, sin, cfg, shard_mode=shard_mode,
                    n_q_chunks=n_q_chunks, return_kv=True,
                    kernel_attention=kernel_attention)
                x = x + a
                m = mlp_forward(engine, sp["mlp"],
                                norm_apply(cfg.norm, sp["norm2"], x,
                                           cfg.norm_eps), cfg.act)
                x = x + m
                hh = hh + engine.matmul(x, sp["wout"])
                return hh, {"mamba": mcaches, "shared": kv}

            h, cache = jax.lax.scan(super_body, h, stacked)
            caches.append(cache)
            continue

        def layer_body(hh, lp, kind=kind):
            x1 = norm_apply(cfg.norm, lp["norm1" if kind != "mamba"
                                         else "norm"], hh, cfg.norm_eps)
            if kind == "mamba":
                m, mc = ssm_mod.ssm_forward(engine, lp["mixer"], x1, cfg,
                                            return_cache=True)
                return hh + m, mc
            if kind in ("mla_dense", "mla_moe"):
                a, entry = attn.mla_forward(engine, lp["attn"], x1, cos, sin,
                                            cfg, n_q_chunks=n_q_chunks,
                                            return_cache=True,
                                            kernel_attention=kernel_attention)
            else:
                a, entry = attn.gqa_forward(engine, lp["attn"], x1, cos, sin,
                                            cfg, shard_mode=shard_mode,
                                            n_q_chunks=n_q_chunks,
                                            return_kv=True,
                                            kernel_attention=kernel_attention)
            hh = hh + a
            x2 = norm_apply(cfg.norm, lp["norm2"], hh, cfg.norm_eps)
            if kind in ("mla_moe", "gqa_moe"):
                m, _ = moe_mod.moe_forward(engine, lp["moe"], x2, cfg)
            else:
                m = mlp_forward(engine, lp["mlp"], x2, cfg.act)
            return hh + m, entry

        h, cache = jax.lax.scan(layer_body, h, stacked)
        caches.append(cache)

    h = norm_apply(cfg.norm, params["final_norm"], h, cfg.norm_eps)
    return h, caches


def decode_hidden(engine: ComputeEngine, cfg, params, caches, token, pos):
    """Decode a chunk of C new tokens against the caches.

    token: (B, C) int32 — C == 1 is plain one-token decode; C > 1 is a
    chunked-prefill step (attention-cache stacks only: SSM decode is
    strictly one-token).  pos: scalar int32, or (B,) per-sequence START
    positions (continuous batching) — the chunk occupies [pos, pos + C).

    Off-mesh, GQA and MLA decode both dispatch the registry `attention`
    op (MLA in its absorbed multi-query-over-the-latent form); on the
    pallas backend a deep-cache dispatch selects the split-KV
    flash-decoding formulation (kernels/flash_decode.py).

    Returns (hidden (B, C, D), new caches).
    """
    C = token.shape[1]
    dt = engine.precision.compute_dtype
    h = embed_lookup(params["embed"], token, dt)
    h = hints.shard(h, "dp", None, None)
    if cfg.n_heads:
        rd = cfg.qk_rope_dim if cfg.is_mla else cfg.head_dim
        if pos.ndim == 0:
            # (C,) absolute positions -> (C, rd/2) tables broadcast over B.
            positions = pos + jnp.arange(C, dtype=jnp.int32)
        else:  # per-sequence starts: (B, C) -> (B, C, rd/2)
            positions = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        cos, sin = rope_table(positions, rd, cfg.rope_theta)
    else:
        cos = sin = None
    emb0 = h
    new_caches = []

    for (kind, n), stacked, cache in zip(stack_program(cfg),
                                         params["stacks"], caches):
        if kind == "zamba_super":
            def super_body(hh, xs):
                lps, mcache, scache = xs

                def inner(c, xs2):
                    lp, lc = xs2
                    x = norm_apply(cfg.norm, lp["norm"], c, cfg.norm_eps)
                    m, nc = ssm_mod.ssm_decode(engine, lp["mixer"], x, lc,
                                               cfg)
                    return c + m, nc

                hh, new_mc = jax.lax.scan(inner, hh, (lps, mcache))
                from repro.models.common import rmsnorm
                sp = params["shared"]
                x = jnp.concatenate([hh, emb0], axis=-1)
                x = rmsnorm(x, sp["norm_in"]["scale"], cfg.norm_eps)
                x = engine.matmul(x, sp["win"])
                a, new_sc = attn.gqa_decode(
                    engine, sp["attn"],
                    norm_apply(cfg.norm, sp["norm1"], x, cfg.norm_eps),
                    scache, pos, cos, sin, cfg)
                x = x + a
                m = mlp_forward(engine, sp["mlp"],
                                norm_apply(cfg.norm, sp["norm2"], x,
                                           cfg.norm_eps), cfg.act)
                x = x + m
                hh = hh + engine.matmul(x, sp["wout"])
                return hh, {"mamba": new_mc, "shared": new_sc}

            h, new_cache = jax.lax.scan(
                super_body, h, (stacked, cache["mamba"], cache["shared"]))
            new_caches.append(new_cache)
            continue

        def layer_body(hh, xs, kind=kind):
            lp, lc = xs
            x1 = norm_apply(cfg.norm, lp["norm1" if kind != "mamba"
                                         else "norm"], hh, cfg.norm_eps)
            if kind == "mamba":
                m, nc = ssm_mod.ssm_decode(engine, lp["mixer"], x1, lc, cfg)
                return hh + m, nc
            if kind in ("mla_dense", "mla_moe"):
                a, nc = attn.mla_decode(engine, lp["attn"], x1, lc, pos,
                                        cos, sin, cfg)
            else:
                a, nc = attn.gqa_decode(engine, lp["attn"], x1, lc, pos,
                                        cos, sin, cfg)
            hh = hh + a
            x2 = norm_apply(cfg.norm, lp["norm2"], hh, cfg.norm_eps)
            if kind in ("mla_moe", "gqa_moe"):
                m, _ = moe_mod.moe_forward(engine, lp["moe"], x2, cfg)
            else:
                m = mlp_forward(engine, lp["mlp"], x2, cfg.act)
            return hh + m, nc

        h, new_cache = jax.lax.scan(layer_body, h, (stacked, cache))
        new_caches.append(new_cache)

    h = norm_apply(cfg.norm, params["final_norm"], h, cfg.norm_eps)
    return h, new_caches


def loss_fn(engine: ComputeEngine, cfg, params, batch, *,
            aux_coef: float = 0.01, remat: bool = True,
            n_q_chunks: int = 8, ce_chunk: int = 512,
            kernel_attention: bool = True):
    """Mean token CE (+ MoE aux) for a training batch.

    Runs the SAME attention implementation as serving: off-mesh the
    registry `attention` op (flash kernel with its custom-VJP backward
    kernels under the pallas backend), so training and inference share one
    set of numerics.  ``kernel_attention=False`` keeps the blockwise jnp
    formulation for A/B comparison; under a mesh the GSPMD blockwise path
    engages regardless of the flag.
    """
    h, aux = forward_hidden(
        engine, cfg, params, tokens=batch.get("tokens"),
        patch_embeds=batch.get("patch_embeds"), frames=batch.get("frames"),
        remat=remat, n_q_chunks=n_q_chunks,
        kernel_attention=kernel_attention)
    w_head = head_weight(params, cfg)
    ce = chunked_cross_entropy(engine, h, w_head, batch["labels"],
                               vocab_real=cfg.vocab_size, chunk=ce_chunk)
    n_moe = sum(n for (k, n) in stack_program(cfg) if "moe" in k)
    aux_mean = aux / max(n_moe, 1)
    return ce + (aux_coef * aux_mean if n_moe else 0.0)
