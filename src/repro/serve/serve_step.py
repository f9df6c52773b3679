"""Serving steps: prefill (build cache + first logits) and decode (one token).

These are the functions the dry-run lowers for the inference shape cells:
``decode_*`` / ``long_*`` lower decode_step (one new token against a KV cache
of seq_len), per the harness definition.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import ComputeEngine
from repro.models import transformer as tfm
from repro.models.common import lm_head_logits


def make_prefill_step(engine: ComputeEngine, cfg, *, n_q_chunks: int = 8,
                      kernel_attention: bool = True):
    """Prefill through the grouped attention path: GQA layers dispatch
    the registry `attention` op at every scale with the compact
    (B, S, KV, hd) K/V — the same layout the caches (serve/kvcache.py)
    store, so no H-broadcast exists anywhere between projection and
    cache.  Distribution lives in the backend: under a mesh, the
    sharded_pallas backend runs the same kernels per-shard via shard_map.
    ``kernel_attention=False`` forces the blockwise jnp formulation (the
    A/B baseline; the op path is differentiable too, via the flash
    kernel's custom VJP)."""
    def prefill_step(params, inputs):
        h, caches = tfm.forward_prefill(
            engine, cfg, params, tokens=inputs.get("tokens"),
            patch_embeds=inputs.get("patch_embeds"),
            frames=inputs.get("frames"), n_q_chunks=n_q_chunks,
            kernel_attention=kernel_attention)
        w = tfm.head_weight(params, cfg)
        logits = lm_head_logits(engine, h[:, -1:, :], w,
                                vocab_real=cfg.vocab_size)
        return logits, caches
    return prefill_step


def make_forward_step(engine: ComputeEngine, cfg, *, n_q_chunks: int = 8,
                      kernel_attention: bool = True):
    """Encoder-only 'prefill': full-sequence logits, no cache."""
    def forward_step(params, inputs):
        h, _ = tfm.forward_hidden(
            engine, cfg, params, tokens=inputs.get("tokens"),
            patch_embeds=inputs.get("patch_embeds"),
            frames=inputs.get("frames"), remat=False,
            n_q_chunks=n_q_chunks, kernel_attention=kernel_attention)
        w = tfm.head_weight(params, cfg)
        logits = lm_head_logits(engine, h[:, -1:, :], w,
                                vocab_real=cfg.vocab_size)
        return logits
    return forward_step


def make_decode_step(engine: ComputeEngine, cfg):
    """One-token decode against the slot engine's fixed cache buffers.

    The attention dispatch rides the registry `attention` op at every
    scale; on the pallas backend a decode-shaped dispatch (Sq <= 8
    against a cache buffer >= 256 rows) selects the split-KV
    flash-decoding formulation (kernels/flash_decode.py) — same
    contract, tiles under the lazy "attention_decode" tile-plan key.
    Under a mesh the sharded_pallas backend shards the slot batch (and
    KV-head groups) via shard_map around those same kernels."""
    def decode_step(params, caches, token, pos):
        h, new_caches = tfm.decode_hidden(engine, cfg, params, caches,
                                          token, pos)
        w = tfm.head_weight(params, cfg)
        logits = lm_head_logits(engine, h, w, vocab_real=cfg.vocab_size)
        return logits, new_caches
    return decode_step


def make_paged_step(engine: ComputeEngine, cfg):
    """Block-table-aware step over a paged KV pool (serve/kvpool.py).

    Gathers the batch's blocks into the compact (B, S, KV, hd) cache
    layout, runs `chunk` new tokens through `decode_hidden` with
    per-sequence (B,) start positions — the registry `attention` op masks
    each sequence at its own live `kv_len` — then scatters only the newly
    written rows back into the pools.  One function serves both traffic
    shapes: chunked prefill dispatches (B=1, chunk=C) and batched decode
    dispatches (B=batch, chunk=1); the scheduler pads both to bucketed
    shapes so a `StepCompileCache` bounds the trace count.  Decode-shaped
    dispatches whose gathered buffer reaches 256 rows take the split-KV
    flash-decoding formulation on the pallas backend (see
    make_decode_step) — formulation choice never changes tokens
    (benchmarks/decode_sweep.py --smoke gates bit-parity).
    """
    from repro.serve import kvpool

    def paged_step(params, pools, block_tables, tokens, pos):
        chunk = tokens.shape[1]
        caches = kvpool.gather_block_cache(pools, block_tables)
        h, new_caches = tfm.decode_hidden(engine, cfg, params, caches,
                                          tokens, pos)
        w = tfm.head_weight(params, cfg)
        logits = lm_head_logits(engine, h, w, vocab_real=cfg.vocab_size)
        new_pools = kvpool.scatter_chunk(pools, new_caches, block_tables,
                                         pos, chunk)
        return logits, new_pools
    return paged_step


def greedy_sample(logits):
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
