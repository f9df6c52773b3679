"""Split-KV flash-decoding kernel: saturate the chip at long-KV decode.

The forward flash kernel runs ONE program per (batch, head, query tile)
with the KV axis innermost and sequential ("arbitrary") — correct for
prefill, where B*H*(Sq/bq) programs fill the chip, but a decode step
(Sq <= 8) collapses that to B*H programs each streaming the whole KV
extent: at long context most of the chip idles while a handful of
programs crawl the cache.  This is exactly the utilization gap
flash-decoding closes, and the same structural argument as the paper's
streaming engine — keep every lane busy by splitting the REDUCTION, not
the (tiny) output.

Here the KV extent is split into ``n_splits`` independent spans, one grid
program per (batch*head, split).  Each program runs the usual online
softmax over its span's ``bk``-sized blocks and emits a PARTIAL
``(o, lse)`` pair — its span's softmax-weighted value sum plus the
logsumexp of its span's scores.  The partials are combined outside the
kernel by the standard logsumexp merge (associative and exact up to fp
rounding):

    m    = max_s lse_s
    o    = sum_s o_s * exp(lse_s - m) / sum_s exp(lse_s - m)
    lse  = m + log(sum_s exp(lse_s - m))

The combine is O(n_splits * Sq * D) — vanishingly small next to the
KV streaming — so it runs as plain jnp and XLA fuses it.

Empty spans (entirely at/beyond ``kv_len``, or fully above the causal
diagonal) emit ``lse = -1e30`` with a zero partial, which the merge
weighs to exactly 0 against any live span; when EVERY span of a row is
empty (``kv_len == 0``, rows past the causal extent) the merged output
is exact 0, never NaN — same contract as the forward kernel and the ref
oracle.  Partials, statistics and the merge are fp32 regardless of the
operand dtype (bf16 operands keep fp32 lse accumulation).

Layout matches ``flash_attention.flash_attention``: q (B, H, Sq, D),
k/v (B, KV, Skv, D) grouped-KV native — query head h reads kv-head
h // (H // KV) straight from its BlockSpec, no broadcast.  ``n_splits``
and ``bk`` come from the rule-made plan of the lazy ``attention_decode``
tile-plan key (docs/engine_api.md, "Tile plans").

This path is inference-only: decode is never differentiated, so there is
no VJP here (the registry routes differentiated attention through the
forward kernel's custom VJP; see core/backends.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret
from repro.kernels.flash_attention import (_LANES, _NEG_INF, _SEMANTICS,
                                           KV_LEN_SPEC, _dot)

# The lse value an empty (fully-masked) KV span reports; `combine` weighs
# such partials to zero.  Cross-device partial emitters
# (ops.attention_partial, kernels/sharded.py) must use the SAME sentinel.
EMPTY_SPAN_LSE = _NEG_INF


def _decode_kernel(q_ref, k_ref, v_ref, kvl_ref, o_ref, lse_ref,
                   m_ref, l_ref, acc_ref, *, nj: int, bq: int, bk: int,
                   span: int, sm_scale: float, causal: bool, q_len: int,
                   heads: int):
    """One (batch*head, split) program: online softmax over the split's
    span of KV blocks, emitting the span's partial (o, lse)."""
    s_idx, j = pl.program_id(1), pl.program_id(2)
    kv_len = kvl_ref[pl.program_id(0) // heads]
    base = s_idx * span + j * bk          # global start of this KV block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)        # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)        # (bk, d)
        s = _dot(q, k, ((1,), (1,))) * sm_scale    # (bq, bk)
        kj = base + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            # Queries right-align against the live extent: query row qi
            # sits at global position kv_len - q_len + qi.
            qi = (kv_len - q_len
                  + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
            s = jnp.where(kj <= qi, s, _NEG_INF)
        s = jnp.where(kj < kv_len, s, _NEG_INF)
        m_prev = m_ref[...][:, :1]                 # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # Fully-masked rows have m_new == _NEG_INF, where exp(s - m_new)
        # would be 1 at every masked position; zero them so l stays 0.
        p = jnp.where(s > _NEG_INF * 0.5, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[...][:, :1] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot(p, v, ((1,), (0,)))
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    # Skip blocks entirely at/beyond kv_len (the causal diagonal never
    # trims below kv_len here: decode queries sit at the extent's end).
    pl.when(base < kv_len)(_body)

    @pl.when(j == nj - 1)
    def _finish():
        l = l_ref[...][:, :1]
        lsafe = jnp.where(l == 0.0, 1.0, l)
        # Partial output normalized within the span; fp32 out so the merge
        # never round-trips through a narrow operand dtype.
        o_ref[0, 0, 0] = acc_ref[...] / lsafe
        m = m_ref[...][:, :1]
        # Span logsumexp in the scaled score space, one (bq, 1) column;
        # empty spans emit the _NEG_INF sentinel the merge weighs to zero.
        lse_ref[0, 0, 0] = jnp.where(l > 0.0, m + jnp.log(lsafe), _NEG_INF)


def flash_decode(q, k, v, kv_len, *, causal: bool = True, sm_scale=None,
                 bk: int = 256, n_splits: int = 4, q_len: int = 0,
                 interpret: bool | None = None):
    """q: (B, H, Sq, D); k, v: (B, KV, Skv, D) with H % KV == 0.

    Split-KV decode: Skv must equal ``n_splits * span`` with
    ``span % bk == 0`` (the ops wrapper pads and masks via ``kv_len``).
    ``kv_len`` is REQUIRED — (B,) or (B, 1) int32 live extents, read from
    scalar memory (padding and cache masking ride the same operand).  Causal queries right-align against
    ``kv_len`` with ``q_len`` real rows (padded rows are sliced off by the
    caller).  Returns (B, H, Sq, D) fp32 — partials and the logsumexp
    merge never leave fp32; the caller casts.
    """
    b, h, sq, d = q.shape
    _, kvh, skv, _ = k.shape
    grp = h // kvh
    assert skv % n_splits == 0, (skv, n_splits)
    span = skv // n_splits
    assert span % bk == 0, (span, bk)
    assert h % kvh == 0, (h, kvh)
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    nj = span // bk
    grid = (b * h, n_splits, nj)
    kernel = functools.partial(
        _decode_kernel, nj=nj, bq=sq, bk=bk, span=span,
        sm_scale=float(sm_scale), causal=causal,
        q_len=q_len if q_len else sq, heads=h)
    q_spec = pl.BlockSpec((1, 1, sq, d), lambda g, s, j: (g // h, g % h, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, d),
        lambda g, s, j, nj=nj: (g // h, (g % h) // grp, s * nj + j, 0))
    o_spec = pl.BlockSpec((1, 1, 1, sq, d),
                          lambda g, s, j: (g // h, g % h, s, 0, 0))
    # Per-split lse as a (sq, 1) column: a (1, sq) row block would put the
    # split axis in the sublane dim, which the TPU compiler refuses.
    lse_spec = pl.BlockSpec((1, 1, 1, sq, 1),
                            lambda g, s, j: (g // h, g % h, s, 0, 0))
    o_part, lse_part = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, KV_LEN_SPEC],
        out_specs=[o_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, n_splits, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, n_splits, sq, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((sq, _LANES), jnp.float32),   # m
                        pltpu.VMEM((sq, _LANES), jnp.float32),   # l
                        pltpu.VMEM((sq, d), jnp.float32)],       # acc
        interpret=resolve_interpret(interpret),
        compiler_params=_SEMANTICS,
    )(q, k, v, kv_len.astype(jnp.int32).reshape(b))
    return combine(o_part, lse_part[..., 0])


def combine(o_part, lse_part):
    """Logsumexp merge of split-KV partials (SNIPPETS Snippet 2's
    ``combine``): o_part (B, H, S, Sq, D) fp32, lse_part (B, H, S, Sq)
    fp32 with the empty-span sentinel -1e30 -> (B, H, Sq, D) fp32.

    Exact up to fp rounding: each partial is its span's normalized
    softmax-weighted sum, so re-weighting by exp(lse_s - m) recovers the
    global softmax.  All-empty rows (every lse at the sentinel) merge to
    exact 0, never NaN: the zero partials dominate a finite denominator.
    """
    m = jnp.max(lse_part, axis=2, keepdims=True)           # (B, H, 1, Sq)
    alpha = jnp.exp(lse_part - m)                          # (B, H, S, Sq)
    denom = jnp.sum(alpha, axis=2)                         # (B, H, Sq)
    num = jnp.sum(o_part * alpha[..., None], axis=2)       # (B, H, Sq, D)
    return num / denom[..., None]
