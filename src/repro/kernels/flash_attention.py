"""Blockwise (flash) attention kernel for prefill/train, grouped-KV native.

This is the paper's streaming idea applied to the attention hot-spot: KV
tiles stream through VMEM while running softmax statistics (m, l) and the
output accumulator stay resident on-chip — the S×S score matrix never exists
in HBM, exactly like the engine's GEMM accumulator never round-trips.

Grouped KV (GQA/MQA) is a *layout* property, not a compute property: with
H query heads sharing KV kv-heads (H % KV == 0, group size G = H/KV), the
kernel reads the SAME (bk, d) K/V tile for all G query heads of a group —
the BlockSpec index map sends query-head h to kv-head h // G, so K/V ride
the bus once per group instead of once per head (G× less KV bandwidth and
zero caller-side broadcast; see docs/engine_api.md for the layout
contract).

Grid: (B*H, Sq/bq, Skv/bk), KV innermost ("arbitrary") so the (m, l, acc)
scratch carries across KV steps for a fixed query tile.  Causal masking uses
global indices; fully-masked KV blocks are skipped with pl.when (on TPU the
DMA still prefetches them; a §Perf iteration notes the trimmed-grid variant).
Decode-shaped problems (Sq <= 8 against a deep cache) leave this grid with
only B*H programs — the registry instead selects the split-KV formulation
in kernels/flash_decode.py, which shares this kernel's masking and fp32
conventions and degenerates to it bit-identically at one split.
An optional per-batch ``kv_len`` masks keys at/beyond the given length —
this is what lets the ops-level wrapper zero-pad Skv to a block multiple
(padded keys are masked out exactly) and what decode uses to attend a
cache filled only up to ``pos``.

The op is DIFFERENTIABLE via ``jax.custom_vjp``: the forward additionally
emits the per-row softmax logsumexp residual, and two backward kernels
recompute the probability tiles from (q, k, lse) — never materializing the
S×S matrix in the backward either:

  dQ    : same (B*H, Sq/bq, Skv/bk) grid as the forward, KV innermost,
          a (bq, d) fp32 accumulator carrying across KV steps;
  dK/dV : (B*KV, Skv/bk, G*Sq/bq) grid — one program per *kv-head* and KV
          tile, with the innermost axis sweeping all G query heads of the
          group and every query tile, accumulating into (bk, d) scratch.
          Gradients come out in the compact (B, KV, Skv, D) layout: the
          group reduction happens inside the kernel, so grouped KV never
          broadcasts to H heads — in the backward pass either.

Fully-masked rows (kv_len == 0, or rows past the causal extent) carry an
lse residual of 0 and a probability tile forced to exact 0, so their
dQ/dK/dV contributions are exact 0 — never NaN from the 0·logsumexp
delta term.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret

_NEG_INF = -1e30
_LANES = 128  # stats scratch is lane-replicated for TPU vector layout

# (batch*head, query tile) programs are independent; the innermost axis
# carries the online-softmax / gradient accumulators.
_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))

# Per-batch live extents (B,) int32 sit whole in scalar memory: a kernel
# reads its row's extent as a scalar, and no (1, 1) VMEM block of a (B, 1)
# array (a layout the TPU compiler refuses) is ever needed.
KV_LEN_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)


def _flash_kernel(*refs, nk: int, bq: int, bk: int, sm_scale: float,
                  causal: bool, q_offset: int, q_len: int, heads: int,
                  has_kv_len: bool, return_lse: bool):
    if has_kv_len:
        q_ref, k_ref, v_ref, kvl_ref, *rest = refs
        kv_len = kvl_ref[pl.program_id(0) // heads]
    else:
        q_ref, k_ref, v_ref, *rest = refs
        kv_len = None
    if return_lse:
        o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
        lse_ref = None
    # Causal alignment: queries right-align against the LIVE key extent —
    # kv_len when given (per-batch, dynamic), else the static q_offset.
    if causal and kv_len is not None:
        q_offset = kv_len - q_len
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)       # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)       # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)       # (bk, d)
        s = _dot(q, k, ((1,), (1,)))
        s = s * sm_scale                           # (bq, bk)
        kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            qi = q_offset + i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            s = jnp.where(kj <= qi, s, _NEG_INF)
        if kv_len is not None:
            s = jnp.where(kj < kv_len, s, _NEG_INF)
        m_prev = m_ref[...][:, :1]                 # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # (bq, bk)
        # A fully-masked row has m_new == _NEG_INF, where exp(s - m_new)
        # would be 1 at every masked position; zero them so l stays 0 and
        # _finish emits exact 0 rows (kv_len < row position, kv_len == 0).
        p = jnp.where(s > _NEG_INF * 0.5, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)            # (bq, 1)
        l_new = l_ref[...][:, :1] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot(p, v, ((1,), (0,)))
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    # Skip KV blocks that are entirely masked for this query tile: strictly
    # above the causal diagonal, or entirely at/beyond kv_len.
    cond = None
    if causal:
        cond = j * bk <= q_offset + i * bq + bq - 1
    if kv_len is not None:
        live = j * bk < kv_len
        cond = live if cond is None else jnp.logical_and(cond, live)
    if cond is None:
        _body()
    else:
        pl.when(cond)(_body)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_ref[...][:, :1]
        lsafe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, 0] = (acc_ref[...] / lsafe).astype(o_ref.dtype)
        if return_lse:
            # Per-row softmax residual m + log(l) in the *scaled* score
            # space, one (bq, 1) column; fully-masked rows store 0 — any
            # finite value works, since the backward forces their
            # probability tiles to exact 0.
            m = m_ref[...][:, :1]
            lse_ref[0, 0] = jnp.where(l > 0.0, m + jnp.log(lsafe), 0.0)


def _bwd_mask(*, i, j, bq, bk, causal, q_offset, kv_len):
    """The live-entry mask of the forward pass, recomputed for a backward
    tile: within the causal diagonal (global indices) and below kv_len."""
    kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    live = None
    if causal:
        qi = q_offset + i * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        live = kj <= qi
    if kv_len is not None:
        in_len = kj < kv_len
        live = in_len if live is None else jnp.logical_and(live, in_len)
    return live


def _bwd_block_live(*, i, j, bq, bk, causal, q_offset, kv_len):
    """pl.when condition mirroring the forward's block-skip rule."""
    cond = None
    if causal:
        cond = j * bk <= q_offset + i * bq + bq - 1
    if kv_len is not None:
        in_len = j * bk < kv_len
        cond = in_len if cond is None else jnp.logical_and(cond, in_len)
    return cond


def _flash_bwd_dq_kernel(*refs, nk: int, bq: int, bk: int, sm_scale: float,
                         causal: bool, q_offset: int, q_len: int, heads: int,
                         has_kv_len: bool):
    """dQ = (P ∘ (dO Vᵀ − Δ)) K · sm_scale, streamed over KV tiles.

    Same grid/index-map family as the forward (one program per (b, h, query
    tile), KV innermost); P is recomputed from (q, k, lse) so no S×S matrix
    ever exists.  Δ (the rowsum(dO ∘ O) delta term) and lse arrive as
    per-row operands.
    """
    if has_kv_len:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kvl_ref,
         dq_ref, acc_ref) = refs
        kv_len = kvl_ref[pl.program_id(0) // heads]
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, acc_ref) = refs
        kv_len = None
    if causal and kv_len is not None:
        q_offset = kv_len - q_len
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)        # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)        # (bk, d)
        do = do_ref[0, 0].astype(jnp.float32)      # (bq, d)
        lse = lse_ref[0, 0]                        # (bq, 1) fp32
        delta = delta_ref[0, 0]                    # (bq, 1) fp32
        s = _dot(q, k, ((1,), (1,))) * sm_scale    # (bq, bk)
        live = _bwd_mask(i=i, j=j, bq=bq, bk=bk, causal=causal,
                         q_offset=q_offset, kv_len=kv_len)
        p = jnp.exp(s - lse)                       # normalized: lse = m+log l
        if live is not None:
            p = jnp.where(live, p, 0.0)
        dp = _dot(do, v, ((1,), (1,)))             # (bq, bk)
        ds = p * (dp - delta) * sm_scale
        acc_ref[...] += _dot(ds, k, ((1,), (0,)))

    cond = _bwd_block_live(i=i, j=j, bq=bq, bk=bk, causal=causal,
                           q_offset=q_offset, kv_len=kv_len)
    if cond is None:
        _body()
    else:
        pl.when(cond)(_body)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, nq: int, nt: int, bq: int, bk: int,
                          sm_scale: float, causal: bool, q_offset: int,
                          q_len: int, heads: int, has_kv_len: bool):
    """dV = Pᵀ dO and dK = (P ∘ (dO Vᵀ − Δ))ᵀ Q · sm_scale per kv tile.

    One program per (b, KV-HEAD, kv tile): the innermost grid axis sweeps
    all G query heads of the group and every query tile, accumulating into
    (bk, d) scratch — the group reduction the grouped layout requires
    happens HERE, so dK/dV come out compact (B, KV, Skv, D) with no
    H-broadcast anywhere in the backward.
    """
    if has_kv_len:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kvl_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        kv_len = kvl_ref[pl.program_id(0) // heads]
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        kv_len = None
    if causal and kv_len is not None:
        q_offset = kv_len - q_len
    j, t = pl.program_id(1), pl.program_id(2)
    i = t % nq                                     # query-tile index

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)        # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)        # (bk, d)
        do = do_ref[0, 0].astype(jnp.float32)      # (bq, d)
        lse = lse_ref[0, 0]                        # (bq, 1) fp32
        delta = delta_ref[0, 0]                    # (bq, 1) fp32
        s = _dot(q, k, ((1,), (1,))) * sm_scale    # (bq, bk)
        live = _bwd_mask(i=i, j=j, bq=bq, bk=bk, causal=causal,
                         q_offset=q_offset, kv_len=kv_len)
        p = jnp.exp(s - lse)
        if live is not None:
            p = jnp.where(live, p, 0.0)
        dv_acc[...] += _dot(p, do, ((0,), (0,)))   # pᵀ dO: (bk, d)
        dp = _dot(do, v, ((1,), (1,)))             # (bq, bk)
        ds = p * (dp - delta) * sm_scale
        dk_acc[...] += _dot(ds, q, ((0,), (0,)))   # dsᵀ q: (bk, d)

    cond = _bwd_block_live(i=i, j=j, bq=bq, bk=bk, causal=causal,
                           q_offset=q_offset, kv_len=kv_len)
    if cond is None:
        _body()
    else:
        pl.when(cond)(_body)

    @pl.when(t == nt - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@dataclasses.dataclass(frozen=True)
class _Config:
    """Hashable static configuration of one flash_attention call — the
    nondiff arg of the custom_vjp, shared by forward and backward."""
    causal: bool
    sm_scale: float
    bq: int
    bk: int
    bq_bwd: int            # 0 = resolve at backward-trace time
    bk_bwd: int
    q_offset: int
    q_len: int
    interpret: bool
    # Engine-layout (q_shape, k_shape) for the "attention_bwd" tile-plan key,
    # or None (direct kernel calls: backward reuses the forward tiles).
    bwd_key: tuple | None = None


def _forward(cfg: _Config, q, k, v, kvl, *, return_lse: bool):
    b, h, sq, d = q.shape
    _, kvh, skv, _ = k.shape
    grp = h // kvh
    bq, bk = cfg.bq, cfg.bk
    grid = (b * h, sq // bq, skv // bk)
    kernel = functools.partial(
        _flash_kernel, nk=grid[2], bq=bq, bk=bk, sm_scale=cfg.sm_scale,
        causal=cfg.causal, q_offset=cfg.q_offset, q_len=cfg.q_len, heads=h,
        has_kv_len=kvl is not None, return_lse=return_lse)
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda g, i, j: (g // h, g % h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d),
                           lambda g, i, j: (g // h, (g % h) // grp, j, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [q, k, v]
    if kvl is not None:
        in_specs.append(KV_LEN_SPEC)
        operands.append(kvl)
    out_specs = q_spec
    out_shape = jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)
    if return_lse:
        # lse is stored as a (bq, 1) column per tile: a (1, bq) row block
        # of a (B, H, Sq) array would put the head axis in the sublane dim.
        lse_spec = pl.BlockSpec((1, 1, bq, 1),
                                lambda g, i, j: (g // h, g % h, i, 0))
        out_specs = [q_spec, lse_spec]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),   # m
                        pltpu.VMEM((bq, _LANES), jnp.float32),   # l
                        pltpu.VMEM((bq, d), jnp.float32)],       # acc
        interpret=cfg.interpret,
        compiler_params=_SEMANTICS,
    )(*operands)
    return out if return_lse else (out, None)


def _resolve_bwd_tiles(cfg: _Config, q, sq: int, skv: int) -> tuple[int, int]:
    """Backward (bq, bk) tiles: the explicit pins, else the rule-made plan
    of the lazy "attention_bwd" key (ops-level calls thread `bwd_key`),
    else the forward tiles.  Whatever the source, each tile is clamped to a
    divisor of the forward-padded extent (gcd keeps the 8/128 alignment:
    both operands are multiples of it)."""
    bq2, bk2 = cfg.bq_bwd, cfg.bk_bwd
    if not (bq2 and bk2):
        if cfg.bwd_key is not None:
            from repro.core import backends
            bq2, bk2 = backends.get_backend("pallas").tiles(
                "attention_bwd", cfg.bwd_key, q.dtype)
        else:
            bq2, bk2 = cfg.bq, cfg.bk
    if sq % bq2:
        bq2 = math.gcd(sq, bq2)
    if skv % bk2:
        bk2 = math.gcd(skv, bk2)
    return bq2, bk2


def _backward(cfg: _Config, q, k, v, kvl, do, lse, delta):
    b, h, sq, d = q.shape
    _, kvh, skv, _ = k.shape
    grp = h // kvh
    bq, bk = _resolve_bwd_tiles(cfg, q, sq, skv)
    has_kvl = kvl is not None
    common = dict(bq=bq, bk=bk, sm_scale=cfg.sm_scale, causal=cfg.causal,
                  q_offset=cfg.q_offset, q_len=cfg.q_len, has_kv_len=has_kvl)

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda g, i, j: (g // h, g % h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d),
                           lambda g, i, j: (g // h, (g % h) // grp, j, 0))
    row_spec = pl.BlockSpec((1, 1, bq, 1),
                            lambda g, i, j: (g // h, g % h, i, 0))
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    operands = [q, k, v, do, lse, delta]
    if has_kvl:
        in_specs.append(KV_LEN_SPEC)
        operands.append(kvl)
    nk = skv // bk
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, nk=nk, heads=h, **common),
        grid=(b * h, sq // bq, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=cfg.interpret,
        compiler_params=_SEMANTICS,
    )(*operands)

    # dK/dV: one program per kv-head; the innermost axis walks the G query
    # heads of the group × the query tiles, reducing into (bk, d) scratch.
    nq = sq // bq
    nt = grp * nq
    qh_spec = pl.BlockSpec(
        (1, 1, bq, d),
        lambda n, jk, t: (n // kvh, (n % kvh) * grp + t // nq, t % nq, 0))
    kvh_spec = pl.BlockSpec((1, 1, bk, d),
                            lambda n, jk, t: (n // kvh, n % kvh, jk, 0))
    rowh_spec = pl.BlockSpec(
        (1, 1, bq, 1),
        lambda n, jk, t: (n // kvh, (n % kvh) * grp + t // nq, t % nq, 0))
    in_specs = [qh_spec, kvh_spec, kvh_spec, qh_spec, rowh_spec, rowh_spec]
    operands = [q, k, v, do, lse, delta]
    if has_kvl:
        in_specs.append(KV_LEN_SPEC)
        operands.append(kvl)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, nq=nq, nt=nt, heads=kvh,
                          **common),
        grid=(b * kvh, skv // bk, nt),
        in_specs=in_specs,
        out_specs=[kvh_spec, kvh_spec],
        out_shape=[jax.ShapeDtypeStruct((b, kvh, skv, d), k.dtype),
                   jax.ShapeDtypeStruct((b, kvh, skv, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=cfg.interpret,
        compiler_params=_SEMANTICS,
    )(*operands)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: _Config, q, k, v, kvl):
    o, _ = _forward(cfg, q, k, v, kvl, return_lse=False)
    return o


def _flash_vjp_fwd(cfg: _Config, q, k, v, kvl):
    o, lse = _forward(cfg, q, k, v, kvl, return_lse=True)
    return o, (q, k, v, kvl, o, lse)


def _flash_vjp_bwd(cfg: _Config, res, do):
    # VJP rules trace OUTSIDE the forward dispatch's named scope, so the
    # backward self-scopes: the R002 trace-lint rule requires every dense
    # contraction in a backward jaxpr to sit under a repro.op.* marker.
    with jax.named_scope("repro.op.attention_bwd"):
        q, k, v, kvl, o, lse = res
        # Delta term: rowsum(dO ∘ O) — elementwise O(S·d), no kernel
        # needed.  Fully-masked rows have O == 0, so delta == 0 there by
        # construction.
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)
        dq, dk, dv = _backward(cfg, q, k, v, kvl, do, lse, delta)
    # kv_len is integer-valued: its cotangent is the symbolic zero float0.
    kvl_ct = (None if kvl is None
              else np.zeros(kvl.shape, dtype=jax.dtypes.float0))
    return dq, dk, dv, kvl_ct


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True, sm_scale=None,
                    bq: int = 256, bk: int = 256, kv_len=None,
                    q_offset: int | None = None, q_len: int = 0,
                    interpret: bool | None = None, bq_bwd: int = 0,
                    bk_bwd: int = 0, bwd_key: tuple | None = None):
    """q: (B, H, Sq, D); k, v: (B, KV, Skv, D) with H % KV == 0.

    Returns (B, H, Sq, D) in q.dtype.  Query head h attends kv-head
    h // (H // KV) — the same kv*G+g head order as the grouped reshape
    ``(B, S, KV, G, D)``; H == KV is plain MHA.  Sq % bq == 0 and
    Skv % bk == 0 (the ops wrapper pads and passes ``kv_len`` to mask the
    key padding).  ``kv_len``: optional (B,) or (B, 1) int32, read from
    scalar memory — keys at positions >= kv_len are masked out for that
    batch row (key padding, decode cache extent).

    Causal alignment: queries right-align against the LIVE key extent.
    Without kv_len that is Skv (``q_offset`` overrides it statically — the
    ops wrapper passes the *unpadded* Skv - Sq so padding does not shift
    the diagonal); with kv_len the offset is the dynamic per-batch
    ``kv_len - q_len`` (``q_len`` is the real, unpadded Sq — chunked
    prefill into a larger cache buffer keeps causality between the new
    tokens).  Fully-masked query rows (row position >= kv_len, or
    kv_len == 0) return exact 0.

    DIFFERENTIABLE (``jax.custom_vjp``): the forward saves the per-row
    logsumexp; two backward kernels compute dQ (query-tile grid) and the
    compact grouped dK/dV (kv-tile grid, group reduction in-kernel —
    (B, KV, Skv, D) out, no H-broadcast).  ``bq_bwd``/``bk_bwd`` pin the
    backward tiles; 0 resolves them from the lazy "attention_bwd"
    tile-plan key when ``bwd_key`` (the engine-layout (q_shape, k_shape))
    is threaded through, else reuses (bq, bk).  Backward tiles that do not
    divide (Sq, Skv) are clamped to gcd divisors, so any MXU-aligned pick
    is safe to pin.  Fully-masked rows produce exact-0 gradients.
    kv_len/q_offset/q_len are gradient-transparent.
    """
    b, h, sq, d = q.shape
    _, kvh, skv, _ = k.shape
    assert sq % bq == 0 and skv % bk == 0, ((sq, skv), (bq, bk))
    assert h % kvh == 0, (h, kvh)
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if q_offset is None:
        q_offset = skv - sq
    kvl = None if kv_len is None else kv_len.astype(jnp.int32).reshape(b)
    cfg = _Config(causal=causal, sm_scale=float(sm_scale), bq=bq, bk=bk,
                  bq_bwd=bq_bwd, bk_bwd=bk_bwd, q_offset=q_offset,
                  q_len=q_len if q_len else sq,
                  interpret=resolve_interpret(interpret), bwd_key=bwd_key)
    return _flash(cfg, q, k, v, kvl)


def flash_attention_with_lse(q, k, v, *, causal: bool = True, sm_scale=None,
                             bq: int = 256, bk: int = 256, kv_len=None,
                             q_offset: int | None = None, q_len: int = 0,
                             interpret: bool | None = None):
    """Forward-only flash attention that also emits the softmax residual.

    Same operand/masking contract as `flash_attention`; returns
    ``(o, lse)`` with ``o`` (B, H, Sq, D) in q.dtype and ``lse`` (B, H, Sq)
    fp32 — the per-row ``m + log l`` in the scaled score space.  This is
    the per-shard partial a sequence-split caller merges with the
    flash-decoding logsumexp combine (kernels/flash_decode.py): each KV
    span contributes a span-normalized ``o`` plus its ``lse``, and the
    combine reweights by ``exp(lse - max lse)``.

    Fully-masked rows carry ``o == 0`` and ``lse == 0`` (any finite value;
    the backward never sees this path).  Callers merging partials must
    convert those rows to the combine's -1e30 empty-span sentinel — the
    row-liveness condition is analytic in (kv_len, q_len), see
    `ops.attention_partial`.  NOT differentiable: partial emissions are an
    inference-path contract, like the split-KV decode kernel."""
    b, h, sq, d = q.shape
    _, kvh, skv, _ = k.shape
    assert sq % bq == 0 and skv % bk == 0, ((sq, skv), (bq, bk))
    assert h % kvh == 0, (h, kvh)
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if q_offset is None:
        q_offset = skv - sq
    kvl = None if kv_len is None else kv_len.astype(jnp.int32).reshape(b)
    cfg = _Config(causal=causal, sm_scale=float(sm_scale), bq=bq, bk=bk,
                  bq_bwd=0, bk_bwd=0, q_offset=q_offset,
                  q_len=q_len if q_len else sq,
                  interpret=resolve_interpret(interpret))
    o, lse = _forward(cfg, q, k, v, kvl, return_lse=True)
    return o, lse[..., 0]
