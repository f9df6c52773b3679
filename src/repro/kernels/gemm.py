"""The paper's "Innovative Compute Engine", TPU-native.

FPGA original: a tiled FP32 GEMM unit that (1) stages operand tiles in BRAM,
(2) streams tiles between producer/consumer PEs so the MAC array never stalls
on external memory, and (3) fuses the activation stage into the stream.

TPU adaptation (see DESIGN.md §2):
  * BRAM tiles        -> VMEM blocks, made explicit with pl.BlockSpec.
  * HLS streams       -> the pallas_call grid pipeline: while the MXU consumes
                         tile (i, j, s) the DMA engine prefetches (i, j, s+1);
                         the fp32 accumulator lives in a VMEM scratch and
                         never round-trips to HBM during the K loop.
  * stream-fused act  -> epilogue applied to the VMEM tile on the last K step,
                         so the output is written to HBM exactly once.
  * MAC array width   -> block shapes default to multiples of (8, 128) MXU
                         lanes; 128-aligned shapes hit the systolic sweet spot.

Grid layout is (M/bm, N/bn, K/bk) with K innermost ("arbitrary" semantics on
TPU): consecutive steps share the same output tile, which is what lets the
accumulator stay resident in VMEM — the moral equivalent of the paper's
"multiple mathematical executions in a single clock cycle" on a streaming
operand window.

DIFFERENTIABLE via ``jax.custom_vjp`` (both `gemm` and `bmm`): when an
activation epilogue is fused, the forward kernel additionally emits the
``act'(pre-act)`` residual (and the raw fp32 accumulator when a `scale`
epilogue needs its gradient) from the same VMEM tile it already holds — the
pre-activation never round-trips through HBM twice.  The backward runs two
tiled pallas kernels on the forward's operands:

  dX = (dY ∘ act'(u) ∘ scale) Wᵀ    rows M, contraction N, cols K
  dW = Xᵀ (dY ∘ act'(u) ∘ scale)    rows K, contraction M, cols N

each with its own (bm, bk, bn) plan resolved LAZILY at backward-trace time
from the rule-made ``"gemm_bwd"`` tile-plan keys (variant-tagged: ("dx", m, n,
k) / ("dw", k, m, n) in the backward problem's own dims), its operands
zero-padded up to that plan's multiples where it does not divide them —
the pattern flash_attention.py established for ``attention_bwd``.
dscale/dshift are column reductions of the residuals (no kernel needed).
Inference-only traces never resolve a backward key.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import act_deriv, apply_act, resolve_interpret

# M/N tiles are independent (parallel); K carries the accumulator.
_SEMANTICS_2D = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))
# Batched GEMMs add a leading parallel batch grid dim.
_SEMANTICS_3D = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))

# The backward's dispatch scope: contains backends.OP_SCOPE_PREFIX
# ("repro.op."), so the R002 trace-lint rule accepts the backward kernels'
# contractions as registry-dispatched (the VJP bwd rule traces OUTSIDE the
# forward dispatch's named_scope).
GEMM_BWD_SCOPE = "repro.op.gemm_bwd"


def _acc_dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)


def _gemm_kernel(x_ref, w_ref, scale_ref, shift_ref, o_ref, g_ref, racc_ref,
                 acc_ref, *, nsteps: int, act: str, out_dtype):
    """One (bm, bn) output tile; K-loop accumulates into VMEM scratch.

    Optional residual outputs written on the last K step, straight from the
    accumulator tile still resident in VMEM: ``g_ref`` = act'(pre-act)
    (fused-activation backward), ``racc_ref`` = the raw fp32 accumulator
    (x @ w before the epilogue — the dscale reduction needs it).
    """
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _acc_dot(x_ref[...], w_ref[...], ((1,), (0,)))

    @pl.when(pl.program_id(2) == nsteps - 1)
    def _epilogue():
        _store_epilogue(acc_ref[...], scale_ref, shift_ref, o_ref, g_ref,
                        racc_ref, act=act, out_dtype=out_dtype)


def _store_epilogue(acc, scale_ref, shift_ref, o_ref, g_ref=None,
                    racc_ref=None, *, act: str, out_dtype):
    """Write act(acc * scale + shift) to ``o_ref`` and the optional
    residuals (act'(pre-act), the raw accumulator) beside it."""
    u = acc
    if scale_ref is not None:
        u = u * scale_ref[...]
    if shift_ref is not None:
        u = u + shift_ref[...]
    o_ref[...] = apply_act(u, act).astype(out_dtype)
    if g_ref is not None:
        g_ref[...] = act_deriv(u, act)
    if racc_ref is not None:
        racc_ref[...] = acc


@dataclasses.dataclass(frozen=True)
class _Config:
    """Hashable static configuration of one gemm/bmm call — the nondiff arg
    of the custom_vjp, shared by forward and backward."""
    act: str
    out_dtype: str
    bm: int
    bk: int
    bn: int
    has_scale: bool
    has_shift: bool
    interpret: bool
    # Engine-layout unpadded (m, k, n) for the "gemm_bwd" tile-plan keys, or
    # None (direct kernel calls: backward permutes the forward tiles).
    bwd_key: tuple | None = None
    bwd_dx: tuple = ()     # () = resolve at backward-trace time
    bwd_dw: tuple = ()
    batched: bool = False  # bmm: keys tagged "bdx"/"bdw", batch grid dim


def _gemm_forward(cfg: _Config, x, w, scale, shift, *, residuals: bool):
    """Run the fused forward kernel; with ``residuals``, additionally emit
    g = act'(pre-act) (when an activation is fused) and the raw fp32
    accumulator (when a scale epilogue is fused)."""
    m, k = x.shape
    _, n = w.shape
    bm, bk, bn = cfg.bm, cfg.bk, cfg.bn
    out_dtype = jnp.dtype(cfg.out_dtype)
    grid = (m // bm, n // bn, k // bk)
    want_g = residuals and cfg.act != "linear"
    want_acc = residuals and cfg.has_scale

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),   # x tile: row i, K step s
        pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),   # w tile: K step s, col j
    ]
    args = [x, w]
    # scale/shift ride along as (1, bn) column blocks (same col index map).
    if scale is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, s: (0, j)))
        args.append(scale)
    if shift is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, s: (0, j)))
        args.append(shift)

    out_spec = pl.BlockSpec((bm, bn), lambda i, j, s: (i, j))
    out_specs = [out_spec]
    out_shape = [jax.ShapeDtypeStruct((m, n), out_dtype)]
    for want in (want_g, want_acc):
        if want:
            out_specs.append(out_spec)
            out_shape.append(jax.ShapeDtypeStruct((m, n), jnp.float32))

    # Bind optional refs positionally.
    def kernel_fn(*refs):
        x_ref, w_ref = refs[0], refs[1]
        idx = 2
        s_ref = b_ref = None
        if scale is not None:
            s_ref = refs[idx]; idx += 1
        if shift is not None:
            b_ref = refs[idx]; idx += 1
        o_ref = refs[idx]; idx += 1
        g_ref = racc_ref = None
        if want_g:
            g_ref = refs[idx]; idx += 1
        if want_acc:
            racc_ref = refs[idx]; idx += 1
        acc_ref = refs[idx]
        _gemm_kernel(x_ref, w_ref, s_ref, b_ref, o_ref, g_ref, racc_ref,
                     acc_ref, nsteps=grid[2], act=cfg.act,
                     out_dtype=out_dtype)

    out = pl.pallas_call(
        kernel_fn,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=cfg.interpret,
        compiler_params=_SEMANTICS_2D,
    )(*args)
    y = out[0]
    idx = 1
    g = racc = None
    if want_g:
        g = out[idx]; idx += 1
    if want_acc:
        racc = out[idx]
    return y, g, racc


# ------------------------------------------------------ backward kernels ---
# Two tiled GEMMs per backward, each on the forward's operands with its
# OWN (bm, bk, bn) plan (the backward problems transpose the roles of the
# forward dims, so the forward winner is usually mis-aligned for them).

def _bwd_matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, nsteps: int,
                       grid_axis: int, dims: tuple, out_dtype):
    """Shared K-innermost accumulate-and-write body for the backward GEMMs:
    `dims` picks the contraction axes of the two VMEM tiles."""
    @pl.when(pl.program_id(grid_axis) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...] if a_ref.ndim == 2 else a_ref[0]
    b = b_ref[...] if b_ref.ndim == 2 else b_ref[0]
    acc_ref[...] += _acc_dot(a, b, dims)

    @pl.when(pl.program_id(grid_axis) == nsteps - 1)
    def _out():
        if o_ref.ndim == 2:
            o_ref[...] = acc_ref[...].astype(out_dtype)
        else:
            o_ref[0] = acc_ref[...].astype(out_dtype)


def gemm_bwd_dx(dy, w, *, bm: int, bk: int, bn: int, out_dtype=None,
                interpret: bool | None = None):
    """dX[m, k] = Σ_n dY[m, n] · W[k, n] — the input-gradient GEMM.

    dy: (M, N), w: (K, N) → (M, K).  Backward-problem tile roles:
    bm | M (rows), bk | N (contraction), bn | K (cols).
    """
    m, n = dy.shape
    k, n2 = w.shape
    assert n == n2, (dy.shape, w.shape)
    assert m % bm == 0 and n % bk == 0 and k % bn == 0, (
        f"dx problem {(m, n, k)} vs blocks {(bm, bk, bn)}")
    out_dtype = out_dtype or dy.dtype
    grid = (m // bm, k // bn, n // bk)
    call = pl.pallas_call(
        functools.partial(_bwd_matmul_kernel, nsteps=grid[2], grid_axis=2,
                          dims=((1,), (1,)), out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),   # dY tile
            pl.BlockSpec((bn, bk), lambda i, j, s: (j, s)),   # W tile (Kᵢ, Nₛ)
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, k), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
        compiler_params=_SEMANTICS_2D,
    )
    return call(dy, w)


def gemm_bwd_dw(x, dy, *, bm: int, bk: int, bn: int, out_dtype=None,
                interpret: bool | None = None):
    """dW[k, n] = Σ_m X[m, k] · dY[m, n] — the weight-gradient GEMM.

    x: (M, K), dy: (M, N) → (K, N).  Backward-problem tile roles:
    bm | K (rows), bk | M (contraction), bn | N (cols).
    """
    m, k = x.shape
    m2, n = dy.shape
    assert m == m2, (x.shape, dy.shape)
    assert k % bm == 0 and m % bk == 0 and n % bn == 0, (
        f"dw problem {(k, m, n)} vs blocks {(bm, bk, bn)}")
    out_dtype = out_dtype or x.dtype
    grid = (k // bm, n // bn, m // bk)
    call = pl.pallas_call(
        functools.partial(_bwd_matmul_kernel, nsteps=grid[2], grid_axis=2,
                          dims=((0,), (0,)), out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, s: (s, i)),   # X tile (Mₛ, Kᵢ)
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),   # dY tile
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((k, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
        compiler_params=_SEMANTICS_2D,
    )
    return call(x, dy)


def bmm_bwd_dx(dy, w, *, bm: int, bk: int, bn: int, out_dtype=None,
               interpret: bool | None = None):
    """Batched dX: (B, M, N) × (B, K, N) → (B, M, K), per-batch grid dim."""
    b, m, n = dy.shape
    _, k, _ = w.shape
    assert m % bm == 0 and n % bk == 0 and k % bn == 0
    out_dtype = out_dtype or dy.dtype
    grid = (b, m // bm, k // bn, n // bk)
    call = pl.pallas_call(
        functools.partial(_bwd_matmul_kernel, nsteps=grid[3], grid_axis=3,
                          dims=((1,), (1,)), out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda g, i, j, s: (g, i, s)),
            pl.BlockSpec((1, bn, bk), lambda g, i, j, s: (g, j, s)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, s: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, m, k), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
        compiler_params=_SEMANTICS_3D,
    )
    return call(dy, w)


def bmm_bwd_dw(x, dy, *, bm: int, bk: int, bn: int, out_dtype=None,
               interpret: bool | None = None):
    """Batched dW: (B, M, K) × (B, M, N) → (B, K, N), per-batch grid dim."""
    b, m, k = x.shape
    _, _, n = dy.shape
    assert k % bm == 0 and m % bk == 0 and n % bn == 0
    out_dtype = out_dtype or x.dtype
    grid = (b, k // bm, n // bn, m // bk)
    call = pl.pallas_call(
        functools.partial(_bwd_matmul_kernel, nsteps=grid[3], grid_axis=3,
                          dims=((0,), (0,)), out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bk, bm), lambda g, i, j, s: (g, s, i)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, s: (g, s, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, s: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, k, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
        compiler_params=_SEMANTICS_3D,
    )
    return call(x, dy)


def gemm_bwd_problem(variant: str, m: int, k: int, n: int
                     ) -> tuple[int, int, int]:
    """Map an engine-layout (m, k, n) GEMM to the backward variant's own
    (rows, contraction, cols) problem dims — what the ``"gemm_bwd"``
    tile-plan key carries and the tile roles refer to."""
    if variant.endswith("dx"):
        return (m, n, k)
    if variant.endswith("dw"):
        return (k, m, n)
    raise ValueError(f"unknown gemm_bwd variant {variant!r}")


def _resolve_bwd_tiles(cfg: _Config, variant: str, dtype
                       ) -> tuple[int, int, int]:
    """Backward (bm, bk, bn) for one variant: the explicit pin, else the
    rule-made plan of the lazy ``("gemm_bwd", (variant, rows, contraction,
    cols), dtype)`` key (ops-level calls thread `bwd_key`), else the
    forward tiles permuted into the variant's roles.  The backward pads its operands to
    the plan's multiples (`_pad_to`), so any plan is safe."""
    pin = cfg.bwd_dx if variant.endswith("dx") else cfg.bwd_dw
    if pin:
        return tuple(pin)
    if cfg.bwd_key is not None:
        from repro.core import backends
        key_shapes = (variant,) + gemm_bwd_problem(variant, *cfg.bwd_key)
        return backends.get_backend("pallas").tiles(
            "gemm_bwd", key_shapes, dtype)
    if variant.endswith("dx"):
        return cfg.bm, cfg.bn, cfg.bk
    return cfg.bk, cfg.bm, cfg.bn


def _pad_to(a, blocks: tuple[int, int]):
    """Zero-pad the last two dims of ``a`` up to multiples of ``blocks``
    (zeros are exact in a GEMM); ``a`` itself when they already divide."""
    pads = [(0, 0)] * (a.ndim - 2) + [(0, -(-d // b) * b - d) for d, b in
                                      zip(a.shape[-2:], blocks)]
    return jnp.pad(a, pads) if any(hi for _, hi in pads) else a


# ---------------------------------------------------------- gemm (fused) ---

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gemm(cfg: _Config, x, w, scale, shift):
    y, _, _ = _gemm_forward(cfg, x, w, scale, shift, residuals=False)
    return y


def _gemm_vjp_fwd(cfg: _Config, x, w, scale, shift):
    y, g, racc = _gemm_forward(cfg, x, w, scale, shift, residuals=True)
    return y, (x, w, scale, g, racc)


def _gemm_vjp_bwd(cfg: _Config, res, dy):
    x, w, scale, g, racc = res
    m, k = x.shape
    n = w.shape[1]
    with jax.named_scope(GEMM_BWD_SCOPE):
        dyf = dy.astype(jnp.float32)
        # dY through the epilogue: u = acc*scale + shift, y = act(u).
        dyg = dyf * g if g is not None else dyf          # dL/du
        dshift = (jnp.sum(dyg, axis=0, keepdims=True)
                  if cfg.has_shift else None)
        dscale = (jnp.sum(dyg * racc, axis=0, keepdims=True)
                  if cfg.has_scale else None)
        dacc = dyg * scale if cfg.has_scale else dyg     # dL/d(x@w)
        dacc = dacc.astype(x.dtype)
        bm, bk, bn = _resolve_bwd_tiles(cfg, "dx", x.dtype)
        dx = gemm_bwd_dx(_pad_to(dacc, (bm, bk)), _pad_to(w, (bn, bk)),
                         bm=bm, bk=bk, bn=bn, out_dtype=x.dtype,
                         interpret=cfg.interpret)[:m, :k]
        bm, bk, bn = _resolve_bwd_tiles(cfg, "dw", x.dtype)
        dw = gemm_bwd_dw(_pad_to(x, (bk, bm)), _pad_to(dacc, (bk, bn)),
                         bm=bm, bk=bk, bn=bn, out_dtype=w.dtype,
                         interpret=cfg.interpret)[:k, :n]
    return dx, dw, dscale, dshift


_gemm.defvjp(_gemm_vjp_fwd, _gemm_vjp_bwd)


def gemm(x, w, *, scale=None, shift=None, act: str = "linear",
         out_dtype=None, bm: int = 256, bk: int = 512, bn: int = 256,
         interpret: bool | None = None, bwd_key: tuple | None = None,
         bwd_dx: tuple = (), bwd_dw: tuple = ()):
    """Fused tiled GEMM: act((x @ w) * scale + shift).

    x: (M, K), w: (K, N) with M % bm == K % bk == N % bn == 0 (ops.matmul
    picks such blocks, or pads); scale/shift: (N,) vectors or None.  fp32
    accumulation always.

    DIFFERENTIABLE (``jax.custom_vjp``): the forward emits act'(pre-act)
    (and the raw accumulator when `scale` is given) as residuals; two
    backward pallas kernels compute dX/dW from the same operands.
    ``bwd_dx``/``bwd_dw`` pin the backward (bm, bk, bn) plans; () resolves
    them at backward-trace time from the lazy ``"gemm_bwd"`` tile-plan
    keys when ``bwd_key`` (the unpadded engine (m, k, n)) is threaded
    through, else permutes the forward tiles.  The backward pads its
    operands to whatever plan it runs, so any MXU-aligned pin is safe.
    Forward-only callers never touch a backward key.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (
        f"unpadded shapes {(m, k, n)} vs blocks {(bm, bk, bn)}")
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    cfg = _Config(act=act, out_dtype=str(out_dtype), bm=bm, bk=bk, bn=bn,
                  has_scale=scale is not None, has_shift=shift is not None,
                  interpret=resolve_interpret(interpret), bwd_key=bwd_key,
                  bwd_dx=tuple(bwd_dx), bwd_dw=tuple(bwd_dw))
    sp = None if scale is None else scale.reshape(1, n).astype(jnp.float32)
    bp = None if shift is None else shift.reshape(1, n).astype(jnp.float32)
    return _gemm(cfg, x, w, sp, bp)


# ------------------------------------------------------------------- bmm ---

def _bmm_forward(cfg: _Config, x, w):
    b, m, k = x.shape
    _, _, n = w.shape
    bm, bk, bn = cfg.bm, cfg.bk, cfg.bn
    out_dtype = jnp.dtype(cfg.out_dtype)
    grid = (b, m // bm, n // bn, k // bk)
    call = pl.pallas_call(
        functools.partial(_bwd_matmul_kernel, nsteps=grid[3], grid_axis=3,
                          dims=((1,), (0,)), out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda g, i, j, s: (g, i, s)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, s: (g, s, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, s: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=cfg.interpret,
        compiler_params=_SEMANTICS_3D,
    )
    return call(x, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bmm(cfg: _Config, x, w):
    return _bmm_forward(cfg, x, w)


def _bmm_vjp_fwd(cfg: _Config, x, w):
    return _bmm_forward(cfg, x, w), (x, w)


def _bmm_vjp_bwd(cfg: _Config, res, dy):
    x, w = res
    _, m, k = x.shape
    n = w.shape[-1]
    with jax.named_scope(GEMM_BWD_SCOPE):
        dyc = dy.astype(x.dtype)
        bm, bk, bn = _resolve_bwd_tiles(cfg, "bdx", x.dtype)
        dx = bmm_bwd_dx(_pad_to(dyc, (bm, bk)), _pad_to(w, (bn, bk)),
                        bm=bm, bk=bk, bn=bn, out_dtype=x.dtype,
                        interpret=cfg.interpret)[:, :m, :k]
        bm, bk, bn = _resolve_bwd_tiles(cfg, "bdw", x.dtype)
        dw = bmm_bwd_dw(_pad_to(x, (bk, bm)), _pad_to(dyc, (bk, bn)),
                        bm=bm, bk=bk, bn=bn, out_dtype=w.dtype,
                        interpret=cfg.interpret)[:, :k, :n]
    return dx, dw


_bmm.defvjp(_bmm_vjp_fwd, _bmm_vjp_bwd)


def bmm(x, w, *, out_dtype=None, bm: int = 256, bk: int = 256, bn: int = 256,
        interpret: bool | None = None, bwd_key: tuple | None = None,
        bwd_dx: tuple = (), bwd_dw: tuple = ()):
    """Batched GEMM (B, M, K) @ (B, K, N) with per-batch grid dimension.

    DIFFERENTIABLE via the same custom-VJP machinery as `gemm`: backward
    tiles resolve lazily under variant-tagged ``"gemm_bwd"`` keys
    ("bdx"/"bdw" — the batch dimension scales all candidates equally and
    stays out of the key, like the forward "bmm" key).
    """
    b, m, k = x.shape
    b2, k2, n = w.shape
    assert b == b2 and k == k2
    assert m % bm == 0 and k % bk == 0 and n % bn == 0
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    cfg = _Config(act="linear", out_dtype=str(out_dtype), bm=bm, bk=bk,
                  bn=bn, has_scale=False, has_shift=False,
                  interpret=resolve_interpret(interpret), bwd_key=bwd_key,
                  bwd_dx=tuple(bwd_dx), bwd_dw=tuple(bwd_dw), batched=True)
    return _bmm(cfg, x, w)


# ------------------------------------------------- implicit-patch conv ---
# A stride-1 KxK convolution as the fused GEMM without im2col's KH*KW-fold
# patch matrix in HBM.  Each image's padded input is flattened to
# (rows * Wp, Cin): output pixel (i, j) sits at flat row r = i * Wp + j,
# and tap (ki, kj) reads flat row r + ki * Wp + kj.  So a block of bm
# output rows reads one contiguous window of its bm rows plus a halo of
# (K - 1) * (Wp + 1), and every tap is that window at a static offset: the
# patches are formed in VMEM.  The kernel computes Wp outputs per image
# row; those past OW read past the row's end and are discarded.
#
# The pitch Wp (W + 2 * pad) is rounded up to a multiple of 8 where that
# discards at most 2 of every 9 flat rows (a 7-wide 3x3's own share): the
# flat views of the padded input and of the output are then bitcasts of
# their (8, 128)-tiled 4-D layouts rather than copies.
_MAX_DISCARD = 2 / 9


def conv_layout(h: int, w: int, size: int, pad: int
                ) -> tuple[int, int, int]:
    """How `conv_gemm` lays out a stride-1 size x size convolution of an
    (h, w) image: (wp, rows, halo) — the flat rows per image row, the flat
    output rows per image, and the halo rows a block's window reads beyond
    its own."""
    oh, ow = h + 2 * pad - size + 1, w + 2 * pad - size + 1
    wp = w + 2 * pad
    aligned = -(-wp // 8) * 8
    if aligned - ow <= _MAX_DISCARD * aligned:
        wp = aligned
    return wp, oh * wp, (size - 1) * (wp + 1)


def _conv_gemm_kernel(x_ref, w_ref, scale_ref, shift_ref, o_ref, *,
                      offs: tuple, act: str, out_dtype):
    """One (bm, bn) output block from its input window x_ref (win, Cin):
    tap t is the window's rows offs[t] .. offs[t] + bm."""
    bm = o_ref.shape[0]
    taps = [x_ref[pl.ds(off, bm), :] for off in offs]
    if x_ref.shape[1] % 128:
        # Rows short of the MXU depth: one dot over the (bm, taps * Cin)
        # patch tile, the im2col GEMM's shape, not shallow ones per tap.
        acc = _acc_dot(jnp.concatenate(taps, axis=1),
                       w_ref[...].reshape(-1, w_ref.shape[-1]),
                       ((1,), (0,)))
    else:
        acc = _acc_dot(taps[0], w_ref[0], ((1,), (0,)))
        for t in range(1, len(taps)):
            acc += _acc_dot(taps[t], w_ref[t], ((1,), (0,)))
    _store_epilogue(acc, scale_ref, shift_ref, o_ref, act=act,
                    out_dtype=out_dtype)


def conv_gemm(x, w, scale=None, shift=None, *, size: int, pad: int,
              act: str = "linear", out_dtype=None, bm: int, bn: int,
              interpret: bool | None = None):
    """Fused stride-1 convolution act(conv(x, w) * scale + shift) with the
    patches formed in VMEM.

    x: (B, H, W, Cin); w: (size*size*Cin, N) in (kh, kw, Cin) row order;
    scale/shift: (N,) or None.  bm flat output rows of one image per block
    (a multiple of 8, or all of `conv_layout`'s rows), bn | N.  Forward
    only: `ops.conv2d_implicit` gives it its VJP.  The kernel is named
    `matmul`, the fused GEMM's name, so that traces file convolution time
    under one kernel whichever form runs it.
    """
    b, h, wd, c = x.shape
    n = w.shape[-1]
    oh, ow = h + 2 * pad - size + 1, wd + 2 * pad - size + 1
    wp, rows, halo = conv_layout(h, wd, size, pad)
    nm = -(-rows // bm)
    win = -(-(bm + halo) // 8) * 8
    # Image rows in the flat layout: enough that every window lies inside
    # its image, and a multiple of 8 flat rows, so each window starts on
    # a sublane tile.
    hx = h + 2 * pad
    while hx * wp < (nm - 1) * bm + win or hx * wp % 8:
        hx += 1
    lx = hx * wp
    xf = jnp.pad(x, ((0, 0), (pad, hx - h - pad), (pad, wp - wd - pad),
                     (0, 0))).reshape(b * lx, c)
    wt = w.reshape(size * size, c, n)
    offs = tuple(ki * wp + kj for ki in range(size) for kj in range(size))
    out_dtype = jnp.dtype(out_dtype or x.dtype)

    # Grid (column block, image, row block), columns outermost: each
    # weight block is fetched once; the input windows once per column
    # block.  Windows overlap by the halo, hence element offsets.
    in_specs = [
        pl.BlockSpec((pl.Element(win), pl.Element(c)),
                     lambda j, g, i: (pl.multiple_of(g * lx + i * bm, 8), 0)),
        pl.BlockSpec((size * size, c, bn), lambda j, g, i: (0, 0, j)),
    ]
    args = [xf, wt]
    for vec in (scale, shift):
        if vec is not None:
            in_specs.append(pl.BlockSpec((1, bn), lambda j, g, i: (0, j)))
            args.append(vec.reshape(1, n).astype(jnp.float32))

    def kernel_fn(*refs):
        x_ref, w_ref, *vecs, o_ref = refs
        vecs = iter(vecs)
        s_ref = next(vecs) if scale is not None else None
        b_ref = next(vecs) if shift is not None else None
        _conv_gemm_kernel(x_ref, w_ref, s_ref, b_ref, o_ref, offs=offs,
                          act=act, out_dtype=out_dtype)

    out = pl.pallas_call(
        kernel_fn,
        grid=(n // bn, b, nm),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, bm, bn), lambda j, g, i: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, nm * bm, n), out_dtype),
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        name="matmul",
    )(*args)
    return out[:, :rows].reshape(b, oh, wp, n)[:, :, :ow]
