"""Backend/op registry for the compute engine.

The paper's claim is that ONE full-precision compute engine serves every
dense layer of a CNN (conv-as-im2col, FC, deconv) across a heterogeneous
system.  This module is the software form of that claim: a fixed op set
(`OP_SET`) that every backend must implement, a `register_backend` /
`get_backend` API so new execution targets plug in without touching
`ComputeEngine`, and a per-process tile-plan cache so each backend's
block-shape rule runs once per (op, shapes, dtype, backend) and its plan
is reused across traces (docs/engine_api.md, "Tile plans").

Built-in backends:

  pallas : the TPU-target kernels (kernels/gemm.py, flash_attention.py) with
           explicit VMEM BlockSpec tiling — compiled on a TPU, interpreted
           on the CPU backend (kernels.common.default_interpret).
  xla    : jax.lax dot_general / jnp formulations with the same precision
           policy and the same fused epilogue, expressed so XLA fuses them.

A third backend (`ref`, the pure-jnp oracles in kernels/ref.py) registers
through the public API in the test suite — the reference example of adding a
backend; see docs/engine_api.md.

Op contract (all impls are pure functions called at trace time; `ctx` is an
`OpContext` carrying the engine's precision policy, interpret flag and the
tile plan resolved from the tile-plan cache):

  matmul(x, w, scale, shift, *, act, out_dtype, ctx)   (M,K)@(K,N) -> (M,N)
      fused epilogue act((x @ w) * scale + shift), scale/shift (N,) or None,
      fp32 accumulation.
  bmm(x, w, *, out_dtype, ctx)                         (B,M,K)@(B,K,N)
  conv2d(x, w, scale, shift, *, size, stride, pad, act, out_dtype, ctx)
      NHWC x, flattened (kh*kw*Cin, Cout) w, same fused epilogue — one
      engine invocation per conv+BN+act layer.
  attention(q, k, v, *, causal, sm_scale, kv_len, ctx)
      softmax(q k^T / sqrt(D)) v with fp32 softmax statistics.  Grouped-KV
      native: q (B,Sq,H,D), k/v (B,Skv,KV,D) with KV <= H, H % KV == 0 —
      query head h attends kv-head h // (H/KV), NO caller-side broadcast
      (KV == H is plain MHA).  kv_len (None | scalar | (B,)) masks keys
      at/beyond the per-batch length (decode cache extent); causal queries
      right-align against kv_len when given, else Skv; fully-masked rows
      return exact 0.  Output (B,Sq,H,D).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp

from repro.core.precision import Precision
from repro.kernels import ops as kernel_ops
from repro.kernels.common import apply_act, im2col

OP_SET = ("matmul", "bmm", "conv2d", "attention")

# Every engine dispatch runs its backend impl under
# jax.named_scope(op_scope(op)); the marker lands on the traced equations'
# name stacks, where the trace linter's R002 rule (analysis/rules/) checks
# that every dense contraction originated from a registry op.
OP_SCOPE_PREFIX = "repro.op."


def op_scope(op: str) -> str:
    """The named-scope marker the engine wraps a dispatch of `op` in."""
    return OP_SCOPE_PREFIX + op


@dataclasses.dataclass(frozen=True)
class OpContext:
    """Per-dispatch context handed to backend op implementations."""
    precision: Precision
    # None: kernels derive interpret mode from the platform
    # (kernels.common.default_interpret).
    interpret: bool | None = None
    # (bm, bk, bn) for GEMM-shaped ops on tiled backends, (bm, bn) for a
    # conv2d that forms its patches in VMEM, (bq, bk) sequence tiles for
    # attention, () otherwise.
    tiles: tuple = ()


@dataclasses.dataclass(frozen=True)
class Backend:
    """A registered execution target: op impls + an optional tile rule.

    `tile_picker(op, shapes, dtype) -> tuple` is the backend's block-shape
    rule, a pure function of the problem; `tiles` memoizes its plans.

    `differentiable` is the per-op autodiff capability: the subset of the
    registered ops that support `jax.grad` through their implementation
    (a custom VJP, or plain differentiable jnp).  The engine consults it
    at dispatch and raises a CLEAR NotImplementedError when a
    non-differentiable op is differentiated — instead of the bare
    AssertionError a VJP-less pallas_call dies with deep inside autodiff.
    """
    name: str
    ops: Mapping[str, Callable]
    tile_picker: Callable[[str, tuple, Any], tuple] | None = None
    differentiable: frozenset = frozenset(OP_SET)

    def supports_grad(self, op: str) -> bool:
        """Whether `jax.grad` may flow through this backend's `op`."""
        return op in self.differentiable

    def op(self, name: str) -> Callable:
        """The registered impl for `name`.

        Raises NotImplementedError when this backend does not provide the
        op (registration already rejected names outside OP_SET).
        """
        try:
            return self.ops[name]
        except KeyError:
            raise NotImplementedError(
                f"backend {self.name!r} does not implement op {name!r} "
                f"(has: {sorted(self.ops)})") from None

    def tiles(self, op: str, shapes: tuple, dtype) -> tuple:
        """Block plan for one dispatch, memoized in the tile-plan cache
        (see `tile_plan`)."""
        if self.tile_picker is None:  # untiled backend: skip the cache
            return ()
        return tile_plan(op, shapes, dtype, self.name, self.tile_picker)


_REGISTRY: dict[str, Backend] = {}


def register_backend(name: str, ops: Mapping[str, Callable], *,
                     tile_picker=None, differentiable=None,
                     overwrite: bool = False) -> Backend:
    """Register a backend implementing (a subset of) OP_SET.

    Args:
      name: registry key; `make_engine(name)` selects it.
      ops: op name -> impl following the op contract above.
      tile_picker: optional `(op, shapes, dtype) -> (bm, bk, bn)` rule;
        its plans are memoized in the process-wide tile-plan cache.
      differentiable: iterable of op names `jax.grad` may flow through, or
        None meaning ALL registered ops (the right default for plain-jnp
        backends, which JAX differentiates natively).  Kernel backends
        whose ops lack a VJP must name only the ops that have one — the
        engine turns a differentiated dispatch of any other op into a
        clear NotImplementedError.
      overwrite: replace an existing registration instead of raising.

    Returns the registered `Backend`.

    Raises ValueError on a duplicate name without `overwrite`, on op
    names outside OP_SET — typos fail at registration, not dispatch — or
    on a `differentiable` entry naming an unregistered op.
    """
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {name!r} already registered "
                         "(pass overwrite=True to replace)")
    unknown = set(ops) - set(OP_SET)
    if unknown:
        raise ValueError(f"unknown ops {sorted(unknown)}; op set is {OP_SET}")
    diff = frozenset(ops if differentiable is None else differentiable)
    if not diff <= set(ops):
        raise ValueError(f"differentiable names unregistered ops "
                         f"{sorted(diff - set(ops))}; registered: "
                         f"{sorted(ops)}")
    be = Backend(name=name, ops=dict(ops), tile_picker=tile_picker,
                 differentiable=diff)
    _REGISTRY[name] = be
    return be


def get_backend(name: str) -> Backend:
    """The registered `Backend` for `name`.

    Raises ValueError (naming the registered backends) when unknown.
    """
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; registered: "
                         f"{list_backends()}") from None


def list_backends() -> tuple[str, ...]:
    """Sorted names of all registered backends."""
    return tuple(sorted(_REGISTRY))


def unregister_backend(name: str) -> None:
    """Remove a backend registration (no-op when absent)."""
    _REGISTRY.pop(name, None)


# --------------------------------------------------- autodiff capability ---
# A kernel op without a VJP dies deep inside autodiff with a bare
# AssertionError when differentiated.  The engine instead threads operands
# of ops the backend does NOT declare differentiable through this identity
# custom_jvp: forward passes are untouched, and any differentiation hits
# the jvp rule — which raises a clear, actionable error at trace time.

@functools.partial(jax.custom_jvp, nondiff_argnums=(0, 1, 2))
def _nondiff_guard(op, backend, diff, *operands):
    return operands


@_nondiff_guard.defjvp
def _nondiff_guard_jvp(op, backend, diff, primals, tangents):
    raise NotImplementedError(
        f"op {op!r} on backend {backend!r} is not differentiable — "
        f"jax.grad cannot flow through its kernel.  The backend declares "
        f"differentiable={sorted(diff)}, which does not include {op!r}.  "
        f"Use a backend that supports grad for {op!r} (the 'xla' backend "
        f"differentiates every registry op), or register the backend with "
        f"a custom-VJP implementation of {op!r}.")


def guard_grad(backend: Backend, op: str, *operands):
    """Pass `operands` through unchanged, arming the clear
    not-differentiable error unless `backend` declares `op` differentiable.
    Called by the engine on every dispatch with ALL gradient-carrying
    operands — the epilogue `scale`/`shift` vectors and a traced
    `sm_scale` included, since a bias gradient alone reaches the kernel's
    backward too.  None and python scalars pass through untouched (no
    tangent can flow through a non-array).  Free after jit when armed, a
    no-op when the op supports autodiff.  The raised error names the op,
    the backend, the `differentiable` set it checked, and the xla
    fallback."""
    if backend.supports_grad(op):
        return operands
    arrays = [x for x in operands if isinstance(x, jax.Array)]
    if not arrays:
        return operands
    diff = tuple(sorted(backend.differentiable))
    guarded = iter(_nondiff_guard(op, backend.name, diff, *arrays))
    return tuple(next(guarded) if isinstance(x, jax.Array) else x
                 for x in operands)


# ----------------------------------------------------- tile-plan cache ---
# Block-shape plans are memoized process-wide, keyed on
# (op, shapes, dtype, backend): a miss runs the backend's rule (for pallas,
# the exact-plan and VMEM-budget pickers in kernels/ops.py) and stores the
# result.  Stats and the plans themselves are observable so benchmarks and
# tests can assert cache behaviour and which keys a trace resolved.

_TILE_CACHE: dict[tuple, tuple] = {}
_TILE_STATS = collections.Counter()


def set_autotune_policy(policy: str) -> str:
    """Accept the one tile policy there is: "heuristic" (the rule-made,
    memoized plan), which it returns.  Any other value raises ValueError.

    A vestige kept only because bench/benchlib/harness.py still calls it;
    it goes once the harness stops doing so.
    """
    if policy != "heuristic":
        raise ValueError(f"unknown autotune policy {policy!r}; tile plans "
                         f"come from the backend's rule (\"heuristic\")")
    return policy


def tile_plan(op: str, shapes: tuple, dtype, backend: str,
              picker: Callable[[str, tuple, Any], tuple]) -> tuple:
    """Block-shape plan keyed on (op, shapes, dtype, backend): the cached
    plan, or on a miss `picker`'s plan, stored for the next lookup."""
    dtype_str = str(jnp.dtype(dtype))
    key = (op, shapes, dtype_str, backend)
    plan = _TILE_CACHE.get(key)
    if plan is not None:
        _TILE_STATS["hits"] += 1
        return plan
    _TILE_STATS["misses"] += 1
    plan = _TILE_CACHE[key] = tuple(picker(op, shapes, dtype_str))
    return plan


def validate_tiles(op: str, shapes: tuple, dtype, tiles: tuple) -> list[str]:
    """Static legality of a resolved tile plan for one dispatch problem.

    Args:
      op: registry op name (plus the "attention_bwd" / "gemm_bwd"
        backward keys and the "attention_decode" formulation key).
      shapes: the op's cache-key shapes (see `gemm_dims` /
        `kernel_ops.attention_dims` for the accepted forms).
      dtype: operand dtype (anything `jnp.dtype` accepts).
      tiles: the resolved plan — (bm, bk, bn) for GEMM-shaped ops,
        (bm, bn) for an implicit-patch conv2d, (bq, bk) for attention,
        (bk_split, n_splits) for the decode formulation.  An empty plan
        is vacuously legal (untiled backend).

    Returns a list of human-readable problems (empty = legal): MXU
    (8, 128) lane alignment, the kernels' VMEM working-set budget, and
    tiles no larger than the padded problem extents.  Malformed
    shapes/plans (a bad pin) come back as a problem string, never an
    exception.
    """
    if not tiles:
        return []
    try:
        if op == "attention_decode":
            _, sq, skv, _, _, d = kernel_ops.attention_dims(shapes)
            return kernel_ops.validate_attention_decode_tiles(
                sq, skv, d, dtype, tuple(tiles))
        if op in ("attention", "attention_bwd"):
            _, sq, skv, _, _, d = kernel_ops.attention_dims(shapes)
            return kernel_ops.validate_attention_tiles(
                sq, skv, d, dtype, tuple(tiles),
                bwd=(op == "attention_bwd"))
        if op == "conv2d" and len(tiles) == 2:
            return kernel_ops.validate_conv_implicit_tiles(
                *shapes, dtype, tuple(tiles))
        dims = gemm_dims(op, shapes)
        if dims is None:
            return []
        return kernel_ops.validate_gemm_tiles(*dims, dtype, tuple(tiles))
    except Exception as e:
        return [f"unparseable shapes/plan for op {op!r}: {e!r}"]


def cache_stats() -> dict[str, int]:
    """Counters for the tile-plan cache: `hits`/`misses` are lookups,
    `entries` is the resident cache size."""
    return {"hits": _TILE_STATS["hits"], "misses": _TILE_STATS["misses"],
            "entries": len(_TILE_CACHE)}


def tile_plans() -> dict[tuple, tuple]:
    """The plans this process resolved, ``{(op, shapes, dtype, backend):
    plan}``."""
    return dict(_TILE_CACHE)


def clear_tile_cache() -> None:
    """Reset the in-process cache and its stats."""
    _TILE_CACHE.clear()
    _TILE_STATS.clear()


# ------------------------------------------------------ dispatch counts ---
# Incremented at trace time by ComputeEngine — under jit each compiled
# program pays them exactly once, so a snapshot diff around a trace is the
# static op plan of that program (CompiledNetwork.profile reports it).
# Alongside the counters, a bounded LOG keeps the per-dispatch detail
# (shapes, dtype, resolved tile plan): a slice of it between two
# `dispatch_log_size()` marks is the full dispatch record of one trace —
# the input to the trace linter's R001/R004 rules.

_DISPATCH = collections.Counter()
_DISPATCH_LOG: list[dict] = []
_DISPATCH_LOG_LIMIT = 65536


def record_dispatch(backend: str, op: str, shapes: tuple | None = None,
                    dtype=None, tiles: tuple = ()) -> None:
    """Count one engine dispatch and append its detail record
    ``{backend, op, shapes, dtype, tiles}`` to the bounded log (oldest
    records win; past the limit only the counter advances).  A GEMM-shaped
    dispatch with a (bm, bk, bn) plan also records ``gemm``, its real
    (m, k, n), and ``padded``, the extents the kernel runs on
    (`kernel_ops.gemm_padding`; equal to ``gemm`` for an exact plan)."""
    _DISPATCH[(backend, op)] += 1
    if len(_DISPATCH_LOG) < _DISPATCH_LOG_LIMIT:
        rec = {"backend": backend, "op": op, "shapes": shapes,
               "dtype": None if dtype is None else str(jnp.dtype(dtype)),
               "tiles": tuple(tiles or ())}
        dims = gemm_dims(op, shapes) if len(rec["tiles"]) == 3 else None
        if dims is not None:
            rec["gemm"] = dims
            rec["padded"] = kernel_ops.gemm_padding(*dims, rec["tiles"])
        _DISPATCH_LOG.append(rec)


def gemm_padded(records) -> dict:
    """How much of a dispatch-log slice's tiled GEMM work the wrapper pads.

    Returns ``{gemms, padded, operand_bytes, padded_operand_bytes,
    shapes}``: the GEMM dispatches with a (bm, bk, bn) plan, how many of
    them pad any operand, the bytes of their x and w operands (per batch
    element for bmm) as given and as the kernel runs them, and each padded
    dispatch as ``((m, k, n), (mp, kp, np))``.
    """
    gemms = [r for r in records if "padded" in r]
    out = {"gemms": len(gemms), "padded": 0, "operand_bytes": 0,
           "padded_operand_bytes": 0, "shapes": []}
    for r in gemms:
        item = jnp.dtype(r["dtype"]).itemsize
        (m, k, n), (mp, kp, np_) = r["gemm"], r["padded"]
        out["operand_bytes"] += (m * k + k * n) * item
        out["padded_operand_bytes"] += (mp * kp + kp * np_) * item
        if r["padded"] != r["gemm"]:
            out["padded"] += 1
            out["shapes"].append((r["gemm"], r["padded"]))
    return out


def dispatch_counts() -> dict[tuple[str, str], int]:
    return dict(_DISPATCH)


def dispatch_log() -> list[dict]:
    """Copy of the per-dispatch detail records (trace order)."""
    return list(_DISPATCH_LOG)


def dispatch_log_size() -> int:
    """Current log length — snapshot before a trace, slice after."""
    return len(_DISPATCH_LOG)


def counts_since(snapshot: Mapping[tuple[str, str], int]
                 ) -> dict[tuple[str, str], int]:
    out = {k: v - snapshot.get(k, 0) for k, v in _DISPATCH.items()}
    return {k: v for k, v in out.items() if v}


def reset_dispatch_counts() -> None:
    """Clear the dispatch counters AND the detail log."""
    _DISPATCH.clear()
    _DISPATCH_LOG.clear()


# --------------------------------------------------------- shared pieces ---

def im2col_conv2d(matmul_impl: Callable) -> Callable:
    """Build a conv2d op from a matmul op via materialized im2col — the
    paper's canonical conv lowering.  The pallas backend's conv2d forms
    the patches inside the GEMM where its tile rule gives a (bm, bn)
    implicit-patch plan, and runs this under a (bm, bk, bn) plan
    otherwise."""

    def conv2d(x, w, scale, shift, *, size, stride, pad, act, out_dtype,
               ctx):
        cols = im2col(x, size, size, stride, pad)     # (B, OH, OW, khkwC)
        b, oh, ow, _ = cols.shape
        y = matmul_impl(cols.reshape(b * oh * ow, -1), w, scale, shift,
                        act=act, out_dtype=out_dtype, ctx=ctx)
        return y.reshape(b, oh, ow, -1)

    return conv2d


def im2col_phased(records) -> dict:
    """How many of a dispatch-log slice's conv2d dispatches build their
    patches from the input's phases (`kernels.common.im2col` does at
    every stride above 1): ``{convs, phased}``."""
    # A conv2d record's shapes: (x.shape, cout, size, stride, pad).
    strides = [r["shapes"][3] for r in records if r["op"] == "conv2d"]
    return {"convs": len(strides), "phased": sum(s > 1 for s in strides)}


def conv_implicit(records) -> dict:
    """How many of a dispatch-log slice's conv2d dispatches form their
    patches inside the GEMM (those that ran under an implicit-patch
    (bm, bn) plan): ``{convs, implicit}``."""
    convs = [r for r in records if r["op"] == "conv2d"]
    return {"convs": len(convs),
            "implicit": sum(len(r["tiles"]) == 2 for r in convs)}


# ------------------------------------------------------- pallas backend ---

def _pallas_matmul(x, w, scale, shift, *, act, out_dtype, ctx):
    bm, bk, bn = ctx.tiles or (0, 0, 0)
    return kernel_ops.matmul(x, w, scale, shift, act=act,
                             out_dtype=out_dtype, bm=bm, bk=bk, bn=bn,
                             interpret=ctx.interpret)


def _pallas_bmm(x, w, *, out_dtype, ctx):
    bm, bk, bn = ctx.tiles or (0, 0, 0)
    return kernel_ops.bmm(x, w, out_dtype=out_dtype, bm=bm, bk=bk, bn=bn,
                          interpret=ctx.interpret)


_pallas_im2col_conv2d = im2col_conv2d(_pallas_matmul)


def _pallas_conv2d(x, w, scale, shift, *, size, stride, pad, act, out_dtype,
                   ctx):
    # A (bm, bn) plan forms the patches in VMEM; a (bm, bk, bn) plan runs
    # im2col + the GEMM.
    if len(ctx.tiles) == 2:
        bm, bn = ctx.tiles
        return kernel_ops.conv2d_implicit(
            x, w, scale, shift, size=size, pad=pad, act=act,
            out_dtype=out_dtype, bm=bm, bn=bn, interpret=ctx.interpret)
    return _pallas_im2col_conv2d(x, w, scale, shift, size=size,
                                 stride=stride, pad=pad, act=act,
                                 out_dtype=out_dtype, ctx=ctx)


def _pallas_attention(q, k, v, *, causal, sm_scale, kv_len=None, ctx):
    # Decode-shaped problems (short query, deep KV) switch formulation:
    # the split-KV kernel grids over KV spans so B*H no longer bounds
    # occupancy.  Its (bk_split, n_splits) tiles resolve lazily inside the
    # wrapper under their own "attention_decode" key — ctx.tiles carries
    # the forward (bq, bk) plan, which does not apply to this grid.
    # Inference-only: decode dispatches are never differentiated (training
    # geometries have Sq == Skv and keep the custom-VJP kernel below).
    if kernel_ops.use_decode_formulation(q.shape[1], k.shape[1]):
        return kernel_ops.attention_decode(q, k, v, kv_len,
                                           causal=causal, sm_scale=sm_scale,
                                           interpret=ctx.interpret)
    bq, bk = ctx.tiles if len(ctx.tiles) == 2 else (0, 0)
    return kernel_ops.attention(q, k, v, kv_len, causal=causal,
                                sm_scale=sm_scale, bq=bq, bk=bk,
                                interpret=ctx.interpret)


def gemm_dims(op: str, shapes: tuple) -> tuple[int, int, int] | None:
    """Normalize an op's cache-key shapes to the (m, k, n) GEMM problem the
    tiled kernels actually run — conv2d maps to its im2col GEMM, and a
    "gemm_bwd" key's (variant, rows, contraction, cols) maps to the
    backward problem's own dims.  None for ops without a (bm, bk, bn)-
    shaped tiling (attention tiles by sequence: see
    `kernel_ops.attention_dims`)."""
    if op in ("matmul", "bmm", "gemm_bwd"):
        return tuple(shapes[-3:])
    if op == "conv2d":
        (b, h, w, c), n, size, stride, pad = shapes
        oh = (h + 2 * pad - size) // stride + 1
        ow = (w + 2 * pad - size) // stride + 1
        return (b * oh * ow, size * size * c, n)
    return None


def _pallas_tile_picker(op: str, shapes: tuple, dtype) -> tuple:
    if op == "attention":
        return kernel_ops.default_attention_blocks(
            *kernel_ops.attention_dims(shapes), dtype)
    if op == "attention_bwd":
        return kernel_ops.default_attention_bwd_blocks(
            *kernel_ops.attention_dims(shapes), dtype)
    if op == "attention_decode":
        return kernel_ops.default_attention_decode_blocks(
            *kernel_ops.attention_dims(shapes), dtype)
    if op == "conv2d":
        plan = kernel_ops.default_conv_implicit_blocks(*shapes, dtype)
        if plan:
            return plan
    if op == "gemm_bwd":
        variant, rows, kdim, cols = shapes
        return kernel_ops.default_gemm_bwd_blocks(variant, rows, kdim,
                                                  cols, dtype)
    dims = gemm_dims(op, shapes)
    if dims is None:
        return ()
    return kernel_ops.default_blocks(op, *dims, dtype)


# ---------------------------------------------------------- xla backend ---

def _xla_matmul(x, w, scale, shift, *, act, out_dtype, ctx):
    # Same math as the Pallas kernel, fused by XLA.  Emission dtype =
    # precision.reduce_dtype (see core/precision.py): f32 under fp32_strict;
    # bf16 under mixed so row-parallel partial-sum all-reduces ride the wire
    # at half width.
    prec = ctx.precision
    rdt = prec.reduce_dtype
    acc = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=rdt, precision=prec.lax_precision)
    if scale is not None:
        acc = acc * scale.astype(rdt)
    if shift is not None:
        acc = acc + shift.astype(rdt)
    return apply_act(acc, act).astype(out_dtype)


def _xla_bmm(x, w, *, out_dtype, ctx):
    acc = jnp.einsum("bmk,bkn->bmn", x, w,
                     preferred_element_type=jnp.float32,
                     precision=ctx.precision.lax_precision)
    return acc.astype(out_dtype)


def _xla_attention(q, k, v, *, causal, sm_scale, kv_len=None, ctx):
    # Grouped without broadcast: the G query heads sharing a kv-head are
    # FOLDED into the query-sequence axis — (B, KV, G*Sq, D) against
    # (B, KV, Skv, D) — so the contraction stays MHA-shaped (which XLA
    # lowers well) while the KV operand is read once per group.  G == 1
    # (MHA) reduces to the plain per-head formulation.
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    qf = (q.reshape(B, Sq, KV, G, D).transpose(0, 2, 3, 1, 4)
          .reshape(B, KV, G * Sq, D).astype(jnp.float32))
    kt = k.transpose(0, 2, 1, 3).astype(jnp.float32)   # (B, KV, Skv, D)
    vt = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kt,
                   precision=ctx.precision.lax_precision) * sm_scale
    # (B|1, Sq, Skv) mask; causal right-aligns against the LIVE key extent
    # (kv_len when given, else Skv) — same contract as the flash kernel.
    kj = jnp.arange(Skv)
    mask = jnp.ones((1, Sq, Skv), bool)
    if kv_len is not None:
        # Clamp to the key buffer (same as the pallas wrapper) so every
        # backend derives the same causal alignment from an oversized
        # cache-extent value.
        kvl = jnp.minimum(jnp.broadcast_to(
            jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,)), Skv)
        mask = mask & (kj[None, None] < kvl[:, None, None])
        if causal:
            qi = jnp.arange(Sq)[None, :, None] + (kvl[:, None, None] - Sq)
            mask = mask & (kj[None, None] <= qi)
    elif causal:
        qi = jnp.arange(Sq)[:, None] + (Skv - Sq)
        mask = mask & (kj[None, :] <= qi)[None]
    mb = mask.shape[0]
    maskf = jnp.broadcast_to(mask[:, None], (mb, G, Sq, Skv)).reshape(
        mb, G * Sq, Skv)
    s = jnp.where(maskf[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # Fully-masked rows (kv_len == 0, or row position >= kv_len under
    # causal) softmax to NaN; emit exact 0 like the flash kernel.
    p = jnp.where(maskf.any(-1)[:, None, :, None], p, 0.0)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vt,
                   precision=ctx.precision.lax_precision)
    return (o.reshape(B, KV, G, Sq, D).transpose(0, 3, 1, 2, 4)
            .reshape(B, Sq, H, D).astype(q.dtype))


# Every pallas op carries a custom VJP: flash attention's backward kernels
# live in kernels/flash_attention.py, the GEMM backward kernels (dX/dW,
# shared by matmul, bmm and conv2d-as-im2col — im2col itself backpropagates
# through a col2im scatter in kernels/common.py, and the implicit-patch
# conv through the im2col formulation's VJP) in kernels/gemm.py, with
# backward tiles resolved lazily under "gemm_bwd"/"attention_bwd" tile-plan
# keys.  The full op set trains on the kernel path.
register_backend("pallas", {
    "matmul": _pallas_matmul,
    "bmm": _pallas_bmm,
    "conv2d": _pallas_conv2d,
    "attention": _pallas_attention,
}, tile_picker=_pallas_tile_picker,
    differentiable=("matmul", "bmm", "conv2d", "attention"))

register_backend("xla", {
    "matmul": _xla_matmul,
    "bmm": _xla_bmm,
    "conv2d": im2col_conv2d(_xla_matmul),
    "attention": _xla_attention,
})
