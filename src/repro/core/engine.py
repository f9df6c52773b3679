"""ComputeEngine — the paper's contribution as a composable JAX module.

Every dense computation in this framework (CNN conv layers via im2col, LM
QKV/O/MLP/MoE projections, SSD intra-chunk matmuls, LM head) routes through
this engine.  The engine itself is a thin dispatcher: each op resolves
through the backend/op registry (core/backends.py), so adding an execution
target is `register_backend(...)` — no engine changes.  Built-in backends:

  pallas : the TPU-target kernels with explicit VMEM BlockSpec tiling —
           compiled on a TPU, interpreted on the CPU backend.
  xla    : jax.lax formulations with the same precision policy and the same
           fused epilogue, expressed so XLA fuses them.  Used where Pallas
           cannot lower (the 512-host-device dry-run on the CPU backend) and
           as the A/B reference for §Perf.

Block shapes come from the per-process tile-plan cache (keyed on
(op, shapes, dtype, backend)) unless pinned via bm/bk/bn.

The engine is a frozen dataclass → hashable → usable as a static jit arg and
inside jit'd model code.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import backends
from repro.core.precision import Precision


@dataclasses.dataclass(frozen=True)
class ComputeEngine:
    backend: str = "xla"
    precision: Precision = Precision("fp32_strict")
    # 0 = auto-pick via the registry's tile-plan cache (the backend's rule).
    bm: int = 0
    bk: int = 0
    bn: int = 0
    # None derives it from the platform (kernels.common.default_interpret);
    # an explicit bool is for compiling against a described chip.
    interpret: bool | None = None

    # ---------------------------------------------------------- dispatch ---
    def _resolve(self, op: str, shapes: tuple, dtype,
                 act: str | None = None) -> backends.OpContext:
        """Look up the backend, take the tile plan from the cache (the
        backend's rule runs once per key, on first sight), count the
        dispatch (trace-time: compiled
        programs pay this once; the detail record — shapes, dtype, the
        fused activation and the RESOLVED tiles, pinned picks included —
        feeds the trace linter's dispatch log)."""
        be = backends.get_backend(self.backend)
        if self.bm and self.bk and self.bn and op != "attention":
            # Pinned (bm, bk, bn) applies to the GEMM-shaped ops only;
            # attention tiles by (bq, bk) sequence blocks and always
            # resolves through the cache.
            tiles = (self.bm, self.bk, self.bn)
        else:
            tiles = be.tiles(op, shapes, dtype)
        backends.record_dispatch(self.backend, op, shapes=shapes,
                                 dtype=dtype, tiles=tiles, act=act)
        return backends.OpContext(precision=self.precision,
                                  interpret=self.interpret, tiles=tiles)

    def _op(self, op: str):
        return backends.get_backend(self.backend).op(op)

    def _guard(self, op: str, *operands):
        """Arm the autodiff capability check: operands of an op the backend
        does not declare `differentiable` pass through a guard whose jvp
        raises a clear NotImplementedError — a VJP-less kernel op can then
        never die with a bare AssertionError deep inside jax.grad."""
        return backends.guard_grad(backends.get_backend(self.backend), op,
                                   *operands)

    # --------------------------------------------------------------- ops ---
    def matmul(self, x, w, *, scale=None, shift=None, act: str = "linear",
               out_dtype=None):
        """act((x @ w) * scale + shift) over the last dim of x.

        Args:
          x: (..., K) input; leading dims are flattened for the kernel and
            restored on the result.
          w: (K, N) weight.
          scale, shift: (N,) epilogue vectors or None (folded BN / bias).
          act: activation name understood by `kernels.common.apply_act`.
          out_dtype: result dtype; defaults to the policy compute dtype.

        Returns (..., N) with fp32 accumulation regardless of out_dtype.
        Raises NotImplementedError when the backend lacks the op.
        """
        *lead, k = x.shape
        n = w.shape[-1]
        out_dtype = out_dtype or self.precision.compute_dtype
        xc = x.astype(self.precision.compute_dtype).reshape(-1, k)
        wc = w.astype(self.precision.compute_dtype)
        xc, wc, scale, shift = self._guard("matmul", xc, wc, scale, shift)
        ctx = self._resolve("matmul", (xc.shape[0], k, n), xc.dtype, act)
        with jax.named_scope(backends.op_scope("matmul")):
            y = self._op("matmul")(xc, wc, scale, shift, act=act,
                                   out_dtype=out_dtype, ctx=ctx)
        return y.reshape(*lead, n)

    def bmm(self, x, w, *, out_dtype=None):
        """Batched GEMM (B, M, K) @ (B, K, N), fp32 accumulate.

        Returns (B, M, N) in `out_dtype` (default: x.dtype).  Raises
        NotImplementedError when the backend lacks the op.
        """
        b, m, k = x.shape
        n = w.shape[-1]
        out_dtype = out_dtype or x.dtype
        xc = x.astype(self.precision.compute_dtype)
        wc = w.astype(self.precision.compute_dtype)
        xc, wc = self._guard("bmm", xc, wc)
        ctx = self._resolve("bmm", (m, k, n), xc.dtype)
        with jax.named_scope(backends.op_scope("bmm")):
            return self._op("bmm")(xc, wc, out_dtype=out_dtype, ctx=ctx)

    def conv2d(self, x, w, *, scale=None, shift=None, size: int,
               stride: int = 1, pad: int = 0, act: str = "linear",
               out_dtype=None):
        """Fused conv+BN+activation as ONE engine invocation.

        Args:
          x: (B, H, W, Cin) NHWC input.
          w: (kh*kw*Cin, Cout) flattened HWIO weight.
          scale, shift: (Cout,) or None (folded batch-norm / bias epilogue).
          size, stride, pad: square kernel size, stride, symmetric padding.
          act: activation name; out_dtype defaults to the compute dtype.

        Returns (B, OH, OW, Cout).  Raises NotImplementedError when the
        backend lacks the op.
        """
        out_dtype = out_dtype or self.precision.compute_dtype
        xc = x.astype(self.precision.compute_dtype)
        wc = w.astype(self.precision.compute_dtype)
        xc, wc, scale, shift = self._guard("conv2d", xc, wc, scale, shift)
        ctx = self._resolve(
            "conv2d", (xc.shape, wc.shape[-1], size, stride, pad), xc.dtype,
            act)
        with jax.named_scope(backends.op_scope("conv2d")):
            return self._op("conv2d")(xc, wc, scale, shift, size=size,
                                      stride=stride, pad=pad, act=act,
                                      out_dtype=out_dtype, ctx=ctx)

    def attention(self, q, k, v, *, causal: bool = True, sm_scale=None,
                  kv_len=None):
        """softmax(q k^T / sqrt(D)) v, fp32 softmax statistics, grouped KV.

        Args:
          q: (B, Sq, H, D) queries.
          k, v: (B, Skv, KV, D) with KV <= H and H % KV == 0 — the compact
            grouped layout: query head h attends kv-head h // (H/KV) (the
            kv*G+g head order of the ``(B, S, KV, G, D)`` reshape) and NO
            caller-side broadcast happens.  KV == H is plain MHA.
          causal: queries right-align against the LIVE key extent — Skv,
            or kv_len when given (chunked prefill into a larger cache
            buffer keeps causality between the new tokens).  Sq <= Skv is
            required (ValueError otherwise).
          sm_scale: softmax scale; defaults to 1/sqrt(D).  May be traced
            (array-valued) on every backend.
          kv_len: None, scalar, or (B,) int — keys at positions >= kv_len
            are masked per batch row; values above Skv clamp to Skv.
            Decode passes its cache extent pos+1.  Fully-masked query rows
            (kv_len == 0, or row position >= kv_len under causal) return
            exact 0 on every backend.

        Returns (B, Sq, H, D) in q's compute dtype.  Raises ValueError on
        a non-dividing head ratio, mismatched q/k/v dtypes or shapes, or a
        mis-shaped kv_len — at dispatch, not deep inside a kernel.  This
        is the single-device kernel-backed op; the distribution-aware
        blockwise formulation GSPMD shards lives in models/attention.py.
        """
        from repro.kernels import ops as kernel_ops
        kernel_ops.validate_attention_shapes(q, k, v)
        if causal and q.shape[1] > k.shape[1]:
            raise ValueError(
                f"causal attention requires Sq <= Skv (right-aligned "
                f"queries); got Sq={q.shape[1]}, Skv={k.shape[1]}")
        kernel_ops.validate_kv_len(kv_len, q.shape[0])
        if kv_len is not None:
            kv_len = jnp.asarray(kv_len, jnp.int32)
        qc = q.astype(self.precision.compute_dtype)
        kc = k.astype(self.precision.compute_dtype)
        vc = v.astype(self.precision.compute_dtype)
        qc, kc, vc, sm_scale = self._guard("attention", qc, kc, vc,
                                           sm_scale)
        ctx = self._resolve("attention", (qc.shape, kc.shape), qc.dtype)
        with jax.named_scope(backends.op_scope("attention")):
            return self._op("attention")(qc, kc, vc, causal=causal,
                                         sm_scale=sm_scale, kv_len=kv_len,
                                         ctx=ctx)

    def einsum(self, spec: str, x, y, *, out_dtype=None,
               acc_dtype=jnp.float32):
        """Precision-policy einsum for the non-GEMM-shaped contractions
        (attention scores, SSD chunk terms).  fp32 accumulate by default;
        acc_dtype=precision.reduce_dtype lets collectives ride bf16 under
        the mixed policy (MoE expert GEMMs)."""
        out_dtype = out_dtype or self.precision.compute_dtype
        with jax.named_scope(backends.op_scope("einsum")):
            acc = jnp.einsum(spec, x.astype(self.precision.compute_dtype),
                             y.astype(self.precision.compute_dtype),
                             preferred_element_type=acc_dtype,
                             precision=self.precision.lax_precision)
        return acc.astype(out_dtype)


# Default engines.  Dry-run/bench lowering uses XLA backend (Pallas cannot
# lower on the CPU backend); kernel tests and the TPU target use pallas.
def make_engine(backend: str = "xla", policy: str = "fp32_strict",
                interpret: bool | None = None, **tiles) -> ComputeEngine:
    backends.get_backend(backend)  # fail fast on unknown backends
    return ComputeEngine(backend=backend, precision=Precision(policy),
                         interpret=interpret, **tiles)
