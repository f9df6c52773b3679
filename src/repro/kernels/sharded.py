"""shard_map wrappers around the Pallas kernel set: distribution inside
the backend, kernels unchanged.

The paper maps ONE full-precision network onto whatever compute a
heterogeneous system offers — partitioning is the toolflow's job, not the
network's.  This module is that idea for a device mesh: the same fused-GEMM
and flash-attention kernels `ops.py` exposes run per-shard inside
`shard_map` over the installed concrete mesh (sharding/hints.physical_mesh),
so model code never forks on `mesh_active()` — the `sharded_pallas` backend
(core/shard_backend.py) decides distribution at dispatch time.

Sharding decisions, in order of preference (every helper degrades to the
single-device wrapper when no mesh is installed or nothing divides — ONE
kernel-backed path at every scale):

  GEMMs      : rows (the flattened token axis) over the strategy's batch
               axes; weights/epilogue vectors replicated.  Zero collectives.
  attention  : batch over the strategy's batch axes, and/or KV-head groups
               over the 'model' axis (strategy "tp") — per-shard problems
               are complete attention problems, zero collectives.
  seq-split  : decode-shaped dispatches (short query, deep cache) whose
               batch/heads don't divide shard the KEY axis instead: each
               device reduces its span to a partial (o, lse) via
               `ops.attention_partial`, an all-gather crosses the span
               boundary, and `flash_decode.combine` merges — the split-KV
               flash-decoding merge, across devices instead of grid
               programs.

Inside the shard bodies the kernel wrappers resolve their block plans from
the PER-SHARD shapes under the usual "pallas" tile-plan keys, so tile picks
stay device-local (a (1, 4096)-row shard never inherits the global
problem's tiles).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import flash_decode as decode_kernel
from repro.kernels import ops as kernel_ops
from repro.sharding import hints


def mesh_plan():
    """(mesh, batch_axes, model_axis) for the installed concrete mesh.

    batch_axes are the strategy's batch axes (sharding/hints.batch_axes —
    under "fsdp" the model axis carries batch) present in the mesh with
    size > 1; model_axis is 'model' under strategy "tp" when present with
    size > 1, else None.  Returns None off-mesh or on a 1-device mesh —
    callers then run the plain single-device wrapper.
    """
    mesh = hints.physical_mesh()
    if mesh is None or mesh.size <= 1:
        return None
    shape = dict(mesh.shape)
    batch = tuple(a for a in hints.batch_axes() if shape.get(a, 1) > 1)
    model = ("model" if hints.current_strategy() == "tp"
             and shape.get("model", 1) > 1 else None)
    return mesh, batch, model


def _axis_size(mesh, axes) -> int:
    return math.prod([mesh.shape[a] for a in axes]) if axes else 1


def _shmap(body, mesh, in_specs, out_specs):
    # check_vma=False: pallas_call has no varying-manual-axes rule, and
    # every body here is replication-correct by construction (outputs
    # either carry the sharded axis or are all-gathered).
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _on_mesh(body, mesh, args, specs, out_spec):
    """`body(*args)` inside shard_map over `mesh`, each arg split by its
    spec (P() replicates).  None args reach `body` as None and take no
    spec.  On a multi-device mesh every kernel call goes through here, even
    when nothing divides: the SPMD partitioner cannot split a Mosaic
    kernel, so a replicated shard_map is what runs it whole per device."""
    live = [i for i, a in enumerate(args) if a is not None]

    def wrapped(*xs):
        full = [None] * len(args)
        for i, x in zip(live, xs):
            full[i] = x
        return body(*full)

    return _shmap(wrapped, mesh, tuple(specs[i] for i in live),
                  out_spec)(*(args[i] for i in live))


# ------------------------------------------------------------------ GEMMs ---

def matmul(x, w, scale=None, shift=None, *, act: str = "linear",
           out_dtype=None, interpret: bool | None = None):
    """Row-sharded fused GEMM: (M, K) rows over the batch axes, w and the
    (N,) epilogue vectors replicated, output rows sharded — zero
    collectives.  M is the flattened token axis, so conv-as-im2col rows
    shard here too.  Off-mesh this is `ops.matmul`; on a mesh whose batch
    axes don't divide M every device runs the whole GEMM."""
    def body(x, w, scale, shift):
        return kernel_ops.matmul(x, w, scale, shift, act=act,
                                 out_dtype=out_dtype, interpret=interpret)

    plan = mesh_plan()
    if plan is None:
        return body(x, w, scale, shift)
    mesh, batch, _ = plan
    n = _axis_size(mesh, batch)
    rows = batch if (n > 1 and x.shape[0] % n == 0) else None
    return _on_mesh(body, mesh, (x, w, scale, shift),
                    (P(rows, None), P(None, None), P(None), P(None)),
                    P(rows, None))


def bmm(x, w, *, out_dtype=None, interpret: bool | None = None):
    """Batch-sharded (B, M, K) @ (B, K, N): both operands shard B over the
    batch axes.  Off-mesh this is `ops.bmm`; on a mesh whose batch axes
    don't divide B every device runs the whole product."""
    def body(x, w):
        return kernel_ops.bmm(x, w, out_dtype=out_dtype, interpret=interpret)

    plan = mesh_plan()
    if plan is None:
        return body(x, w)
    mesh, batch, _ = plan
    n = _axis_size(mesh, batch)
    spec = P(batch if (n > 1 and x.shape[0] % n == 0) else None, None, None)
    return _on_mesh(body, mesh, (x, w), (spec, spec), spec)


# -------------------------------------------------------------- attention ---

def _local_attention(q, k, v, kv_len, sm_scale, *, causal, interpret):
    """The single-device pallas dispatch, formulation choice included:
    decode-shaped per-shard problems take the split-KV kernel, everything
    else the custom-VJP forward kernel.  Shard bodies run this on
    per-shard operands, so block plans resolve from LOCAL shapes under the
    same "pallas" tile-plan keys engine dispatch uses."""
    if kernel_ops.use_decode_formulation(q.shape[1], k.shape[1]):
        return kernel_ops.attention_decode(q, k, v, kv_len, sm_scale,
                                           causal=causal,
                                           interpret=interpret)
    return kernel_ops.attention(q, k, v, kv_len, sm_scale, causal=causal,
                                interpret=interpret)


def attention(q, k, v, kv_len=None, sm_scale=None, *, causal: bool = True,
              interpret: bool | None = None):
    """Mesh-sharded grouped attention; operand contract of `ops.attention`.

    Batch rows shard over the strategy's batch axes and/or KV-head groups
    over the 'model' axis (group boundaries are contiguous in H — query
    head h attends kv-head h // G — so an H split into KV/tp-group chunks
    never cuts a group).  Decode-shaped dispatches neither divides take
    the sequence-split path: per-span partials merged by the flash-decode
    logsumexp combine across devices.  Differentiable on the batch/heads
    paths (the kernel's custom VJP flows through shard_map); the
    seq-split path is inference-only, like the split-KV formulation it
    generalizes.  When nothing divides, every device runs the whole
    problem."""
    kernel_ops.validate_attention_shapes(q, k, v)
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    kernel_ops.validate_kv_len(kv_len, b)
    plan = mesh_plan()
    if plan is None:
        return _local_attention(q, k, v, kv_len, sm_scale, causal=causal,
                                interpret=interpret)
    mesh, batch, model = plan
    if sm_scale is not None:
        # A traced sm_scale can't ride the shard_map body closure: fold it
        # into q here (the same fp32 fold the wrappers apply) and dispatch
        # unscaled — multiplying by the remaining 1.0 is fp-exact.
        scale = jnp.asarray(sm_scale, jnp.float32)
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        sm_scale = 1.0
    n_b = _axis_size(mesh, batch)
    batch = batch if (n_b > 1 and b % n_b == 0) else ()
    heads = model if (model and kvh % mesh.shape[model] == 0) else None
    kvl = (None if kv_len is None else jnp.broadcast_to(
        jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,)))
    seq_axes = tuple(a for a in mesh.axis_names if mesh.shape[a] > 1)
    n_s = _axis_size(mesh, seq_axes)
    if (not (batch or heads) and n_s > 1 and skv % n_s == 0
            and kernel_ops.use_decode_formulation(sq, skv)):
        return _seq_split_attention(q, k, v, kvl, sm_scale, mesh, seq_axes,
                                    causal=causal, interpret=interpret)

    def body(q, k, v, kvl):
        return _local_attention(q, k, v, kvl, sm_scale, causal=causal,
                                interpret=interpret)

    bspec = batch if batch else None
    spec = P(bspec, None, heads, None)
    return _on_mesh(body, mesh, (q, k, v, kvl), (spec, spec, spec,
                                                 P(bspec)), spec)


def _seq_split_attention(q, k, v, kvl, sm_scale, mesh, axes, *, causal,
                         interpret):
    """Sequence-split KV across `axes`: each device owns one contiguous key
    span and reduces it to a span-normalized partial (o, lse) with a
    RELATIVE live extent ``kv_len - offset`` — which preserves both the
    length mask and the right-aligned causal diagonal span-locally (see
    `ops.attention_partial`).  An all-gather crosses the span boundary and
    the flash-decoding `combine` merges the partials; every device
    computes the (tiny) merge, so the output comes back replicated."""
    b, sq, _, _ = q.shape
    skv = k.shape[1]
    span = skv // _axis_size(mesh, axes)
    if kvl is None:
        kvl = jnp.full((b,), skv, jnp.int32)
    rep4 = P(None, None, None, None)
    kv_spec = P(None, axes, None, None)

    def body(q, k, v, kvl):
        offset = jax.lax.axis_index(axes) * span
        o, lse = kernel_ops.attention_partial(
            q, k, v, kvl - offset, sm_scale, causal=causal,
            interpret=interpret)
        o_all = jax.lax.all_gather(o.astype(jnp.float32), axes)
        lse_all = jax.lax.all_gather(lse, axes)
        out = decode_kernel.combine(jnp.moveaxis(o_all, 0, 2),
                                    jnp.moveaxis(lse_all, 0, 2))
        return out.transpose(0, 2, 1, 3).astype(q.dtype)

    return _shmap(body, mesh, (rep4, kv_spec, kv_spec, P(None)),
                  rep4)(q, k, v, kvl)
