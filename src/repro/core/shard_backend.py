"""The `sharded_pallas` backend: one kernel-backed op path at every scale.

Registered through the PUBLIC `register_backend` seam (the same API the
test suite's "ref" backend uses), this backend runs the pallas kernel set
per-shard inside `shard_map` over the installed concrete mesh — batch and
KV-head-group sharding per `sharding/hints.current_strategy()`, and a
sequence-split partial-(o, lse) path for decode-shaped attention (see
kernels/sharded.py for the decision order).  Off-mesh, every op degrades
to the plain single-device pallas wrapper, so `make_engine
("sharded_pallas")` is safe at any scale.

No tile hooks are registered: block plans resolve lazily INSIDE the shard
bodies from the per-shard operand shapes, under the standard "pallas"
tile-plan keys — tile picks stay device-local instead of keying on the
global problem.

All four ops are differentiable: the custom-VJP kernels flow through
shard_map, and the backward kernels resolve their own "gemm_bwd" /
"attention_bwd" keys from the per-shard shapes too.  (Decode-shaped
attention dispatches are inference-only, exactly like the split-KV
formulation on the plain pallas backend.)
"""
from __future__ import annotations

from repro.core import backends
from repro.kernels import sharded


def _matmul(x, w, scale, shift, *, act, out_dtype, ctx):
    return sharded.matmul(x, w, scale, shift, act=act, out_dtype=out_dtype,
                          interpret=ctx.interpret)


def _bmm(x, w, *, out_dtype, ctx):
    return sharded.bmm(x, w, out_dtype=out_dtype, interpret=ctx.interpret)


def _attention(q, k, v, *, causal, sm_scale, kv_len=None, ctx):
    return sharded.attention(q, k, v, kv_len, sm_scale, causal=causal,
                             interpret=ctx.interpret)


backends.register_backend("sharded_pallas", {
    "matmul": _matmul,
    "bmm": _bmm,
    # conv-as-im2col: the flattened (B*OH*OW) patch rows shard over the
    # batch axes inside the matmul impl.
    "conv2d": backends.im2col_conv2d(_matmul),
    "attention": _attention,
}, differentiable=("matmul", "bmm", "conv2d", "attention"))
