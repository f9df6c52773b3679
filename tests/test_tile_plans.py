"""Exact GEMM tile plans: every block divides its extent at the hardware
alignment (bm to 8 sublanes, bk/bn to 128 lanes) or spans it whole, so
`ops.matmul` runs the kernel on its operands as given — no `jnp.pad` of
the activations or weights, no slice of the output.

Covers the plan rule over Darknet-19's 19 conv GEMMs at buckets 1 and 8,
YOLOv3-416's at bucket 1 and the qwen2-0.5b projections, the padded fallback where no exact plan
fits the VMEM budget (and the `gemm_padded` counter that reports it), the
numbers of full-extent unaligned blocks against float64 numpy, and one
gradient through the custom VJP at such a shape.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.darknet_ref import DARKNET_SMALL_CFG
from repro.core import backends, make_engine
from repro.core.darknet.network import Network
from repro.kernels import ops, ref

# Darknet-19 @224's conv GEMMs per image: (OH*OW, k*k*Cin, Cout).
DARKNET19_GEMMS = [
    (50176, 27, 32), (12544, 288, 64), (3136, 576, 128), (3136, 128, 64),
    (3136, 576, 128), (784, 1152, 256), (784, 256, 128), (784, 1152, 256),
    (196, 2304, 512), (196, 512, 256), (196, 2304, 512), (196, 512, 256),
    (196, 2304, 512), (49, 4608, 1024), (49, 1024, 512), (49, 4608, 1024),
    (49, 1024, 512), (49, 4608, 1024), (49, 1024, 1000),
]
# YOLOv3-416's distinct conv GEMMs per image: the stem and downsamples,
# the residual 1x1/3x3 pairs, the heads (N = 255) and the convolutions
# after the cross-scale routes (K = 768, 384).
YOLOV3_GEMMS = [
    (173056, 27, 32), (43264, 288, 64), (43264, 64, 32), (10816, 576, 128),
    (10816, 128, 64), (2704, 1152, 256), (2704, 256, 128), (676, 2304, 512),
    (676, 512, 256), (169, 4608, 1024), (169, 1024, 512), (169, 1024, 255),
    (169, 512, 256), (676, 768, 256), (676, 512, 255), (676, 256, 128),
    (2704, 384, 128), (2704, 256, 255),
]
# qwen2-0.5b (d 896, 14 heads / 2 KV heads of 64, MLP 4864, vocabulary
# 151936): q/o, k/v, gate/up, down and head projections.
QWEN2_PROJECTIONS = [(896, 896), (896, 128), (896, 4864), (4864, 896),
                     (896, 151936)]

PLAN_CASES = (
    [pytest.param(b * hw, k, n, id=f"darknet19-b{b}-conv{i + 1}")
     for b in (1, 8) for i, (hw, k, n) in enumerate(DARKNET19_GEMMS)]
    + [pytest.param(m, k, n, id=f"yolov3-{m}x{k}x{n}")
       for m, k, n in YOLOV3_GEMMS]
    + [pytest.param(m, k, n, id=f"qwen2-{m}x{k}x{n}")
       for m in (1, 64, 512) for k, n in QWEN2_PROJECTIONS])


def _has_pad(closed_jaxpr) -> bool:
    return "pad[" in str(closed_jaxpr)


@pytest.mark.parametrize("m,k,n", PLAN_CASES)
def test_plan_is_exact_fits_vmem_and_pads_nothing(m, k, n):
    bm, bk, bn = plan = ops.default_blocks("matmul", m, k, n, "float32")
    for tile, dim in zip(plan, (m, k, n)):
        assert dim % tile == 0, (plan, (m, k, n))
    assert bm % 8 == 0 or bm == m
    assert bk % 128 == 0 or bk == k
    assert bn % 128 == 0 or bn == n
    assert ops._working_set(*plan, 4) <= ops._GEMM_VMEM_BUDGET
    assert not ops.validate_gemm_tiles(m, k, n, "float32", plan)
    assert ops.gemm_padding(m, k, n, plan) == (m, k, n)
    jaxpr = jax.make_jaxpr(
        lambda x, w, s, b: ops.matmul(x, w, s, b, act="leaky"))(
        jax.ShapeDtypeStruct((m, k), jnp.float32),
        jax.ShapeDtypeStruct((k, n), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32))
    assert not _has_pad(jaxpr)


@pytest.mark.parametrize("m,k,n,plan", [
    (49, 4608, 1024, (49, 1536, 256)),      # conv14 at bucket 1
    (392, 4608, 1024, (392, 1152, 256)),    # conv14 at bucket 8
    (196, 2304, 512, (196, 1152, 256)),     # conv9 at bucket 1
    (49, 1024, 1000, (49, 512, 1000)),      # conv19: bn the whole N
    (50176, 27, 32, (256, 27, 32)),         # conv1: bk, bn whole
    (676, 2304, 512, (676, 384, 256)),      # YOLOv3 26x26 3x3: M = 676
    (676, 768, 256, (676, 384, 256)),       # has no aligned divisor
])
def test_plan_examples(m, k, n, plan):
    """Full extents above the cap where the aligned divisors under it are
    small (M = 392: 392 rather than 56), bk shrunk to an exact divisor
    that fits the budget (for M = 676, bk 768 would need 16.2 MiB of
    Mosaic's 16 MiB scoped VMEM on a v5e)."""
    assert ops.default_blocks("matmul", m, k, n, "float32") == plan


def test_no_exact_plan_falls_back_and_counts_padded():
    """K = 16411 has no aligned divisor and its full extent overflows the
    VMEM budget at the smallest row and column blocks: K alone is padded,
    and the dispatch record counts it."""
    m, k, n = 8, 16411, 128
    bm, bk, bn = plan = ops.default_blocks("matmul", m, k, n, "float32")
    assert (bm, bn) == (m, n) and bk % 128 == 0 and k % bk
    assert ops.gemm_padding(m, k, n, plan)[1] > k
    eng = make_engine("pallas")
    mark = backends.dispatch_log_size()
    traced = jax.jit(lambda x, w: eng.matmul(x, w)).trace(
        jax.ShapeDtypeStruct((m, k), jnp.float32),
        jax.ShapeDtypeStruct((k, n), jnp.float32))
    assert _has_pad(traced.jaxpr)
    got = backends.gemm_padded(backends.dispatch_log()[mark:])
    assert got["gemms"] == 1 and got["padded"] == 1
    assert got["shapes"] == [((m, k, n), ops.gemm_padding(m, k, n, plan))]
    assert got["padded_operand_bytes"] > got["operand_bytes"]


def test_compiled_network_reports_gemm_padded():
    """`CompiledNetwork.profile()` and `CompileCache.stats()` report the
    lowering's tiled GEMMs and how many pad: none, for the small Darknet."""
    net = Network(DARKNET_SMALL_CFG, engine=make_engine("pallas"))
    params = net.init(jax.random.PRNGKey(0))
    cache = net.compile_cache(params, buckets=(1, 2))
    cache.get(1)
    prof = cache.get(2).profile(reps=1)
    assert prof["gemm_padded"]["gemms"] == 4         # three convs, a head
    assert prof["gemm_padded"]["padded"] == 0
    stats = cache.stats()["gemm_padded"]
    assert stats["gemms"] == 8 and stats["padded"] == 0
    assert stats["operand_bytes"] == stats["padded_operand_bytes"]


def _np_gemm(x, w, scale, shift, act):
    u = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    u = u * np.asarray(scale, np.float64) + np.asarray(shift, np.float64)
    return np.where(u > 0, u, 0.1 * u) if act == "leaky" else u


@pytest.mark.parametrize("m,k,n", [
    (256, 27, 32),     # conv1: bk = K = 27, bn = N = 32
    (512, 288, 64),    # conv2: bk = K = 288, bn = N = 64
    (196, 512, 256),   # bm = M = 196
    (49, 256, 1000),   # bn = N = 1000
    (64, 896, 128),    # bk = K = 896, which padded to 1024 before
])
def test_full_extent_unaligned_blocks_match_float64(m, k, n):
    plan = ops.default_blocks("matmul", m, k, n, "float32")
    assert ops.gemm_padding(m, k, n, plan) == (m, k, n)
    assert any(t == d for t, d in zip(plan, (m, k, n))), plan
    ks = jax.random.split(jax.random.PRNGKey(m + k + n), 4)
    x = jax.random.normal(ks[0], (m, k), jnp.float32)
    w = jax.random.normal(ks[1], (k, n), jnp.float32) / np.sqrt(k)
    scale = jax.random.uniform(ks[2], (n,), jnp.float32, 0.5, 1.5)
    shift = jax.random.normal(ks[3], (n,), jnp.float32)
    got = np.asarray(ops.matmul(x, w, scale, shift, act="leaky"),
                     np.float64)
    want = _np_gemm(x, w, scale, shift, "leaky")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_grad_through_exact_unaligned_plan():
    """The custom VJP at an exact plan with unaligned full-extent blocks:
    the backward pads to its own plan and slices back to the operands."""
    m, k, n = 196, 27, 32
    plan = ops.default_blocks("matmul", m, k, n, "float32")
    assert plan == (196, 27, 32)
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(ks[0], (m, k), jnp.float32)
    w = jax.random.normal(ks[1], (k, n), jnp.float32)
    scale = jax.random.uniform(ks[2], (n,), jnp.float32, 0.5, 1.5)
    shift = jax.random.normal(ks[3], (n,), jnp.float32)

    def loss(fn):
        return lambda *a: (fn(*a, act="leaky") ** 2).sum()

    got = jax.grad(loss(ops.matmul), argnums=(0, 1, 2, 3))(x, w, scale,
                                                           shift)
    want = jax.grad(loss(lambda x, w, s, b, act: ref.matmul_ref(
        x, w, scale=s, shift=b, act=act)), argnums=(0, 1, 2, 3))(
        x, w, scale, shift)
    for name, a, b in zip("xw", got, want):
        assert a.shape == b.shape, name
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.abs(a - b).max() / np.abs(b).max() <= 1e-5
