"""Cross-backend gradient-conformance suite (the ISSUE 8 gate).

The paper's full-precision claim is only trainable if `jax.grad` through
the pallas kernels computes the SAME gradients as the plain-jnp oracle —
this suite proves it numerically and structurally:

  * matmul / bmm / conv2d gradient parity on pallas and xla against the
    `ref` backend (conftest.py), over the darknet_ref layer zoo and LM MLP
    shapes — fp32 at 1e-5, bf16 at a loose tier;
  * every fused-epilogue activation (linear/relu/leaky/silu) checked, and
    odd/unaligned shapes (full-extent forward blocks; the backward pads
    its operands to its own plan);
  * `jax.checkpoint` remat parity — the custom VJPs compose with remat;
  * a finite-difference spot check on small shapes (hypothesis property
    when installed, seeded deterministic fallback always);
  * trace-level regressions: the backward jaxpr of a full pallas train
    step (CNN and LM) carries a `repro.op.*` scope on every dense
    contraction (the R002 condition), and `gemm_bwd` tile-plan keys are
    created lazily — an inference-only trace registers none.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dep (requirements-dev.txt)
    from hypothesis_stub import given, settings, st

from repro.analysis import lint
from repro.configs.base import get_arch, reduced
from repro.configs.darknet_ref import DARKNET_SMALL_CFG
from repro.core import backends, make_engine
from repro.core.darknet.network import Network
from repro.kernels.common import apply_act
from repro.models import transformer as tfm
from repro.train.train_step import cnn_loss_fn

BACKENDS = ("pallas", "xla")           # each checked against the ref oracle
ACTS = ("linear", "relu", "leaky", "silu")
FP32_TOL = 1e-5
BF16_TOL = 5e-2                        # bf16 loose tier (~8 mantissa bits)

# darknet_ref (DARKNET_SMALL_CFG) conv zoo plus an odd strided case that
# forces padding on every GEMM axis: (B, H, W, Cin, Cout, size, stride, pad)
CONV_CASES = [
    (2, 28, 28, 3, 16, 3, 1, 1),
    (2, 14, 14, 16, 32, 3, 1, 1),
    (2, 7, 7, 32, 64, 3, 1, 1),
    (1, 9, 11, 5, 7, 3, 2, 1),
]
# Stride-2 convolutions: YOLOv3's 3x3 downsample, odd extents, and an even
# kernel whose last input column no tap reads.
STRIDED_CONV_CASES = [
    (1, 16, 16, 8, 16, 3, 2, 1),
    (2, 9, 11, 5, 7, 3, 2, 1),
    (1, 10, 9, 4, 6, 2, 2, 0),
]
# connected head + LM MLP shapes + a ragged everything-padded case
MATMUL_CASES = [
    (2, 64, 10),
    (32, 128, 256),
    (32, 256, 128),
    (33, 177, 99),
]
BMM_CASES = [
    (2, 32, 16, 32),
    (3, 17, 23, 9),
]


def _relmax(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-12))


def _assert_tree_close(got, want, tol, names):
    for name, a, b in zip(names, got, want):
        rel = _relmax(a, b)
        assert rel <= tol, f"d{name}: rel err {rel:.2e} > {tol:g}"


def _matmul_operands(m, k, n, dtype):
    ks = jax.random.split(jax.random.PRNGKey(m * 1000 + k * 10 + n), 4)
    x = jax.random.normal(ks[0], (m, k), jnp.float32).astype(dtype)
    w = (jax.random.normal(ks[1], (k, n), jnp.float32) * 0.3).astype(dtype)
    sc = (jnp.abs(jax.random.normal(ks[2], (n,))) + 0.5).astype(dtype)
    sh = (jax.random.normal(ks[3], (n,)) * 0.2).astype(dtype)
    return x, w, sc, sh


def _matmul_grads(backend, m, k, n, act, dtype=jnp.float32):
    eng = make_engine(backend)
    x, w, sc, sh = _matmul_operands(m, k, n, dtype)

    def loss(x, w, sc, sh):
        y = eng.matmul(x, w, scale=sc, shift=sh, act=act)
        return (y.astype(jnp.float32) ** 2).sum()

    return jax.grad(loss, argnums=(0, 1, 2, 3))(x, w, sc, sh)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("act", ACTS)
@pytest.mark.parametrize("m,k,n", MATMUL_CASES)
def test_matmul_grad_parity_fp32(backend, act, m, k, n):
    """Epilogue-fused matmul gradients (x, w, scale, shift cotangents all
    flowing) match the ref oracle at fp32 tolerance on every backend, every
    activation, aligned and padded shapes alike."""
    got = _matmul_grads(backend, m, k, n, act)
    want = _matmul_grads("ref", m, k, n, act)
    _assert_tree_close(got, want, FP32_TOL, ("x", "w", "scale", "shift"))


@pytest.mark.parametrize("backend", BACKENDS)
def test_matmul_grad_parity_bf16(backend):
    """bf16 operands ride the same VJPs (fp32 accumulation inside the
    kernels) — loose tier, dominated by bf16 rounding of saved residuals."""
    got = _matmul_grads(backend, 32, 128, 64, "leaky", jnp.bfloat16)
    want = _matmul_grads("ref", 32, 128, 64, "leaky", jnp.bfloat16)
    _assert_tree_close(got, want, BF16_TOL, ("x", "w", "scale", "shift"))


def _bmm_grads(backend, b, m, k, n, dtype=jnp.float32):
    eng = make_engine(backend)
    ks = jax.random.split(jax.random.PRNGKey(b * 100 + m + n), 2)
    x = jax.random.normal(ks[0], (b, m, k), jnp.float32).astype(dtype)
    w = (jax.random.normal(ks[1], (b, k, n), jnp.float32) * 0.3).astype(dtype)

    def loss(x, w):
        return (eng.bmm(x, w).astype(jnp.float32) ** 2).sum()

    return jax.grad(loss, argnums=(0, 1))(x, w)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("b,m,k,n", BMM_CASES)
def test_bmm_grad_parity_fp32(backend, b, m, k, n):
    got = _bmm_grads(backend, b, m, k, n)
    want = _bmm_grads("ref", b, m, k, n)
    _assert_tree_close(got, want, FP32_TOL, ("x", "w"))


def _lax_conv2d(x, wt, *, scale, shift, size, stride, pad, act):
    """conv2d by XLA's own convolution at HIGHEST precision: no im2col, no
    col2im, so an oracle for both directions of the patch map."""
    w4 = wt.reshape(size, size, x.shape[-1], -1)
    y = jax.lax.conv_general_dilated(
        x, w4, (stride, stride), [(pad, pad)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    return apply_act(y * scale + shift, act)


def _conv_grads(backend, b, h, w_, cin, cout, size, stride, pad, act,
                dtype=jnp.float32):
    conv2d = (_lax_conv2d if backend == "lax"
              else make_engine(backend).conv2d)
    ks = jax.random.split(jax.random.PRNGKey(h * 100 + cin + cout), 4)
    x = jax.random.normal(ks[0], (b, h, w_, cin), jnp.float32).astype(dtype)
    wt = (jax.random.normal(ks[1], (size * size * cin, cout))
          * 0.2).astype(dtype)
    sc = (jnp.abs(jax.random.normal(ks[2], (cout,))) + 0.5).astype(dtype)
    sh = (jax.random.normal(ks[3], (cout,)) * 0.2).astype(dtype)

    def loss(x, wt, sc, sh):
        y = conv2d(x, wt, scale=sc, shift=sh, size=size, stride=stride,
                   pad=pad, act=act)
        return (y.astype(jnp.float32) ** 2).sum()

    return jax.grad(loss, argnums=(0, 1, 2, 3))(x, wt, sc, sh)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", CONV_CASES)
def test_conv2d_grad_parity_fp32(backend, case):
    """conv2d differentiates through its im2col GEMM: dL/dinput via the
    col2im scatter, dL/dweight via the transposed im2col GEMM — parity
    with the ref oracle over the darknet_ref layer zoo (folded-BN scale
    and shift cotangents included)."""
    got = _conv_grads(backend, *case, "leaky")
    want = _conv_grads("ref", *case, "leaky")
    _assert_tree_close(got, want, FP32_TOL, ("x", "w", "scale", "shift"))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", STRIDED_CONV_CASES)
def test_strided_conv2d_grad_parity_with_lax_conv(backend, case):
    """At stride 2 im2col reads its taps from the input's phases while the
    col2im backward scatters through strided slices; against XLA's own
    convolution, which shares neither, both directions must agree."""
    got = _conv_grads(backend, *case, "leaky")
    want = _conv_grads("lax", *case, "leaky")
    _assert_tree_close(got, want, FP32_TOL, ("x", "w", "scale", "shift"))


@pytest.mark.parametrize("act", ACTS)
def test_conv2d_grad_parity_all_acts(act):
    got = _conv_grads("pallas", 1, 9, 11, 5, 7, 3, 2, 1, act)
    want = _conv_grads("ref", 1, 9, 11, 5, 7, 3, 2, 1, act)
    _assert_tree_close(got, want, FP32_TOL, ("x", "w", "scale", "shift"))


@pytest.mark.parametrize("backend", BACKENDS)
def test_conv2d_grad_parity_bf16(backend):
    got = _conv_grads(backend, 2, 14, 14, 16, 32, 3, 1, 1, "leaky",
                      jnp.bfloat16)
    want = _conv_grads("ref", 2, 14, 14, 16, 32, 3, 1, 1, "leaky",
                       jnp.bfloat16)
    _assert_tree_close(got, want, BF16_TOL, ("x", "w", "scale", "shift"))


# ---------------------------------------------------------------- remat ---

def test_remat_grad_parity():
    """`jax.checkpoint` composes with the custom VJPs: the rematerialized
    backward recomputes the forward kernels (residuals re-emitted inside
    the remat region) and lands on identical gradients."""
    eng = make_engine("pallas")
    x, w, sc, sh = _matmul_operands(33, 177, 99, jnp.float32)

    def loss(x, w, sc, sh):
        y = eng.matmul(x, w, scale=sc, shift=sh, act="silu")
        return (y.astype(jnp.float32) ** 2).sum()

    plain = jax.grad(loss, argnums=(0, 1, 2, 3))(x, w, sc, sh)
    remat = jax.grad(jax.checkpoint(loss),
                     argnums=(0, 1, 2, 3))(x, w, sc, sh)
    for name, a, b in zip(("x", "w", "scale", "shift"), remat, plain):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6,
                                   err_msg=f"remat d{name}")


def test_remat_cnn_loss_parity():
    """Remat around a whole conv layer (im2col VJP + GEMM VJP together)."""
    eng = make_engine("pallas")
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], (1, 9, 9, 4), jnp.float32)
    wt = jax.random.normal(ks[1], (3 * 3 * 4, 8)) * 0.2

    def loss(x, wt):
        y = eng.conv2d(x, wt, size=3, stride=1, pad=1, act="leaky")
        return (y.astype(jnp.float32) ** 2).sum()

    plain = jax.grad(loss, argnums=(0, 1))(x, wt)
    remat = jax.grad(jax.checkpoint(loss), argnums=(0, 1))(x, wt)
    for a, b in zip(remat, plain):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


# --------------------------------------------- finite-difference check ---

def _fd_spot_check(m, k, n, act, seed):
    """Directional derivative of the pallas matmul loss vs a central
    finite difference.  fp32 arithmetic: modest eps, loose threshold."""
    eng = make_engine("pallas")
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (m, k), jnp.float32)
    w = jax.random.normal(ks[1], (k, n), jnp.float32) * 0.3

    def loss(x):
        return (eng.matmul(x, w, act=act).astype(jnp.float32) ** 2).sum()

    d = jax.random.normal(ks[2], (m, k), jnp.float32)
    d = d / jnp.linalg.norm(d)
    g = jax.grad(loss)(x)
    analytic = float(jnp.vdot(g, d))
    eps = 1e-2
    fd = float((loss(x + eps * d) - loss(x - eps * d)) / (2 * eps))
    scale = max(abs(analytic), abs(fd), 1e-3)
    assert abs(analytic - fd) / scale < 5e-2, (analytic, fd)


@given(st.integers(2, 8), st.integers(2, 8), st.integers(2, 8),
       st.sampled_from(ACTS), st.integers(0, 2 ** 16))
@settings(max_examples=10, deadline=None)
def test_matmul_fd_property(m, k, n, act, seed):
    _fd_spot_check(m, k, n, act, seed)


def test_matmul_fd_seeded_fallback():
    """Deterministic stand-in for the hypothesis property (always runs —
    the property skips when hypothesis is absent)."""
    rng = np.random.default_rng(1234)
    for _ in range(5):
        m, k, n = (int(v) for v in rng.integers(2, 9, size=3))
        act = ACTS[int(rng.integers(len(ACTS)))]
        _fd_spot_check(m, k, n, act, int(rng.integers(2 ** 16)))


# ------------------------------------------------ trace-level regressions ---

_CONTRACTIONS = ("dot_general", "conv_general_dilated")


def _unscoped_contractions(closed_jaxpr) -> list[str]:
    """Dense-contraction eqns missing the engine's repro.op.* dispatch
    scope — the R002 condition, applied to an arbitrary (here: backward)
    jaxpr instead of a compiled network."""
    return [lint.eqn_path(eqn, scope)
            for eqn, scope in lint.walk_eqns_scoped(closed_jaxpr.jaxpr)
            if eqn.primitive.name in _CONTRACTIONS
            and backends.OP_SCOPE_PREFIX not in scope]


def test_cnn_train_backward_trace_r002_clean():
    """The backward jaxpr of a full darknet_ref CNN train step on pallas
    contains NO contraction outside a repro.op.* scope: forward dispatches
    carry the engine scope, the custom-VJP backward kernels self-scope
    (gemm_bwd), and im2col's col2im backward avoids the native
    conv_general_dilated transpose entirely."""
    net = Network(DARKNET_SMALL_CFG, make_engine("pallas"))
    params = net.init(jax.random.PRNGKey(0))
    images = jnp.zeros((2, 28, 28, 3), jnp.float32)
    labels = jnp.zeros((2,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda p: cnn_loss_fn(net, p, images, labels)))(params)
    bad = _unscoped_contractions(jaxpr)
    assert not bad, f"unscoped contractions in backward trace: {bad}"


def test_lm_train_backward_trace_r002_clean():
    """Same structural gate for a reduced LM train step on the all-pallas
    engine: GEMM, bmm and attention backward kernels all trace under
    their repro.op.* markers."""
    cfg = dataclasses.replace(reduced(get_arch("qwen2-0.5b")), n_layers=1)
    eng = make_engine("pallas")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((1, 16), jnp.int32)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: tfm.loss_fn(eng, cfg, p, batch, ce_chunk=16,
                              n_q_chunks=2)))(params)
    bad = _unscoped_contractions(jaxpr)
    assert not bad, f"unscoped contractions in backward trace: {bad}"


def test_gemm_bwd_keys_created_lazily():
    """Backward tiles resolve at backward-trace time only: an
    inference-only trace registers ZERO gemm_bwd tile-plan keys; the first
    differentiated trace of the same problem adds exactly dx + dw."""
    backends.clear_tile_cache()
    jax.clear_caches()
    try:
        eng = make_engine("pallas")
        x = jnp.ones((24, 40), jnp.float32)
        w = jnp.ones((40, 16), jnp.float32)
        eng.matmul(x, w, act="leaky")
        assert not [k for k in backends.tile_plans() if k[0] == "gemm_bwd"]
        jax.grad(lambda x: (eng.matmul(x, w, act="leaky") ** 2).sum())(x)
        bwd = [k for k in backends.tile_plans() if k[0] == "gemm_bwd"]
        assert len(bwd) == 2, bwd
    finally:
        backends.clear_tile_cache()
        jax.clear_caches()
