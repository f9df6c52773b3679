"""Kernel-vs-oracle validation for the GEMM compute engine.

Per harness requirement: sweep shapes/dtypes in interpret mode and
assert_allclose against the pure-jnp oracle in ref.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dep — property tests skip without it
    from hypothesis_stub import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.common import ACTIVATIONS, act_deriv, apply_act

jax.config.update("jax_enable_x64", False)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


SHAPES = [
    (8, 8, 8),            # tiny, heavy padding
    (128, 128, 128),      # exactly one block
    (256, 512, 256),      # default block shape
    (200, 300, 100),      # ragged: every dim padded
    (1, 4096, 64),        # vector-matrix
    (512, 64, 1024),      # skinny K
]


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_matches_oracle(m, k, n, dtype):
    kx, kw = jax.random.split(jax.random.PRNGKey(m * 31 + k * 7 + n))
    x, w = _rand(kx, (m, k), dtype), _rand(kw, (k, n), dtype)
    got = ops.matmul(x, w, interpret=True)
    want = ref.matmul_ref(x, w)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("act", ACTIVATIONS)
def test_gemm_fused_epilogue(act):
    key = jax.random.PRNGKey(0)
    kx, kw, ks, kb = jax.random.split(key, 4)
    m, k, n = 96, 160, 224
    x, w = _rand(kx, (m, k), jnp.float32), _rand(kw, (k, n), jnp.float32)
    scale = _rand(ks, (n,), jnp.float32)
    shift = _rand(kb, (n,), jnp.float32)
    got = ops.matmul(x, w, scale, shift, act=act, interpret=True)
    want = ref.matmul_ref(x, w, scale=scale, shift=shift, act=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def _mish_nn(x):
    return x * jnp.tanh(jax.nn.softplus(x))


def test_mish_matches_jax_nn_across_thresholds():
    """`apply_act(x, "mish")` is x * tanh(softplus(x)) with darknet's
    thresholds at +-20; over +-30 it agrees with the `jax.nn` form, and
    it is exact at and beyond both thresholds."""
    x = jnp.concatenate([jnp.linspace(-30.0, 30.0, 6001),
                         jnp.array([-20.0, 20.0, -20.001, 20.001, 0.0])])
    got = apply_act(x, "mish")
    np.testing.assert_allclose(np.asarray(got), np.asarray(_mish_nn(x)),
                               rtol=2e-6, atol=1e-30)
    big = jnp.array([25.0, 88.0, 1e4])
    np.testing.assert_array_equal(np.asarray(apply_act(big, "mish")),
                                  np.asarray(big))


def test_mish_deriv_matches_grad():
    """`act_deriv(x, "mish")`, the fused GEMM's backward residual, equals
    jax.grad of `apply_act` and of the `jax.nn` form over +-30, and stays
    finite far beyond."""
    x = jnp.linspace(-30.0, 30.0, 6001)
    got = act_deriv(x, "mish")
    auto = jax.vmap(jax.grad(lambda v: apply_act(v, "mish")))(x)
    ref_grad = jax.vmap(jax.grad(_mish_nn))(x)
    # Near the derivative's zero (x ~ -1.19) only an absolute bound
    # means anything; the far negative tail, ~1e-12, is held relatively.
    tail = np.asarray(x) < -10
    for want in (auto, ref_grad):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(np.asarray(got)[tail],
                                   np.asarray(want)[tail], rtol=1e-5)
    far = jnp.array([-1e4, -100.0, 100.0, 1e4])
    for g in (act_deriv(far, "mish"),
              jax.vmap(jax.grad(lambda v: apply_act(v, "mish")))(far)):
        assert np.all(np.isfinite(np.asarray(g)))


def test_gemm_mish_epilogue_matches_jax_nn():
    """The fused GEMM with the mish epilogue (interpret) against
    mish(x @ w * scale + shift) in `jax.nn` terms, with pre-activations
    past both thresholds; and its gradients against jax.grad of that."""
    kx, kw, ks, kb = jax.random.split(jax.random.PRNGKey(5), 4)
    m, k, n = 200, 96, 256
    x = _rand(kx, (m, k), jnp.float32)
    w = _rand(kw, (k, n), jnp.float32) * 3.0       # |pre-act| up to ~100
    scale = jax.random.uniform(ks, (n,), jnp.float32, 0.5, 1.5)
    shift = _rand(kb, (n,), jnp.float32)

    def want_fn(x, w, scale, shift):
        u = jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
        return _mish_nn(u * scale + shift)

    def got_fn(x, w, scale, shift):
        return ops.matmul(x, w, scale, shift, act="mish", interpret=True)

    u = np.asarray(jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
                   * scale + shift)
    assert (u > 20).any() and (u < -20).any()
    np.testing.assert_allclose(np.asarray(got_fn(x, w, scale, shift)),
                               np.asarray(want_fn(x, w, scale, shift)),
                               rtol=1e-5, atol=1e-4)
    cot = _rand(jax.random.PRNGKey(6), (m, n), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(got_fn(*a) * cot),
                   (0, 1, 2, 3))(x, w, scale, shift)
    want = jax.grad(lambda *a: jnp.sum(want_fn(*a) * cot),
                    (0, 1, 2, 3))(x, w, scale, shift)
    for g, wt in zip(got, want):
        wt = np.asarray(wt)
        np.testing.assert_allclose(np.asarray(g), wt, rtol=0,
                                   atol=2e-5 * np.max(np.abs(wt)))


def test_gemm_fp32_strict_is_exactly_xla_dot():
    # Non-quantization invariant: fp32 engine output == fp32 XLA dot output
    # bit-for-bit is too strong across reduction orders, but 1e-6 rel holds.
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (128, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(4), (256, 128), jnp.float32)
    got = ops.matmul(x, w, interpret=True)
    want = x @ w
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("b,m,k,n", [(2, 64, 64, 64), (3, 100, 70, 130),
                                     (1, 256, 256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bmm_matches_oracle(b, m, k, n, dtype):
    kx, kw = jax.random.split(jax.random.PRNGKey(b * 97 + m))
    x, w = _rand(kx, (b, m, k), dtype), _rand(kw, (b, k, n), dtype)
    got = ops.bmm(x, w, interpret=True)
    want = ref.bmm_ref(x, w)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 96), k=st.integers(1, 96), n=st.integers(1, 96),
       use_scale=st.booleans(), use_shift=st.booleans(),
       act=st.sampled_from(ACTIVATIONS))
def test_gemm_property_any_shape(m, k, n, use_scale, use_shift, act):
    """Property: engine == oracle for arbitrary shapes + epilogue combos."""
    key = jax.random.PRNGKey(m * 10007 + k * 101 + n)
    kx, kw, ks, kb = jax.random.split(key, 4)
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    scale = jax.random.normal(ks, (n,), jnp.float32) if use_scale else None
    shift = jax.random.normal(kb, (n,), jnp.float32) if use_shift else None
    got = ops.matmul(x, w, scale, shift, act=act, interpret=True)
    want = ref.matmul_ref(x, w, scale=scale, shift=shift, act=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)
