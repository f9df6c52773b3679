"""Backend/op registry + compile-once network API.

Covers the API-redesign acceptance criteria:
  * all engine ops resolve through get_backend(...) — including a
    third-party `ref` backend registered via the public API (conftest.py);
  * parametrized backend parity on matmul+epilogue, bmm, attention, and a
    2-conv darknet net through `CompiledNetwork`;
  * `Network.compile` produces exactly ONE jit trace;
  * the tile-plan cache is hit on the second identical-shape call.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ComputeEngine, backends, get_backend, list_backends,
                        make_engine, register_backend)
from repro.core.darknet.network import Network

ALL_BACKENDS = ("pallas", "xla", "ref")
# atol per precision policy: fp32_strict accumulates in fp32 everywhere, so
# backends agree to fp32 matmul tolerance.
TOL = {"fp32_strict": 2e-4}

TWO_CONV_CFG = """
[net]
height=16
width=16
channels=3

[convolutional]
batch_normalize=1
filters=8
size=3
stride=1
pad=1
activation=leaky

[convolutional]
filters=4
size=3
stride=2
pad=1
activation=leaky
"""


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


# ---------------------------------------------------------------- registry

def test_ref_backend_registered_via_public_api():
    assert set(ALL_BACKENDS) <= set(list_backends())
    be = get_backend("ref")
    assert set(backends.OP_SET) <= set(be.ops)


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown backend"):
        get_backend("cuda")
    with pytest.raises(ValueError, match="unknown backend"):
        make_engine("cuda")


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="already registered"):
        register_backend("xla", {})


def test_unknown_op_name_rejected_at_registration():
    with pytest.raises(ValueError, match="unknown ops"):
        register_backend("bogus", {"matmul3": lambda: None})


def test_missing_op_fails_at_dispatch_with_clear_error():
    register_backend("partial", {}, overwrite=True)
    try:
        eng = ComputeEngine(backend="partial")
        with pytest.raises(NotImplementedError, match="partial"):
            eng.matmul(_rand(0, (4, 4)), _rand(1, (4, 4)))
    finally:
        backends.unregister_backend("partial")


def test_engine_dispatch_is_counted():
    backends.reset_dispatch_counts()
    eng = make_engine("xla")
    eng.matmul(_rand(0, (8, 8)), _rand(1, (8, 8)))
    eng.bmm(_rand(2, (2, 8, 8)), _rand(3, (2, 8, 8)))
    counts = backends.dispatch_counts()
    assert counts[("xla", "matmul")] == 1
    assert counts[("xla", "bmm")] == 1


# ------------------------------------------------------------- op parity

@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_matmul_epilogue_parity(backend):
    eng = make_engine(backend)
    x, w = _rand(0, (96, 160)), _rand(1, (160, 224))
    scale, shift = _rand(2, (224,)), _rand(3, (224,))
    got = eng.matmul(x, w, scale=scale, shift=shift, act="leaky")
    want = make_engine("ref").matmul(x, w, scale=scale, shift=shift,
                                     act="leaky")
    tol = TOL[eng.precision.policy]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_bmm_parity(backend):
    eng = make_engine(backend)
    x, w = _rand(0, (3, 40, 72)), _rand(1, (3, 72, 56))
    got = eng.bmm(x, w)
    want = make_engine("ref").bmm(x, w)
    tol = TOL[eng.precision.policy]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("causal", [True, False])
def test_attention_parity(backend, causal):
    eng = make_engine(backend)
    q, k, v = (_rand(i, (2, 64, 4, 32)) for i in range(3))
    got = eng.attention(q, k, v, causal=causal)
    want = make_engine("ref").attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_compiled_network_parity(backend):
    """2-conv darknet net through CompiledNetwork agrees across backends."""
    net_ref = Network(TWO_CONV_CFG, make_engine("ref"))
    net = Network(TWO_CONV_CFG, make_engine(backend))
    params = net_ref.init(jax.random.PRNGKey(0))
    x = _rand(1, (2, 16, 16, 3))
    got = net.compile(params, batch_size=2)(x)
    want = net_ref.compile(params, batch_size=2)(x)
    tol = TOL[net.engine.precision.policy]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


# ------------------------------------------------------------ compile-once

def test_compiled_network_single_trace():
    """Network.compile lowers the whole plan in exactly ONE jit trace;
    warmup/profile/calls never retrace."""
    net = Network(TWO_CONV_CFG, make_engine("xla"))
    params = net.init(jax.random.PRNGKey(0))
    cn = net.compile(params, batch_size=2)
    assert cn.trace_count == 1
    x = _rand(1, (2, 16, 16, 3))
    cn.warmup()
    cn(x)
    cn(x)
    prof = cn.profile(x, reps=2)
    assert cn.trace_count == 1
    assert prof["trace_count"] == 1
    # static op plan captured during the single trace: 2 conv layers
    assert prof["op_counts"] == {("xla", "conv2d"): 2}


def test_compiled_network_matches_eager_apply():
    net = Network(TWO_CONV_CFG, make_engine("xla"))
    params = net.init(jax.random.PRNGKey(0))
    x = _rand(1, (2, 16, 16, 3))
    got = net.compile(params, batch_size=2)(x)
    want = jax.jit(net.apply)(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_compiled_network_rejects_wrong_batch():
    net = Network(TWO_CONV_CFG, make_engine("xla"))
    params = net.init(jax.random.PRNGKey(0))
    cn = net.compile(params, batch_size=2)
    with pytest.raises(ValueError, match="compiled for input"):
        cn(_rand(1, (3, 16, 16, 3)))


def test_compiled_network_rejects_wrong_dtype():
    """A float64 input used to slip through to a confusing XLA error; the
    artifact now validates dtype alongside shape."""
    net = Network(TWO_CONV_CFG, make_engine("xla"))
    params = net.init(jax.random.PRNGKey(0))
    cn = net.compile(params, batch_size=2)
    x64 = np.asarray(_rand(1, (2, 16, 16, 3)), np.float64)
    with pytest.raises(ValueError, match="compiled for dtype"):
        cn(x64)


@pytest.mark.slow
def test_darknet_reference_net_compiles_once():
    """The benchmark path: the darknet-19 reference net through
    Network.compile with exactly one jit trace."""
    from repro.configs.darknet_ref import DARKNET19_CFG
    net = Network(DARKNET19_CFG, make_engine("xla"))
    params = net.init(jax.random.PRNGKey(0))
    cn = net.compile(params, batch_size=1, dtype=jnp.float32)
    x = _rand(1, (1, 224, 224, 3))
    cn(x)
    cn(x)
    assert cn.trace_count == 1
    n_convs = sum(p.type == "convolutional" for p in net.plans)
    assert cn.op_counts[("xla", "conv2d")] == n_convs


# --------------------------------------------------------- tile-plan cache

def test_autotune_cache_hit_on_second_identical_shape():
    backends.clear_tile_cache()
    eng = make_engine("pallas")
    x, w = _rand(0, (64, 48)), _rand(1, (48, 32))
    eng.matmul(x, w)
    s1 = backends.cache_stats()
    assert s1["misses"] >= 1
    eng.matmul(x, w)                       # identical shapes -> cache hit
    s2 = backends.cache_stats()
    assert s2["hits"] == s1["hits"] + 1
    assert s2["misses"] == s1["misses"]
    eng.matmul(_rand(2, (128, 48)), w)     # new M -> miss
    s3 = backends.cache_stats()
    assert s3["misses"] == s2["misses"] + 1


def test_autotune_cache_keyed_per_op():
    backends.clear_tile_cache()
    eng = make_engine("pallas")
    x, w = _rand(0, (64, 48)), _rand(1, (48, 32))
    eng.matmul(x, w)
    eng.bmm(x[None], w[None])              # same (m, k, n), different op
    stats = backends.cache_stats()
    assert stats["entries"] == 2
    assert stats["hits"] == 0


def test_untiled_backends_skip_autotune_cache():
    """Backends without a tile_picker (xla, ref) don't pollute the
    tile-plan cache — its stats measure real plan reuse only."""
    backends.clear_tile_cache()
    x, w = _rand(0, (64, 48)), _rand(1, (48, 32))
    make_engine("xla").matmul(x, w)
    make_engine("ref").matmul(x, w)
    assert backends.cache_stats() == {"hits": 0, "misses": 0, "entries": 0}


def test_causal_attention_rejects_more_queries_than_keys():
    eng = make_engine("xla")
    q, k, v = _rand(0, (1, 8, 2, 8)), _rand(1, (1, 4, 2, 8)), \
        _rand(2, (1, 4, 2, 8))
    with pytest.raises(ValueError, match="Sq <= Skv"):
        eng.attention(q, k, v, causal=True)
    # non-causal cross-attention with Sq > Skv is fine
    out = eng.attention(q, k, v, causal=False)
    assert out.shape == q.shape
    assert not np.any(np.isnan(np.asarray(out)))


# ------------------------------------------------- autodiff capability ---

def test_differentiable_defaults_and_pallas_declaration():
    """A backend registered without `differentiable` supports grad on all
    its ops (the right default for jnp backends); the built-in pallas
    backend now declares the FULL op set — every kernel carries a custom
    VJP (flash attention + the gemm_bwd GEMM backward kernels)."""
    for name in ("xla", "ref", "pallas"):
        be = get_backend(name)
        assert all(be.supports_grad(op) for op in be.ops)


def test_differentiable_must_name_registered_ops():
    xla = get_backend("xla")
    with pytest.raises(ValueError, match="differentiable names"):
        register_backend("bogus-diff", {"matmul": xla.op("matmul")},
                         differentiable=("attention",), overwrite=True)
    backends.unregister_backend("bogus-diff")


def test_nondifferentiable_backend_gemm_raises_clear_error():
    """Differentiating an op the backend does NOT declare differentiable
    (a VJP-less kernel registration) fails with the capability error —
    not the bare AssertionError pallas_call used to die with deep inside
    autodiff.  Registered here on purpose: the built-in pallas backend
    now differentiates its whole op set, so the guard is exercised via a
    deliberately grad-less registration (the conv_direct.py situation)."""
    xla = get_backend("xla")
    register_backend("nodiff-gemm", dict(xla.ops), differentiable=(),
                     overwrite=True)
    try:
        eng = make_engine("nodiff-gemm")
        x, w = _rand(0, (16, 16)), _rand(1, (16, 16))
        with pytest.raises(NotImplementedError,
                           match="'matmul' on backend 'nodiff-gemm'"):
            jax.grad(lambda x: eng.matmul(x, w).sum())(x)
        # the guard covers the epilogue operands too: a gradient flowing
        # ONLY through the bias/folded-BN shift must hit the same error
        b = _rand(2, (16,))
        with pytest.raises(NotImplementedError,
                           match="'matmul' on backend 'nodiff-gemm'"):
            jax.grad(lambda b: eng.matmul(x, w, shift=b).sum())(b)
        with pytest.raises(NotImplementedError,
                           match="'matmul' on backend 'nodiff-gemm'"):
            jax.grad(lambda s: eng.matmul(x, w, scale=s).sum())(b)
        # forward dispatch is untouched by the armed guard
        np.testing.assert_allclose(
            np.asarray(eng.matmul(x, w)),
            np.asarray(make_engine("ref").matmul(x, w)),
            rtol=2e-4, atol=2e-4)
    finally:
        backends.unregister_backend("nodiff-gemm")


def test_nondifferentiable_error_is_actionable():
    """The capability error names the op, the backend, the
    `differentiable` set it checked, and points at the xla fallback — a
    user hitting it knows exactly which dispatch tripped and what to do."""
    xla = get_backend("xla")
    register_backend("partial-diff", dict(xla.ops),
                     differentiable=("attention", "bmm"), overwrite=True)
    try:
        eng = make_engine("partial-diff")
        x, w = _rand(0, (16, 16)), _rand(1, (16, 16))
        with pytest.raises(NotImplementedError) as ei:
            jax.grad(lambda x: eng.matmul(x, w).sum())(x)
        msg = str(ei.value)
        assert "'matmul'" in msg                    # the op that tripped
        assert "'partial-diff'" in msg              # the backend
        assert "['attention', 'bmm']" in msg        # the checked set
        assert "'xla'" in msg                       # the suggested fallback
    finally:
        backends.unregister_backend("partial-diff")


def test_pallas_attention_differentiates_through_engine():
    """The tentpole property at the engine surface: jax.grad flows through
    the pallas `attention` dispatch (flash kernel custom VJP) and agrees
    with the ref backend's autodiff."""
    q = _rand(0, (1, 32, 4, 16))
    k = _rand(1, (1, 32, 2, 16))
    v = _rand(2, (1, 32, 2, 16))

    def loss(eng, q):
        return eng.attention(q, k, v, causal=True).sum()

    got = jax.grad(lambda q: loss(make_engine("pallas"), q))(q)
    want = jax.grad(lambda q: loss(make_engine("ref"), q))(q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
