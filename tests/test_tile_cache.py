"""The tile-plan cache: one rule-made plan per (op, shapes, dtype, backend).

Covers:
  * the `set_autotune_policy` vestige accepts only "heuristic";
  * unknown gemm_bwd variants are rejected by the backward picker;
  * the backward keys ("gemm_bwd", "attention_bwd") resolve lazily — a
    forward-only dispatch leaves none in `tile_plans()`, differentiating
    the same problem adds them, each holding its picker's plan;
  * the backward GEMM plans of Darknet-19's conv train step at bucket 8,
    and the forward and backward attention plans at qwen2-0.5b widths,
    pass `validate_tiles`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends, make_engine
from repro.kernels import ops as kernel_ops

from test_tile_plans import DARKNET19_GEMMS


@pytest.fixture(autouse=True)
def clean_tile_cache():
    """Start each test with an empty plan cache and cold jit caches: tile
    resolution happens at trace time, and a jit-cache hit from an earlier
    test would skip it (the backward keys resolve inside the custom-VJP
    backward trace)."""
    backends.clear_tile_cache()
    jax.clear_caches()
    yield
    backends.clear_tile_cache()


def _keys(op):
    return {k: plan for k, plan in backends.tile_plans().items()
            if k[0] == op}


def _attention_operands(b=1, sq=64, skv=64, h=4, kv=2, d=16):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, skv, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, skv, kv, d), jnp.float32)
    return q, k, v


def _matmul_operands(m=48, k=40, n=24):
    x = jnp.asarray(np.random.default_rng(0).standard_normal((m, k)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).standard_normal((k, n)),
                    jnp.float32)
    return x, w


def test_unknown_policy_rejected():
    assert backends.set_autotune_policy("heuristic") == "heuristic"
    for policy in ("fastest", "measure", "off"):
        with pytest.raises(ValueError, match="unknown autotune policy"):
            backends.set_autotune_policy(policy)


def test_gemm_bwd_variant_rejected():
    with pytest.raises(ValueError, match="variant"):
        kernel_ops.default_gemm_bwd_blocks("nope", 8, 128, 128, "float32")


def test_attention_bwd_key_resolved_only_under_grad():
    """Inference never touches the backward key space: a forward-only
    dispatch resolves just the "attention" key; differentiating the same
    problem adds the "attention_bwd" key with the backward rule's plan."""
    eng = make_engine("pallas")
    q, k, v = _attention_operands()
    eng.attention(q, k, v, causal=True)
    assert len(_keys("attention")) == 1
    assert not _keys("attention_bwd")
    jax.grad(lambda q: eng.attention(q, k, v, causal=True).sum())(q)
    bwd = _keys("attention_bwd")
    assert len(bwd) == 1
    (key, plan), = bwd.items()
    assert key[1:] == ((q.shape, k.shape), "float32", "pallas")
    assert plan == kernel_ops.default_attention_bwd_blocks(
        *kernel_ops.attention_dims(key[1]), "float32")
    assert not backends.validate_tiles(*key[:3], plan)


def test_gemm_bwd_keys_resolved_only_under_grad():
    """Inference resolves just the forward "matmul" key; differentiating
    the same problem lazily adds one "gemm_bwd" key per backward GEMM —
    the dX and dW problems, keyed on their OWN dims."""
    eng = make_engine("pallas")
    x, w = _matmul_operands()
    eng.matmul(x, w)
    assert len(_keys("matmul")) == 1
    assert not _keys("gemm_bwd")
    jax.grad(
        lambda x, w: (eng.matmul(x, w, act="leaky")
                      .astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1))(x, w)
    bwd = _keys("gemm_bwd")
    assert {k[1] for k in bwd} == {("dx", 48, 24, 40), ("dw", 40, 48, 24)}
    for key, plan in bwd.items():
        assert plan == kernel_ops.default_gemm_bwd_blocks(*key[1], "float32")
        assert not backends.validate_tiles(*key[:3], plan)


@pytest.mark.parametrize("variant", ("dx", "dw"))
@pytest.mark.parametrize(
    "hw,k,n", [pytest.param(*g, id=f"conv{i + 1}")
               for i, g in enumerate(DARKNET19_GEMMS)])
def test_darknet19_b8_gemm_bwd_plan_is_legal(variant, hw, k, n):
    """The backward GEMM plans of Darknet-19's train step at bucket 8."""
    shapes = (variant,) + kernel_ops.gemm_bwd_problem(variant, 8 * hw, k, n)
    plan = backends.get_backend("pallas").tiles("gemm_bwd", shapes,
                                                "float32")
    assert len(plan) == 3
    assert backends.validate_tiles("gemm_bwd", shapes, "float32", plan) == []


@pytest.mark.parametrize("op", ("attention", "attention_bwd"))
@pytest.mark.parametrize("seq", (128, 512, 2048))
def test_qwen2_attention_plan_is_legal(op, seq):
    """qwen2-0.5b's attention: 14 query heads over 2 KV heads of 64."""
    shapes = ((1, seq, 14, 64), (1, seq, 2, 64))
    plan = backends.get_backend("pallas").tiles(op, shapes, "float32")
    assert len(plan) == 2
    assert backends.validate_tiles(op, shapes, "float32", plan) == []
