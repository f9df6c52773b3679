"""`sharded_pallas` backend: registration, off-mesh degradation, topology-
keyed compile cache, collective-audit helpers, and — under 8 virtual
devices (the `eight_devices` conftest guard; run pytest with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — numeric parity,
gradient parity, seq-split correctness, R002-clean sharded traces and
mesh-threaded serving.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.analysis import diagnose, lint
from repro.configs.base import get_arch, reduced
from repro.core import StepCompileCache, backends, make_engine
from repro.kernels import ops as kernel_ops
from repro.kernels import sharded
from repro.models import transformer as tfm
from repro.sharding import hints


def _qkv(key, b, sq, skv, h, kvh, d, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (b, sq, h, d), dtype),
            jax.random.normal(ks[1], (b, skv, kvh, d), dtype),
            jax.random.normal(ks[2], (b, skv, kvh, d), dtype))


# ----------------------------------------------------------- registration ---

def test_backend_registered_with_full_op_set():
    assert "sharded_pallas" in backends.list_backends()
    be = backends.get_backend("sharded_pallas")
    for op in ("matmul", "bmm", "conv2d", "attention"):
        assert op in be.ops
        assert op in be.differentiable
    # no tile hooks: block plans resolve lazily from PER-SHARD shapes
    # inside the shard bodies, under the standard "pallas" keys.
    assert be.tiles("matmul", (64, 64, 64), "float32") == ()


def test_off_mesh_matches_pallas_bitwise():
    e_s = make_engine("sharded_pallas", "fp32_strict")
    e_p = make_engine("pallas", "fp32_strict")
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 32, 32, 4, 2, 16)
    assert jnp.array_equal(e_s.attention(q, k, v, causal=True),
                           e_p.attention(q, k, v, causal=True))
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 24))
    w = jax.random.normal(jax.random.PRNGKey(2), (24, 8))
    assert jnp.array_equal(e_s.matmul(x, w), e_p.matmul(x, w))


def test_one_device_mesh_takes_local_path():
    devs = np.array(jax.devices()[:1])
    with Mesh(devs, ("data",)):
        assert sharded.mesh_plan() is None   # size-1 mesh -> local kernels
        q, k, v = _qkv(jax.random.PRNGKey(0), 2, 16, 16, 4, 2, 16)
        out = sharded.attention(q, k, v, None, None, causal=True)
    assert out.shape == q.shape


# -------------------------------------------------- topology-keyed cache ---

def test_compile_cache_topology_extends_keys():
    calls = []

    def step(x):
        calls.append(1)          # trace-time side effect
        return x + 1

    topo = (("data", 8),)
    c = StepCompileCache(step, name="s", topology=topo)
    c(jnp.zeros(2))
    c(jnp.zeros(2))
    assert c.traces == 1 and c.calls == 2
    c.record((2, 1))
    assert c.stats()["topology"] == topo
    # recorded dispatch keys carry the topology prefix...
    assert c.stats()["dispatches"] == {(("data", 8), 2, 1): 1}
    # ...and a topology change owns a FRESH jit cache (a trace embeds its
    # mesh's shard_maps; replaying it under another mesh would be wrong).
    c.topology = (("data", 4),)
    c(jnp.zeros(2))
    assert c.traces == 2


def test_compile_cache_off_mesh_keys_unchanged():
    c = StepCompileCache(lambda x: x, name="s")
    c.record((1, 2, 3))
    assert c.stats()["dispatches"] == {(1, 2, 3): 1}   # no prefix when ()


# ------------------------------------------------------- collective audit ---

_HLO = """\
HloModule m

%body (p: f32[8,16]) -> f32[8,16] {
  %p = f32[8,16] parameter(0)
  %ag = f32[64,16] all-gather(%p), replica_groups={{0,1}}, dimensions={0}
  %ar = f32[8,16] all-reduce-start(%p), to_apply=%add
  %ard = f32[8,16] all-reduce-done(%ar)
  ROOT %out = f32[8,16] add(%ard, %ard)
}

ENTRY %main (x: f32[8,16]) -> f32[8,16] {
  %x = f32[8,16] parameter(0)
  %small = f32[2,4] all-gather(%x), replica_groups={{0,1}}, dimensions={0}
  ROOT %c = f32[8,16] call(%x), to_apply=%body
}
"""


def test_count_collectives_folds_async_pairs():
    counts = diagnose.count_collectives(_HLO)
    assert counts == {"all-gather": 2, "all-reduce": 1}


def test_full_kv_gathers_thresholds():
    # full-KV threshold 1024 elems: the 64x16 gather trips, 2x4 doesn't
    bad = diagnose.full_kv_gathers(_HLO, 1024)
    assert len(bad) == 1 and "1024" in bad[0]
    assert diagnose.full_kv_gathers(_HLO, 2000) == []


# ------------------------------------------------------ 8-device parity ----

@pytest.fixture
def mesh8(eight_devices):
    return Mesh(np.array(eight_devices), ("data",))


def test_batch_sharded_attention_parity_and_grads(mesh8):
    q, k, v = _qkv(jax.random.PRNGKey(3), 8, 64, 64, 4, 2, 32)

    def local(q, k, v):
        return kernel_ops.attention(q, k, v, None, None, causal=True)

    def dist(q, k, v):
        return sharded.attention(q, k, v, None, None, causal=True)

    ref = jax.jit(local)(q, k, v)
    with mesh8:
        out = jax.jit(dist)(q, k, v)
        g_ref = jax.grad(lambda *a: jnp.sum(local(*a) ** 2), (0, 1, 2))(
            q, k, v)
        g_out = jax.grad(lambda *a: jnp.sum(dist(*a) ** 2), (0, 1, 2))(
            q, k, v)
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5
    for ga, gb in zip(g_out, g_ref):
        assert float(jnp.max(jnp.abs(ga - gb))) <= 1e-5


def test_seq_split_attention_parity(mesh8):
    # B=2 doesn't divide 8 and there's no head axis -> decode-shaped
    # dispatches take the sequence-split partial-(o, lse) path.
    q, k, v = _qkv(jax.random.PRNGKey(4), 2, 1, 512, 4, 2, 32)
    for kvl in (None, jnp.asarray([3, 300], jnp.int32)):
        ref = kernel_ops.attention_decode(q, k, v, kvl, None, causal=True)
        with mesh8:
            out = sharded.attention(q, k, v, kvl, None, causal=True)
        assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5, f"kv_len={kvl}"


def test_sharded_trace_r002_clean_no_full_kv_gather(mesh8):
    eng = make_engine("sharded_pallas", "fp32_strict")
    q, k, v = _qkv(jax.random.PRNGKey(5), 8, 64, 64, 4, 2, 32)

    def f(q, k, v):
        return eng.attention(q, k, v, causal=True)

    with mesh8:
        rep = lint.lint_traced(f, q, k, v, backend="sharded_pallas",
                               label="sharded-attention")
        text = jax.jit(f).lower(q, k, v).compile().as_text()
    assert not [x for x in rep.errors if x.rule == "R002"], rep.format()
    assert diagnose.full_kv_gathers(text, 8 * 64 * 2 * 32) == []


def test_slot_serving_under_mesh_matches_unsharded(mesh8):
    from repro.serve.engine import Request, ServingEngine
    cfg = reduced(get_arch("qwen2-0.5b"))
    cfg = dataclasses.replace(cfg, n_layers=1)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)

    def stream(backend, mesh):
        se = ServingEngine(cfg, params,
                           engine=make_engine(backend, "fp32_strict"),
                           slots=8, max_len=32, mesh=mesh)
        reqs = [Request(rid=i, prompt=[1 + i, 2 + i, 3], max_new=3)
                for i in range(2)]
        for r in reqs:
            se.submit(r)
        for _ in range(40):
            if all(r.done for r in reqs):
                break
            se.step()
        assert all(r.done for r in reqs)
        return [tuple(r.out) for r in reqs]

    assert stream("pallas", None) == stream("sharded_pallas", mesh8)


def test_per_shard_autotune_keys(mesh8):
    backends.clear_tile_cache()
    q, k, v = _qkv(jax.random.PRNGKey(6), 8, 48, 48, 4, 2, 32)
    with mesh8:
        jax.block_until_ready(
            jax.jit(lambda *a: sharded.attention(*a, None, None,
                                                 causal=True))(q, k, v))
    att = [key for key in backends.tile_plans() if key[0] == "attention"]
    assert att, "no attention tile key resolved"
    assert {a[1][0][0] for a in att} == {1}, att   # per-shard batch only
