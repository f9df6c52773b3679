"""Calibration of the trip-count-aware HLO analyzer against XLA's own
cost analysis (loop-free) and against analytic expectations (loops)."""
import jax
import jax.numpy as jnp

from repro.analysis import hlo_cost


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def _xla_cost(compiled) -> dict:
    return compiled.cost_analysis()


def test_single_dot_flops_match_xla():
    m, k, n = 64, 128, 32
    c = _compile(lambda x, w: x @ w,
                 jax.ShapeDtypeStruct((m, k), jnp.float32),
                 jax.ShapeDtypeStruct((k, n), jnp.float32))
    got = hlo_cost.analyze(c.as_text())
    want = 2 * m * k * n
    assert got["flops"] == want
    xla = _xla_cost(c).get("flops")
    assert abs(xla - want) / want < 0.01


def test_scan_flops_multiply_by_trip_count():
    m, k = 8, 16
    L = 7

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=L)
        return y.sum()

    c = _compile(f, jax.ShapeDtypeStruct((m, k), jnp.float32),
                 jax.ShapeDtypeStruct((k, k), jnp.float32))
    got = hlo_cost.analyze(c.as_text())
    want = L * 2 * m * k * k
    assert got["flops"] == want, (got["flops"], want)
    # XLA undercounts (body counted once) — document the gap this fixes
    xla = _xla_cost(c).get("flops", 0)
    assert xla < want


def test_nested_scan():
    def f(x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ x, None
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y.sum()

    d = 16
    c = _compile(f, jax.ShapeDtypeStruct((d, d), jnp.float32))
    got = hlo_cost.analyze(c.as_text())
    want = 5 * 3 * 2 * d * d * d
    assert got["flops"] == want


def test_batched_dot_flops():
    b, m, k, n = 4, 32, 64, 16
    c = _compile(lambda x, w: jnp.einsum("bmk,bkn->bmn", x, w),
                 jax.ShapeDtypeStruct((b, m, k), jnp.float32),
                 jax.ShapeDtypeStruct((b, k, n), jnp.float32))
    got = hlo_cost.analyze(c.as_text())
    assert got["flops"] == 2 * b * m * k * n


def test_bytes_roughly_match_xla_for_loop_free():
    m, k, n = 256, 256, 256
    c = _compile(lambda x, w: jax.nn.relu(x @ w),
                 jax.ShapeDtypeStruct((m, k), jnp.float32),
                 jax.ShapeDtypeStruct((k, n), jnp.float32))
    got = hlo_cost.analyze(c.as_text())
    xla = _xla_cost(c).get("bytes accessed", 0)
    assert got["bytes"] > 0
    # same order of magnitude (models differ on fusion accounting)
    assert 0.2 < got["bytes"] / max(xla, 1) < 5.0


def test_collectives_counted_with_factors():
    # single-device process: collectives only appear under a mesh — use the
    # dryrun results instead; here just check the regex layer on a synthetic
    # module.
    text = """
HloModule test

%body.1 (arg: (s32[], f32[64,128])) -> (s32[], f32[64,128]) {
  %arg = (s32[], f32[64,128]{1,0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%arg), index=0
  %gte.1 = f32[64,128]{1,0} get-tuple-element(%arg), index=1
  %ar.0 = f32[64,128]{1,0} all-reduce(%gte.1), replica_groups={{0,1,2,3}}, to_apply=%sum.0
  ROOT %t = (s32[], f32[64,128]{1,0}) tuple(%gte.0, %ar.0)
}

%cond.1 (arg.1: (s32[], f32[64,128])) -> pred[] {
  %arg.1 = (s32[], f32[64,128]{1,0}) parameter(0)
  %g = s32[] get-tuple-element(%arg.1), index=0
  %c = s32[] constant(12)
  ROOT %lt = pred[] compare(%g, %c), direction=LT
}

ENTRY %main (p0: f32[64,128]) -> f32[64,128] {
  %p0 = f32[64,128]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %t0 = (s32[], f32[64,128]{1,0}) tuple(%c0, %p0)
  %w = (s32[], f32[64,128]{1,0}) while(%t0), condition=%cond.1, body=%body.1
  %gte = f32[64,128]{1,0} get-tuple-element(%w), index=1
  %ag = f32[64,512]{1,0} all-gather(%gte), replica_groups=[16,4]<=[64], dimensions={1}
  ROOT %rs = f32[64,32]{1,0} reduce-scatter(%ag), replica_groups=[16,4]<=[64], dimensions={1}, to_apply=%sum.0
}
"""
    got = hlo_cost.analyze(text)
    coll = got["collectives"]
    # all-reduce inside 12-trip loop: 64*128*4 bytes * 2 * 12
    assert coll["all-reduce"] == 64 * 128 * 4 * 2 * 12
    # all-gather: result bytes 64*512*4
    assert coll["all-gather"] == 64 * 512 * 4
    # reduce-scatter: result 64*32*4 * group_size 4
    assert coll["reduce-scatter"] == 64 * 32 * 4 * 4


def test_remat_train_flops_ratio():
    """Scan+remat train step ≈ 8·N·D flops (fwd + re-fwd + 2×bwd)."""
    d, L, B = 64, 4, 8

    def loss(ws, x):
        def body(c, w):
            return jnp.tanh(c @ w), None
        body_ck = jax.checkpoint(body)
        y, _ = jax.lax.scan(body_ck, x, ws)
        return (y ** 2).mean()

    g = jax.grad(loss)
    c = _compile(g, jax.ShapeDtypeStruct((L, d, d), jnp.float32),
                 jax.ShapeDtypeStruct((B, d), jnp.float32))
    got = hlo_cost.analyze(c.as_text())
    unit = 2 * B * d * d * L       # one forward pass
    ratio = got["flops"] / unit
    # fwd(1) + recompute(1) + bwd(2) = 4; allow slack for the tanh vjp
    assert 3.5 <= ratio <= 4.6, ratio
