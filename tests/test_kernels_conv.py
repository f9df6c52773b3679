"""The implicit-patch convolution (`kernels.gemm.conv_gemm` behind
`ops.conv2d_implicit`) against `lax.conv_general_dilated` at HIGHEST and
against the im2col + GEMM formulation it replaces, its gradients through
the custom VJP, and the rule that selects it.

The networks' stride-1 3x3 widths (7, 13, 26, 52, 112) and channel counts
(32, 64 below the MXU depth: one patch-tile dot; 128, 512: a dot per
tap), batches 1 and 8, `leaky` and `linear` epilogues with and without
scale and shift.  Kernels run interpreted on the CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends, make_engine
from repro.kernels import gemm, ops
from repro.kernels.common import apply_act, im2col

HI = jax.lax.Precision.HIGHEST


def _operands(b, h, w_, cin, cout, size, affine, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + h * 31 + cin), 4)
    x = jax.random.normal(ks[0], (b, h, w_, cin), jnp.float32)
    wt = (jax.random.normal(ks[1], (size * size * cin, cout), jnp.float32)
          / (size * cin ** 0.5))
    scale = jax.random.uniform(ks[2], (cout,), jnp.float32, 0.5, 1.5)
    shift = jax.random.normal(ks[3], (cout,), jnp.float32)
    return x, wt, (scale if affine else None), (shift if affine else None)


def _lax_conv(x, wt, scale, shift, *, size, pad, act):
    w4 = wt.reshape(size, size, x.shape[-1], -1)
    y = jax.lax.conv_general_dilated(
        x, w4, (1, 1), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
    if scale is not None:
        y = y * scale
    if shift is not None:
        y = y + shift
    return apply_act(y, act)


def _implicit_conv(x, wt, scale, shift, *, size, pad, act):
    """`ops.conv2d_implicit` under the rule's plan for the shape, or one
    block of the whole image where the rule keeps im2col (the kernel runs
    any stride-1 shape)."""
    _, h, w_, cin = x.shape
    bm, bn = (ops.default_conv_implicit_blocks(x.shape, wt.shape[-1], size,
                                               1, pad, x.dtype)
              or (gemm.conv_layout(h, w_, size, pad)[1], wt.shape[-1]))
    return ops.conv2d_implicit(x, wt, scale, shift, size=size, pad=pad,
                               act=act, bm=bm, bn=bn)


def _im2col_conv(x, wt, scale, shift, *, size, pad, act):
    cols = im2col(x, size, size, 1, pad)
    b, oh, ow, kk = cols.shape
    return ops.matmul(cols.reshape(-1, kk), wt, scale, shift,
                      act=act).reshape(b, oh, ow, -1)


# (B, H, Cin, Cout, act, affine): every width, Cin and batch of the
# networks' implicit convolutions, at reduced Cout.
CASES = [
    (1, 7, 512, 128, "leaky", True),
    (8, 7, 128, 128, "linear", False),
    (1, 13, 64, 128, "leaky", True),
    (8, 13, 32, 64, "linear", True),
    (1, 26, 128, 256, "leaky", False),
    (8, 26, 64, 64, "leaky", True),
    (1, 52, 32, 64, "linear", False),
    (1, 52, 128, 128, "leaky", True),
    (1, 112, 32, 32, "leaky", True),
    (8, 14, 512, 128, "linear", True),
    # YOLOv4's mish convolutions: Cin 32 and 64 (nine taps in one dot),
    # Cin 256 (a dot per tap).
    (1, 38, 32, 64, "mish", True),
    (1, 19, 64, 64, "mish", False),
    (1, 19, 256, 128, "mish", True),
]


@pytest.mark.parametrize("b,h,cin,cout,act,affine", CASES)
def test_implicit_conv_matches_lax_and_im2col(b, h, cin, cout, act, affine):
    x, wt, scale, shift = _operands(b, h, h, cin, cout, 3, affine)
    got = _implicit_conv(x, wt, scale, shift, size=3, pad=1, act=act)
    want = _lax_conv(x, wt, scale, shift, size=3, pad=1, act=act)
    assert got.shape == want.shape == (b, h, h, cout)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    im2col_y = _im2col_conv(x, wt, scale, shift, size=3, pad=1, act=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(im2col_y),
                               rtol=1e-5, atol=1e-5)


# Pinned plans the rule would not pick: several row blocks per image
# (the last one ragged), column blocks narrower than Cout, a 5x5 and an
# unpadded (VALID) 3x3, a non-square image.
@pytest.mark.parametrize("b,h,w_,cin,cout,size,pad,bm,bn", [
    (2, 13, 13, 32, 256, 3, 1, 64, 128),
    (1, 26, 26, 128, 256, 3, 1, 200, 128),
    (2, 9, 11, 64, 128, 5, 2, 40, 128),
    (1, 12, 10, 128, 128, 3, 0, 32, 128),
])
def test_implicit_conv_pinned_plans(b, h, w_, cin, cout, size, pad, bm, bn):
    x, wt, scale, shift = _operands(b, h, w_, cin, cout, size, True)
    got = gemm.conv_gemm(x, wt, scale, shift, size=size, pad=pad,
                         act="leaky", bm=bm, bn=bn)
    want = _lax_conv(x, wt, scale, shift, size=size, pad=pad, act="leaky")
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("cin", [32, 256])
def test_conv_gemm_mish_epilogue_matches_jax_nn(cin):
    """The implicit-patch kernel's mish epilogue (interpret) against
    mish(conv(x, w) * scale + shift) in `jax.nn` terms, on inputs wide
    enough that pre-activations pass both of darknet's thresholds."""
    x, wt, scale, shift = _operands(1, 19, 19, cin, 128, 3, True, seed=3)
    x = 12.0 * x
    got = _implicit_conv(x, wt, scale, shift, size=3, pad=1, act="mish")
    u = _lax_conv(x, wt, scale, shift, size=3, pad=1, act="linear")
    assert (u > 20).any() and (u < -20).any()
    want = u * jnp.tanh(jax.nn.softplus(u))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("b,h,cin,cout,act,affine", [
    (2, 7, 128, 128, "leaky", True),
    (1, 13, 32, 64, "linear", True),
    (2, 9, 64, 32, "leaky", False),
    (1, 11, 32, 64, "mish", True),
])
def test_implicit_conv_grads_equal_im2col(b, h, cin, cout, act, affine):
    """The custom VJP is the im2col formulation's: the same gradients."""
    x, wt, scale, shift = _operands(b, h, h, cin, cout, 3, affine)
    cot = jax.random.normal(jax.random.PRNGKey(7), (b, h, h, cout))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a, size=3, pad=1, act=act) * cot)

    argnums = (0, 1, 2, 3) if affine else (0, 1)
    got = jax.grad(loss(_implicit_conv), argnums)(x, wt, scale, shift)
    want = jax.grad(loss(_im2col_conv), argnums)(x, wt, scale, shift)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


def test_pallas_conv2d_dispatches_by_rule():
    """The pallas engine's conv2d plan is the implicit (bm, bn) exactly
    where the rule admits the convolution and im2col's (bm, bk, bn)
    elsewhere, and both paths agree with XLA's convolution."""
    eng = make_engine("pallas")
    for cin, stride in ((64, 1), (3, 1), (64, 2)):
        x, wt, scale, shift = _operands(1, 12, 12, cin, 64, 3, True)
        shapes = (x.shape, 64, 3, stride, 1)
        plan = backends.get_backend("pallas").tiles("conv2d", shapes,
                                                    x.dtype)
        implicit = cin >= ops.IMPLICIT_MIN_CIN and stride == 1
        assert len(plan) == (2 if implicit else 3)
        assert backends.validate_tiles("conv2d", shapes, x.dtype,
                                       plan) == []
        got = eng.conv2d(x, wt, scale=scale, shift=shift, size=3,
                         stride=stride, pad=1, act="leaky")
        want = make_engine("xla").conv2d(x, wt, scale=scale, shift=shift,
                                         size=3, stride=stride, pad=1,
                                         act="leaky")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_rule_keeps_im2col_outside_its_domain():
    plan = ops.default_conv_implicit_blocks
    assert plan((1, 13, 13, 512), 1024, 1, 1, 0, "float32") == ()  # 1x1
    assert plan((1, 26, 26, 256), 512, 3, 2, 1, "float32") == ()   # stride 2
    assert plan((1, 416, 416, 3), 32, 3, 1, 1, "float32") == ()    # stem
    assert plan((8, 7, 7, 512), 1024, 3, 1, 1, "float32") == ()    # 63 rows
    assert plan((1, 7, 7, 512), 1024, 3, 1, 1, "float32")          # batch 1
    assert plan((1, 26, 26, 256), 512, 3, 1, 1, "bfloat16") == ()
    assert plan((1, 26, 26, 256), 512, 3, 1, 1, "float32")


@pytest.mark.parametrize("b,h,cin,cout", [
    (8, 14, 256, 512), (8, 13, 512, 1024), (8, 26, 256, 512),
    (8, 52, 128, 256), (8, 104, 64, 128), (8, 208, 32, 64),
    (1, 7, 512, 1024)])
def test_rule_plans_fit_and_waste_little(b, h, cin, cout):
    """At the networks' implicit shapes the plan fits the GEMM's VMEM
    budget and the rows computed and discarded stay within the flat
    layout's 2 of every Wp (3/16 at 13 and 26 wide, 2/9 at 7 wide)."""
    bm, bn = ops.default_conv_implicit_blocks((b, h, h, cin), cout, 3, 1,
                                              1, "float32")
    _, rows, halo = gemm.conv_layout(h, h, 3, 1)
    assert cout % bn == 0
    assert ops._conv_working_set(bm, bn, cin, 9, halo, 4) <= (
        ops._GEMM_VMEM_BUDGET)
    computed = -(-rows // bm) * bm
    assert 1 - h * h / computed <= 2 / 9 + 0.01


@pytest.mark.parametrize("w_,pitch", [
    (7, 9),        # 16 would discard 9 of 16 rows: the natural pitch
    (13, 16), (26, 32), (52, 56), (14, 16), (28, 32),
    (104, 112), (208, 216), (112, 120),
])
def test_conv_layout_pitch(w_, pitch):
    """The flat row pitch: a multiple of 8 wherever that discards at most
    2 of every 9 rows, so the flat views are bitcasts of the tiled 4-D
    arrays; the halo reaches the last tap."""
    wp, rows, halo = gemm.conv_layout(w_, w_, 3, 1)
    assert wp == pitch and rows == w_ * wp
    assert halo == 2 * wp + 2
