"""Split-KV flash-decoding conformance suite.

Decode-shaped attention dispatches (Sq <= 8, Skv >= 256) switch to the
split-KV formulation (kernels/flash_decode.py): n_splits programs per
(batch, head) each reduce one KV span to a partial (o, lse), merged by
the logsumexp combine.  This suite pins:

  * parity vs the ref oracle over the shipped head ratios, causal and
    non-causal, scalar / per-batch kv_len, non-multiple key extents (the
    padded span path), fp32 tight / bf16 loose;
  * decode edges through the merge: kv_len == 0 and fully-masked rows
    give exact 0 (never NaN); split-count == 1 degenerates BIT-identically
    to the forward kernel; bf16 operands keep fp32 partials and lse;
  * registry selection: a decode-shaped `engine.attention` dispatch on
    the pallas backend resolves (bk_split, n_splits) tiles under the lazy
    "attention_decode" tile-plan key, while prefill shapes keep the
    forward (bq, bk) plan — and both agree with the xla formulation;
  * the (bk_split, n_splits) tile family: the rule's legality and
    validator rejections (mis-alignment, dead splits).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends, make_engine
from repro.kernels import ops
from repro.kernels.flash_decode import flash_decode
from repro.kernels.ref import flash_attention_ref

HEAD_RATIOS = [(16, 16), (14, 2), (8, 1)]
TOL = {jnp.float32: 2e-4, jnp.bfloat16: 2e-2}


def _mk(seed, b, sq, skv, h, kv, d, dtype=jnp.float32):
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (b, sq, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (b, skv, kv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(kv_, (b, skv, kv, d), jnp.float32).astype(dtype)
    return q, k, v


def _assert_close(got, want, dtype):
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ------------------------------------------------------------- parity ---

@pytest.mark.parametrize("h,kv", HEAD_RATIOS)
@pytest.mark.parametrize("causal", [True, False])
def test_decode_parity_vs_ref(h, kv, causal):
    q, k, v = _mk(h * 13 + kv, 2, 1, 512, h, kv, 32)
    got = ops.attention_decode(q, k, v, causal=causal, bk_split=128,
                               n_splits=4)
    want = flash_attention_ref(q, k, v, causal=causal)
    _assert_close(got, want, jnp.float32)


@pytest.mark.parametrize("sq", [1, 4, 8])
def test_decode_parity_chunked_query(sq):
    """Chunked-prefill decode steps (1 < Sq <= 8) right-align causally
    against the live extent, matching the forward wrapper's semantics."""
    q, k, v = _mk(sq, 2, sq, 384, 8, 2, 64)
    kvl = jnp.array([384, 200], jnp.int32)
    got = ops.attention_decode(q, k, v, kvl, causal=True, bk_split=128,
                               n_splits=3)
    want = flash_attention_ref(q, k, v, causal=True, kv_len=kvl)
    _assert_close(got, want, jnp.float32)


def test_decode_non_multiple_extent_pads_and_masks():
    """Skv=700 pads to the (bk_split * n_splits) multiple; the synthesized
    kv_len masks the key padding so parity holds exactly."""
    q, k, v = _mk(3, 2, 1, 700, 4, 4, 64)
    got = ops.attention_decode(q, k, v, causal=True, bk_split=128,
                               n_splits=2)
    want = flash_attention_ref(q, k, v, causal=True)
    _assert_close(got, want, jnp.float32)


def test_decode_scalar_and_per_batch_kv_len():
    q, k, v = _mk(5, 2, 1, 512, 8, 2, 32)
    want = flash_attention_ref(q, k, v, causal=True,
                               kv_len=jnp.array([300, 300], jnp.int32))
    got_scalar = ops.attention_decode(q, k, v, 300, causal=True,
                                      bk_split=128, n_splits=4)
    _assert_close(got_scalar, want, jnp.float32)
    kvl = jnp.array([300, 17], jnp.int32)
    got = ops.attention_decode(q, k, v, kvl, causal=True, bk_split=128,
                               n_splits=4)
    want = flash_attention_ref(q, k, v, causal=True, kv_len=kvl)
    _assert_close(got, want, jnp.float32)


# ------------------------------------------------------- decode edges ---

def test_kv_len_zero_is_exact_zero_not_nan():
    """Every span of every row empty: the merge sums zero partials over a
    finite denominator — exact 0, never NaN."""
    q, k, v = _mk(9, 2, 4, 512, 8, 2, 32)
    kvl = jnp.zeros((2,), jnp.int32)
    got = ops.attention_decode(q, k, v, kvl, causal=True, bk_split=128,
                               n_splits=4)
    assert not np.any(np.isnan(np.asarray(got)))
    assert np.all(np.asarray(got) == 0.0)


def test_mixed_empty_rows_exact_zero():
    """One batch row live, one at kv_len=0 — the dead row is exact 0 while
    the live row keeps full parity (no cross-row contamination through the
    shared merge)."""
    q, k, v = _mk(10, 2, 1, 512, 4, 1, 32)
    kvl = jnp.array([512, 0], jnp.int32)
    got = ops.attention_decode(q, k, v, kvl, causal=True, bk_split=128,
                               n_splits=4)
    want = flash_attention_ref(q, k, v, causal=True, kv_len=kvl)
    assert np.all(np.asarray(got[1]) == 0.0)
    _assert_close(got, want, jnp.float32)


def test_single_split_degenerates_bit_identically():
    """n_splits=1 runs the same online-softmax block walk as the forward
    kernel at bq=8 — the merge reduces to o_0 * exp(0) / 1, so the result
    is BIT-identical, not just close."""
    q, k, v = _mk(12, 1, 8, 256, 4, 1, 64)
    got = ops.attention_decode(q, k, v, causal=True, bk_split=256,
                               n_splits=1)
    want = ops.attention(q, k, v, causal=True, bq=8, bk=256)
    assert jnp.array_equal(got, want)


def test_bf16_operands_keep_fp32_lse_and_partials():
    """bf16 in, bf16 out — but the kernel's partials, lse and the merge
    never leave fp32: the raw flash_decode return is fp32, and the result
    tracks an all-fp32 reference at bf16 input-rounding error only."""
    q, k, v = _mk(15, 2, 1, 512, 8, 8, 64, jnp.bfloat16)
    raw = flash_decode(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                       v.transpose(0, 2, 1, 3),
                       jnp.full((2, 1), 512, jnp.int32), causal=True,
                       sm_scale=1.0 / 8.0, bk=128, n_splits=4, q_len=1)
    assert raw.dtype == jnp.float32
    got = ops.attention_decode(q, k, v, causal=True, bk_split=128,
                               n_splits=4)
    assert got.dtype == jnp.bfloat16
    want = flash_attention_ref(q.astype(jnp.float32),
                               k.astype(jnp.float32),
                               v.astype(jnp.float32), causal=True)
    _assert_close(got, want, jnp.bfloat16)


# ------------------------------------------------- registry selection ---

def test_use_decode_formulation_boundary():
    assert ops.use_decode_formulation(1, ops.DECODE_MIN_SKV)
    assert ops.use_decode_formulation(ops.DECODE_MAX_SQ, 512)
    assert not ops.use_decode_formulation(ops.DECODE_MAX_SQ + 1, 512)
    assert not ops.use_decode_formulation(1, ops.DECODE_MIN_SKV - 1)
    assert not ops.use_decode_formulation(512, 512)


def test_registry_selects_decode_formulation_lazily():
    """A decode-shaped pallas dispatch resolves its tiles under the
    "attention_decode" key; a prefill dispatch does not touch that key
    space — and both match the xla formulation."""
    backends.clear_tile_cache()
    q, k, v = _mk(20, 2, 1, 512, 8, 2, 32)
    kvl = jnp.array([512, 300], jnp.int32)
    got = make_engine("pallas").attention(q, k, v, causal=True, kv_len=kvl)
    want = make_engine("xla").attention(q, k, v, causal=True, kv_len=kvl)
    _assert_close(got, want, jnp.float32)
    keys = [k2 for k2 in backends.tile_plans()
            if k2[0] == "attention_decode"]
    assert len(keys) == 1, keys

    backends.clear_tile_cache()
    qp, kp, vp = _mk(21, 1, 512, 512, 8, 2, 32)
    make_engine("pallas").attention(qp, kp, vp, causal=True)
    assert not [k2 for k2 in backends.tile_plans()
                if k2[0] == "attention_decode"]


# ----------------------------------------------------- tile machinery ---

def test_decode_tile_heuristic_and_candidates_are_legal():
    for skv in (256, 512, 2048, 8192):
        dims = ops.attention_dims(((2, 1, 8, 64), (2, skv, 1, 64)))
        pick = ops.default_attention_decode_blocks(*dims, jnp.float32)
        assert ops.validate_attention_decode_tiles(
            1, skv, 64, jnp.float32, pick) == [], (skv, pick)


def test_decode_tile_validator_rejects_illegal_plans():
    bad_align = ops.validate_attention_decode_tiles(
        1, 512, 64, jnp.float32, (100, 2))
    assert any("128-lane" in p for p in bad_align)
    dead_split = ops.validate_attention_decode_tiles(
        1, 512, 64, jnp.float32, (256, 9))
    assert any("empty spans" in p for p in dead_split)
    oversized = ops.validate_attention_decode_tiles(
        1, 256, 64, jnp.float32, (512, 1))
    assert any("padded key extent" in p for p in oversized)
    malformed = ops.validate_attention_decode_tiles(
        1, 512, 64, jnp.float32, (128,))
    assert malformed and "two positive ints" in malformed[0]
