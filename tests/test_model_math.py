"""Math-level correctness of the model building blocks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dep — property tests skip without it
    from hypothesis_stub import given, settings, st

from repro.core import backends, make_engine
from repro.kernels import ref as kref
from repro.launch.mesh import make_mesh
from repro.models import ssm as ssm_mod
from repro.models.attention import (blockwise_attention, gqa_forward,
                                    gqa_init, mla_forward, mla_init)
from repro.models.common import chunked_cross_entropy, rope_apply, rope_table
from repro.models.moe import capacity, moe_forward, moe_init
from repro.configs.base import get_arch, reduced

ENGINE = make_engine("xla", "fp32_strict")


# ------------------------------------------------- blockwise attention ----

@pytest.mark.parametrize("S,H,KV,D,causal", [
    (128, 4, 2, 32, True),
    (128, 4, 4, 32, False),
    (96, 6, 2, 16, True),      # ragged chunks (96/4 = 24 per chunk)
    (256, 2, 1, 64, True),
])
def test_blockwise_attention_vs_oracle(S, H, KV, D, causal):
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    qg = q.reshape(B, S, KV, H // KV, D)
    got = blockwise_attention(ENGINE, qg, k, v, causal=causal,
                              n_q_chunks=4, kv_chunk=32)
    got = got.reshape(B, S, H, D)
    # oracle: broadcast kv heads
    G = H // KV
    kb = jnp.repeat(k, G, axis=2)
    vb = jnp.repeat(v, G, axis=2)
    # interleave must match reshape grouping: head h = kv*(G) + g
    want = kref.flash_attention_ref(q, kb, vb, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_blockwise_attention_chunk_invariance():
    B, S, H, D = 1, 128, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, 1, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    a = blockwise_attention(ENGINE, q, k, v, causal=True, n_q_chunks=2,
                            kv_chunk=16)
    b = blockwise_attention(ENGINE, q, k, v, causal=True, n_q_chunks=8,
                            kv_chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


def _dense_grouped_oracle(q, k, v, *, causal, q_offset=None):
    """Dense jnp oracle for the grouped (B, Sq, KV, G, Dh) layout with an
    independent value width (MLA); fully-masked rows come out exact 0."""
    B, Sq, KV, G, Dh = q.shape
    Skv = k.shape[1]
    if q_offset is None:
        q_offset = Skv - Sq
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                   preferred_element_type=jnp.float32) / (Dh ** 0.5)
    if causal:
        qi = q_offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
        ki = jax.lax.broadcasted_iota(jnp.int32, s.shape, 4)
        s = jnp.where(ki <= qi, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(s > -0.5e30, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgqk,bkhv->bhgqv", p, v,
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(l, 1e-37)
    return out.transpose(0, 3, 1, 2, 4)


@pytest.mark.parametrize("Skv,kv_chunk,causal,shard_mode", [
    (100, 64, True, "seq"),     # the clamped-final-chunk repro
    (100, 64, True, "heads"),
    (100, 64, False, "seq"),
    (100, 33, True, "seq"),     # several ragged windows
    (192, 128, True, "heads"),
])
def test_blockwise_attention_non_multiple_kv_chunk(Skv, kv_chunk, causal,
                                                   shard_mode):
    """Regression: when the causal KV extent exceeds and is not a multiple
    of `kv_chunk`, dynamic_slice clamps the final chunk's start while the
    mask's key iota used to assume the unclamped start — keys were scored
    at wrong positions and some attended twice (max abs err 0.25 at
    Skv=100, kv_chunk=64 before the fix)."""
    B, KV, G, D = 2, 2, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(Skv + kv_chunk), 3)
    q = jax.random.normal(ks[0], (B, Skv, KV, G, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Skv, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Skv, KV, D), jnp.float32)
    got = blockwise_attention(ENGINE, q, k, v, causal=causal,
                              n_q_chunks=4, kv_chunk=kv_chunk,
                              shard_mode=shard_mode)
    want = _dense_grouped_oracle(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_blockwise_attention_mla_geometry_non_multiple_chunk():
    """MLA geometry (value width != qk width) crossing the kv_chunk
    boundary at a non-multiple extent — the mla_forward prefill shape of
    the clamp bug (S=1500 > kv_chunk=1024, final window clamped)."""
    B, S, H, Dh, Dv = 1, 1500, 2, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, S, H, 1, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, Dv), jnp.float32)
    got = blockwise_attention(ENGINE, q, k, v, causal=True, n_q_chunks=4,
                              kv_chunk=1024, shard_mode="heads")
    want = _dense_grouped_oracle(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_blockwise_attention_sq_gt_skv_negative_offset():
    """Sq > Skv right-alignment: early query rows sit at negative global
    positions with NO live keys under causality — the causal extent is
    <= 0 and the clamped slice geometry must still mask everything, so
    those rows come out exact 0 (never NaN)."""
    B, Sq, Skv, KV, G, D = 1, 16, 8, 2, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, Sq, KV, G, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Skv, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Skv, KV, D), jnp.float32)
    got = blockwise_attention(ENGINE, q, k, v, causal=True, n_q_chunks=4,
                              kv_chunk=4)
    want = _dense_grouped_oracle(q, k, v, causal=True)
    assert np.all(np.isfinite(np.asarray(got)))
    # rows at negative global positions: exact 0
    dead = Sq - Skv
    assert np.all(np.asarray(got[:, :dead]) == 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_mla_forward_prefill_crosses_kv_chunk_boundary():
    """End-to-end mla_forward at S > 1024 (the hard-wired blockwise
    kv_chunk): before the clamp fix the final KV window silently
    corrupted every off-mesh MLA prefill at these lengths.  Oracle:
    the same projections with one dense softmax attention."""
    from repro.models.common import rmsnorm
    cfg = reduced(get_arch("deepseek-v2-lite-16b"))
    p = mla_init(jax.random.PRNGKey(0), cfg)
    B, S = 1, 1500
    nope, rope_d = cfg.qk_nope_dim, cfg.qk_rope_dim
    lora, vd, H = cfg.kv_lora_rank, cfg.v_head_dim, cfg.n_heads
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                          jnp.float32) * 0.5
    cos, sin = rope_table(jnp.arange(S), rope_d, cfg.rope_theta)
    got = mla_forward(ENGINE, p, x, cos, sin, cfg, n_q_chunks=4)

    q = (x @ p["wq"]).reshape(B, S, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rope_apply(q_rope, cos, sin)
    dkv = x @ p["w_dkv"]
    c_kv = rmsnorm(dkv[..., :lora], p["kv_norm"]["scale"], cfg.norm_eps)
    k_rope = rope_apply(dkv[..., lora:][:, :, None, :], cos, sin)
    k_nope = (c_kv @ p["w_uk"]).reshape(B, S, H, nope)
    v = (c_kv @ p["w_uv"]).reshape(B, S, H, vd)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, rope_d))], axis=-1)
    y = _dense_grouped_oracle(q_full.reshape(B, S, H, 1, nope + rope_d),
                              k_full, v, causal=True)
    want = y.reshape(B, S, H * vd) @ p["wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-4, atol=5e-4)


def test_gqa_prefill_routes_through_registry_attention_at_every_scale():
    """Prefill dispatches the registry `attention` op UNCONDITIONALLY —
    with or without a mesh installed (distribution is the backend's job,
    not the model's); ``kernel_attention=False`` is the only way to the
    blockwise oracle, and the two formulations agree numerically."""
    cfg = reduced(get_arch("qwen2-0.5b"))
    p = gqa_init(jax.random.PRNGKey(0), cfg)
    B, S = 2, 32
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                          jnp.float32)
    cos, sin = rope_table(jnp.arange(S), cfg.head_dim, cfg.rope_theta)

    snap = backends.dispatch_counts()
    y_off = gqa_forward(ENGINE, p, x, cos, sin, cfg)
    off_counts = backends.counts_since(snap)
    assert off_counts.get(("xla", "attention")) == 1

    mesh = make_mesh((1,), ("data",))
    with jax.set_mesh(mesh):
        snap = backends.dispatch_counts()
        y_on = gqa_forward(ENGINE, p, x, cos, sin, cfg)
        on_counts = backends.counts_since(snap)
    assert on_counts.get(("xla", "attention")) == 1   # same op path on-mesh
    np.testing.assert_allclose(np.asarray(y_off), np.asarray(y_on),
                               rtol=2e-4, atol=2e-4)

    snap = backends.dispatch_counts()
    y_bw = gqa_forward(ENGINE, p, x, cos, sin, cfg, kernel_attention=False)
    bw_counts = backends.counts_since(snap)
    assert ("xla", "attention") not in bw_counts      # the A/B oracle
    np.testing.assert_allclose(np.asarray(y_off), np.asarray(y_bw),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------- RoPE -----

def test_rope_rotation_preserves_norm_and_relativity():
    S, D = 16, 32
    cos, sin = rope_table(jnp.arange(S), D, 1e4)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, 2, D), jnp.float32)
    y = rope_apply(x, cos, sin)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(y, axis=-1)),
                               np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-5)
    # relative property: <rope(q,i), rope(k,j)> depends only on i-j
    q = jax.random.normal(jax.random.PRNGKey(2), (D,))
    k = jax.random.normal(jax.random.PRNGKey(3), (D,))

    def dot_at(i, j):
        ci, si = rope_table(jnp.array([i]), D, 1e4)
        cj, sj = rope_table(jnp.array([j]), D, 1e4)
        qi = rope_apply(q[None, None, None, :], ci, si)[0, 0, 0]
        kj = rope_apply(k[None, None, None, :], cj, sj)[0, 0, 0]
        return float(qi @ kj)

    assert abs(dot_at(3, 1) - dot_at(10, 8)) < 1e-3
    assert abs(dot_at(5, 5) - dot_at(12, 12)) < 1e-3


# ---------------------------------------------------------------- SSD -----

@settings(max_examples=8, deadline=None)
@given(s=st.sampled_from([32, 64, 96]), h=st.sampled_from([2, 4]),
       p=st.sampled_from([8, 16]), n=st.sampled_from([4, 8]),
       chunk=st.sampled_from([16, 32]))
def test_ssd_chunked_matches_recurrence(s, h, p, n, chunk):
    if s % chunk:
        return
    B, G = 2, 1
    ks = jax.random.split(jax.random.PRNGKey(s * h + p), 4)
    x = jax.random.normal(ks[0], (B, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, s, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32) * 0.3)
    Bm = jax.random.normal(ks[3], (B, s, G, n), jnp.float32)
    Cm = jax.random.normal(jax.random.PRNGKey(9), (B, s, G, n), jnp.float32)
    got_y, got_state = ssm_mod.ssd_chunked(ENGINE, x, dt, A, Bm, Cm, chunk)
    want_y, want_state = ssm_mod.ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(want_state),
                               rtol=2e-3, atol=2e-3)


def test_ssd_decode_continues_prefill():
    """Prefill state then step-by-step decode == full-sequence SSD."""
    cfg = reduced(get_arch("mamba2-1.3b"))
    p = ssm_mod.ssm_init(jax.random.PRNGKey(0), cfg)
    B, S = 1, 64
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                          jnp.float32) * 0.5
    y_full, cache = ssm_mod.ssm_forward(ENGINE, p, x, cfg, return_cache=True)
    # replay the last 8 tokens through decode from a mid-sequence cache
    S0 = S - 8
    _, cache0 = ssm_mod.ssm_forward(ENGINE, p, x[:, :S0], cfg,
                                    return_cache=True)
    ys = []
    c = cache0
    for t in range(S0, S):
        y1, c = ssm_mod.ssm_decode(ENGINE, p, x[:, t:t + 1], c, cfg)
        ys.append(y1[:, 0])
    got = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(y_full[:, S0:]),
                               rtol=5e-3, atol=5e-3)


# ---------------------------------------------------------------- MoE -----

def test_moe_routes_and_balances():
    cfg = reduced(get_arch("deepseek-v2-lite-16b"))
    p = moe_init(jax.random.PRNGKey(0), cfg)
    B, S = 2, 64
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                          jnp.float32)
    y, aux = moe_forward(ENGINE, p, x, cfg)
    assert y.shape == x.shape
    assert np.all(np.isfinite(np.asarray(y)))
    assert float(aux) > 0.5  # ~1.0 for near-uniform routing

    # capacity: C >= S*K/E
    C = capacity(S, cfg)
    assert C * cfg.n_routed_experts >= S * cfg.top_k


def test_moe_matches_dense_reference_when_capacity_unbounded():
    """With capacity >> tokens, grouped dispatch == per-token dense mixture."""
    import dataclasses
    cfg = dataclasses.replace(reduced(get_arch("llama4-scout-17b-a16e")),
                              capacity_factor=64.0, n_shared_experts=0)
    p = moe_init(jax.random.PRNGKey(0), cfg)
    B, S, D = 2, 16, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32)
    y, _ = moe_forward(ENGINE, p, x, cfg)

    # dense reference
    scores = x @ p["router"]
    probs = jax.nn.softmax(scores, -1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    w = w / w.sum(-1, keepdims=True)
    ref_out = np.zeros((B, S, D), np.float32)
    for b in range(B):
        for s in range(S):
            acc = np.zeros((D,), np.float32)
            for kk in range(cfg.top_k):
                e = int(idx[b, s, kk])
                xe = x[b, s]
                g = np.asarray(xe @ p["wg"][e])
                u = np.asarray(xe @ p["wu"][e])
                h = (g / (1 + np.exp(-g))) * u
                acc += float(w[b, s, kk]) * np.asarray(h @ p["wd"][e])
            ref_out[b, s] = acc
    np.testing.assert_allclose(np.asarray(y), ref_out, rtol=2e-3, atol=2e-3)


# ------------------------------------------------- chunked cross-entropy --

def test_chunked_ce_matches_dense_ce():
    B, S, D, V, Vreal = 2, 64, 32, 128, 100
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(ks[0], (B, S, D), jnp.float32)
    w = jax.random.normal(ks[1], (D, V), jnp.float32) * 0.1
    labels = jax.random.randint(ks[2], (B, S), 0, Vreal)
    got = chunked_cross_entropy(ENGINE, h, w, labels, vocab_real=Vreal,
                                chunk=16)
    logits = h @ w
    logits = jnp.where(jnp.arange(V) < Vreal, logits, -1e30)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    want = jnp.mean(lse - gold)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
