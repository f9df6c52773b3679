"""Measured autotuner: policy knobs, timing records, per-device persistence.

Covers the ISSUE 3 acceptance criteria:
  * measured picks are recorded ({pick, candidates_timed, est_ms, source})
    and persisted to a per-device JSON table, written atomically;
  * a fresh process (simulated: cleared in-memory caches) serves the
    persisted pick with ZERO re-timing — counter-asserted and enforced by
    poisoning the timer;
  * corrupted / stale / wrong-device table files fall back to measurement
    without crashing, then get overwritten with a valid table;
  * `Network.compile(autotune="measure")` runs the measured warmup pass and
    surfaces the records through `profile()` / `CompileCache.stats()`;
  * attention (bq, bk) sequence tiles ride the same machinery (ISSUE 4):
    MXU-aligned VMEM-filtered candidates, measured + persisted + served
    with zero re-timing, keys visible in `autotune_report()`.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune, backends, make_engine
from repro.core.darknet.network import Network
from repro.kernels import ops as kernel_ops

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


@pytest.fixture(autouse=True)
def isolated_autotune(tmp_path, monkeypatch):
    """Point persistence at a scratch dir and reset all in-process state,
    restoring the policy afterwards so other test modules are unaffected."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path))
    backends.clear_tile_cache()
    autotune.reset()
    # Tile resolution happens at trace time; a jit-cache hit from an
    # earlier test would skip it entirely (the backward keys resolve
    # inside the custom-VJP backward trace), so start each test cold.
    jax.clear_caches()
    prev = backends.get_autotune_policy()
    yield tmp_path
    backends.set_autotune_policy(prev)
    backends.clear_tile_cache()
    autotune.reset()


def _matmul(m=48, k=40, n=24):
    eng = make_engine("pallas")
    x = jnp.asarray(np.random.default_rng(0).standard_normal((m, k)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).standard_normal((k, n)),
                    jnp.float32)
    return eng.matmul(x, w)


def _fresh_process():
    """Simulate a new process on the same device: in-memory caches gone,
    the persisted table still on disk."""
    backends.clear_tile_cache()
    autotune.reset()


# ------------------------------------------------------------ measuring ---

def test_measured_pick_recorded_and_persisted(tmp_path):
    backends.set_autotune_policy("measure")
    _matmul()
    st = backends.cache_stats()
    assert st["measured"] == 1 and st["persisted"] == 0

    (key, rec), = backends.autotune_report().items()
    assert rec["source"] == "measured"
    assert tuple(rec["pick"]) in {tuple(c) for c, _ in
                                  rec["candidates_timed"]}
    assert rec["est_ms"] == min(ms for _, ms in rec["candidates_timed"])
    assert len(rec["candidates_timed"]) >= 2

    path = autotune.table_path()
    assert os.path.dirname(path) == str(tmp_path)
    with open(path) as f:
        table = json.load(f)
    assert table["version"] == autotune.TABLE_VERSION
    assert table["fingerprint"] == autotune.device_fingerprint()
    assert table["entries"][key]["pick"] == rec["pick"]


def test_roundtrip_uses_persisted_pick_with_zero_retiming(monkeypatch):
    backends.set_autotune_policy("measure")
    _matmul()
    (key, rec), = backends.autotune_report().items()

    _fresh_process()

    def _no_timing(*a, **kw):  # persisted path must never re-time
        raise AssertionError("re-timed a persisted pick")
    monkeypatch.setattr(autotune, "time_thunk", _no_timing)

    _matmul()
    st = backends.cache_stats()
    assert st["measured"] == 0
    assert st["persisted"] == 1
    got = backends.autotune_report()[key]
    assert got["pick"] == rec["pick"]
    assert got["source"] == "persisted"


def test_measured_pick_is_used_on_cache_hits():
    backends.set_autotune_policy("measure")
    _matmul()
    (_, rec), = backends.autotune_report().items()
    before = backends.cache_stats()
    _matmul()  # identical shapes: in-process cache hit, no new timing
    st = backends.cache_stats()
    assert st["hits"] == before["hits"] + 1
    assert st["measured"] == before["measured"]
    assert tuple(rec["pick"]) == backends._TILE_CACHE[
        ("matmul", (48, 40, 24), "float32", "pallas")]


# ---------------------------------------------- corruption / staleness ---

@pytest.mark.parametrize("content", [
    "{ not json",                                            # corrupted
    json.dumps({"version": 999, "fingerprint": "x",
                "entries": {}}),                             # stale schema
    json.dumps({"version": autotune.TABLE_VERSION,
                "fingerprint": "some-other-device__v1",
                "entries": {"k": {"pick": [1, 1, 1]}}}),     # wrong device
    json.dumps({"version": autotune.TABLE_VERSION}),         # no entries
    json.dumps([1, 2, 3]),                                   # wrong type
])
def test_bad_table_file_falls_back_to_measurement(content):
    path = autotune.table_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(content)

    backends.set_autotune_policy("measure")
    _matmul()                                # must not crash
    st = backends.cache_stats()
    assert st["measured"] == 1 and st["persisted"] == 0

    # the bad file was overwritten with a valid table
    with open(path) as f:
        table = json.load(f)
    assert table["version"] == autotune.TABLE_VERSION
    assert len(table["entries"]) == 1

    _fresh_process()
    _matmul()
    assert backends.cache_stats()["persisted"] == 1


def test_unwritable_cache_dir_is_not_fatal(tmp_path, monkeypatch):
    """Persistence failures never abort dispatch: with the cache dir
    unwritable (here: occupied by a regular file, as with a read-only
    shipped-table deployment), measurement still serves the pick."""
    blocked = tmp_path / "not-a-dir"
    blocked.write_text("in the way")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(blocked))
    backends.set_autotune_policy("measure")
    y = _matmul()                            # measures, fails to persist
    assert y.shape == (48, 24)
    st = backends.cache_stats()
    assert st["measured"] == 1
    (_, rec), = backends.autotune_report().items()
    assert rec["source"] == "measured"
    assert autotune.store("k", {"pick": [8, 128, 128]}) is False


def test_store_merges_concurrent_writers():
    """A table written by another process between our load and our store
    is merged, not clobbered."""
    backends.set_autotune_policy("measure")
    _matmul()
    path = autotune.table_path()
    with open(path) as f:
        table = json.load(f)
    other_key = autotune.key_str("matmul", (7, 7, 7), "float32", "pallas")
    table["entries"][other_key] = {"pick": [8, 128, 128], "est_ms": 1.0,
                                   "candidates_timed": [],
                                   "source": "measured"}
    with open(path, "w") as f:
        json.dump(table, f)

    _matmul(m=96)                            # new key -> measure + store
    with open(path) as f:
        merged = json.load(f)
    assert other_key in merged["entries"]
    assert len(merged["entries"]) == 3


# ---------------------------------------------------------- policy knobs ---

def test_policy_off_bypasses_cache():
    backends.set_autotune_policy("off")
    _matmul()
    _matmul()
    assert backends.cache_stats() == {"hits": 0, "misses": 0, "measured": 0,
                                      "persisted": 0, "entries": 0}


def test_heuristic_policy_never_touches_disk(tmp_path):
    backends.set_autotune_policy("heuristic")
    _matmul()
    assert backends.cache_stats()["measured"] == 0
    assert not os.path.exists(autotune.table_path())
    (_, rec), = backends.autotune_report().items()
    assert rec["source"] == "heuristic"
    assert rec["est_ms"] is None


def test_unknown_policy_rejected():
    with pytest.raises(ValueError, match="unknown autotune policy"):
        backends.set_autotune_policy("fastest")
    with pytest.raises(ValueError, match="unknown autotune policy"):
        with backends.autotune_policy("bogus"):
            pass


def test_env_policy_default_validates_loudly():
    """A typo'd REPRO_AUTOTUNE must warn, not silently run heuristic."""
    assert backends._policy_from_env(None) == "heuristic"
    for p in backends.AUTOTUNE_POLICIES:
        assert backends._policy_from_env(p) == p
    with pytest.warns(UserWarning, match="REPRO_AUTOTUNE='measured'"):
        assert backends._policy_from_env("measured") == "heuristic"


def test_policy_context_manager_restores_on_error():
    prev = backends.get_autotune_policy()
    with pytest.raises(RuntimeError):
        with backends.autotune_policy("measure"):
            assert backends.get_autotune_policy() == "measure"
            raise RuntimeError("boom")
    assert backends.get_autotune_policy() == prev


# -------------------------------------------------- candidate enumeration ---

def test_candidates_include_heuristic_and_respect_budget():
    """Every candidate is a legal plan: each block aligned (bm to 8, bk/bn
    to 128) or the full extent, and under the VMEM budget."""
    for op, m, k, n in [("matmul", 512, 288, 128), ("bmm", 128, 128, 128),
                        ("matmul", 64, 2048, 64)]:
        base = kernel_ops.default_blocks(op, m, k, n, "float32")
        cands = kernel_ops.candidate_blocks(op, m, k, n, "float32")
        assert cands[0] == base
        assert len(cands) == len(set(cands)) >= 2
        for bm, bk, bn in cands:
            assert bm % 8 == 0 or bm == m
            assert bk % 128 == 0 or bk == k
            assert bn % 128 == 0 or bn == n
            assert kernel_ops._working_set(
                bm, bk, bn, 4) <= kernel_ops._GEMM_VMEM_BUDGET
            assert not kernel_ops.validate_gemm_tiles(m, k, n, "float32",
                                                      (bm, bk, bn))


def test_measured_pick_matches_heuristic_numerics():
    """Whatever block shape measurement picks, the result is bitwise the
    problem's answer — blocks only change the schedule."""
    eng = make_engine("pallas")
    x = jnp.asarray(np.random.default_rng(0).standard_normal((100, 70)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).standard_normal((70, 50)),
                    jnp.float32)
    backends.set_autotune_policy("heuristic")
    want = eng.matmul(x, w, act="leaky")
    backends.clear_tile_cache()
    backends.set_autotune_policy("measure")
    got = eng.matmul(x, w, act="leaky")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ------------------------------------------------- attention (bq, bk) ---

def _attention(b=1, sq=64, skv=64, h=4, kv=2, d=16):
    eng = make_engine("pallas")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, skv, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, skv, kv, d), jnp.float32)
    return eng.attention(q, k, v, causal=True)


def test_attention_candidates_mxu_aligned_and_vmem_filtered():
    """bq/bk sequence-tile candidates: heuristic pick first, MXU-aligned
    (bq mult of 8 sublanes, bk mult of 128 lanes), capped at the padded
    sequence extents, filtered to the grouped-KV VMEM working set."""
    for dims in [(1, 256, 256, 8, 2, 64),     # even, GQA
                 (2, 33, 33, 14, 2, 64),      # odd S (padded path)
                 (1, 1, 128, 8, 1, 64),       # decode shape, MQA
                 (1, 4096, 4096, 16, 16, 128)]:  # budget-limited MHA
        base = kernel_ops.default_attention_blocks(*dims, "float32")
        cands = kernel_ops.candidate_attention_blocks(*dims, "float32")
        assert cands[0] == base
        assert len(cands) == len(set(cands)) >= 1
        _, sq, skv, _, _, d = dims
        for bq, bk in cands:
            assert bq % 8 == 0 and bk % 128 == 0
            assert bq <= max(512, kernel_ops._round_up(sq, 8))
            assert kernel_ops._attention_working_set(
                bq, bk, d, 4) <= kernel_ops._VMEM_BUDGET


def test_attention_key_measured_recorded_and_in_report():
    backends.set_autotune_policy("measure")
    _attention()
    st = backends.cache_stats()
    assert st["measured"] == 1
    att = {k: r for k, r in backends.autotune_report().items()
           if k.startswith('["attention"')}
    assert len(att) == 1
    (key, rec), = att.items()
    assert rec["source"] == "measured"
    assert len(tuple(rec["pick"])) == 2        # (bq, bk), not (bm, bk, bn)
    assert tuple(rec["pick"]) in {tuple(c) for c, _ in
                                  rec["candidates_timed"]}
    # persisted alongside the GEMM keys in the same per-device table
    with open(autotune.table_path()) as f:
        table = json.load(f)
    assert key in table["entries"]


def test_attention_persisted_roundtrip_zero_retiming(monkeypatch):
    backends.set_autotune_policy("measure")
    _attention()
    (key, rec), = backends.autotune_report().items()

    _fresh_process()

    def _no_timing(*a, **kw):
        raise AssertionError("re-timed a persisted attention pick")
    monkeypatch.setattr(autotune, "time_thunk", _no_timing)

    _attention()
    st = backends.cache_stats()
    assert st["measured"] == 0 and st["persisted"] == 1
    got = backends.autotune_report()[key]
    assert got["pick"] == rec["pick"] and got["source"] == "persisted"


def _attention_grad(b=1, sq=64, skv=64, h=4, kv=2, d=16):
    eng = make_engine("pallas")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, skv, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, skv, kv, d), jnp.float32)
    return jax.grad(lambda q: eng.attention(q, k, v, causal=True).sum())(q)


def test_attention_bwd_candidates_mxu_aligned_and_vmem_filtered():
    """Backward (bq, bk) candidates: same alignment/caps as the forward
    set, filtered against the LARGER backward working set (q/dO + k/v/dK/dV
    tiles + three fp32 score tiles live per step)."""
    for dims in [(1, 256, 256, 8, 2, 64),
                 (2, 33, 33, 14, 2, 64),
                 (1, 4096, 4096, 16, 16, 128)]:
        base = kernel_ops.default_attention_bwd_blocks(*dims, "float32")
        cands = kernel_ops.candidate_attention_bwd_blocks(*dims, "float32")
        assert cands[0] == base
        assert len(cands) == len(set(cands)) >= 1
        _, sq, skv, _, _, d = dims
        for bq, bk in cands:
            assert bq % 8 == 0 and bk % 128 == 0
            assert bq <= max(512, kernel_ops._round_up(sq, 8))
            assert kernel_ops._attention_bwd_working_set(
                bq, bk, d, 4) <= kernel_ops._VMEM_BUDGET
        # the backward working set really is bigger than the forward's
        assert kernel_ops._attention_bwd_working_set(*base, d, 4) > \
            kernel_ops._attention_working_set(*base, d, 4)


def test_attention_bwd_key_measured_only_under_grad():
    """Inference never touches the backward key space: a forward-only
    dispatch resolves just the "attention" key; differentiating the same
    problem adds (and measures) the "attention_bwd" key."""
    backends.set_autotune_policy("measure")
    _attention()
    assert not [k for k in backends.autotune_report()
                if k.startswith('["attention_bwd"')]
    _attention_grad()
    bwd = {k: r for k, r in backends.autotune_report().items()
           if k.startswith('["attention_bwd"')}
    assert len(bwd) == 1
    (key, rec), = bwd.items()
    assert rec["source"] == "measured"
    assert len(tuple(rec["pick"])) == 2
    assert tuple(rec["pick"]) in {tuple(c) for c, _ in
                                  rec["candidates_timed"]}
    with open(autotune.table_path()) as f:
        assert key in json.load(f)["entries"]


def test_attention_bwd_persisted_roundtrip_zero_retiming(monkeypatch):
    """A fresh process serves the backward pick from the per-device table
    with zero measurements — the --check-persisted property, for the
    backward key space."""
    backends.set_autotune_policy("measure")
    _attention_grad()
    rep = {k: r for k, r in backends.autotune_report().items()
           if k.startswith('["attention_bwd"')}
    (key, rec), = rep.items()

    _fresh_process()
    jax.clear_caches()           # a fresh process also has no jit cache

    def _no_timing(*a, **kw):
        raise AssertionError("re-timed a persisted attention_bwd pick")
    monkeypatch.setattr(autotune, "time_thunk", _no_timing)

    _attention_grad()
    st = backends.cache_stats()
    assert st["measured"] == 0 and st["persisted"] == 2  # fwd + bwd keys
    got = backends.autotune_report()[key]
    assert got["pick"] == rec["pick"] and got["source"] == "persisted"


def test_attention_bwd_measured_pick_matches_heuristic_numerics():
    """Backward tiling only changes the schedule: gradients under the
    measured pick equal gradients under the heuristic pick."""
    backends.set_autotune_policy("heuristic")
    want = _attention_grad(sq=33, skv=33)
    backends.clear_tile_cache()
    jax.clear_caches()
    backends.set_autotune_policy("measure")
    got = _attention_grad(sq=33, skv=33)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_attention_measured_pick_matches_heuristic_numerics():
    """Sequence tiling only changes the schedule, never the math — the
    measured pick agrees with the heuristic pick's output."""
    backends.set_autotune_policy("heuristic")
    want = _attention(sq=33, skv=33)
    backends.clear_tile_cache()
    backends.set_autotune_policy("measure")
    got = _attention(sq=33, skv=33)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ------------------------------------------------ GEMM backward tiles ---

def _matmul_grad(m=48, k=40, n=24):
    eng = make_engine("pallas")
    x = jnp.asarray(np.random.default_rng(0).standard_normal((m, k)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).standard_normal((k, n)),
                    jnp.float32)
    return jax.grad(
        lambda x, w: (eng.matmul(x, w, act="leaky")
                      .astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1))(x, w)


def test_gemm_bwd_candidates_mxu_aligned_and_vmem_filtered():
    """gemm_bwd candidates ride the forward GEMM sweep on the backward
    problem's own dims: heuristic pick first, MXU-aligned, working-set
    filtered, with the bmm clamp on the batched variants."""
    for variant, rows, kdim, cols in [("dx", 512, 128, 288),
                                      ("dw", 288, 512, 128),
                                      ("bdx", 128, 128, 128),
                                      ("bdw", 333, 177, 99)]:
        base = kernel_ops.default_gemm_bwd_blocks(variant, rows, kdim,
                                                  cols, "float32")
        cands = kernel_ops.candidate_gemm_bwd_blocks(variant, rows, kdim,
                                                     cols, "float32")
        assert cands[0] == base
        assert len(cands) == len(set(cands)) >= 2
        for bm, bk, bn in cands:
            assert bm % 8 == 0 and bk % 128 == 0 and bn % 128 == 0
            assert kernel_ops._working_set(
                bm, bk, bn, 4) <= kernel_ops._GEMM_VMEM_BUDGET
        if variant.startswith("b"):       # the bmm clamp applies
            assert base == kernel_ops.padded_blocks(
                rows, kdim, cols, "float32", kernel_ops._CAPS["bmm"])


def test_gemm_bwd_variant_rejected():
    with pytest.raises(ValueError, match="variant"):
        kernel_ops.default_gemm_bwd_blocks("nope", 8, 128, 128, "float32")


def test_gemm_bwd_keys_measured_only_under_grad():
    """Inference resolves just the forward "matmul" key; differentiating
    the same problem lazily adds (and measures) one "gemm_bwd" key per
    backward GEMM — the dX and dW problems, keyed on their OWN dims."""
    backends.set_autotune_policy("measure")
    _matmul()
    assert not [k for k in backends.autotune_report()
                if k.startswith('["gemm_bwd"')]
    _matmul_grad()
    bwd = {k: r for k, r in backends.autotune_report().items()
           if k.startswith('["gemm_bwd"')}
    assert len(bwd) == 2
    variants = {json.loads(k)[1][0] for k in bwd}
    assert variants == {"dx", "dw"}
    with open(autotune.table_path()) as f:
        table = json.load(f)
    for key, rec in bwd.items():
        assert rec["source"] == "measured"
        assert len(tuple(rec["pick"])) == 3
        assert tuple(rec["pick"]) in {tuple(c) for c, _ in
                                      rec["candidates_timed"]}
        assert key in table["entries"]


def test_gemm_bwd_persisted_roundtrip_zero_retiming(monkeypatch):
    """A fresh process serves every gemm_bwd pick from the per-device
    table with zero measurements — the --check-persisted property for the
    GEMM backward key space."""
    backends.set_autotune_policy("measure")
    _matmul_grad()
    rep = {k: r for k, r in backends.autotune_report().items()
           if k.startswith('["gemm_bwd"')}
    assert len(rep) == 2

    _fresh_process()
    jax.clear_caches()           # a fresh process also has no jit cache

    def _no_timing(*a, **kw):
        raise AssertionError("re-timed a persisted gemm_bwd pick")
    monkeypatch.setattr(autotune, "time_thunk", _no_timing)

    _matmul_grad()
    st = backends.cache_stats()
    assert st["measured"] == 0 and st["persisted"] == 3  # fwd + dx + dw
    for key, rec in rep.items():
        got = backends.autotune_report()[key]
        assert got["pick"] == rec["pick"] and got["source"] == "persisted"


def test_gemm_bwd_measured_pick_matches_heuristic_numerics():
    """Backward tiling only changes the schedule: gradients under the
    measured picks equal gradients under the heuristic picks (odd dims
    force the padded backward path too).  Max-relative tolerance, not
    elementwise: which candidate wins the timing varies with machine
    load, and a different tile shape can shift fp32 reduction order by
    one ulp at the gradient's magnitude."""
    backends.set_autotune_policy("heuristic")
    want = _matmul_grad(m=33, k=41, n=17)
    backends.clear_tile_cache()
    jax.clear_caches()
    backends.set_autotune_policy("measure")
    got = _matmul_grad(m=33, k=41, n=17)
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        rel = np.abs(a - b).max() / (np.abs(b).max() + 1e-12)
        assert rel <= 1e-5, rel


# ------------------------------------------------------- network wiring ---

def test_compile_measured_warmup_pass_and_report():
    net = Network(TWO_CONV_CFG, make_engine("pallas"))
    params = net.init(jax.random.PRNGKey(0))
    assert backends.get_autotune_policy() == "heuristic"
    cn = net.compile(params, batch_size=2, autotune="measure")
    assert backends.get_autotune_policy() == "heuristic"  # scoped

    rep = cn.autotune_report()
    assert len(rep) == 2                     # one conv2d key per layer
    assert all(r["source"] == "measured" for r in rep.values())
    prof = cn.profile(reps=1)
    assert prof["autotune"] == rep

    # fresh process: the same compile serves both picks from disk
    _fresh_process()
    cn2 = net.compile(params, batch_size=2, autotune="measure")
    st = backends.cache_stats()
    assert st["measured"] == 0 and st["persisted"] == 2
    assert {k: r["pick"] for k, r in cn2.autotune_report().items()} \
        == {k: r["pick"] for k, r in rep.items()}


def test_compile_cache_forwards_autotune_and_reports():
    net = Network(TWO_CONV_CFG, make_engine("pallas"))
    params = net.init(jax.random.PRNGKey(0))
    cache = net.compile_cache(params, buckets=(1, 2), autotune="measure")
    x = jnp.zeros((2, 16, 16, 3), jnp.float32)
    cache.run(x)
    st = cache.stats()
    assert st["autotune"]["keys"] == 2
    assert st["autotune"]["sources"] == {"measured": 2}
    # second bucket reuses in-process picks where shapes collide; the
    # report unions bucket records without re-measuring persisted keys
    cache.run(x[:1])
    assert cache.stats()["autotune"]["keys"] >= 2


def test_compile_rejects_unknown_autotune_policy():
    net = Network(TWO_CONV_CFG, make_engine("pallas"))
    params = net.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="unknown autotune policy"):
        net.compile(params, batch_size=1, autotune="bogus")
