"""Trace-lint suite: every rule fires on its intentionally-bad fixture
graph (and ONLY its rule), shipped configs lint clean, and the end-to-end
surfaces work — `CompiledNetwork.lint()`, `Network.compile(lint=...)`,
suppressions, the JSON CLI, and the dispatch-log capture feeding R004.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import lint
from repro.configs.base import get_arch, reduced
from repro.configs.darknet_ref import DARKNET_SMALL_CFG
from repro.core import backends, make_engine
from repro.core.darknet.network import Network
from repro.models import transformer as tfm
from repro.serve.serve_step import make_prefill_step

B, S, H, KV, HD = 2, 16, 4, 2, 32


def _only_rule(report, rule_id):
    assert report.findings, f"{rule_id} did not fire"
    assert {f.rule_id for f in report.findings} == {rule_id}


# ------------------------------------------------------- bad fixtures ---

def test_r001_fires_on_explicit_repeat():
    """The retired formulation — jnp.repeat(k, G, axis=2) — trips R001."""
    traced = jax.jit(lambda k: jnp.repeat(k, H // KV, axis=2)).trace(
        jnp.zeros((B, S, KV, HD)))
    ctx = lint.LintContext(jaxpr=traced.jaxpr, head_hints=((H, KV, HD),))
    report = lint.run_lint(ctx)
    _only_rule(report, "R001")
    assert "KV->H" in report.findings[0].message


def test_r001_silent_without_grouping():
    """MHA geometry (G == 1) has nothing to expand; no head hints means
    no geometry to check."""
    traced = jax.jit(lambda k: jnp.repeat(k, 2, axis=2)).trace(
        jnp.zeros((B, S, KV, HD)))
    mha = lint.LintContext(jaxpr=traced.jaxpr, head_hints=((H, H, HD),))
    assert not lint.run_lint(mha, rules=("R001",)).findings
    no_hints = lint.LintContext(jaxpr=traced.jaxpr)
    assert not lint.run_lint(no_hints, rules=("R001",)).findings


def test_r002_fires_on_raw_einsum():
    """A contraction emitted outside the engine (raw jnp.einsum) trips
    R002; the same math through `ComputeEngine.matmul` is clean."""
    x, w = jnp.zeros((4, 8)), jnp.zeros((8, 16))
    bad = jax.jit(lambda x, w: jnp.einsum("bk,kn->bn", x, w)).trace(x, w)
    report = lint.run_lint(lint.LintContext(jaxpr=bad.jaxpr))
    _only_rule(report, "R002")
    assert "dot_general" in report.findings[0].message

    eng = make_engine("xla")
    good = jax.jit(lambda x, w: eng.matmul(x, w)).trace(x, w)
    assert not lint.run_lint(lint.LintContext(jaxpr=good.jaxpr),
                             rules=("R002",)).findings


def test_r002_scope_inherited_through_kernel_call():
    """The pallas kernel's dot_generals live inside nested pjit /
    pallas_call bodies whose own name stacks are empty — the dispatch
    scope must be inherited from the call site for R002 to stay clean."""
    eng = make_engine("pallas")
    traced = jax.jit(lambda x, w: eng.matmul(x, w)).trace(
        jnp.zeros((16, 256)), jnp.zeros((256, 128)))
    assert not lint.run_lint(lint.LintContext(jaxpr=traced.jaxpr),
                             rules=("R002",)).findings


def test_r003_fires_on_fp64_leak():
    with jax.enable_x64(True):
        traced = jax.jit(lambda x: x * jnp.float64(2.0)).trace(
            jnp.zeros((4,), jnp.float64))
    report = lint.run_lint(lint.LintContext(jaxpr=traced.jaxpr))
    _only_rule(report, "R003")
    assert all(f.severity == "error" for f in report.findings)
    assert "float64" in report.findings[0].message


def test_r003_weak_typed_entry_warns():
    traced = jax.jit(lambda x, s: x * s).trace(jnp.zeros((4,)), 2.0)
    report = lint.run_lint(lint.LintContext(jaxpr=traced.jaxpr),
                           rules=("R003",))
    assert [f.severity for f in report.findings] == ["warning"]
    assert "weakly-typed" in report.findings[0].message


def test_r003_upcast_outside_dispatch_warns():
    traced = jax.jit(lambda x: x.astype(jnp.float32) + 1.0).trace(
        jnp.zeros((4,), jnp.bfloat16))
    report = lint.run_lint(lint.LintContext(jaxpr=traced.jaxpr),
                           rules=("R003",))
    assert any("upcast" in f.message and f.severity == "warning"
               for f in report.findings)


def test_r004_fires_on_misaligned_plan():
    """A corrupt tile plan (as a persisted table would replay it) trips
    every violated legality condition."""
    ctx = lint.LintContext(op_log=(
        {"backend": "pallas", "op": "matmul", "shapes": (64, 256, 128),
         "dtype": "float32", "tiles": (12, 100, 130)},))
    report = lint.run_lint(ctx)
    _only_rule(report, "R004")
    msgs = " ".join(f.message for f in report.findings)
    assert "bm=12" in msgs and "bk=100" in msgs and "bn=130" in msgs


@pytest.mark.parametrize("tiles, bad", [
    ((196, 27, 1000), None),        # every block the full extent: legal
    ((56, 27, 1000), None),         # bm aligned, bk/bn full: legal
    ((196, 20, 1000), "bk=20"),     # unaligned and not the full K
    ((100, 27, 1000), "bm=100"),    # unaligned and not the full M
    ((196, 27, 500), "bn=500"),     # unaligned and not the full N
])
def test_r004_full_extent_blocks_legal_unaligned_partial_blocks_not(tiles,
                                                                   bad):
    """A block is legal when aligned or equal to its whole extent (what
    Mosaic takes); an unaligned block short of the extent is an R004
    finding."""
    ctx = lint.LintContext(op_log=(
        {"backend": "pallas", "op": "matmul", "shapes": (196, 27, 1000),
         "dtype": "float32", "tiles": tiles},))
    report = lint.run_lint(ctx)
    if bad is None:
        assert report.findings == [], report.format()
    else:
        _only_rule(report, "R004")
        assert bad in report.findings[0].message


@pytest.mark.parametrize("batch", [1, 8])
def test_darknet19_pallas_dispatch_plans_lint_clean(batch):
    """Darknet-19 @224's exact plans (full-extent blocks included) pass
    R004 at both served buckets."""
    from repro.configs.darknet_ref import DARKNET19_CFG
    net = Network(DARKNET19_CFG, engine=make_engine("pallas"))
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    mark = backends.dispatch_log_size()
    jax.jit(net.apply).trace(
        params, jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.float32))
    log = tuple(backends.dispatch_log()[mark:])
    assert sum("gemm" in r for r in log) == 19
    report = lint.run_lint(lint.LintContext(op_log=log), rules=("R004",))
    assert report.findings == [], report.format()


def test_r004_catches_pinned_engine_tiles_via_dispatch_log():
    """End to end: an engine with hand-pinned misaligned tiles leaves its
    plan in the dispatch log at trace time, where R004 finds it."""
    eng = make_engine("pallas", bm=12, bk=128, bn=128)
    mark = backends.dispatch_log_size()
    traced = jax.jit(lambda x, w: eng.matmul(x, w)).trace(
        jnp.zeros((16, 256)), jnp.zeros((256, 128)))
    log = tuple(backends.dispatch_log()[mark:])
    assert log and log[0]["tiles"] == (12, 128, 128)
    report = lint.run_lint(lint.LintContext(jaxpr=traced.jaxpr,
                                            op_log=log))
    _only_rule(report, "R004")
    assert "bm=12" in report.findings[0].message


def test_r004_attention_and_malformed_plans():
    probs = backends.validate_tiles(
        "attention", ((B, S, H, HD), (B, S, KV, HD)), "float32", (12, 100))
    assert any("bq=12" in p for p in probs)
    assert any("bk=100" in p for p in probs)
    # oversized tiles = dead grid steps
    probs = backends.validate_tiles(
        "attention", ((B, S, H, HD), (B, S, KV, HD)), "float32", (256, 512))
    assert any("padded query extent" in p for p in probs)
    # malformed plans/shapes come back as problems, never exceptions
    assert backends.validate_tiles("matmul", (64, 256, 128), "float32",
                                   (8, 128))
    assert backends.validate_tiles("matmul", ("garbage",), "float32",
                                   (8, 128, 128))
    # the legal heuristic pick is legal
    from repro.kernels import ops as kernel_ops
    pick = kernel_ops.default_blocks("matmul", 64, 256, 128, "float32")
    assert not backends.validate_tiles("matmul", (64, 256, 128), "float32",
                                       pick)


def test_r005_fires_on_baked_constant():
    big = jnp.ones((1024, 1024), jnp.float32)         # 4 MiB closure const
    traced = jax.jit(lambda x: x + big).trace(jnp.zeros((1024, 1024)))
    report = lint.run_lint(lint.LintContext(jaxpr=traced.jaxpr))
    _only_rule(report, "R005")
    assert "4194304 bytes" in report.findings[0].message
    # threshold is honored
    loose = lint.LintContext(jaxpr=traced.jaxpr, const_threshold=1 << 23)
    assert not lint.run_lint(loose, rules=("R005",)).findings


# ---------------------------------------------------- clean shipped nets ---

def test_darknet_compiled_network_lints_clean():
    net = Network(DARKNET_SMALL_CFG, engine=make_engine("xla"))
    params = net.init(jax.random.PRNGKey(0))
    cn = net.compile(params, batch_size=2)
    report = cn.lint()
    assert report.findings == [], report.format()
    assert report.ok
    assert report.hlo_totals and report.hlo_totals["flops"] > 0
    # the capture that feeds the linter kept the single-trace invariant
    assert cn.trace_count == 1
    assert cn.closed_jaxpr is not None
    assert len(cn.op_log) == sum(cn.op_counts.values())
    assert "ENTRY" in cn.hlo_text()


def test_qwen2_prefill_lints_clean_on_pallas():
    """The LM gate config on the kernel-backed path: jaxpr rules plus the
    R004 check over the REAL resolved attention/GEMM tiles (compile_hlo
    off keeps this a trace, not an XLA compile)."""
    cfg = reduced(get_arch("qwen2-0.5b"))             # H=4, KV=2 GQA
    eng = make_engine("pallas")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    step = make_prefill_step(eng, cfg)
    report = lint.lint_traced(
        step, params, {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)},
        backend="pallas", label="qwen2-prefill",
        head_hints=((cfg.n_heads, cfg.n_kv_heads, cfg.head_dim),),
        compile_hlo=False)
    assert report.findings == [], report.format()
    assert report.hlo_totals is None


# -------------------------------------------------- integration surfaces ---

def test_compile_lint_gate_warn_and_error():
    net = Network(DARKNET_SMALL_CFG, engine=make_engine("xla"))
    params = net.init(jax.random.PRNGKey(0))
    # clean network: no warning, artifact returned
    cn = net.compile(params, batch_size=1, lint="error")
    assert cn.trace_count == 1
    with pytest.raises(ValueError, match="lint mode"):
        net.compile(params, batch_size=1, lint="bogus")

    @lint.register_rule("T900", title="always-fires", severity="error")
    def _always(ctx):
        return [lint.Finding(rule_id="T900", severity="error",
                             op_path="test", message="planted finding")]

    try:
        with pytest.raises(lint.LintError, match="T900"):
            net.compile(params, batch_size=1, lint="error")
        with pytest.warns(UserWarning, match="T900"):
            cn = net.compile(params, batch_size=1, lint="warn")
        assert cn.trace_count == 1                   # warn still compiles
    finally:
        lint.unregister_rule("T900")


def test_suppressions():
    ctx = lint.LintContext(op_log=(
        {"backend": "pallas", "op": "matmul", "shapes": (64, 256, 128),
         "dtype": "float32", "tiles": (12, 128, 128)},))
    full = lint.run_lint(ctx)
    assert full.findings and not full.ok
    by_rule = lint.run_lint(ctx, suppress=("R004",))
    assert by_rule.ok and not by_rule.findings and by_rule.suppressed
    by_path = lint.run_lint(ctx, suppress=("R004:matmul",))
    assert by_path.ok and by_path.suppressed
    miss = lint.run_lint(ctx, suppress=("R004:attention",))
    assert not miss.ok                      # substring doesn't match
    with pytest.raises(ValueError, match="empty rule id"):
        lint.run_lint(ctx, suppress=(":matmul",))
    with pytest.raises(ValueError, match="unknown rule ids"):
        lint.run_lint(ctx, rules=("R999",))


def test_report_shapes_and_registry():
    f = lint.Finding(rule_id="R001", severity="error", op_path="p",
                     message="m")
    assert f.to_dict() == {"rule_id": "R001", "severity": "error",
                           "op_path": "p", "message": "m"}
    with pytest.raises(ValueError, match="severity"):
        lint.register_rule("T901", title="t", severity="fatal")
    with pytest.raises(ValueError, match="already registered"):
        lint.register_rule("R001", title="dup", severity="error")(
            lambda ctx: [])


def test_cli_list_rules_and_json(capsys):
    assert lint.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("R001", "R002", "R003", "R004", "R005"):
        assert rid in out
    assert lint.main(["--config", "darknet_ref", "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["label"] == "darknet_ref"
    assert report["summary"]["errors"] == 0
    assert report["hlo_totals"]["flops"] > 0
    with pytest.raises(ValueError, match="unknown config"):
        lint.lint_config("no-such-net")
