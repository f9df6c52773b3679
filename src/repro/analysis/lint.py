"""Trace-lint: a rule-based static analyzer for compiled networks.

The paper's toolflow lineage (fpgaConvNet's per-layer design-space checks,
CNN2Gate's automated HLS validation) statically validates the mapped design
*before* anything runs on hardware.  This module is that validator for the
jax_pallas reproduction: a rule registry that walks a compiled network's
closed jaxpr (recursing into sub-jaxprs — scan bodies, pjit calls,
interpret-mode pallas_call), its lowered HLO (via `analysis/hlo_cost` /
`analysis/diagnose`), and the engine's trace-time dispatch log, emitting
structured findings ``{rule_id, severity, op_path, message}``.

Shipped rules (see `repro/analysis/rules/` and docs/lint.md):

  R001 no-head-broadcast   no eqn expands a KV-shaped operand to H heads
  R002 registry-dispatch   every dot/conv originates from a registry op
  R003 dtype-hygiene       no fp64 leaks; weak-type + stray-upcast hazards
  R004 kernel-param        pallas tile plans are statically legal
  R005 const-bloat         no large constants baked into the trace

Entry points:

  * `CompiledNetwork.lint()` / `Network.compile(..., lint="warn"|"error")`
  * `run_lint(ctx)` on a hand-built `LintContext` (rule unit tests)
  * CLI: ``python -m repro.analysis.lint --config darknet_ref --backend
    pallas`` over the shipped config zoo (``--json`` for machine output);
    exit status 1 when any error-severity finding survives suppression.

Suppression syntax: ``"R005"`` silences a rule, ``"R002:scan"`` silences
findings whose op_path (or message) contains the substring after the colon.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Any, Callable, Iterator

import jax
from jax.extend import core as jax_core

SEVERITIES = ("error", "warning")

# Default byte threshold above which a baked-in constant is const-bloat.
DEFAULT_CONST_THRESHOLD = 1 << 20


# ------------------------------------------------------- jaxpr traversal ---
# Shared by the rules AND the trace-regression tests (tests/test_attention_op
# used to carry a private copy of these; they now live here so the linter and
# the regression suite can never drift).

def eqn_subjaxprs(eqn) -> Iterator[jax_core.Jaxpr]:
    """Sub-jaxprs referenced by one equation's params (scan/while bodies,
    pjit/custom_vjp calls, interpret-mode pallas_call kernel bodies)."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else [val]
        for sub in vals:
            if isinstance(sub, jax_core.ClosedJaxpr):
                yield sub.jaxpr
            elif isinstance(sub, jax_core.Jaxpr):
                yield sub


def has_subjaxpr(eqn) -> bool:
    """Whether the equation is call-like (aggregates a whole body's
    input->output) rather than a leaf computation."""
    return next(eqn_subjaxprs(eqn), None) is not None


def walk_eqns(jaxpr) -> Iterator[Any]:
    """All equations of a jaxpr, recursing into sub-jaxprs."""
    for eqn, _ in walk_eqns_scoped(jaxpr):
        yield eqn


def walk_eqns_scoped(jaxpr, _scope: str = "") -> Iterator[tuple[Any, str]]:
    """(eqn, scope) pairs, where scope is the '/'-joined name-stack path
    INHERITED through call-like equations: an eqn inside a pjit whose call
    site sits under `jax.named_scope("repro.op.matmul")` reports that scope
    even though its own (independently traced) name stack is empty."""
    for eqn in jaxpr.eqns:
        own = str(eqn.source_info.name_stack)
        scope = f"{_scope}/{own}" if own else _scope
        yield eqn, scope
        for sub in eqn_subjaxprs(eqn):
            yield from walk_eqns_scoped(sub, scope)


def eqn_path(eqn, scope: str = "") -> str:
    """Stable-ish human-readable location for a finding: primitive name
    plus the inherited name-stack scope."""
    name = eqn.primitive.name
    return f"{name}@{scope}" if scope else name


# --------------------------------------------------------------- findings ---

@dataclasses.dataclass(frozen=True)
class Finding:
    """One structured lint finding."""
    rule_id: str
    severity: str      # "error" | "warning"
    op_path: str       # where: eqn path, HLO op name, or dispatch-log key
    message: str

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        return (f"{self.rule_id} [{self.severity}] {self.op_path}: "
                f"{self.message}")


@dataclasses.dataclass(frozen=True)
class Rule:
    rule_id: str
    title: str
    severity: str               # default severity (rules may mix)
    doc: str
    fn: Callable[["LintContext"], list[Finding]]


RULES: dict[str, Rule] = {}


def register_rule(rule_id: str, *, title: str, severity: str, doc: str = ""):
    """Decorator registering a rule function `(LintContext) -> [Finding]`.

    Raises ValueError on a duplicate id or an unknown severity — rule
    typos fail at import, not at lint time.
    """
    if severity not in SEVERITIES:
        raise ValueError(f"unknown severity {severity!r}; "
                         f"choose from {SEVERITIES}")

    def deco(fn):
        if rule_id in RULES:
            raise ValueError(f"rule {rule_id!r} already registered")
        RULES[rule_id] = Rule(rule_id=rule_id, title=title,
                              severity=severity, doc=doc or fn.__doc__ or "",
                              fn=fn)
        return fn
    return deco


def unregister_rule(rule_id: str) -> None:
    """Remove a rule registration (no-op when absent; test scaffolding)."""
    RULES.pop(rule_id, None)


# ---------------------------------------------------------------- context ---

@dataclasses.dataclass(frozen=True)
class LintContext:
    """Everything the rules may inspect for one compiled network.

    Any field may be empty/None — each rule checks only what it needs, so a
    hand-built context with just a jaxpr unit-tests the jaxpr rules.
    """
    label: str = ""
    backend: str = ""
    jaxpr: Any = None                    # jax_core.ClosedJaxpr | None
    hlo_text: str | None = None          # compiled (optimized) HLO text
    op_log: tuple = ()                   # engine dispatch records (dicts)
    head_hints: tuple = ()               # ((H, KV, head_dim), ...) for R001
    const_threshold: int = DEFAULT_CONST_THRESHOLD

    def attention_heads(self) -> tuple:
        """(H, KV, head_dim) triples: the explicit hints plus every
        attention dispatch recorded in the op log."""
        hints = set(tuple(h) for h in self.head_hints)
        for rec in self.op_log:
            if rec.get("op") != "attention" or not rec.get("shapes"):
                continue
            q_shape, k_shape = rec["shapes"]
            hints.add((q_shape[2], k_shape[2], q_shape[3]))
        return tuple(sorted(hints))


# ----------------------------------------------------------------- report ---

class LintError(Exception):
    """Raised by `Network.compile(..., lint="error")` on error findings."""

    def __init__(self, report: "LintReport"):
        self.report = report
        super().__init__(report.format())


@dataclasses.dataclass
class LintReport:
    label: str
    backend: str
    findings: list[Finding]
    suppressed: list[Finding]
    hlo_totals: dict | None = None   # flops/bytes/collectives (diagnose)

    @property
    def errors(self) -> list[Finding]:
        return [f for f in self.findings if f.severity == "error"]

    @property
    def warnings(self) -> list[Finding]:
        return [f for f in self.findings if f.severity == "warning"]

    @property
    def ok(self) -> bool:
        """True when no error-severity finding survived suppression."""
        return not self.errors

    def to_dict(self) -> dict:
        return {
            "label": self.label,
            "backend": self.backend,
            "summary": {"errors": len(self.errors),
                        "warnings": len(self.warnings),
                        "suppressed": len(self.suppressed)},
            "findings": [f.to_dict() for f in self.findings],
            "suppressed": [f.to_dict() for f in self.suppressed],
            "hlo_totals": self.hlo_totals,
        }

    def to_json(self, indent: int | None = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    def format(self) -> str:
        head = (f"lint[{self.label or '?'} @ {self.backend or '?'}]: "
                f"{len(self.errors)} error(s), "
                f"{len(self.warnings)} warning(s)"
                + (f", {len(self.suppressed)} suppressed"
                   if self.suppressed else ""))
        lines = [head] + [f"  {f}" for f in self.findings]
        return "\n".join(lines)


# ------------------------------------------------------------ suppression ---

def _parse_suppression(token: str) -> tuple[str, str | None]:
    """'R005' -> (R005, None); 'R002:scan' -> (R002, 'scan')."""
    rule_id, _, pattern = token.partition(":")
    rule_id = rule_id.strip()
    if not rule_id:
        raise ValueError(f"empty rule id in suppression {token!r}")
    return rule_id, (pattern or None)


def _is_suppressed(f: Finding, parsed: list[tuple[str, str | None]]) -> bool:
    for rule_id, pattern in parsed:
        if f.rule_id != rule_id:
            continue
        if pattern is None or pattern in f.op_path or pattern in f.message:
            return True
    return False


# ----------------------------------------------------------------- runner ---

def run_lint(ctx: LintContext, *, suppress=(), rules=None) -> LintReport:
    """Run the registered rules over one context.

    Args:
      ctx: the `LintContext` under test.
      suppress: iterable of suppression tokens (see module docstring).
      rules: optional iterable of rule ids to restrict the run to.

    Returns a `LintReport` (errors first, then warnings, by rule id).
    Raises ValueError on a malformed suppression token or an unknown rule
    id in `rules`.
    """
    from repro.analysis import rules as _rules_pkg  # noqa: F401  (registers)
    parsed = [_parse_suppression(t) for t in suppress]
    if rules is not None:
        unknown = set(rules) - set(RULES)
        if unknown:
            raise ValueError(f"unknown rule ids {sorted(unknown)}; "
                             f"registered: {sorted(RULES)}")
    findings: list[Finding] = []
    suppressed: list[Finding] = []
    for rule_id in sorted(RULES):
        if rules is not None and rule_id not in rules:
            continue
        for f in RULES[rule_id].fn(ctx):
            (suppressed if _is_suppressed(f, parsed) else findings).append(f)
    findings.sort(key=lambda f: (SEVERITIES.index(f.severity), f.rule_id))
    hlo_totals = None
    if ctx.hlo_text:
        # The HLO walk doubles as the diagnose smoke path: every lint run
        # exercises analysis/diagnose.attribute on real compiled HLO
        # (including entry computations without op_name metadata).
        from repro.analysis import diagnose
        hlo_totals = diagnose.attribute(ctx.hlo_text, top=5)["totals"]
    return LintReport(label=ctx.label, backend=ctx.backend,
                      findings=findings, suppressed=suppressed,
                      hlo_totals=hlo_totals)


# ---------------------------------------------------------------- drivers ---

def lint_traced(fn, *args, backend: str, label: str = "", head_hints=(),
                suppress=(), const_threshold: int | None = None,
                compile_hlo: bool = True) -> LintReport:
    """Trace `fn(*args)` once (AOT), then lint jaxpr + HLO + dispatch log.

    args may be arrays or ShapeDtypeStructs.  `compile_hlo=False` skips the
    XLA compile and the HLO-side checks (jaxpr rules only — faster)."""
    from repro.core import backends
    mark = backends.dispatch_log_size()
    traced = jax.jit(fn).trace(*args)
    op_log = tuple(backends.dispatch_log()[mark:])
    hlo_text = traced.lower().compile().as_text() if compile_hlo else None
    ctx = LintContext(
        label=label, backend=backend, jaxpr=traced.jaxpr, hlo_text=hlo_text,
        op_log=op_log, head_hints=tuple(head_hints),
        const_threshold=(DEFAULT_CONST_THRESHOLD if const_threshold is None
                         else const_threshold))
    return run_lint(ctx, suppress=suppress)


def lint_compiled_network(cn, *, suppress=(),
                          const_threshold: int | None = None) -> LintReport:
    """Lint a `CompiledNetwork` from its captured compile artifacts (the
    closed jaxpr, the compiled executable's HLO, the dispatch log) — no
    retrace happens."""
    ctx = LintContext(
        label=f"CompiledNetwork(batch={cn.batch_size})",
        backend=cn.net.engine.backend,
        jaxpr=cn.closed_jaxpr,
        hlo_text=cn.hlo_text(),
        op_log=tuple(cn.op_log),
        const_threshold=(DEFAULT_CONST_THRESHOLD if const_threshold is None
                         else const_threshold))
    return run_lint(ctx, suppress=suppress)


# ----------------------------------------------------------- config zoo ---

_CNN_CONFIGS = ("darknet_ref", "darknet19", "segnet_small")


def _cnn_cfg_text(name: str) -> str:
    from repro.configs import darknet_ref as dk
    return {"darknet_ref": dk.DARKNET_SMALL_CFG,
            "darknet19": dk.DARKNET19_CFG,
            "segnet_small": dk.SEGNET_SMALL_CFG}[name]


def _resolve_lm_arch(name: str) -> str:
    """Accept both module-style ('qwen2_0p5b') and arch-id ('qwen2-0.5b')
    spellings.  Raises ValueError with the full zoo when unknown."""
    from repro.configs import base
    if name in base._MODULES:
        return name
    by_module = {mod: arch for arch, mod in base._MODULES.items()}
    if name in by_module:
        return by_module[name]
    raise ValueError(
        f"unknown config {name!r}; CNN configs: {list(_CNN_CONFIGS)}, "
        f"LM configs: {sorted(base._MODULES)} "
        f"(module names {sorted(by_module)} also accepted)")


def lint_config(name: str, *, backend: str = "xla", batch: int = 2,
                seq: int = 16, suppress=(),
                const_threshold: int | None = None) -> LintReport:
    """Compile one shipped config on `backend` and lint it.

    CNN configs (darknet_ref/darknet19/segnet_small) go through
    `Network.compile`; LM configs compile the reduced architecture's
    prefill step (forward step for encoder-only archs) at (batch, seq).

    Returns the `LintReport`.  Raises ValueError for an unknown config or
    backend.
    """
    from repro.core import make_engine
    if name in _CNN_CONFIGS:
        from repro.core.darknet.network import Network
        net = Network(_cnn_cfg_text(name), engine=make_engine(backend))
        params = net.init(jax.random.PRNGKey(0))
        cn = net.compile(params, batch_size=batch)
        report = lint_compiled_network(cn, suppress=suppress,
                                       const_threshold=const_threshold)
        report.label = name
        return report

    from repro.configs import base
    from repro.models import transformer as tfm
    from repro.serve import serve_step
    arch_id = _resolve_lm_arch(name)
    cfg = base.reduced(base.get_arch(arch_id))
    eng = make_engine(backend)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    shape = base.ShapeConfig("lint", seq, batch, "prefill")
    specs = base.input_specs(cfg, shape)
    if cfg.causal:
        step = serve_step.make_prefill_step(eng, cfg)
    else:
        step = serve_step.make_forward_step(eng, cfg)
    return lint_traced(
        step, params, specs, backend=backend, label=name,
        head_hints=((cfg.n_heads, cfg.n_kv_heads, cfg.head_dim),),
        suppress=suppress, const_threshold=const_threshold)


# -------------------------------------------------------------------- CLI ---

def _format_rules() -> str:
    from repro.analysis import rules as _rules_pkg  # noqa: F401
    lines = ["registered rules:"]
    for rule_id in sorted(RULES):
        r = RULES[rule_id]
        lines.append(f"  {r.rule_id} [{r.severity:7s}] {r.title}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="Trace-lint a shipped config's compiled network "
                    "(docs/lint.md).")
    ap.add_argument("--config", help="config name: darknet_ref | darknet19 "
                    "| segnet_small | an LM arch (qwen2_0p5b / qwen2-0.5b)")
    ap.add_argument("--backend", default="xla",
                    help="registry backend to compile on (default: xla)")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=16,
                    help="sequence length for LM configs")
    ap.add_argument("--suppress", action="append", default=[],
                    metavar="RULE[:SUBSTR]",
                    help="suppress a rule (repeatable), e.g. R005 or "
                    "R002:scan")
    ap.add_argument("--const-threshold", type=int,
                    default=DEFAULT_CONST_THRESHOLD,
                    help="R005 byte threshold for baked-in constants")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    args = ap.parse_args(argv)

    if args.list_rules:
        print(_format_rules())
        return 0
    if not args.config:
        ap.error("--config is required (or --list-rules)")

    report = lint_config(args.config, backend=args.backend,
                         batch=args.batch, seq=args.seq,
                         suppress=args.suppress,
                         const_threshold=args.const_threshold)
    print(report.to_json() if args.json else report.format())
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
