"""R002 registry-dispatch: every dot_general / conv_general_dilated in a
compiled network originates from a registry op.

The paper's single-engine claim is only checkable if every dense
contraction actually routes through `ComputeEngine` — model code calling
`jnp.einsum` / `x @ w` directly bypasses the backend registry, the
precision policy, and the tile-plan cache, silently forking the compute
path per call site.  The engine wraps each registry dispatch in
``jax.named_scope(backends.op_scope(op))`` ("repro.op.<op>"), which lands
on the traced equations' name stacks (and is INHERITED through call-like
equations by `lint.walk_eqns_scoped` — an inner pjit's body eqns carry the
scope of their call site).  Any contraction eqn without that marker was
emitted outside the engine.
"""
from repro.analysis import lint
from repro.core import backends

RULE_ID = "R002"
SEVERITY = "error"

_CONTRACTIONS = ("dot_general", "conv_general_dilated")


@lint.register_rule(RULE_ID, title="registry-dispatch", severity=SEVERITY)
def check(ctx: lint.LintContext) -> list:
    """Every dot/conv eqn carries the engine's repro.op.* dispatch scope."""
    if ctx.jaxpr is None:
        return []
    findings = []
    for eqn, scope in lint.walk_eqns_scoped(ctx.jaxpr.jaxpr):
        if eqn.primitive.name not in _CONTRACTIONS:
            continue
        if backends.OP_SCOPE_PREFIX in scope:
            continue
        outs = [tuple(v.aval.shape) for v in eqn.outvars]
        findings.append(lint.Finding(
            rule_id=RULE_ID, severity=SEVERITY,
            op_path=lint.eqn_path(eqn, scope),
            message=(f"{eqn.primitive.name} -> {outs} was emitted outside "
                     f"a registry op (no '{backends.OP_SCOPE_PREFIX}*' "
                     f"dispatch scope on its name stack) — route dense "
                     f"math through ComputeEngine")))
    return findings
