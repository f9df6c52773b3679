"""R004 kernel-param legality: every tile plan a compiled network
dispatched is statically legal for its problem.

The static pre-launch check the paper's toolflow lineage runs before
committing a design to hardware: each dispatch record in the engine's
trace-time log carries the RESOLVED tile plan (the backend rule's pick
or engine-pinned bm/bk/bn), and
`backends.validate_tiles` re-derives the kernel legality conditions — MXU
(8, 128) lane alignment, the `_working_set` / `_attention_working_set`
VMEM budget, and tiles no larger than the padded problem extents (a grid
of dead tiles) — from the same formulas the kernels use.  An edited
rule or a hand-pinned engine cannot reach `pallas_call` with an illegal
plan unnoticed.
"""
from repro.analysis import lint
from repro.core import backends

RULE_ID = "R004"
SEVERITY = "error"


@lint.register_rule(RULE_ID, title="kernel-param-legality", severity=SEVERITY)
def check(ctx: lint.LintContext) -> list:
    """Dispatched tile plans satisfy alignment/VMEM/extent legality."""
    findings = []
    seen = set()
    for rec in ctx.op_log:
        tiles = tuple(rec.get("tiles") or ())
        if not tiles or rec.get("shapes") is None:
            continue   # untiled backend (xla/ref) or a legacy record
        key = (rec["op"], rec["shapes"], rec.get("dtype"), tiles)
        if key in seen:
            continue
        seen.add(key)
        problems = backends.validate_tiles(rec["op"], rec["shapes"],
                                           rec.get("dtype") or "float32",
                                           tiles)
        for problem in problems:
            findings.append(lint.Finding(
                rule_id=RULE_ID, severity=SEVERITY,
                op_path=f"{rec.get('backend', '?')}:{rec['op']}"
                        f"{tuple(rec['shapes'])}",
                message=f"tile plan {tiles} is illegal: {problem}"))
    return findings
