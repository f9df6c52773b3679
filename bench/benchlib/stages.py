"""The program's own step spans, matched to the window's steps.

`CNNServingEngine` records every `step()` as one span of contiguous stages,
stamped with `time.perf_counter_ns()` inside the program
(`step_records()`).  The client stamps its steps with `time.perf_counter()`,
the same clock, so each working step of the window holds exactly one
program record.  Only untraced working steps are read
(`measure.untraced_steps`), so the stages run at the host path's own
speed; `client` is the gap from one program step's end to the next one's
start, the benchmark's own driving between steps.
"""
from __future__ import annotations

import numpy as np

from benchlib import measure


def stage_ms(ctx) -> dict | None:
    """{stage: mean ms} over the window's untraced working steps, the
    program's stages by their short names (`cnn.wait` -> `wait`) and
    `client`; None where the program keeps no step records, or where a
    step's record is missing (the ring overwrote it)."""
    read = getattr(ctx.system.server, "step_records", None)
    steps = measure.untraced_steps(ctx.window, ctx.tracer)
    if read is None or not steps:
        return None
    rec = read()
    t = rec["perf_ns"]
    if not len(t):
        return None
    start = np.array([s.start for s in steps]) * 1e9
    end = np.array([s.end for s in steps]) * 1e9
    j = np.minimum(np.searchsorted(t[:, 0], start), len(t) - 1)
    own = t[j]
    if not ((own[:, 0] >= start) & (own[:, -1] <= end)).all():
        return None
    out = dict(zip((s.split(".", 1)[1] for s in rec["stages"]),
                   np.diff(own, axis=1).mean(axis=0) / 1e6))
    nxt = np.flatnonzero(np.diff(j) == 1)     # the next record is read too
    if len(nxt):
        out["client"] = (t[j[nxt] + 1, 0] - own[nxt, -1]).mean() / 1e6
    return {k: float(v) for k, v in out.items()}


def period_ms(ctx) -> float | None:
    """The untraced wall time of the window over its untraced working
    steps: what the six stage means sum to."""
    w, tracer = ctx.window, ctx.tracer
    steps = measure.untraced_steps(w, tracer)
    spans = tracer.spans() if tracer is not None else []
    wall = (w.end - w.start) - sum(max(0.0, min(b, w.end) - max(a, w.start))
                                   for a, b in spans)
    return 1e3 * wall / len(steps) if steps and wall > 0 else None
