"""Samples of a window, taken by the host clock from outside the program.

A request counts for a latency if its result came back inside the window.
Percentiles are nearest-rank over every sample, with the count behind each
printed by `describe`.
"""
from __future__ import annotations

import math


def nearest_rank(values: list, p: float) -> float | None:
    if not values:
        return None
    ordered = sorted(values)
    return ordered[max(0, math.ceil(p / 100.0 * len(ordered)) - 1)]


def latencies(w) -> list:
    """Sent to result, for requests completed in the window."""
    return [r.done - r.due for r in w.records
            if r.done is not None and r.done <= w.end]


def working_steps(w) -> list:
    return [s for s in w.steps if s.work and s.end <= w.end]


def device_slice_steps(ctx) -> list:
    """The steps that ran wholly inside the traced run's device slice."""
    t0, t1, _ = ctx.tracer.slices["device"]
    return [s for s in ctx.window.steps if s.start >= t0 and s.end <= t1]


def untraced_steps(w, tracer) -> list:
    """Working steps that overlap no traced slice (all of them untraced)."""
    spans = tracer.spans() if tracer is not None else []
    return [s for s in working_steps(w)
            if not any(s.end > a and s.start < b for a, b in spans)]


def idle_check(w, reduction, tracer) -> str:
    """The device slice's idle share beside the one its device time per
    step gives at the step rate of the untraced part of the window: they
    agree where tracing leaves the host path's speed alone."""
    t0, t1, _ = tracer.slices["device"]
    n = len([s for s in w.steps if s.work and s.start >= t0 and s.end <= t1])
    traced = 100.0 * (1.0 - reduction.busy_s / reduction.window_s)
    untraced = untraced_steps(w, tracer)
    wall = (w.end - w.start) - sum(max(0.0, min(b, w.end) - max(a, w.start))
                                   for a, b in tracer.spans())
    if not n or not untraced or wall <= 0:
        return f"idle share: traced {traced!r} %, no untraced estimate"
    est = 100.0 * (1.0 - reduction.busy_s / n * len(untraced) / wall)
    return (f"idle share: traced {traced!r} % over {n} steps; from the "
            f"untraced step rate {est!r} % over {len(untraced)} steps")


def describe(w) -> list[str]:
    """A line on the counts behind every percentile."""
    return [f"window: {len(w.records)} requests sent, "
            f"{len(latencies(w))} completed in it, {w.unanswered} without "
            f"a result after the drain, {len(w.steps)} steps "
            f"({len(working_steps(w))} with work), {w.failed} refused"]
