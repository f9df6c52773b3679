"""A kernel's share of its roofline in the traced slice.

The least time of one call is the larger of its FLOPs over the peak FLOP/s
and its bytes over the peak bandwidth (bench/peaks.json), with FLOPs and
bytes computed from the operation's shapes by the configuration module
(`op_calls`).  The share is the summed least time over the device time the
trace shows for the Pallas kernel that runs the operation, found by the
kernel's name (the `name` of its `pallas_call`).  Nothing to read (no
call, or no device time) gives None, never 0.
"""
from __future__ import annotations

from benchlib import measure


def least_time(calls, peaks) -> float:
    return sum(n * max(f / peaks["flops_per_s"], b / peaks["hbm_bytes_per_s"])
               for f, b, n in calls)


def share(ctx, category: str, kernel: str) -> float | None:
    if ctx.trace is None:
        return None
    calls = ctx.system.op_calls(measure.device_slice_steps(ctx)).get(category)
    seconds = ctx.trace.kernel_time(kernel)
    if not calls or seconds <= 0:
        return None
    return 100.0 * least_time(calls, ctx.peaks) / seconds
