"""img_p95_ms: 95th percentile of image latency, from the time a request
was due (closed loop: sent) to its result back from `step()`, over every
image completed in the window."""
from benchlib import measure


def read(name, ctx):
    p = measure.nearest_rank(measure.latencies(ctx.window), 95)
    return None if p is None else p * 1e3
