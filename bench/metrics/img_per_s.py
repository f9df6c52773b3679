"""img_per_s: images completed in the window over the window's seconds."""
from benchlib import measure


def read(name, ctx):
    return len(measure.latencies(ctx.window)) / ctx.seconds
