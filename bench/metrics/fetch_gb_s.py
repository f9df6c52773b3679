"""fetch_gb_s.<cells>: how fast the answers cross to the host, in GB/s:
the output bytes per image, from the program's `outputs` counter
(`CompileCache.stats()["outputs"]["bytes_per_item"]`, fixed at compile),
times the images of the window's untraced working steps, over the time
those steps spent in the program's `cnn.fetch` stage (`benchlib/stages.py`:
the one device-to-host copy of every output and the requests' completion).
Nothing where the program keeps no such counter or no step records."""
from benchlib import measure, stages


def read(name, ctx):
    outputs = ctx.system.server.cache.stats().get("outputs")
    means = stages.stage_ms(ctx)
    if not outputs or not means or not means.get("fetch"):
        return None
    steps = measure.untraced_steps(ctx.window, ctx.tracer)
    seconds = means["fetch"] * 1e-3 * len(steps)
    return outputs["bytes_per_item"] * sum(s.work for s in steps) / seconds / 1e9
