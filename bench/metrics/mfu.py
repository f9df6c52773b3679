"""mfu.<cells>: the whole step's share of the chip's peak.  Useful model
FLOPs of the work the window's steps did (the configuration's FLOPs per
item times the items), over the summed host duration of the
`step()` calls that did work outside the traced slices, over the bf16 peak
of bench/peaks.json.  The suffix names the end-to-end metric the value
moves."""
from benchlib import measure


def read(name, ctx):
    steps = measure.untraced_steps(ctx.window, ctx.tracer)
    busy = sum(s.end - s.start for s in steps)
    if not steps or busy <= 0:
        return None
    flops = ctx.system.flops_per_item() * sum(s.work for s in steps)
    return 100.0 * flops / busy / ctx.peaks["flops_per_s"]
