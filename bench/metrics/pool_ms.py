"""pool_ms.<cells>: the max-pools' device time per image, in ms: the
device time of the traced slice's operations that XLA names
`reduce_window*` (the [maxpool] layers; in YOLOv4 the SPP block's three),
over the images of the steps that ran wholly inside the slice.  Nothing
where the slice holds no such operation or no whole step."""
from benchlib import measure


def read(name, ctx):
    if ctx.trace is None:
        return None
    seconds = sum(t for op, t in ctx.trace.op_s.items()
                  if op.startswith("reduce_window"))
    images = sum(s.work for s in measure.device_slice_steps(ctx))
    if seconds <= 0 or not images:
        return None
    return 1e3 * seconds / images
