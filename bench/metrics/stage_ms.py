"""stage_ms.<stage>.<cells>: the mean time of one stage of the program's
serving step, in ms, over the window's untraced working steps, from the
host-clock stamps the program takes inside `step()` (`benchlib/stages.py`).
The stages are the program's (`batch`, `put`, `dispatch`, `wait`, `fetch`)
and `client`, the benchmark's own time between two program steps.  The
suffix names the cell whose end-to-end metric the value moves.  Nothing
where the program keeps no step records, or where its ring dropped a
record of the window."""
from benchlib import stages


def read(name, ctx):
    means = stages.stage_ms(ctx)
    return None if means is None else means.get(name.split(".")[1])
