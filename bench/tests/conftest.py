"""The benchmark's own tests, on the CPU at tiny sizes:
`python -m pytest bench/tests`."""
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

# A tiny Darknet (same layer kinds as Darknet-19) and traffic settings that
# a CPU run in interpret mode can hold.
TINY = {
    "darknet19": dict(config={
        "height": 32, "width": 32, "layers": [
            {"type": "convolutional", "batch_normalize": 1, "filters": 16,
             "size": 3, "stride": 1, "pad": 1, "activation": "leaky"},
            {"type": "maxpool", "size": 2, "stride": 2},
            {"type": "convolutional", "batch_normalize": 1, "filters": 32,
             "size": 3, "stride": 1, "pad": 1, "activation": "leaky"},
            {"type": "convolutional", "batch_normalize": 1, "filters": 16,
             "size": 1, "stride": 1, "pad": 1, "activation": "leaky"},
            {"type": "convolutional", "filters": 10, "size": 1, "stride": 1,
             "pad": 1, "activation": "linear"},
            {"type": "avgpool"}, {"type": "softmax"}]},
        traffic={"pool": 8}),
}

CELLS = {"darknet19.b1": "darknet19", "darknet19.b8": "darknet19"}


@pytest.fixture
def tiny_run():
    """Run a cell at its tiny size on the CPU, skipping the look for a
    chip; returns the result object."""
    from benchlib import harness

    def run(cell, seed=2**31 + 7, seconds=2.0, overrides=None, **kw):
        opts = {k: dict(v) for k, v in TINY[CELLS[cell]].items()}
        for key, extra in (overrides or {}).items():
            opts[key] = dict(opts.get(key, {}), **extra)
        return harness.run_cell(cell, seed, seconds, False, require_chip=False,
                                overrides=opts, log=lambda m: None, **kw)
    return run
