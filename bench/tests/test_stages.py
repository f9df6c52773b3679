"""The `stage_ms` reader: the program's step records matched to the
window's untraced working steps."""
import types

import pytest

from benchlib import drive, harness, stages, traffic

STAGES = ("batch", "put", "dispatch", "wait", "fetch", "client")
T = 1000.0                       # s on the host clock
PERIOD = 0.010                   # one bench step every 10 ms
# one program record per step: t0 0.1 ms into the bench step, then the
# five stages 1, 1, 1, 2 and 2.5 ms; the bench step ends 0.4 ms later
OFFSETS = (0.1, 1.1, 2.1, 3.1, 5.1, 7.6)
WANT = {"batch": 1.0, "put": 1.0, "dispatch": 1.0, "wait": 2.0,
        "fetch": 2.5, "client": 2.5}


class Server:
    """Step records as `CNNServingEngine.step_records` gives them."""

    def __init__(self, n, first=0, odd=()):
        import numpy as np
        rows = []
        for k in range(first, n):
            offs = (0.1, 5.1, 5.2, 5.3, 5.4, 5.5) if k in odd else OFFSETS
            rows.append([round((T + k * PERIOD + o * 1e-3) * 1e9)
                         for o in offs])
        self.rec = {"stages": ("cnn.batch", "cnn.put", "cnn.dispatch",
                               "cnn.wait", "cnn.fetch"),
                    "perf_ns": np.array(rows, np.int64).reshape(-1, 6),
                    "overwritten": first}

    def step_records(self):
        return self.rec


class Tracer:
    def __init__(self, spans):
        self._spans = spans

    def spans(self):
        return self._spans


def ctx_for(server, n=10, spans=()):
    steps = [drive.Step(start=T + k * PERIOD, end=T + k * PERIOD + 8e-3,
                        work=1) for k in range(n)]
    window = drive.Window(start=T, end=T + n * PERIOD, records=[],
                          steps=steps, compiles=0, failed=0, unanswered=0)
    return types.SimpleNamespace(system=types.SimpleNamespace(server=server),
                                 window=window, tracer=Tracer(list(spans)))


def read_all(ctx, cell="b1"):
    reader = harness.reader("stage_ms")
    return {s: reader.read(f"stage_ms.{s}.{cell}", ctx) for s in STAGES}


def test_reads_each_steps_own_record_and_sums_to_the_period():
    ctx = ctx_for(Server(10))
    got = read_all(ctx)
    assert got == pytest.approx(WANT, abs=1e-6)
    assert sum(got.values()) == pytest.approx(stages.period_ms(ctx))


def test_skips_the_steps_of_a_traced_slice():
    # steps 4-6 lie in the traced slice and their records read otherwise
    ctx = ctx_for(Server(10, odd=(4, 5, 6)),
                  spans=[(T + 0.040, T + 0.070)])
    got = read_all(ctx)
    assert got == pytest.approx(WANT, abs=1e-6)
    assert stages.period_ms(ctx) == pytest.approx(10.0)
    # without the slice the odd records count
    assert read_all(ctx_for(Server(10, odd=(4, 5, 6))))["batch"] > 2.0


@pytest.mark.parametrize("server", [
    pytest.param(Server(10, first=3), id="ring_overwrote_the_first_steps"),
    pytest.param(Server(0), id="nothing_kept"),
    pytest.param(types.SimpleNamespace(), id="no_step_records"),
])
def test_nothing_where_records_are_missing(server):
    assert read_all(ctx_for(server)) == dict.fromkeys(STAGES)


def test_tiny_cpu_window(monkeypatch):
    """A real window at a tiny size: every stage read, the six means sum
    to the untraced mean step period, and a ring too small for the window
    reads nothing."""
    from conftest import CELLS, TINY
    from repro.serve import frontend
    opts = {k: dict(v) for k, v in TINY[CELLS["darknet19.b8"]].items()}
    opts["workload"] = {"backend": "xla"}
    cell = harness.resolve("darknet19.b8", False, opts)
    system = cell.module.build(cell.config, cell.workload, cell.traffic, 5)
    items = traffic.schedule(cell.traffic, 5)
    system.warmup(items)

    def window(server):
        system.server = server
        counter = drive.CompileCounter()
        try:
            w = drive.run_window(system, cell.traffic, items, 1.0,
                                 compiles=counter)
        finally:
            counter.close()
        return types.SimpleNamespace(system=system, window=w, tracer=None)

    ctx = window(system.server)
    got = read_all(ctx, "b8")
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert sum(got.values()) == pytest.approx(stages.period_ms(ctx), rel=0.03)

    monkeypatch.setattr(frontend, "STEPS_KEPT", 8)
    small = frontend.CNNServingEngine(system.cache)
    assert read_all(window(small), "b8") == dict.fromkeys(STAGES)
