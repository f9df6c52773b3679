"""The client side of a run: send requests as the traffic says, call the
server's `step()`, and stamp what comes back.

Everything is timed by the host clock from outside the program.  A
request's result becomes visible when `step()` returns, so it is stamped
with that instant.  Every call into the program runs under a host span of
the benchmark's own (`bench.submit`, `bench.step`), so a profiler trace
can attribute device idle time to what the host was doing.
"""
from __future__ import annotations

import dataclasses
import time

import jax

from benchlib.traffic import Item

clock = time.perf_counter


@dataclasses.dataclass
class Record:
    """One request's life as the client saw it (host-clock seconds)."""
    item: Item
    req: object
    due: float                      # when the client sent it
    done: float | None = None       # when its result came back


@dataclasses.dataclass
class Step:
    start: float
    end: float
    work: int
    obs: object = None   # what the system observed around this step


@dataclasses.dataclass
class Window:
    start: float
    end: float
    records: list
    steps: list
    compiles: int         # jit traces + backend compiles inside the window
    failed: int           # requests the server refused
    unanswered: int       # requests still without a result after the drain


class CompileCounter:
    """Counts JAX traces and backend compiles (persistent-cache loads
    included) between `start()` and `stop()`."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **_):
        if self._on and event in self.EVENTS:
            self.count += 1

    def start(self):
        self.count, self._on = 0, True

    def stop(self) -> int:
        self._on = False
        return self.count

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._listen)


def run_window(system, spec: dict, items: list, seconds: float, *,
               compiles: CompileCounter, tracer=None,
               drain_s: float = 60.0) -> Window:
    """Drive `system.server` for `seconds` with the traffic `spec` and its
    `items`, and return every request's stamps and every step.  After the
    window closes, the server keeps stepping (for at most `drain_s`, and
    while its steps do work) until every request sent has its result, so
    that each can be checked; those results come back after the window and
    count for no latency.

    `system` supplies `request(item, rid)` (the program's request object),
    `done(req)` and `rejected` (the exception types by which `submit`
    refuses a request); it observes each step through `before_step()` /
    `after_step(obs)`.  `tracer`, when given, is told the time before each
    step and may start or stop a profiler trace there."""
    server = system.server
    want = spec["clients"] if spec["kind"] == "closed" else spec["depth"]
    records, steps, live = [], [], []
    n_sent = failed = 0

    def send(item):
        nonlocal n_sent, failed
        due = clock()
        req = system.request(item, n_sent)
        n_sent += 1
        with jax.profiler.TraceAnnotation("bench.submit"):
            try:
                server.submit(req)
            except system.rejected:
                failed += 1
                return
        rec = Record(item=item, req=req, due=due)
        records.append(rec)
        live.append(rec)

    def step(window_open: bool) -> int:
        obs = system.before_step()
        t0 = clock()
        with jax.profiler.TraceAnnotation("bench.step"):
            work = server.step()
        t1 = clock()
        if window_open:
            steps.append(Step(start=t0, end=t1, work=work,
                              obs=system.after_step(obs)))
        still = []
        for rec in live:
            if system.done(rec.req):
                rec.done = t1
            else:
                still.append(rec)
        live[:] = still
        return work

    compiles.start()
    start = clock()
    end = start + seconds
    while clock() < end:
        while len(live) < want:
            send(items[n_sent % len(items)])
        if tracer is not None:
            tracer.before_step(clock())
        step(True)
    if tracer is not None:
        tracer.before_step(float("inf"))
    n_compiles = compiles.stop()
    while live and clock() < end + drain_s and step(False):
        pass
    return Window(start=start, end=end, records=records, steps=steps,
                  compiles=n_compiles, failed=failed, unanswered=len(live))
