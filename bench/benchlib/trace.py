"""From profiler traces (`.xplane.pb`) to device busy and idle time, device
time by kernel and operation, and idle gaps by host span.

A traced run records two slices of its window (`harness.Tracer`).  The
first runs with the host tracer off, so the host path keeps its own speed:
its busy time is the union of the intervals in which an operation ran on a
device (the "XLA Ops" line of each `/device:TPU:<n>` plane), averaged over
the devices, and its length is taken by the host clock.  The second
records host spans, which slow the host path several times over; it only
files idle gaps under host spans.

On the TPU the trace's operations carry no `repro.op.*` scope: their names
are the optimized HLO instructions, without metadata.  So each operation
is filed under its instruction's name stem: for a Pallas kernel (a
`tpu_custom_call`) the kernel's name (`matmul`, `attention`,
`attention_decode`), for the rest the fusion or operation kind (`copy`,
`pad`, `dynamic_update_slice`, ...).  A loop or call whose body runs as
operations of its own (`while`, `conditional`, `call`) counts toward busy
time but not as an operation of its own, so that no time is counted twice.

An idle gap is an interval of the second slice in which no operation ran.  It is
filed under the innermost host span open at its midpoint on the threads
that run the benchmark's spans: a span of the benchmark (`bench.step`,
`bench.submit`) or one that JAX's runtime opened inside it.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"/device:(TPU|GPU):\d+")
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")
BENCH_SPANS = ("bench.step", "bench.submit")
_SUFFIX = re.compile(r"(\.(\d+|remat\d*|clone|sunk))+$")


def stem(event_name: str) -> str:
    """`%attention_decode.8 = (...) custom-call(...)` -> `attention_decode`."""
    return _SUFFIX.sub("", event_name.split(" = ", 1)[0].lstrip("%"))


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float            # averaged over devices
    devices: int
    op_s: dict               # stem -> device seconds, summed over devices
    kernels: tuple           # stems that are Pallas kernels
    idle_by_span: dict       # host span -> idle seconds

    def kernel_time(self, name: str) -> float:
        return self.op_s.get(name, 0.0) if name in self.kernels else 0.0

    def breakdown(self) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in idle]}


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _innermost(spans, points):
    """For each of the ascending `points`, the name of the innermost of the
    (nested, start-sorted) `spans` open at it."""
    names, stack, i = [], [], 0
    for t in points:
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        names.append(stack[-1][2] if stack else "(no host span)")
    return names


def device_time(data, t0=None, t1=None):
    """(busy seconds averaged over the devices, device count, {stem:
    seconds summed over the devices}, Pallas kernel stems, the first
    device's busy intervals) of the operations in `data` that fall in
    [t0, t1] (ns; all of them where not given)."""
    op_s = collections.Counter()
    kernels = set()
    intervals = []
    for plane in data.planes:
        if not DEVICE_PLANE.fullmatch(plane.name):
            continue
        ivs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = int(ev.start_ns), int(ev.end_ns)
                if t0 is not None:
                    s, e = max(s, t0), min(e, t1)
                if e <= s:
                    continue
                ivs.append((s, e))
                name = stem(ev.name)
                if name in CONTAINERS:
                    continue
                op_s[name] += (e - s) * 1e-9
                if "tpu_custom_call" in ev.name:
                    kernels.add(name)
        intervals.append(_union(ivs))
    if not intervals:
        raise ValueError("the trace holds no device plane")
    busy = sum(e - s for ivs in intervals for s, e in ivs) / len(intervals)
    return (busy * 1e-9, len(intervals), dict(op_s),
            tuple(sorted(kernels)), intervals[0])


def idle_gaps(data) -> dict:
    """{host span: idle seconds} of a slice traced with host spans: the
    idle gaps of the first device from the start of the first `bench.step`
    to the end of the last, each under the span open at its midpoint."""
    host = collections.defaultdict(list)     # thread -> [(start, end, name)]
    bench_threads = set()
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            key = (plane.name, line.name)
            for ev in line.events:
                host[key].append((int(ev.start_ns), int(ev.end_ns), ev.name))
                if ev.name in BENCH_SPANS:
                    bench_threads.add(key)
    steps = [(s, e) for key in bench_threads for s, e, n in host[key]
             if n == "bench.step"]
    if not steps:
        raise ValueError("the trace holds no bench.step span")
    t0 = min(s for s, _ in steps)
    t1 = max(e for _, e in steps)
    first = device_time(data, t0, t1)[4]
    edges = [t0] + [x for iv in first for x in iv] + [t1]
    idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    spans = sorted(sp for key in bench_threads for sp in host[key])
    idle_by_span = collections.Counter()
    names = _innermost(spans, [(a + b) // 2 for a, b in idle])
    for (a, b), name in zip(idle, names):
        idle_by_span[name] += (b - a) * 1e-9
    return dict(idle_by_span)


def reduce(data, window_s: float, host=None) -> Reduction:
    """Reduce the `jax.profiler.ProfileData` of a slice traced with the host
    tracer off, `window_s` long by the host clock, and, where given, that
    of a slice traced with host spans (`idle_gaps`)."""
    busy, devices, op_s, kernels, _ = device_time(data)
    return Reduction(window_s=window_s, busy_s=busy, devices=devices,
                     op_s=op_s, kernels=kernels,
                     idle_by_span=idle_gaps(host) if host is not None else {})


def xplane_file(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def load_dir(log_dir: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(xplane_file(log_dir))
