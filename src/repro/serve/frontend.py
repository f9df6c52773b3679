"""Shared serving frontend: one submit/step/run/stats surface for all
workloads.

The paper's framework is an inference accelerator: compile the network once,
then feed it a stream of requests.  This module is the traffic side of that
deployment shape — a `ServingFrontend` protocol every serving engine
implements (the slot-based LM `ServingEngine` in serve/engine.py and the
micro-batching `CNNServingEngine` here), a shared `Request` base carrying
identity + lifecycle + latency timestamps, and one stats schema
(`STATS_KEYS`) so dashboards and benchmarks read CNN and LM engines
identically.

`CNNServingEngine` is the CNN twin of the LM slot model: instead of slots
decoding in lockstep, it drains its request queue into padded-bucket
dispatches through a `CompileCache` — each step stacks up to top-bucket
images, pads to the smallest compiled bucket that fits, runs ONE compiled
call, and completes every request in the batch.  Per-request latency and
aggregate images/sec come out of `stats()`.  Every step is also recorded as
one span cut into contiguous stages (`CNN_STAGES`), kept in a bounded ring
with an anchor that places them on a profiler trace's clock.
"""
from __future__ import annotations

import abc
import dataclasses
import math
import random
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.darknet.network import CompileCache

# Every ServingFrontend.stats() dict carries at least these keys; "requests"
# is itself a dict with the REQUEST_KEYS counters and "latency_s" a dict
# with the LATENCY_KEYS aggregates.  Engine-specific extras ride alongside.
STATS_KEYS = ("engine", "requests", "steps", "wall_s", "latency_s",
              "throughput")
REQUEST_KEYS = ("submitted", "completed", "rejected", "truncated")
LATENCY_KEYS = ("avg", "max", "p50", "p95", "p99")

# The stages of one `CNNServingEngine.step`, in order; they tile the step
# span.  A kept record is the six boundary stamps (`time.perf_counter_ns`)
# around the five stages.
CNN_STAGES = ("cnn.batch", "cnn.put", "cnn.dispatch", "cnn.wait",
              "cnn.fetch")
# Records kept whole, in a preallocated ring of 3 MB: at a few ms per
# step, the last few minutes of serving.
STEPS_KEPT = 1 << 16


class RejectedRequest(ValueError):
    """An ADMISSION failure: the request itself is inadmissible (bad image
    shape, prompt overflowing the KV cache) and was counted as rejected.

    Engines raise this — and only this — from `submit`'s admission checks,
    so `run` can skip a rejected request and keep serving the batch while
    any other ValueError (a genuine programming error: mis-shaped engine
    state, a corrupt cache) propagates instead of being silently
    swallowed as a "rejection"."""


@dataclasses.dataclass
class Request:
    """Base serving request: identity, lifecycle, latency timestamps.

    Engines set `t_submit` at admission to the frontend and `t_done` at
    completion; `latency_s` is the queueing + execution time in between —
    NaN until the request completes (rejected and in-flight requests keep
    NaN timestamps, which is why `LatencyAgg` refuses them).  Lifecycle
    fields are keyword-only so subclass payload fields (prompt, image,
    ...) keep their positional slots right after `rid`.
    """
    rid: int
    done: bool = dataclasses.field(default=False, kw_only=True)
    truncated: bool = dataclasses.field(default=False, kw_only=True)
    t_submit: float = dataclasses.field(default=float("nan"), kw_only=True)
    t_done: float = dataclasses.field(default=float("nan"), kw_only=True)

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit


@dataclasses.dataclass
class ImageRequest(Request):
    """One image through a compiled CNN; `result` holds the image's row of
    the network output (a tuple of its rows of each output for a
    multi-output network, such as a detector's heads)."""
    image: np.ndarray | None = None
    result: np.ndarray | tuple | None = None


class ServingFrontend(abc.ABC):
    """The serving protocol: `submit(req)`, `step() -> work`, `run(reqs)`,
    `stats() -> dict` (STATS_KEYS schema).

    `step()` returns the number of requests it advanced (0 = fully idle),
    so `run` is engine-agnostic: submit everything, step until idle.

    `submit` raises `RejectedRequest` on an inadmissible request (bad
    image shape, prompt overflowing the KV cache); `run` catches exactly
    that per request — rejections are counted in `stats()` and the request
    stays `done=False` — so one bad request cannot strand the rest of a
    batch, while any OTHER exception (a genuine programming error)
    propagates.
    """

    @abc.abstractmethod
    def submit(self, req: Request) -> None:
        ...

    @abc.abstractmethod
    def step(self) -> int:
        ...

    @abc.abstractmethod
    def stats(self) -> dict:
        ...

    def run(self, requests: list, max_steps: int = 10_000) -> list:
        for r in requests:
            try:
                self.submit(r)
            except RejectedRequest:
                pass  # rejected: counted in stats, left not-done
        for _ in range(max_steps):
            if self.step() == 0:
                break
        return requests


class LatencyAgg:
    """Running per-request latency aggregate — O(1) sum/max/count plus a
    bounded reservoir for tail percentiles, so long-running servers never
    keep per-request history.

    Percentiles (p50/p95/p99, nearest-rank) come from reservoir sampling
    (Algorithm R) with a deterministic seeded RNG: up to `reservoir`
    samples are exact, beyond that each sample survives with probability
    k/n — an unbiased estimate whose memory never grows, and bit-stable
    across runs for a fixed sample stream.

    Aggregates COMPLETED requests only: a rejected or in-flight request
    has `t_done = NaN`, so its `latency_s` is NaN and one such sample
    would poison `avg`/`max` for the server's whole lifetime (`max(x,
    nan)` and the running sum never recover).  `add` therefore rejects
    non-finite samples loudly instead of absorbing them."""

    def __init__(self, reservoir: int = 4096):
        if reservoir < 1:
            raise ValueError(f"need reservoir >= 1, got {reservoir}")
        self.sum = 0.0
        self.max = 0.0
        self.count = 0
        self._capacity = reservoir
        self._samples: list[float] = []
        self._rng = random.Random(0)

    def add(self, latency_s: float) -> None:
        if not math.isfinite(latency_s):
            raise ValueError(
                f"non-finite latency sample {latency_s!r}: only COMPLETED "
                "requests (t_submit and t_done set) may be aggregated — "
                "rejected or in-flight requests have NaN timestamps")
        self.sum += latency_s
        self.max = max(self.max, latency_s)
        self.count += 1
        if len(self._samples) < self._capacity:
            self._samples.append(latency_s)
        else:  # Algorithm R: keep with probability capacity/count
            j = self._rng.randrange(self.count)
            if j < self._capacity:
                self._samples[j] = latency_s

    def percentile(self, p: float) -> float:
        """Nearest-rank percentile over the reservoir; 0.0 when empty."""
        if not self._samples:
            return 0.0
        ordered = sorted(self._samples)
        rank = math.ceil(p / 100.0 * len(ordered))
        return ordered[max(0, rank - 1)]

    def summary(self) -> dict:
        return {"avg": (self.sum / self.count) if self.count else 0.0,
                "max": self.max,
                "p50": self.percentile(50),
                "p95": self.percentile(95),
                "p99": self.percentile(99)}


def build_stats(*, engine: str, submitted: int, completed: int,
                rejected: int, truncated: int, steps: int, wall_s: float,
                latency: LatencyAgg, items: int,
                extra: dict | None = None) -> dict:
    """Assemble the shared stats dict; `items` is the engine's throughput
    unit (images for CNN, generated tokens for LM)."""
    stats = {
        "engine": engine,
        "requests": {"submitted": submitted, "completed": completed,
                     "rejected": rejected, "truncated": truncated},
        "steps": steps,
        "wall_s": wall_s,
        "latency_s": latency.summary(),
        "throughput": (items / wall_s) if wall_s > 0 else 0.0,
    }
    if extra:
        stats.update(extra)
    return stats


class CNNServingEngine(ServingFrontend):
    """Micro-batching CNN server over a bucketed `CompileCache`.

    submit() queues `ImageRequest`s (shape-checked against the network's
    input plan); each step() drains up to top-bucket requests, stacks them
    into one ragged batch, and dispatches through `CompileCache.run` — the
    pad/slice and the one-trace-per-bucket guarantee live there.

    Each working step is one span of contiguous stages (`CNN_STAGES`),
    stamped with `time.perf_counter_ns()` where the work happens:
    `cnn.batch` pops the requests and stacks their images, `cnn.put` copies
    the batch to the device (enqueue), `cnn.dispatch` is `CompileCache.run`
    (bucket pick, pad, the executable call, the row slice; returns once
    enqueued), `cnn.wait` blocks until the output is ready (input transfer,
    device program, output), `cnn.fetch` copies the output to the host
    (every output of a multi-output network, in one `jax.device_get`) and
    completes each request.  `stats()["stages"]` aggregates them over every
    step; the last `STEPS_KEPT` records are kept whole (`step_records()`)
    in a preallocated ring.  `anchor_ns`,
    one `(time.time_ns(), time.perf_counter_ns())` pair taken at
    construction, converts the stamps to epoch time, the clock of a
    profiler trace (`profile_start_time` + an event's start).
    """

    def __init__(self, cache: CompileCache):
        self.cache = cache
        self.max_batch = cache.buckets[-1]
        self.in_shape = tuple(cache.net.in_shape)  # (H, W, C)
        self.pending: deque[ImageRequest] = deque()
        self._submitted = 0
        self._completed = 0
        self._rejected = 0
        self._steps = 0
        self._latency = LatencyAgg()
        self._stage_ns = [0] * len(CNN_STAGES)
        self._ring = np.zeros((STEPS_KEPT, len(CNN_STAGES) + 1), np.int64)
        self.anchor_ns = (time.time_ns(), time.perf_counter_ns())

    def submit(self, req: ImageRequest) -> None:
        try:
            img = np.asarray(req.image)
        except (ValueError, TypeError) as e:
            self._rejected += 1  # count before raising: run() skips it
            raise RejectedRequest(f"bad image payload: {e}") from e
        if tuple(img.shape) != self.in_shape:
            self._rejected += 1
            raise RejectedRequest(
                f"image shape {tuple(img.shape)} != network "
                f"input {self.in_shape}")
        req.image = img.astype(self.cache.dtype, copy=False)
        req.t_submit = time.perf_counter()
        self.pending.append(req)
        self._submitted += 1

    def step(self) -> int:
        """Drain one micro-batch through the compile cache."""
        if not self.pending:
            return 0
        ns = time.perf_counter_ns
        t0 = ns()
        batch = [self.pending.popleft()
                 for _ in range(min(self.max_batch, len(self.pending)))]
        x = np.stack([r.image for r in batch])
        t1 = ns()
        x = jnp.asarray(x)
        t2 = ns()
        y = self.cache.run(x)
        t3 = ns()
        jax.block_until_ready(y)
        t4 = ns()
        if isinstance(y, tuple):
            rows = list(zip(*jax.device_get(y)))
        else:
            rows = np.asarray(y)
        t_done = time.perf_counter()
        for i, r in enumerate(batch):
            r.result = rows[i]
            r.done = True
            r.t_done = t_done
            self._latency.add(r.latency_s)
        self._completed += len(batch)
        t5 = ns()
        total = self._stage_ns
        total[0] += t1 - t0
        total[1] += t2 - t1
        total[2] += t3 - t2
        total[3] += t4 - t3
        total[4] += t5 - t4
        self._ring[self._steps % len(self._ring)] = t0, t1, t2, t3, t4, t5
        self._steps += 1
        return len(batch)

    def step_records(self) -> dict:
        """The kept step records, oldest first: `step`, the step ids (n,);
        the six boundary stamps of each step in both clocks, `perf_ns`
        (`time.perf_counter_ns`) and `epoch_ns` (`time.time_ns`, through
        the anchor), (n, 6), around the `stages` in order; and
        `overwritten`, the records the ring has dropped since
        construction."""
        n, cap = self._steps, len(self._ring)
        lost = max(0, n - cap)
        kept = (np.roll(self._ring, -(n % cap), axis=0) if lost
                else self._ring[:n].copy())
        epoch, perf = self.anchor_ns
        return {"stages": CNN_STAGES, "step": np.arange(lost, n),
                "perf_ns": kept, "epoch_ns": kept - perf + epoch,
                "overwritten": lost}

    def stats(self) -> dict:
        n = self._steps
        stages = {name: {"count": n, "total_ns": tot,
                         "mean_ns": (tot / n) if n else 0.0}
                  for name, tot in zip(CNN_STAGES, self._stage_ns)}
        return build_stats(
            engine="cnn", submitted=self._submitted,
            completed=self._completed, rejected=self._rejected, truncated=0,
            steps=n, wall_s=sum(self._stage_ns) * 1e-9,
            latency=self._latency, items=self._completed,
            extra={"images": self._completed, "cache": self.cache.stats(),
                   "stages": stages})
