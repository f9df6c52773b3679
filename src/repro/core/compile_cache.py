"""Bucketed step-compile cache: one jit trace per shape bucket for an
arbitrary step function.

`CompileCache` (core/darknet/network.py) solves ragged CNN traffic by
padding batches to a small set of compiled batch-size buckets.  LM serving
has the same problem in more dimensions: the continuous-batching scheduler
(serve/scheduler.py) dispatches decode steps whose active-set size AND
per-sequence block-table width both vary per step.  Left alone, `jax.jit`
would retrace on every distinct (batch, n_blocks) pair — unbounded compile
churn under a ragged arrival stream.

`StepCompileCache` is the function-level twin of the network-level cache:
wrap a step fn once, pad every dynamic axis up to a configured bucket, and
the jit cache can only ever hold |bucket set| entries.  `pick_bucket`
implements the shared smallest-bucket-that-fits rule; `traces` counts
actual retraces (a python-side counter incremented inside the traced fn, so
compiled-path calls never bump it) — the serving benchmark's retrace gate
asserts against it.
"""
from __future__ import annotations

import collections
import os
import pathlib
from typing import Callable, Iterable

import jax

# The checkout root (src/repro/core/ -> three levels up): a fixed location,
# because the cache directory is part of what a cached entry is found by.
_CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``
    — the same path on every call and in every process, so a second run of
    any entry point finds the first one's executables.  Entry points call
    this from their ``main``; importing a module never does, and tests never
    do."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def normalize_buckets(buckets: Iterable[int]) -> tuple[int, ...]:
    """Sorted unique positive bucket sizes.  Raises ValueError when empty
    or non-positive."""
    bs = tuple(sorted({int(b) for b in buckets}))
    if not bs or bs[0] < 1:
        raise ValueError(f"buckets must be positive ints, got {buckets!r}")
    return bs


def pick_bucket(n: int, buckets: tuple[int, ...]) -> int:
    """Smallest bucket >= n.  Raises ValueError when n exceeds the top
    bucket (callers split oversize work before dispatch) or n < 1."""
    if n < 1:
        raise ValueError(f"need n >= 1, got {n}")
    for b in buckets:
        if b >= n:
            return b
    raise ValueError(f"{n} exceeds the top bucket of {buckets}")


class StepCompileCache:
    """One jit trace per shape bucket for a step function.

    The wrapped fn is jit'd exactly once; distinct argument shapes retrace
    as usual under jax, but because callers pad every dynamic axis to a
    bucket from a fixed set (via `pick_bucket`), the number of traces is
    bounded by the bucket-set product instead of the traffic's shape
    diversity.  `traces`/`calls`/`stats()` expose the retrace accounting
    the serving smoke gate asserts on.

    `static_argnames` forwards to `jax.jit` for hashable static args
    (engine/config objects).

    `topology` is a hashable mesh fingerprint (``hints.mesh_topology``:
    ``(("data", 8), ...)``, or ``()`` off-mesh).  It extends every cache
    key: each topology owns its own jit cache (a step traced under one
    mesh embeds that mesh's shard_maps — replaying it under another would
    silently compute on the wrong device set), and recorded dispatch keys
    are prefixed with it, so `stats()['dispatches']` distinguishes the
    same shape bucket dispatched under different meshes.
    """

    def __init__(self, fn: Callable, *, name: str = "step",
                 static_argnames=(), topology: tuple = ()):
        self.name = name
        self.topology = tuple(topology)
        self._traces = 0
        self._static = tuple(static_argnames)
        self._fn = fn
        self._jits: dict = {}
        self.calls = 0
        self._dispatch_shapes = collections.Counter()

    def _jit_for(self, topology: tuple):
        jit = self._jits.get(topology)
        if jit is None:
            # a FRESH closure per topology: jax.jit keys its trace cache
            # on the underlying callable, so reusing one function object
            # would silently replay a trace (and its embedded shard_maps)
            # across meshes.
            def counted(*args, **kwargs):
                self._traces += 1  # python side effect: trace-time only
                return self._fn(*args, **kwargs)

            jit = self._jits[topology] = jax.jit(
                counted, static_argnames=self._static)
        return jit

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return self._jit_for(self.topology)(*args, **kwargs)

    def record(self, key) -> None:
        """Log one dispatch under a caller-chosen bucket key (shows up in
        `stats()['dispatches']`, prefixed by the mesh topology when one
        is set)."""
        self._dispatch_shapes[self.topology + tuple(key)] += 1

    @property
    def traces(self) -> int:
        return self._traces

    def stats(self) -> dict:
        return {"name": self.name, "traces": self._traces,
                "calls": self.calls, "topology": self.topology,
                "dispatches": dict(self._dispatch_shapes)}
