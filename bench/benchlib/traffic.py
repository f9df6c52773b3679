"""Seeded traffic from a data file: one general generator.

A traffic file (``bench/traffic/<name>.json``) names a loop kind and its
parameters:

  closed   ``clients`` callers, each sending its next request once the
           previous result is back; requests cycle through a pool of
           ``pool`` seeded inputs.
  backlog  the client keeps at least ``depth`` requests pending before
           every server step; the same cycled pool.

Every seed gets the same work: the pool's size and the loop's shape come
from the file; the seed draws the order in which the pool is cycled (and
the weights and inputs, in the configuration modules).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

KINDS = ("closed", "backlog")


@dataclasses.dataclass
class Item:
    """One request as the client will send it."""
    rid: int
    pool_index: int     # which pooled input


def load(path: str | pathlib.Path) -> dict:
    spec = json.loads(pathlib.Path(path).read_text())
    if spec.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}, "
                         f"got {spec.get('kind')!r}")
    return spec


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy generator per (seed, stream); any non-negative
    seed, however large."""
    return np.random.default_rng([stream, seed])


def schedule(spec: dict, seed: int) -> list[Item]:
    """The requests of one run: one seeded permutation of the pool, which
    the loop cycles through until the window closes."""
    size = int(spec["pool"])
    order = rng_for(seed, 1).permutation(size)
    return [Item(rid=i, pool_index=int(order[i])) for i in range(size)]
