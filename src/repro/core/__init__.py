"""Public compute-engine API.

The paper's contribution as a package surface: one `ComputeEngine` serving
every dense layer, backed by a backend/op registry (`backends.py`), the
non-quantization precision contract (`precision.py`), and one rule-made
tile plan per (op, shapes, dtype, backend), memoized in process
(docs/engine_api.md, "Tile plans").  Import from here:

    from repro.core import ComputeEngine, make_engine, register_backend
"""
from repro.core.backends import (OP_SET, get_backend, list_backends,
                                 register_backend, tile_plans)
from repro.core.compile_cache import (StepCompileCache,
                                      enable_persistent_cache,
                                      normalize_buckets, pick_bucket)
from repro.core.engine import ComputeEngine, make_engine
from repro.core.precision import Precision
from repro.core import shard_backend as _shard_backend  # noqa: F401
# importing repro.core registers the built-in backends: "pallas"/"xla"
# (core/backends.py at module load) and "sharded_pallas" (the line above,
# through the public register_backend seam).

__all__ = ["ComputeEngine", "make_engine", "Precision", "OP_SET",
           "register_backend", "get_backend", "list_backends",
           "tile_plans",
           "StepCompileCache", "enable_persistent_cache",
           "normalize_buckets", "pick_bucket"]
