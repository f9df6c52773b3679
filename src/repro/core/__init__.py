"""Public compute-engine API.

The paper's contribution as a package surface: one `ComputeEngine` serving
every dense layer, backed by a backend/op registry (`backends.py`), the
non-quantization precision contract (`precision.py`), and a measured
autotuner with per-device persisted block picks (`autotune.py`,
docs/autotune.md).  Import from here:

    from repro.core import ComputeEngine, make_engine, register_backend
    from repro.core import set_autotune_policy, autotune_policy
"""
from repro.core.backends import (AUTOTUNE_POLICIES, OP_SET, autotune_policy,
                                 autotune_report, get_autotune_policy,
                                 get_backend, list_backends, register_backend,
                                 set_autotune_policy)
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
           "AUTOTUNE_POLICIES", "autotune_policy", "autotune_report",
           "get_autotune_policy", "set_autotune_policy",
           "StepCompileCache", "enable_persistent_cache",
           "normalize_buckets", "pick_bucket"]
