"""Activation-sharding hints that degrade to no-ops off-mesh.

Model code calls ``shard(x, "dp", None, "model", None)`` with *logical* axis
tags; if a mesh is installed (``jax.set_mesh``) the tag resolves to real mesh
axes and a with_sharding_constraint is applied, otherwise the call is a
no-op.  This keeps model code mesh-agnostic: smoke tests run on 1 device,
the dry-run runs on the 512-device production mesh, same code path.

Tags:  "dp"    -> every batch-parallel axis present (("pod", "data"))
       "model" -> the tensor-parallel axis
       None    -> unsharded dim
Uneven dims are fine here (GSPMD pads inside jit; DESIGN.md §7).
"""
from __future__ import annotations

import contextlib

import jax
from jax._src import mesh as mesh_lib
from jax.sharding import PartitionSpec as P

# Distribution strategy (set by the launcher, read at trace time):
#   tp   : batch over (pod, data); tensors over 'model' (Megatron TP)
#   fsdp : batch over ALL axes (pure ZeRO-3); 'model' tag resolves to None
#          (the model axis carries batch, params are gathered per layer)
_STRATEGY = "tp"


@contextlib.contextmanager
def strategy(name: str):
    global _STRATEGY
    assert name in ("tp", "fsdp"), name
    prev = _STRATEGY
    _STRATEGY = name
    try:
        yield
    finally:
        _STRATEGY = prev


def current_strategy() -> str:
    return _STRATEGY


def batch_axes() -> tuple:
    return (("pod", "data", "model") if _STRATEGY == "fsdp"
            else ("pod", "data"))


def physical_mesh():
    """The installed CONCRETE device mesh, or None off-mesh: the mesh
    `jax.set_mesh` installs, else the one a ``with mesh:`` block (what
    `use_mesh` does) installs.  Unlike the abstract mesh an allocation-free
    trace installs, the physical mesh carries real devices — it is the mesh
    `shard_map`-based backends (core/shard_backend.py, kernels/sharded.py)
    wrap kernels over.  Readable inside a jit trace, where
    `jax.sharding.get_mesh` refuses."""
    for mesh in (mesh_lib.get_concrete_mesh(),
                 mesh_lib.thread_resources.env.physical_mesh):
        if not mesh.empty:
            return mesh
    return None


def _current_mesh():
    """The abstract mesh when one is installed (`jax.set_mesh`, an
    allocation-free trace), else the physical mesh, else None."""
    mesh = jax.sharding.get_abstract_mesh()
    return physical_mesh() if mesh.empty else mesh


def _current_axis_names():
    mesh = _current_mesh()
    return () if mesh is None else tuple(mesh.axis_names)


def axis_size(name: str) -> int:
    """Size of mesh axis `name` in the installed mesh (1 when absent or
    off-mesh)."""
    mesh = _current_mesh()
    return 1 if mesh is None else dict(mesh.shape).get(name, 1)


def mesh_active() -> bool:
    """True when a device mesh is installed (sharding hints will apply).
    Model code no longer forks on this — attention/GEMM dispatch the
    registry op at every scale and the BACKEND distributes (see
    core/shard_backend.py); it remains for launchers/diagnostics."""
    return bool(_current_axis_names())


def mesh_topology(mesh=None) -> tuple:
    """((axis, size), ...) for `mesh` (default: the installed physical
    mesh), or () off-mesh.  A hashable topology fingerprint — serving
    layers fold it into `StepCompileCache` keys so a step traced under
    one mesh is never replayed under another."""
    if mesh is None:
        mesh = physical_mesh()
    if mesh is None or getattr(mesh, "empty", False):
        return ()
    return tuple((str(a), int(mesh.shape[a])) for a in mesh.axis_names)


def use_mesh(mesh):
    """Context manager installing `mesh` as the ambient physical mesh for
    the duration (trace-time is what matters: shard_map embeds the
    concrete mesh into the jaxpr).  None -> no-op context, so callers can
    write ``with use_mesh(self.mesh):`` unconditionally."""
    return contextlib.nullcontext() if mesh is None else mesh


def resolve(tag):
    """Logical tag -> mesh axis (or None if absent from current mesh)."""
    names = _current_axis_names()
    if tag is None:
        return None
    if tag == "dp":
        axes = tuple(a for a in batch_axes() if a in names)
        return axes if axes else None
    if tag == "model" and _STRATEGY == "fsdp":
        return None  # the model axis carries batch under pure FSDP
    if tag in names:
        return tag
    return None


def shard(x, *tags):
    names = _current_axis_names()
    if not names:
        return x
    spec = P(*(resolve(t) for t in tags))
    return jax.lax.with_sharding_constraint(x, spec)


def pspec(*tags) -> P:
    """PartitionSpec from logical tags (for boundary shardings)."""
    return P(*(resolve(t) for t in tags))
