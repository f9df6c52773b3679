"""sharding/hints.py unit coverage: tag resolution under both strategies,
missing-axis meshes, the abstract-vs-physical mesh fallback in
`_current_axis_names`, and the mesh helpers the sharded backend and
serving layer ride (`physical_mesh`, `mesh_topology`, `use_mesh`).

All tests run on 1-device meshes — axis NAMES drive resolution, not axis
sizes, so none of this needs the forced device-count flag.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.sharding import hints


def mesh1(*names) -> Mesh:
    """1-device mesh with the given axis names (every axis size 1)."""
    devs = np.array(jax.devices()[:1]).reshape((1,) * len(names))
    return Mesh(devs, names)


# ------------------------------------------------------------- off-mesh ---

def test_off_mesh_everything_degrades():
    assert not hints.mesh_active()
    assert hints.physical_mesh() is None
    assert hints.mesh_topology() == ()
    assert hints.resolve("dp") is None
    assert hints.resolve("model") is None
    assert hints.pspec("dp", None, "model") == P(None, None, None)
    x = jnp.ones((4, 4))
    assert hints.shard(x, "dp", None) is x   # literal no-op, same object


# ------------------------------------------------- resolution, tp vs fsdp ---

def test_resolve_tp_full_mesh():
    with mesh1("pod", "data", "model"), hints.strategy("tp"):
        assert hints.mesh_active()
        assert hints.current_strategy() == "tp"
        assert hints.batch_axes() == ("pod", "data")
        assert hints.resolve("dp") == ("pod", "data")
        assert hints.resolve("model") == "model"
        assert hints.resolve(None) is None
        assert hints.pspec("dp", None, "model") == P(("pod", "data"), None,
                                                     "model")


def test_resolve_fsdp_model_axis_carries_batch():
    with mesh1("pod", "data", "model"), hints.strategy("fsdp"):
        assert hints.batch_axes() == ("pod", "data", "model")
        assert hints.resolve("dp") == ("pod", "data", "model")
        # under pure FSDP the 'model' TAG resolves to nothing: the mesh
        # axis named "model" is a batch axis, params gather per layer.
        assert hints.resolve("model") is None
        assert hints.pspec("dp", "model") == P(("pod", "data", "model"),
                                               None)


def test_resolve_missing_axes():
    with mesh1("data"):   # no pod, no model
        assert hints.resolve("dp") == ("data",)
        assert hints.resolve("model") is None
    with mesh1("rows"):   # mesh with NO recognized axes
        assert hints.mesh_active()
        assert hints.resolve("dp") is None
        assert hints.resolve("model") is None
        x = jnp.ones((2, 2))
        # constraint applies with a fully-replicated spec; value unchanged
        assert jnp.array_equal(hints.shard(x, "dp", "model"), x)


def test_shard_applies_constraint_on_mesh():
    with mesh1("data"):
        x = jnp.arange(8.0).reshape(4, 2)
        y = hints.shard(x, "dp", None)
        assert jnp.array_equal(y, x)       # constraint is value-preserving
        # and the constraint survives tracing (the real consumption site)
        assert jnp.array_equal(jax.jit(lambda a: hints.shard(a, "dp",
                                                             None))(x), x)


# ----------------------------------------- abstract vs physical fallback ---

def test_current_axis_names_physical_fallback():
    """On jax builds without `get_abstract_mesh` (or with no abstract mesh
    installed), `_current_axis_names` must fall back to the physical mesh
    context."""
    assert hints._current_axis_names() == ()
    with mesh1("pod", "data"):
        assert hints._current_axis_names() == ("pod", "data")
    assert hints._current_axis_names() == ()


def test_current_axis_names_abstract_mesh():
    """An installed abstract mesh wins over the physical context (the
    allocation-free dry-run path)."""
    amesh = jax.sharding.AbstractMesh((1, 1), ("pod", "data"))
    with jax.sharding.use_abstract_mesh(amesh):
        assert hints._current_axis_names() == ("pod", "data")
        assert hints.axis_size("data") == 1
        assert hints.physical_mesh() is None


# ----------------------------------------------------------- mesh helpers ---

def test_physical_mesh_and_topology():
    m = mesh1("data", "model")
    assert hints.mesh_topology(m) == (("data", 1), ("model", 1))
    with m:
        assert hints.physical_mesh() is not None
        assert tuple(hints.physical_mesh().axis_names) == ("data", "model")
        assert hints.mesh_topology() == (("data", 1), ("model", 1))
    assert hints.physical_mesh() is None


def test_physical_mesh_sees_set_mesh_inside_jit():
    """`jax.set_mesh` installs the concrete mesh where the sharded backend
    looks for it — at trace time, inside jit."""
    m = mesh1("data", "model")
    seen = []

    def f(x):
        seen.append(hints.physical_mesh())
        return x

    with jax.set_mesh(m):
        assert hints.physical_mesh() == m
        jax.jit(f)(jnp.ones(2))
    assert seen == [m]
    assert hints.physical_mesh() is None


def test_use_mesh_context():
    assert hints.physical_mesh() is None
    with hints.use_mesh(None):
        assert hints.physical_mesh() is None   # None -> no-op context
    with hints.use_mesh(mesh1("data")):
        m = hints.physical_mesh()
        assert m is not None and tuple(m.axis_names) == ("data",)
    assert hints.physical_mesh() is None
