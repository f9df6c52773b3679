"""The plain references agree with the program at a tiny size on the CPU,
and a served run of every cell comes out correct."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import harness
from conftest import CELLS, TINY


def _config(name):
    mod = harness.load_module(harness.BENCH / "configs" / f"{name}.py", "t_")
    conf = dict(harness.load_json(harness.BENCH / "configs" / f"{name}.json"),
                **TINY[name]["config"])
    return mod, conf


def test_darknet_reference_matches_program():
    from repro.core import make_engine
    from repro.core.darknet.network import Network
    mod, conf = _config("darknet19")
    params = mod.make_params(conf, 11)
    x = jnp.asarray(mod.make_images(conf, 11, 3))
    net = Network(mod.cfg_text(conf), make_engine("pallas", "fp32_strict"))
    got = np.asarray(jax.jit(net.apply)(params, x))
    want = np.asarray(mod.reference(conf, params, x))
    assert got.shape == want.shape == (3, 10)
    assert np.max(np.abs(got - want)) <= 1e-5 * np.max(np.abs(want))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_served_run_is_correct(tiny_run, cell):
    r = tiny_run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) >= {"setup_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())
