"""The paper's own scenario: take a Darknet cfg, compile it once on the
engine, run batched image inference — including a deconvolutional network.

    PYTHONPATH=src python examples/cnn_inference.py
"""
import jax
import jax.numpy as jnp

from repro.configs.darknet_ref import (DARKNET19_CFG, DARKNET_SMALL_CFG,
                                       SEGNET_SMALL_CFG)
from repro.core.darknet.network import Network
from repro.core import enable_persistent_cache, make_engine


def main():
    enable_persistent_cache()
    engine = make_engine("xla", "fp32_strict")

    for name, cfg_text, shape in [
        ("darknet-small (classifier)", DARKNET_SMALL_CFG, (8, 28, 28, 3)),
        ("segnet-small (deconv)", SEGNET_SMALL_CFG, (8, 32, 32, 3)),
        ("darknet19 (imagenet trunk)", DARKNET19_CFG, (1, 224, 224, 3)),
    ]:
        net = Network(cfg_text, engine)
        params = net.init(jax.random.PRNGKey(0))
        n_params = net.num_params(params)
        x = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
        # Plan once, compile once, serve many: one jit trace here, then
        # every call is a straight executable invocation.
        compiled = net.compile(params, batch_size=shape[0]).warmup()
        y = compiled(x)
        prof = compiled.profile(x, reps=3)
        op_plan = " ".join(f"{op}x{n}" for (_, op), n in
                           sorted(prof["op_counts"].items()))
        print(f"[cnn] {name}: params={n_params/1e6:.2f}M "
              f"in={tuple(shape)} out={tuple(y.shape)} "
              f"{prof['per_call_s']*1000:.1f} ms/batch "
              f"traces={prof['trace_count']} plan=[{op_plan}]")


if __name__ == "__main__":
    main()
