"""Quickstart: the paper's compute engine in 30 lines.

1. Run a fused FP32 GEMM on the engine (every backend in the registry).
2. Build a Darknet CNN from a cfg string, compile once, run inference.
3. Run one LM training step on a reduced architecture.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs.base import get_arch, reduced
from repro.configs.darknet_ref import DARKNET_SMALL_CFG
from repro.core.darknet.network import Network
from repro.core import enable_persistent_cache, list_backends, make_engine
from repro.models import transformer as tfm

enable_persistent_cache()

# --- 1. the engine: fused act((x@w)*scale+shift), fp32 strict -------------
# Backends resolve through the op registry; add your own with
# repro.core.register_backend (see docs/engine_api.md).
print(f"registered backends: {list_backends()}")
engine_xla = make_engine("xla", "fp32_strict")
engine_pallas = make_engine("pallas", "fp32_strict")  # TPU-target kernel
x = jax.random.normal(jax.random.PRNGKey(0), (200, 300), jnp.float32)
w = jax.random.normal(jax.random.PRNGKey(1), (300, 100), jnp.float32)
bias = jnp.ones((100,), jnp.float32)
y1 = engine_xla.matmul(x, w, shift=bias, act="leaky")
y2 = engine_pallas.matmul(x, w, shift=bias, act="leaky")
print(f"engine backends agree: {jnp.max(jnp.abs(y1 - y2)):.2e}")

# --- 2. the paper's use-case: Darknet CNN, compiled once ------------------
net = Network(DARKNET_SMALL_CFG, engine_xla)
params = net.init(jax.random.PRNGKey(2))
img = jax.random.normal(jax.random.PRNGKey(3), (4, 28, 28, 3), jnp.float32)
compiled = net.compile(params, batch_size=4)       # ONE jit trace
probs = compiled(img)
print(f"darknet CNN: input {img.shape} -> class probs {probs.shape}, "
      f"sum={probs.sum(-1)[0]:.4f}, engine plan={compiled.op_counts}")

# --- 3. the substrate: one LM train step (reduced qwen2) ------------------
cfg = reduced(get_arch("qwen2-0.5b"))
lm_params = tfm.init_params(jax.random.PRNGKey(4), cfg)
batch = {
    "tokens": jax.random.randint(jax.random.PRNGKey(5), (2, 64), 0,
                                 cfg.vocab_size),
    "labels": jax.random.randint(jax.random.PRNGKey(6), (2, 64), 0,
                                 cfg.vocab_size),
}
loss = jax.jit(lambda p, b: tfm.loss_fn(engine_xla, cfg, p, b,
                                        ce_chunk=32, n_q_chunks=4))(
    lm_params, batch)
print(f"LM train loss (random init, V={cfg.vocab_size}): {loss:.3f} "
      f"(ln V = {jnp.log(cfg.vocab_size):.3f})")
