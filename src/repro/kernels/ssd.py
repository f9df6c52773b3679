"""Pallas SSD (Mamba2) chunk-scan kernel — the paper's streaming dataflow
applied to the state-space mixer.

The jnp formulation (models/ssm.ssd_chunked) materializes per-chunk decay
matrices L=(Q,Q) and chunk states in HBM — the memory term that dominates
the mamba2 train cell (EXPERIMENTS.md §Roofline).  This kernel streams
chunks through VMEM with the running state held in a scratch accumulator
(exactly the GEMM engine's "accumulator never leaves the chip" structure):

  grid = (BH, S/Q), chunk dim innermost ("arbitrary");
  scratch: state (P, N) fp32 — carried across chunk steps;
  per chunk (all in VMEM):
    L      = exp(segsum(dA))                 (Q, Q) lower-tri
    scores = (C @ Bᵀ) ∘ L                    (Q, Q)
    y      = scores @ x̄ + exp(dA_cs) ∘ (C @ stateᵀ)
    state  = exp(dA_tot)·state + (x̄ ∘ decay_in)ᵀ @ B

x̄ = x·dt.  Heads/groups are pre-broadcast and flattened into the BH grid
dim by the ops wrapper.  Validated against models/ssm.ssd_reference in
interpret mode (tests/test_kernels_ssd.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, y_ref, st_ref, *,
                nq: int, Q: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)        # (Q,)
    dA = da_ref[0].astype(jnp.float32)        # (Q,)  = dt * A  (negative)
    Bm = b_ref[0].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)         # (Q, N)

    xbar = x * dt[:, None]
    cs = jnp.cumsum(dA)                       # (Q,)
    # segsum: L[i, j] = exp(cs[i] - cs[j]) for i >= j else 0
    diff = cs[:, None] - cs[None, :]
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(qi >= kj, jnp.exp(diff), 0.0)

    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(scores * L, xbar, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, P)
    # carried-state contribution: exp(cs) ∘ (C @ stateᵀ)
    st = st_ref[...]                           # (P, N)
    y_off = jax.lax.dot_general(Cm, st, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y = y + jnp.exp(cs)[:, None] * y_off
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: exp(dA_tot)·state + (x̄ ∘ decay_in)ᵀ @ B
    decay_in = jnp.exp(cs[-1] - cs)            # (Q,)
    st_new = (jnp.exp(cs[-1]) * st
              + jax.lax.dot_general(xbar * decay_in[:, None], Bm,
                                    (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32))
    st_ref[...] = st_new


def ssd_scan(x, dt, dA, B, C, *, chunk: int = 128,
             interpret: bool | None = None):
    """x: (BH, S, P); dt, dA: (BH, S); B, C: (BH, S, N) -> y (BH, S, P).

    S % chunk == 0 (the ops wrapper pads with dt=0 rows — exact, as in
    models/ssm.ssd_chunked).
    """
    BH, S, P = x.shape
    N = B.shape[-1]
    assert S % chunk == 0, (S, chunk)
    grid = (BH, S // chunk)
    kernel = functools.partial(_ssd_kernel, nq=grid[1], Q=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, chunk), lambda g, j: (g, j)),
            pl.BlockSpec((1, chunk), lambda g, j: (g, j)),
            pl.BlockSpec((1, chunk, N), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, chunk, N), lambda g, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda g, j: (g, j, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(x, dt, dA, B, C)
