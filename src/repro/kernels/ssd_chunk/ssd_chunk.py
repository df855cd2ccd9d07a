"""Fused SSD chunk scan (Mamba-2) as a Pallas TPU kernel.

EXPERIMENTS.md §Perf cycles 3/4 showed that dtype tweaks to the XLA SSD path
don't move the memory roofline because the O(Q²) intra-chunk tensors and the
elementwise chains are *materialized to HBM* between XLA ops.  This kernel is
the structural fix: per (sequence, chunk) grid step it keeps

    cum-decay (Q,)  ·  decay kernel (Q, Q)  ·  CBᵀ (Q, Q)  ·  state (N, P)

entirely in VMEM — HBM sees only the streamed inputs (x·dt, B, C, a) and the
(Q, P) output tile.  The carried state lives in a VMEM scratch accumulator
across the *sequential* chunk grid dimension (same pattern as the matmul
k-loop accumulator), zeroed at chunk 0.

MXU shapes: CBᵀ is (Q, N)×(N, Q), the intra product (Q, Q)×(Q, P), the state
update (N, Q)×(Q, P) — all 128-aligned for Q, P, N multiples of 128/8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, b_ref, c_ref, y_ref, hfin_ref, state, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    a = a_ref[0].astype(jnp.float32)                     # (Q,)
    x = x_ref[0].astype(jnp.float32)                     # (Q, P)
    b = b_ref[0].astype(jnp.float32)                     # (Q, N)
    c = c_ref[0].astype(jnp.float32)                     # (Q, N)

    cum = jnp.cumsum(a)                                  # (Q,)
    # intra-chunk: y_t += Σ_{s≤t} exp(cum_t - cum_s) (c_t·b_s) xdt_s
    l_ts = cum[:, None] - cum[None, :]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(cols <= rows, jnp.exp(l_ts), 0.0)
    cb = jnp.dot(c, b.T, preferred_element_type=jnp.float32)
    y = jnp.dot(cb * decay, x, preferred_element_type=jnp.float32)
    # inter-chunk: y_t += (c_t ⊙ exp(cum_t)) · h_in
    y = y + jnp.dot(c * jnp.exp(cum)[:, None], state[...],
                    preferred_element_type=jnp.float32)
    # state update: h_out = exp(cum_Q) h_in + Σ_s exp(cum_Q - cum_s) b_s ⊗ x_s
    seg = jnp.exp(cum[-1] - cum)                         # (Q,)
    state[...] = (jnp.exp(cum[-1]) * state[...]
                  + jnp.dot((b * seg[:, None]).T, x,
                            preferred_element_type=jnp.float32))
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _flush():
        hfin_ref[0] = state[...].astype(hfin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_pallas(a: jax.Array, xdt: jax.Array, b: jax.Array, c: jax.Array,
                     *, chunk: int = 128, interpret: bool):
    """a: (BH, S) log-decays; xdt: (BH, S, P); b/c: (BH, S, N), S % chunk == 0.

    Returns (y (BH, S, P) f32, h_final (BH, N, P) f32)."""
    bh, s = a.shape
    n, p = b.shape[-1], xdt.shape[-1]
    assert s % chunk == 0
    nc = s // chunk
    kernel = functools.partial(_kernel, chunk=chunk)
    grid = (bh, nc)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk), lambda i, j: (i, j)),
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, n, p), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(a, xdt, b, c)
