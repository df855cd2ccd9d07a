"""Blockwise causal flash attention (forward) as a Pallas TPU kernel.

Used by the LM stack of the framework (the assigned architectures); VMEM
tiling follows the classic FlashAttention recipe: Q tile resident, K/V
streamed block-by-block with an online-softmax accumulator.  Causal blocks
above the diagonal are skipped via the grid index map (no masked compute at
all for fully-masked tiles — the same "skip empty tiles" economics as the
sparse-conv occupancy masks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_q: int, block_k: int, causal: bool, kv_len: int):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qb = pl.program_id(1)
    q_start = qb * block_q
    k_start = kb * block_k

    def compute():
        q = q_ref[0].astype(jnp.float32) * scale           # (bq, d)
        k = k_ref[0].astype(jnp.float32)                    # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            offset = kv_len - pl.num_programs(1) * block_q
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows + offset, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal:
        offset = kv_len - pl.num_programs(1) * block_q

        @pl.when(k_start <= q_start + offset + block_q - 1)
        def _run():
            compute()
    else:
        compute()

    @pl.when(kb == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, block_q: int = 128,
                           block_k: int = 128, interpret: bool) -> jax.Array:
    """q: (BH, S, D); k/v: (BH, T, D) — heads pre-flattened/broadcast."""
    bh, s, d = q.shape
    t = k.shape[1]
    assert s % block_q == 0 and t % block_k == 0
    scale = d ** -0.5
    grid = (bh, s // block_q, t // block_k)
    kernel = functools.partial(_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, causal=causal, kv_len=t)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v)
