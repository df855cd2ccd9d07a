"""Fetch-on-demand sparse convolution as a single fused Pallas TPU kernel.

Paper §2.2.2: gather + GEMM + scatter fused into one kernel; inputs are
fetched on demand into on-chip memory, partial sums are scattered straight to
the output without a DRAM scatter buffer.  PCEngine's "block fusion" (the
host δ-loop becoming a parallel dimension) maps to the leading grid axis.

TPU adaptation: the paper needs atomics because CUDA thread blocks race on
output rows.  A Pallas TPU grid runs *sequentially* on a core, so the
read-modify-write scatter (DMA out-row → VMEM, add, DMA back) is race-free
by construction; the cost — Σ_δ |M_δ| output-row writes, 4-10× the output
size — is exactly the write-amplification the paper attributes to this
dataflow, and is what the Autotuner trades off against implicit GEMM.

The output is accumulated in place, in float32, via
``input_output_aliases``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def _kernel(wsin_ref, wsout_ref, x_ref, w_ref, acc_in_ref, o_ref,
            xbuf, obuf, sems, osems, *, tile_r: int):
    del acc_in_ref  # aliased with o_ref
    # Fetch this tile's input rows and current output rows, all in flight.
    # The read-modify-write scatter is race-free: a TPU Pallas grid runs
    # sequentially on a core, and within one δ every output row appears at
    # most once, so tile-internal rows never collide either.
    common.start_gather(wsout_ref, o_ref, obuf, osems, n=tile_r)
    common.gather_rows(wsin_ref, x_ref, xbuf, sems, n=tile_r)
    common.wait_gather(wsout_ref, o_ref, obuf, osems, n=tile_r)

    y = common.chunked_dot(xbuf, w_ref, w_ref.dtype)
    for j in range(obuf.shape[0]):
        obuf[j] += y[:, j * common.LANES:(j + 1) * common.LANES]

    # scatter the partial sums straight back to the output rows
    def write_back(r):
        return common.row_copies(o_ref, wsout_ref[0, r], obuf, r,
                                 osems.at[r], scatter=True)

    def start(r, carry):
        @pl.when(wsout_ref[0, r] >= 0)
        def _wb():
            for c in write_back(r):
                c.start()
        return carry

    def wait(r, carry):
        @pl.when(wsout_ref[0, r] >= 0)
        def _wb_wait():
            for c in write_back(r):
                c.wait()
        return carry

    jax.lax.fori_loop(0, tile_r, start, 0)
    jax.lax.fori_loop(0, tile_r, wait, 0)


@functools.partial(jax.jit, static_argnames=("n_out", "tile_r", "interpret"))
def fetch_on_demand_pallas(ws_in: jax.Array, ws_out: jax.Array, x: jax.Array,
                           w: jax.Array, *, n_out: int, tile_r: int,
                           interpret: bool) -> jax.Array:
    """ws_in/ws_out: (KD, cap) int32 pair lists (-1 pad, compacted to front);
    x: (N_in, Cin); w: (KD, Cin, Cout).  Returns sparse_conv(x, w) as
    (n_out, Cout) in x.dtype, accumulated in float32."""
    kd, cap = ws_in.shape
    out_dtype, cout = x.dtype, w.shape[-1]
    assert cap % tile_r == 0
    nci, nco = common.chunks(x.shape[1]), common.chunks(cout)
    x = common.gather_operand(x)
    w = common.pad_lanes(common.pad_lanes(w, 1), 2)
    sq = pl.squeezed
    pairs = pl.BlockSpec((sq, sq, 1, tile_r), lambda k, r: (k, r, 0, 0),
                         memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, tile_r=tile_r),
        grid=(kd, cap // tile_r),
        in_specs=[
            pairs, pairs,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((sq,) + w.shape[1:], lambda k, r: (k, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # aliased accumulator
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((n_out * nco, common.LANES),
                                       jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((nci, tile_r, common.LANES), x.dtype),
            pltpu.VMEM((nco, tile_r, common.LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((tile_r,)),
            pltpu.SemaphoreType.DMA((tile_r,)),
        ],
        input_output_aliases={4: 0},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(common.pair_blocks(ws_in, tile_r), common.pair_blocks(ws_out, tile_r),
      x, w, jnp.zeros((n_out * nco, common.LANES), jnp.float32))
    return out.reshape(n_out, -1)[:, :cout].astype(out_dtype)
