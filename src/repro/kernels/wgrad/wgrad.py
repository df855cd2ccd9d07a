"""Sparse-conv weight gradient (wgrad) as a Pallas TPU kernel.

The paper's third training kernel (§4.2/§6.1): a GEMM with *two* sparse
iterators — both operands are gathered through the kernel map, and the K
loop runs over output points (large), which is why the paper tunes wgrad's
dataflow separately and prefers offline-reordered maps for it.

Structure mirrors the fwd kernels: pair lists in SMEM, per-row async DMA
gathers of BOTH operands into VMEM (double scratch), MXU outer-product
accumulation into a VMEM (Cin, Cout) accumulator across the *sequential*
row-tile grid dimension, one write-back per offset.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def _kernel(wsin_ref, wsout_ref, x_ref, dy_ref, o_ref, xs, ys, acc,
            sems_x, sems_y, *, tile_r: int, dtype):
    r = pl.program_id(1)
    lanes = common.LANES

    @pl.when(r == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    # gather both operands' rows (all DMAs in flight before any wait)
    common.start_gather(wsin_ref, x_ref, xs, sems_x, n=tile_r)
    common.gather_rows(wsout_ref, dy_ref, ys, sems_y, n=tile_r)
    common.wait_gather(wsin_ref, x_ref, xs, sems_x, n=tile_r)

    for i in range(xs.shape[0]):
        for j in range(ys.shape[0]):
            acc[pl.ds(i * lanes, lanes), pl.ds(j * lanes, lanes)] += \
                jax.lax.dot_general(
                    xs[i].astype(dtype), ys[j].astype(dtype),
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

    @pl.when(r == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("tile_r", "interpret"))
def wgrad_pallas(ws_in: jax.Array, ws_out: jax.Array, x: jax.Array,
                 dy: jax.Array, *, tile_r: int, interpret: bool) -> jax.Array:
    """ws_in/ws_out: (KD, cap) int32 pair lists; x: (N_in, Cin);
    dy: (N_out, Cout) → dW (KD, Cin, Cout) f32."""
    kd, cap = ws_in.shape
    cin, cout, dtype = x.shape[1], dy.shape[1], x.dtype
    assert cap % tile_r == 0
    nci, nco = common.chunks(cin), common.chunks(cout)
    lanes = common.LANES
    x, dy = common.gather_operand(x), common.gather_operand(dy)
    sq = pl.squeezed
    pairs = pl.BlockSpec((sq, sq, 1, tile_r), lambda k, r: (k, r, 0, 0),
                         memory_space=pltpu.SMEM)
    dw = pl.pallas_call(
        functools.partial(_kernel, tile_r=tile_r, dtype=dtype),
        grid=(kd, cap // tile_r),
        in_specs=[
            pairs, pairs,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((sq, nci * lanes, nco * lanes),
                               lambda k, r: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((kd, nci * lanes, nco * lanes),
                                       jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((nci, tile_r, lanes), x.dtype),
            pltpu.VMEM((nco, tile_r, lanes), dy.dtype),
            pltpu.VMEM((nci * lanes, nco * lanes), jnp.float32),
            pltpu.SemaphoreType.DMA((tile_r,)),
            pltpu.SemaphoreType.DMA((tile_r,)),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(common.pair_blocks(ws_in, tile_r), common.pair_blocks(ws_out, tile_r),
      x, dy)
    return dw[:, :cin, :cout]
