"""Implicit-GEMM sparse convolution — the flagship Pallas TPU kernel.

Paper §3.1 (Fig. 7): a sparse conv kernel is a dense GEMM whose operand-A
loads go through one level of indirection (the kernel map).  TPU adaptation
(DESIGN.md §2):

* the kernel map tile lives in **SMEM** (BlockSpec memory_space=SMEM) — the
  structural equivalent of the paper's hoisted, register-resident addressing;
* operand A rows are fetched **HBM→VMEM by per-row async DMA**
  (`pltpu.make_async_copy`, one per 128-lane chunk of the row — see
  ``common.gather_operand``), all `tile_m` rows in flight before the MXU
  consumes them — this is the "sparse DRAM→L1 iterator" with overlapped
  memory access and compute (paper Fig. 3d);
* per-(tile, δ) **occupancy scalars** gate the whole gather+matmul with
  `@pl.when` — warp-level zero skipping becomes MXU-tile-level skipping;
* `-1` map entries (paper §3.2 padding) zero the scratch row instead of
  issuing a DMA, so the inner loop has no bounds check.

Grid: (m_tiles, n_tiles, KD_split) with δ innermost; the f32 accumulator
lives in VMEM across δ steps and is written once at the last δ.

``implicit_gemm_worklist_pallas`` is the tile-*skipping* variant (Spira's
structure-exploiting scheduling): instead of the dense (m_tiles, KD) product
gated per step by ``@pl.when``, the grid runs over a host-compacted worklist
of the occupied (m_tile, δ) pairs only — empty tiles are never scheduled.
The worklist is sorted by m_tile so all δ entries of one output tile are
consecutive grid steps; Pallas keeps the revisited output block (and the
VMEM accumulator) resident across them, and per-entry flags mark the
first/last entry of each tile (zero / flush points).  Scalar-prefetch
(``pltpu.PrefetchScalarGridSpec``) feeds the worklist to the index maps, so
the weight block and output block are data-dependent on the worklist entry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def _kernel(midx_ref, occ_ref, x_ref, w_ref, o_ref, scratch, acc, sems, *,
            tile_m: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(occ_ref[0, 0] == 1)
    def _compute():
        common.gather_rows(midx_ref, x_ref, scratch, sems, n=tile_m)
        acc[...] += common.chunked_dot(scratch, w_ref, w_ref.dtype)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n", "interpret"))
def implicit_gemm_pallas(midx: jax.Array, occ: jax.Array, x: jax.Array,
                         w: jax.Array, *, tile_m: int, tile_n: int,
                         interpret: bool) -> jax.Array:
    """One split of sorted/unsorted implicit GEMM.

    midx: (N_out_pad, KD) int32 — (already row-permuted) kernel map slice.
    occ:  (N_out_pad // tile_m, KD) int32 — per-(tile, δ) occupancy.
    x:    (N_in, Cin) — input features (stays in HBM; gathered by DMA).
    w:    (KD, Cin, Cout) — weights for this split's offsets.
    Returns (N_out_pad, Cout) partial sums in x.dtype.
    """
    n_out, kd = midx.shape
    out_dtype = x.dtype
    nc, cout = common.chunks(x.shape[1]), w.shape[-1]
    x, w = common.gather_operand(x), common.pad_lanes(w, 1)
    assert n_out % tile_m == 0, "pad map rows to tile_m (paper §3.2)"
    assert cout % tile_n == 0, f"Cout {cout} must be a multiple of tile_n {tile_n}"
    n_tiles = n_out // tile_m
    grid = (n_tiles, cout // tile_n, kd)
    # Index blocks whose last two dims span the whole array, as Mosaic
    # requires of blocks that are not (8, 128)-aligned: one (1, tile_m) row
    # of map entries and one (1, 1) occupancy scalar per (tile, δ) step.
    midx_t = midx.reshape(n_tiles, tile_m, kd).transpose(0, 2, 1)[:, :, None, :]
    occ_t = occ.reshape(n_tiles, kd, 1, 1)
    sq = pl.squeezed

    kernel = functools.partial(_kernel, tile_m=tile_m)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((sq, sq, 1, tile_m), lambda i, j, k: (i, k, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((sq, sq, 1, 1), lambda i, j, k: (i, k, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((sq, w.shape[1], tile_n), lambda i, j, k: (k, 0, j)),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_out, cout), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((nc, tile_m, common.LANES), x.dtype),
            pltpu.VMEM((tile_m, tile_n), jnp.float32),
            pltpu.SemaphoreType.DMA((tile_m,)),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(midx_t, occ_t, x, w)


# ------------------------------------------------------- tile skipping
# Worklist entry flags (bit field; 0 = padding entry, never computes)
WL_FIRST = 1   # first entry of its output tile: zero the accumulator
WL_LAST = 2    # last entry of its output tile: flush acc → output block
WL_VALID = 4   # real entry: gather + accumulate (middle entries are
#                VALID-only; pads are 0)


def _wl_kernel(wl_tile_ref, wl_delta_ref, wl_flags_ref, midx_ref, x_ref,
               w_ref, o_ref, scratch, acc, sems, *, tile_m: int):
    del wl_tile_ref, wl_delta_ref   # consumed by the index maps
    i = pl.program_id(1)
    fl = wl_flags_ref[i]

    @pl.when((fl & WL_FIRST) != 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    @pl.when((fl & WL_VALID) != 0)
    def _compute():
        common.gather_rows(midx_ref, x_ref, scratch, sems, n=tile_m)
        acc[...] += common.chunked_dot(scratch, w_ref, w_ref.dtype)

    @pl.when((fl & WL_LAST) != 0)
    def _flush():
        o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("n_tiles_m", "tile_m", "tile_n",
                                    "interpret"))
def implicit_gemm_worklist_pallas(wl_tile: jax.Array, wl_delta: jax.Array,
                                  wl_flags: jax.Array, wl_midx: jax.Array,
                                  x: jax.Array, w: jax.Array, *,
                                  n_tiles_m: int, tile_m: int, tile_n: int,
                                  interpret: bool) -> jax.Array:
    """One split of tile-skipping implicit GEMM over a compacted worklist.

    wl_tile:  (W,) int32 — output m-tile of each entry, sorted ascending
              (all entries of one tile consecutive); pads repeat the last
              real tile so no fresh output block is visited.
    wl_delta: (W,) int32 — δ offset (into this split's weight slice).
    wl_flags: (W,) int32 — WL_VALID/WL_FIRST/WL_LAST bit field; 0 ⇒ padding
              entry (no compute, no write).
    wl_midx:  (W, tile_m) int32 — pre-gathered kernel-map rows of each
              entry (``midx[tile·tile_m:(tile+1)·tile_m, δ]``).
    x:        (N_in, Cin); w: (KD_split, Cin, Cout).
    Returns (n_tiles_m · tile_m, Cout) partials; tiles with NO worklist
    entry hold uninitialized garbage — callers must mask them to zero
    (the wrapper does).
    """
    wn = wl_midx.shape[0]
    out_dtype = x.dtype
    nc, cout = common.chunks(x.shape[1]), w.shape[-1]
    x, w = common.gather_operand(x), common.pad_lanes(w, 1)
    assert cout % tile_n == 0, f"Cout {cout} must be a multiple of tile_n {tile_n}"
    grid = (cout // tile_n, wn)   # worklist innermost: same-tile steps stay
    #                               resident in the output block / acc
    sq = pl.squeezed

    kernel = functools.partial(_wl_kernel, tile_m=tile_m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((sq, 1, tile_m), lambda j, i, wt, wd, wf: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((sq, w.shape[1], tile_n),
                         lambda j, i, wt, wd, wf: (wd[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n),
                               lambda j, i, wt, wd, wf: (wt[i], j)),
        scratch_shapes=[
            pltpu.VMEM((nc, tile_m, common.LANES), x.dtype),
            pltpu.VMEM((tile_m, tile_n), jnp.float32),
            pltpu.SemaphoreType.DMA((tile_m,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles_m * tile_m, cout), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(wl_tile, wl_delta, wl_flags, wl_midx[:, None, :], x, w)
