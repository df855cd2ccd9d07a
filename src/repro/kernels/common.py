"""Shared helpers for the Pallas kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128


def pad_lanes(x: jax.Array, axis: int) -> jax.Array:
    """Zero-pad ``x`` along ``axis`` to a multiple of the 128-lane vreg
    width: Mosaic DMAs only lane-aligned row slices, so feature rows that
    a kernel gathers row by row must be a whole number of lane tiles wide.
    The zero channels add exact zeros to every dot product."""
    pad = (-x.shape[axis]) % LANES
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def gather_operand(x: jax.Array) -> jax.Array:
    """Lay out a (rows, C) array for row-by-row DMA gathers: as
    (rows · C/128, 128) 32-bit lane rows, C zero-padded to a multiple of
    128.  Mosaic DMAs a single row only out of a 128-lane-wide 32-bit array
    (a wider row spans several (8, 128) tiles; a bf16 row is half a packed
    sublane), so row ``i`` of ``x`` becomes lane rows ``i·C/128 + j``.
    Widening bf16 is exact; kernels cast the gathered tile back to the GEMM
    operand dtype before the MXU sees it."""
    x = pad_lanes(x, 1)
    if x.dtype.itemsize < 4:
        x = x.astype(jnp.float32)
    return x.reshape(-1, LANES)


def chunks(c: int) -> int:
    """Number of 128-lane chunks a C-wide row occupies."""
    return -(-c // LANES)


def gather_rows(idx_ref, src_ref, dst_ref, sems, *, n: int):
    """DMA rows ``src[idx[0, r]]`` into ``dst[:, r]`` for ``r < n``, all
    copies in flight before any wait; a ``-1`` index zeroes its row instead
    (the paper's §3.2 padding, so the loop needs no bounds check).
    ``idx_ref`` is a (1, n) SMEM block; ``src_ref`` a :func:`gather_operand`
    array in HBM; ``dst_ref`` a (C/128, n, 128) VMEM buffer; ``sems`` holds
    one DMA semaphore per row."""
    start_gather(idx_ref, src_ref, dst_ref, sems, n=n)
    wait_gather(idx_ref, src_ref, dst_ref, sems, n=n)


def start_gather(idx_ref, src_ref, dst_ref, sems, *, n: int):
    """The issuing half of :func:`gather_rows`."""
    def body(r, carry):
        idx = idx_ref[0, r]

        @pl.when(idx >= 0)
        def _start():
            for c in row_copies(src_ref, idx, dst_ref, r, sems.at[r]):
                c.start()

        @pl.when(idx < 0)
        def _zero_row():
            dst_ref[:, pl.ds(r, 1), :] = jnp.zeros(
                (dst_ref.shape[0], 1, LANES), dst_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def wait_gather(idx_ref, src_ref, dst_ref, sems, *, n: int):
    """The waiting half of :func:`gather_rows`."""
    def body(r, carry):
        idx = idx_ref[0, r]

        @pl.when(idx >= 0)
        def _wait():
            for c in row_copies(src_ref, idx, dst_ref, r, sems.at[r]):
                c.wait()
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def row_copies(src_ref, idx, dst_ref, r, sem, *, scatter: bool = False):
    """The per-chunk DMAs that move one C-wide row between a
    :func:`gather_operand` array (row ``idx``) and row ``r`` of a
    (C/128, n, 128) VMEM buffer — HBM→VMEM, or VMEM→HBM with
    ``scatter``.  All chunks signal ``sem``."""
    nc = dst_ref.shape[0]
    out = []
    for j in range(nc):
        hbm = src_ref.at[pl.ds(idx * nc + j, 1)]
        vmem = dst_ref.at[j, pl.ds(r, 1)]
        src, dst = (vmem, hbm) if scatter else (hbm, vmem)
        out.append(pltpu.make_async_copy(src, dst, sem))
    return out


def chunked_dot(buf, w_ref, dtype) -> jax.Array:
    """``concat(buf[j] for j) @ w`` for a gathered (C/128, n, 128) buffer
    and a (C, N) weight ref, one 128-deep MXU pass per chunk, in f32."""
    acc = None
    for j in range(buf.shape[0]):
        part = jnp.dot(buf[j].astype(dtype), w_ref[pl.ds(j * LANES, LANES), :],
                       preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    return acc


def pair_blocks(ws: jax.Array, tile_r: int) -> jax.Array:
    """(KD, cap) pair list → (KD, cap // tile_r, 1, tile_r), so a grid step's
    (1, tile_r) SMEM block spans the array's last two dims (Mosaic refuses
    a (1, tile_r) block over a (KD, cap) array: 1 is neither KD nor a
    multiple of 8)."""
    kd, cap = ws.shape
    return ws.reshape(kd, cap // tile_r, 1, tile_r)


def default_interpret() -> bool:
    """Pallas kernels run compiled on a TPU and in interpret mode elsewhere
    (the CPU tests); wrappers resolve ``interpret=None`` through this, and
    the jitted kernels themselves take the flag with no default."""
    return jax.default_backend() != "tpu"
