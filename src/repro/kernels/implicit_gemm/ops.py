"""Jit'd wrapper: splits + sorting + padding around the implicit-GEMM kernel.

The Sparse Kernel Generator (core/generator.py) picks ``tile_m/tile_n`` and
the Sparse Autotuner picks ``n_splits``/``sorted``/``worklist``; this
wrapper is the glue that turns a (KernelMap, SplitPlan) pair into
pallas_call invocations plus the split-sum reduction of paper Fig. 10.

Two launch geometries:

* dense grid — ``(m_tiles, n_tiles, KD_split)``, empty (tile, δ) pairs
  gated off per step by the occupancy scalar (``@pl.when``);
* worklist (``worklist=True``) — the occupied (m_tile, δ) pairs are
  compacted host-side from the ``SplitPlan`` occupancy (fused into
  ``make_split_plan(tile_m=...)``) and the grid runs over *only* those —
  Spira-style structure-exploiting tile skipping.  It needs concrete
  occupancy to size the grid, so it runs eagerly only: under ``jit`` it
  raises rather than quietly running the dense grid.

Requested tiles are clamped to what the shapes allow so any tuner-proposed
config runs on any layer: ``tile_m`` to ``gcd(tile_m, capacity)``, and
``tile_n`` to the whole of Cout where it does not divide Cout (a lane block
is either a divisor of Cout or all of it).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kmap import KernelMap, SplitPlan
from repro.kernels.common import default_interpret
from repro.kernels.implicit_gemm.implicit_gemm import (
    WL_FIRST, WL_LAST, WL_VALID, implicit_gemm_pallas,
    implicit_gemm_worklist_pallas)


def _build_worklist(occ: np.ndarray):
    """Compact a concrete (n_tiles, KD_split) occupancy into the sorted
    worklist arrays.  Returns ``None`` for an empty split, else
    ``(wl_tile, wl_delta, wl_flags, tile_visited)`` with the entry count
    padded to a multiple of 8 (pads repeat the last real entry, flags 0 —
    no compute, no write) to bound shape-specialized recompiles."""
    ts, ds = np.nonzero(occ)          # row-major ⇒ sorted by (tile, δ)
    wn = ts.size
    if wn == 0:
        return None
    wcap = -(-wn // 8) * 8
    wl_tile = np.concatenate([ts, np.full(wcap - wn, ts[-1])]).astype(np.int32)
    wl_delta = np.concatenate([ds, np.full(wcap - wn, ds[-1])]).astype(np.int32)
    new_tile = np.empty(wn, bool)
    new_tile[0] = True
    np.not_equal(ts[1:], ts[:-1], out=new_tile[1:])
    flags = np.zeros(wcap, np.int32)
    flags[:wn] |= WL_VALID
    flags[:wn] |= np.where(new_tile, WL_FIRST, 0)
    flags[: wn - 1] |= np.where(new_tile[1:], WL_LAST, 0)
    flags[wn - 1] |= WL_LAST
    return wl_tile, wl_delta, flags, occ.any(axis=1)


def implicit_gemm(x: jax.Array, w: jax.Array, kmap: KernelMap, plan: SplitPlan,
                  *, tile_m: int = 128, tile_n: int = 128,
                  worklist: bool = False,
                  interpret: bool | None = None) -> jax.Array:
    """Full sparse conv via (split, sorted) implicit GEMM. Returns (N_out_cap, Cout)."""
    if interpret is None:
        interpret = default_interpret()
    cap = kmap.capacity
    cout = w.shape[-1]
    tile_m = math.gcd(tile_m, cap)
    tile_n = tile_n if cout % tile_n == 0 else cout
    n_tiles = cap // tile_m
    out = jnp.zeros((cap, cout), x.dtype)
    for s, (a, b) in enumerate(plan.ranges):
        order = plan.order[s]
        midx = kmap.m_out[order][:, a:b]
        occ3 = (midx.reshape(n_tiles, tile_m, b - a) >= 0).any(axis=1)
        if worklist and isinstance(occ3, jax.core.Tracer):
            raise ValueError(
                "the worklist implicit GEMM sizes its grid from concrete "
                "occupancy and cannot run under jit; use worklist=False")
        if worklist:
            if plan.occupancy is not None and plan.tile_m == tile_m \
                    and not isinstance(plan.occupancy, jax.core.Tracer):
                occ_np = np.asarray(plan.occupancy[s][:, a:b]) != 0
            else:
                occ_np = np.asarray(occ3)
            wl = _build_worklist(occ_np)
            if wl is None:
                continue                      # empty split contributes zero
            wl_tile, wl_delta, wl_flags, visited = wl
            partial = implicit_gemm_worklist_pallas(
                jnp.asarray(wl_tile), jnp.asarray(wl_delta),
                jnp.asarray(wl_flags),
                midx.reshape(n_tiles, tile_m, b - a)[wl_tile, :, wl_delta],
                x, w[a:b], n_tiles_m=n_tiles, tile_m=tile_m, tile_n=tile_n,
                interpret=interpret)
            # tiles with no entries were never scheduled: their output
            # blocks are uninitialized — zero them (they have no neighbors
            # in this split, so zero IS their partial sum)
            row_ok = jnp.asarray(np.repeat(visited, tile_m))
            partial = jnp.where(row_ok[:, None], partial, 0)
        else:
            partial = implicit_gemm_pallas(midx, occ3.astype(jnp.int32), x,
                                           w[a:b], tile_m=tile_m,
                                           tile_n=tile_n, interpret=interpret)
        out = out + partial[plan.inv_order[s]]
    return out
