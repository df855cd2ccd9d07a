"""Jit'd wrapper for the fetch-on-demand kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.kmap import KernelMap
from repro.kernels.common import default_interpret
from repro.kernels.fetch_on_demand.fetch_on_demand import fetch_on_demand_pallas


def fetch_on_demand(x: jax.Array, w: jax.Array, kmap: KernelMap, *,
                    tile_r: int = 128, interpret: bool | None = None) -> jax.Array:
    """Full sparse conv via the fused fetch-on-demand dataflow."""
    if interpret is None:
        interpret = default_interpret()
    kd, cap = kmap.ws_in.shape
    pad = (-cap) % tile_r
    ws_in = jnp.pad(kmap.ws_in, ((0, 0), (0, pad)), constant_values=-1)
    ws_out = jnp.pad(kmap.ws_out, ((0, 0), (0, pad)), constant_values=-1)
    return fetch_on_demand_pallas(ws_in, ws_out, x, w, n_out=kmap.capacity,
                                  tile_r=tile_r, interpret=interpret)
