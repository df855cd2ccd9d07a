"""Capacity-padded sparse tensors.

The paper's workloads are point clouds with *dynamic* point counts.  JAX traces
static shapes, so every sparse tensor in this framework carries a static
capacity ``Nmax`` plus the number of valid rows.  Invalid rows hold the
sentinel coordinate ``INVALID_COORD`` which never matches a hash query, so all
kernel-map machinery is oblivious to padding.  This is the static-shape
analogue of the paper's dynamic-shape kernels (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# Sentinel for padded coordinate rows.  Chosen so that shifted/strided variants
# of a padded coordinate also never collide with a real voxel key.  A numpy
# scalar: a jnp one would start a jax backend (and claim a TPU) on import.
INVALID_COORD = np.int32(0x3FFFFFF)


def axis_strides(s, ndim: int = 3) -> tuple:
    """A stride (or kernel shape) as one int per spatial axis: an int is the
    same on every axis, a tuple is already per axis."""
    return tuple(s) if isinstance(s, tuple) else (s,) * ndim


def stride_mul(a, b):
    """Per-axis product of two strides; an int when it is the same on every
    axis, so each stride has one spelling (a dict key, a static field)."""
    if isinstance(a, int) and isinstance(b, int):
        return a * b
    s = tuple(x * y for x, y in zip(axis_strides(a), axis_strides(b)))
    return s[0] if len(set(s)) == 1 else s


def stride_name(s) -> str:
    """A stride as it appears in scope names: ``4``, or ``8x8x4`` when the
    axes differ."""
    s = axis_strides(s)
    return str(s[0]) if len(set(s)) == 1 else "x".join(map(str, s))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseTensor:
    """A batched, quantized point cloud (or any D-dim sparse feature map).

    coords: (Nmax, 1 + D) int32 — [batch, x, y, z, ...]; padded rows are
        INVALID_COORD in every spatial column.
    feats:  (Nmax, C) — feature rows; padded rows are zero.
    num_valid: () int32 — number of real rows.
    stride: static int, or a tuple of one int per spatial axis — the tensor
        stride (grows by conv stride; ``(16, 16, 4)`` after strides that
        leave an axis alone).
    batch_bound: static int — declared number of batches (0 = unknown).
    spatial_bound: static int — declared max |spatial coordinate| (0 =
        unknown).  The packed-key mapping engine (core/hashing.py) derives
        its key bit budget from these; declaring them lets every voxel key
        fit one int32 word so kernel-map construction is a single argsort.
    """

    coords: jax.Array
    feats: jax.Array
    num_valid: jax.Array
    stride: int = dataclasses.field(metadata=dict(static=True), default=1)
    batch_bound: int = dataclasses.field(metadata=dict(static=True), default=0)
    spatial_bound: int = dataclasses.field(metadata=dict(static=True), default=0)

    @property
    def capacity(self) -> int:
        return self.coords.shape[0]

    @property
    def ndim_space(self) -> int:
        return self.coords.shape[1] - 1

    @property
    def num_channels(self) -> int:
        return self.feats.shape[1]

    @property
    def valid_mask(self) -> jax.Array:
        return jnp.arange(self.capacity) < self.num_valid

    def replace_feats(self, feats: jax.Array) -> "SparseTensor":
        return dataclasses.replace(self, feats=feats)


def make_sparse_tensor(coords: jax.Array, feats: jax.Array, num_valid, stride: int = 1,
                       batch_bound: int = 0, spatial_bound: int = 0) -> SparseTensor:
    """Build a SparseTensor, forcing padded rows to sentinel/zero.

    Declared bounds are a caller promise (|spatial coord| ≤ spatial_bound,
    0 ≤ batch < batch_bound); coordinates violating them pack to the PAD key
    and drop out of kernel maps.  ``voxelize`` enforces the promise by
    clamping; here the coords are taken as-is.
    """
    n = coords.shape[0]
    mask = jnp.arange(n) < num_valid
    coords = jnp.where(mask[:, None], coords.astype(jnp.int32), INVALID_COORD)
    feats = jnp.where(mask[:, None], feats, 0)
    return SparseTensor(coords=coords, feats=feats, num_valid=jnp.asarray(num_valid, jnp.int32),
                        stride=stride, batch_bound=batch_bound, spatial_bound=spatial_bound)


@partial(jax.jit, static_argnames=("capacity", "batch_size", "spatial_bound"))
def voxelize(points: jax.Array, feats: jax.Array, voxel_size: float, capacity: int,
             batch_idx: Optional[jax.Array] = None, batch_size: int = 1,
             spatial_bound: int = 0) -> SparseTensor:
    """Quantize raw points to voxel coordinates and deduplicate.

    points: (N, D) float — raw coordinates.
    feats:  (N, C) — per-point features (first point in each voxel wins; the
        paper keeps one point per voxel, matching CenterPoint preprocessing).
    Returns a SparseTensor with static ``capacity`` rows.
    """
    n, d = points.shape
    if batch_idx is None:
        batch_idx = jnp.zeros((n,), jnp.int32)
    q = jnp.floor(points / voxel_size).astype(jnp.int32)
    if spatial_bound > 0:
        # A declared bound is a promise the mapping engine packs keys by;
        # enforce it here (range cap, as real LiDAR pipelines do) so stray
        # points clamp to the boundary voxel instead of silently vanishing
        # from every kernel map.
        q = jnp.clip(q, -spatial_bound, spatial_bound)
    coords = jnp.concatenate([batch_idx[:, None].astype(jnp.int32), q], axis=1)
    #

    # Deduplicate via lexicographic sort; first occurrence wins.
    from repro.core import hashing

    order = hashing.lex_argsort(coords)
    coords_sorted = coords[order]
    same_as_prev = hashing.rows_equal(coords_sorted[1:], coords_sorted[:-1])
    is_first = jnp.concatenate([jnp.ones((1,), bool), ~same_as_prev])
    # Stable compaction of the first-occurrence rows.
    dest = jnp.cumsum(is_first) - 1
    dest = jnp.where(is_first, dest, capacity)  # drop dups past the end
    out_coords = jnp.full((capacity + 1, d + 1), INVALID_COORD, jnp.int32)
    out_feats = jnp.zeros((capacity + 1, feats.shape[1]), feats.dtype)
    out_coords = out_coords.at[dest].set(coords[order], mode="drop")
    out_feats = out_feats.at[dest].set(feats[order], mode="drop")
    num = jnp.minimum(jnp.sum(is_first), capacity)
    return SparseTensor(coords=out_coords[:capacity], feats=out_feats[:capacity],
                        num_valid=num.astype(jnp.int32), stride=1,
                        batch_bound=batch_size, spatial_bound=spatial_bound)


def to_dense(st: SparseTensor, grid: tuple, batch_size: int) -> jax.Array:
    """Scatter a SparseTensor to a dense (B, *grid, C) array (test oracle)."""
    d = st.ndim_space
    assert len(grid) == d
    mask = st.valid_mask
    idx = [jnp.where(mask, st.coords[:, 0], batch_size)]  # OOB batch drops row
    for i in range(d):
        c = st.coords[:, 1 + i] // st.stride
        idx.append(jnp.where(mask & (c >= 0) & (c < grid[i]), c, grid[i]))
    dense = jnp.zeros((batch_size + 1,) + tuple(g + 1 for g in grid) + (st.num_channels,), st.feats.dtype)
    dense = dense.at[tuple(idx)].add(st.feats, mode="drop")
    slicer = (slice(0, batch_size),) + tuple(slice(0, g) for g in grid) + (slice(None),)
    return dense[slicer]
