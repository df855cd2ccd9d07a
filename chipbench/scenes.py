"""Synthetic LiDAR scenes and stream deltas, in numpy, from a seed.

The same statistics as the program's ``data.synthetic.lidar_scene`` (half
the points on a noisy ground plane, half in 32 Gaussian clusters, clipped
to the declared range, voxelized with the first point of a voxel winning),
drawn to an exact voxel count, and host-only: no jitted voxelizer, so no
scene size costs a compile.  Voxel rows come out in lexicographic
(x, y, z) order, as the program's voxelizer emits them.
"""
from __future__ import annotations

import dataclasses

import numpy as np

#: voxel keys pack three coordinates into one int64 (21 bits each, biased)
_BITS = 21
_BIAS = 1 << (_BITS - 1)


def pack(coords: np.ndarray) -> np.ndarray:
    """(n, 3) int coordinates -> (n,) int64 keys ordered as the rows'
    lexicographic (x, y, z) order."""
    c = coords.astype(np.int64) + _BIAS
    return (c[:, 0] << (2 * _BITS)) | (c[:, 1] << _BITS) | c[:, 2]


def unpack(keys: np.ndarray) -> np.ndarray:
    mask = (1 << _BITS) - 1
    return (np.stack([keys >> (2 * _BITS), (keys >> _BITS) & mask,
                      keys & mask], axis=1) - _BIAS).astype(np.int32)


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Where a configuration's scenes live: the sensor's range and voxel."""

    voxel: float
    extent: float
    margin: float
    clusters: int
    channels: int

    @property
    def spatial_bound(self) -> int:
        return int(np.ceil((self.extent + self.margin) / self.voxel)) + 2

    @staticmethod
    def of(config: dict) -> "Geometry":
        return Geometry(voxel=config["voxel_size"], extent=config["extent"],
                        margin=config["margin"],
                        clusters=config["clusters"],
                        channels=config["model"]["in_channels"])


def _points(rng: np.random.Generator, n: int, geo: Geometry,
            centers: np.ndarray) -> np.ndarray:
    n_ground = n // 2
    ground = np.stack([rng.uniform(0, geo.extent, n_ground),
                       rng.uniform(0, geo.extent, n_ground),
                       rng.normal(1.0, 0.2, n_ground)], axis=1)
    n_obj = n - n_ground
    objs = (centers[rng.integers(0, len(centers), n_obj)]
            + rng.normal(size=(n_obj, 3)) * np.array([1.5, 1.5, 0.8]))
    pts = np.concatenate([ground, objs])
    return np.clip(pts, -geo.margin, geo.extent + geo.margin)


def _voxelize(pts: np.ndarray, geo: Geometry):
    """Unique voxel keys (sorted) and, per voxel, the index of its first
    point."""
    b = geo.spatial_bound
    q = np.clip(np.floor(pts / geo.voxel), -b, b).astype(np.int32)
    keys, first = np.unique(pack(q), return_index=True)
    return keys, first


@dataclasses.dataclass
class SceneData:
    """One request as the harness holds it: voxel coords and features, and
    the cluster centres it was drawn around (deltas draw from them)."""

    coords: np.ndarray   # (n, 3) int32, lexicographic order
    feats: np.ndarray    # (n, C) float32
    centers: np.ndarray  # (clusters, 3)

    @property
    def num_points(self) -> int:
        return self.coords.shape[0]


def scene(seed: int, index: int, n_voxels: int, geo: Geometry) -> SceneData:
    """Scene ``index`` of the stream drawn from ``seed``, with exactly
    ``n_voxels`` voxels: points are drawn until that many distinct voxels
    are hit, and the voxels hit first are kept, so every seed asks the
    same rows of each size."""
    rng = np.random.default_rng([seed, index])
    centers = rng.uniform(size=(geo.clusters, 3)) * np.array(
        [geo.extent, geo.extent, 4.0])
    pts = _points(rng, n_voxels, geo, centers)
    keys, first = _voxelize(pts, geo)
    while keys.size < n_voxels:
        pts = np.concatenate([pts, _points(rng, len(pts), geo, centers)])
        keys, first = _voxelize(pts, geo)
    kept = np.sort(np.argsort(first, kind="stable")[:n_voxels])
    feats = rng.normal(size=(n_voxels, geo.channels)).astype(np.float32)
    return SceneData(coords=unpack(keys[kept]), feats=feats, centers=centers)


def moved(s: SceneData, offset: np.ndarray) -> SceneData:
    """``s`` moved by ``offset`` voxels; the rows keep their order."""
    return SceneData(coords=s.coords + offset.astype(np.int32),
                     feats=s.feats, centers=s.centers)


@dataclasses.dataclass
class Delta:
    """A frame update: evict ``removed`` voxels, append the added rows."""

    removed: np.ndarray       # (r, 3) int32, present in the previous frame
    added_coords: np.ndarray  # (a, 3) int32, absent from it
    added_feats: np.ndarray   # (a, C) float32


def delta(rng: np.random.Generator, prev: SceneData, share: float,
          geo: Geometry) -> Delta:
    """Replace ``share`` of ``prev``'s voxels: evict that many at random and
    add as many fresh voxels drawn from the scene's own distribution
    (objects moving through a static background)."""
    n = prev.num_points
    r = max(1, int(round(share * n)))
    removed = prev.coords[np.sort(rng.choice(n, size=r, replace=False))]
    taken = pack(prev.coords)
    added = np.empty((0,), np.int64)
    while added.size < r:
        keys, _ = _voxelize(_points(rng, 4 * r, geo, prev.centers), geo)
        keys = keys[~np.isin(keys, taken) & ~np.isin(keys, added)]
        added = np.concatenate([added, rng.permutation(keys)[:r - added.size]])
    feats = rng.normal(size=(r, geo.channels)).astype(np.float32)
    return Delta(removed=removed, added_coords=unpack(added),
                 added_feats=feats)


def apply(prev: SceneData, d: Delta) -> SceneData:
    """The next frame: ``prev``'s rows minus the evicted ones (order kept),
    then the added rows."""
    keep = ~np.isin(pack(prev.coords), pack(d.removed))
    if prev.num_points - keep.sum() != d.removed.shape[0]:
        raise ValueError("delta evicts a voxel that is not in the scene")
    return SceneData(coords=np.concatenate([prev.coords[keep], d.added_coords]),
                     feats=np.concatenate([prev.feats[keep], d.added_feats]),
                     centers=prev.centers)
