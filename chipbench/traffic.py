"""The one traffic generator: turns a mix's parameters and a seed into the
requests of a run.

A mix is a data file (``traffic/<mix>.json``), optionally overridden by
the cell's own file (``workloads/<cell>.json``).  Keys:

* ``loop``: ``"closed"`` (a backlog of ``backlog`` scenes is queued before
  every flush) or ``"open"`` (arrivals on a fixed schedule at ``rate``
  scenes per second);
* ``pool``: distinct fresh scenes made for a closed loop, a multiple of
  ``backlog``; once every one has been sent, the pool is sent again, each
  scene moved by a multiple of ``ALIGN`` voxels (``shifts``), so no
  request repeats another and a run never runs out of scenes;
* ``streams``: 0 for fresh scenes, else that many sensor streams, each
  frame resubmitting every stream and replacing ``churn_points`` of the
  voxels of ``churn_streams`` of them (``frames`` frames are made);
* ``serving``: engine knobs layered over the configuration's.

A scene's size is its exact voxel count, from the configuration's
``voxels_per_scene`` range.  Every seed gets the same work in another
order: each flush of a closed loop sends one permutation of the same grid
of ``backlog`` sizes; an open loop's sizes are one permutation of a grid
as long as its arrivals, and its gaps the quantiles
of an exponential distribution, shuffled, so a run offers exactly
``round(rate * seconds)`` arrivals whatever the seed.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from chipbench import scenes

#: a pool scene is moved by multiples of this many voxels on each axis,
#: the deepest stride of either network, so the moved scene has the same
#: kernel maps, level by level, as the scene it was moved from
ALIGN = 16

#: scene indices of the set-up scenes, apart from any the window sends
WARM_INDEX = 1 << 40


def merged(traffic: dict, cell: Optional[dict]) -> dict:
    """The mix's parameters with the cell's overrides on top (``serving``
    merged key by key)."""
    out = dict(traffic)
    for k, v in (cell or {}).items():
        out[k] = {**out.get(k, {}), **v} if k == "serving" else v
    return out


def size_grid(lo: int, hi: int, n: int) -> np.ndarray:
    return np.round(lo + (hi - lo) * (np.arange(n) + 0.5) / n).astype(int)


def sizes(rng: np.random.Generator, lo: int, hi: int, count: int,
          block: int) -> np.ndarray:
    """``count`` sizes, each ``block`` of them one permutation of the same
    grid of ``block`` sizes."""
    if count % block:
        raise ValueError(f"pool {count} is not a multiple of {block}")
    grid = size_grid(lo, hi, block)
    return np.concatenate([rng.permutation(grid)
                           for _ in range(count // block)])


def shifts(pool: List[scenes.SceneData], bound: int) -> np.ndarray:
    """(m, 3) offsets, multiples of ``ALIGN`` toward the negative end of
    each axis, that keep every voxel of the pool within ``[-bound,
    bound]``; the first is no move at all."""
    low = np.min([s.coords.min(axis=0) for s in pool], axis=0)
    steps = [-ALIGN * np.arange((int(v) + bound) // ALIGN + 1) for v in low]
    return np.stack(np.meshgrid(*steps, indexing="ij"),
                    axis=-1).reshape(-1, 3).astype(np.int32)


@dataclasses.dataclass
class Requests:
    """What a run will send: fresh scenes (with arrival times for an open
    loop), or sensor streams and their per-frame deltas."""

    fresh: List[scenes.SceneData]
    arrivals: Optional[np.ndarray]          # seconds after the window opens
    moves: Optional[np.ndarray]             # closed loop: shifts of the pool
    streams: List[scenes.SceneData]
    frames: List[List[tuple]]   # frames[t][stream] = (delta or None, scene)
    warm: List[List[scenes.SceneData]]      # set-up batches, one flush each
    warm_deltas: List[tuple]                # (scene, delta) set-up frames

    def fresh_at(self, k: int) -> scenes.SceneData:
        """Request ``k`` of a closed loop: pool scene ``k mod pool``, moved
        by the ``k // pool``-th shift."""
        turn, i = divmod(k, len(self.fresh))
        if turn >= len(self.moves):
            raise RuntimeError(f"all {k} moved pool scenes sent before the "
                               "window closed: raise the mix's 'pool'")
        return scenes.moved(self.fresh[i], self.moves[turn])


def build(mix: dict, config: dict, seed: int, seconds: float) -> Requests:
    geo = scenes.Geometry.of(config)
    lo, hi = config["voxels_per_scene"]
    rng = np.random.default_rng([seed, 1])
    # warm-up scenes come from their own stream of the seed: the smallest
    # and largest scene alone, then two of the smallest in one batch, so
    # every rung the mix reaches compiles before the window
    small = scenes.scene(seed, WARM_INDEX, lo, geo)
    large = scenes.scene(seed, WARM_INDEX + 1, hi, geo)
    warm = [[small], [large],
            [small, scenes.scene(seed, WARM_INDEX + 2, lo, geo)]]
    fresh, arrivals, moves, streams, frames, warm_deltas = ([], None, None,
                                                           [], [], [])
    if mix.get("streams", 0):
        warm_deltas = [(s, scenes.delta(rng, s, mix["churn_points"], geo))
                       for s in (small, large)]
        n = mix["streams"]
        streams = [scenes.scene(seed, i, int(k), geo)
                   for i, k in enumerate(size_grid(lo, hi, n))]
        per_frame = max(1, int(round(mix["churn_streams"] * n)))
        cur = list(streams)
        for t in range(1, mix["frames"] + 1):
            churned = {(t * per_frame + j) % n for j in range(per_frame)}
            row = []
            for i in range(n):
                d = None
                if i in churned:
                    d = scenes.delta(rng, cur[i], mix["churn_points"], geo)
                    cur[i] = scenes.apply(cur[i], d)
                row.append((d, cur[i]))
            frames.append(row)
    elif mix["loop"] == "open":
        count = int(round(mix["rate"] * seconds))
        q = (np.arange(count) + 0.5) / count
        gaps = rng.permutation(-np.log1p(-q))
        gaps *= seconds / gaps.sum()
        arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        fresh = [scenes.scene(seed, i, int(k), geo) for i, k in
                 enumerate(rng.permutation(size_grid(lo, hi, count)))]
    else:
        fresh = [scenes.scene(seed, i, int(k), geo) for i, k in
                 enumerate(sizes(rng, lo, hi, mix["pool"], mix["backlog"]))]
        moves = shifts(fresh, geo.spatial_bound)
    return Requests(fresh=fresh, arrivals=arrivals, moves=moves,
                    streams=streams, frames=frames, warm=warm,
                    warm_deltas=warm_deltas)
