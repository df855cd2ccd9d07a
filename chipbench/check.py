"""The comparison that decides ``correct``.

Four numbers, each against its limit:

* ``refused``: requests of the window whose ``submit``/``submit_delta``
  raised (limit 0): a refused scene costs a closed loop no time, so
  refusing the costly ones would otherwise read as speed;
* ``missing``: requests of the window that never got an answer (limit 0);
* ``wrong_voxels``: answers whose output voxels differ from the ones the
  reference derives from the scene the ticket carried, over every answer
  of the window (limit 0) — a ticket given another scene's rows, or a
  wrong down-sampling, shows here;
* ``rel_err``: over a sample of answers drawn from the seed, the largest
  scene in it, max |served - reference| / max |reference| per scene, the
  widest of them (limit: the configuration's ``limits.rel_err``, set from
  readings of the program and of the lower-precision control, PERF.md);
  with no answer to compare it reads nothing and fails.

The reference is the configuration's plain module (``configs/ref_*.py``)
in float32 at ``highest`` precision, on the same parameters, run after
the window on each sampled scene alone.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as R
from chipbench.scenes import pack

#: answers compared with the reference per run (the largest scene is one)
SAMPLE = 8


def expected_voxels(ref, model: dict, coords: np.ndarray) -> np.ndarray:
    s = ref.out_stride(model)
    return coords if s == 1 else R.downsample(coords, s)


def _order(coords: np.ndarray) -> np.ndarray:
    return np.argsort(pack(coords), kind="stable")


def sample(window, seed: int) -> List[int]:
    """Tickets to compare: the largest scene answered, and the rest drawn
    from the seed."""
    done = sorted(window.results)
    if not done:
        return []
    largest = max(done, key=lambda t: window.requests[t].scene.num_points)
    rest = [t for t in done if t != largest]
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(rest), size=min(SAMPLE - 1, len(rest)),
                      replace=False)
    return [largest] + [rest[i] for i in sorted(pick)]


class Reference:
    """The configuration's reference, jitted once per number format at the
    capacity of the largest rung."""

    def __init__(self, ref, config: dict):
        self.ref, self.model = ref, config["model"]
        self.cap = max(config["serving"]["buckets"])
        self._fwd = jax.jit(lambda p, f, m, mode: ref.forward(
            p, f, m, self.model, mode, self.cap), static_argnums=3)

    def __call__(self, params, scene, mode: str = "f32"):
        """(output voxels, output rows) of one scene, in voxel key order."""
        pyr = self.ref.pyramid(scene.coords, self.model)
        feats = np.zeros((self.cap, scene.feats.shape[1]), np.float32)
        feats[:scene.num_points] = scene.feats
        vox = pyr.coords[self.ref.out_stride(self.model)]
        with jax.default_matmul_precision("highest"):
            out = np.asarray(self._fwd(params, jnp.asarray(feats),
                                       pyr.padded(self.cap), mode))
        o = _order(vox)
        return vox[o], out[:len(vox)][o]


def rel_err(out: np.ndarray, ref: np.ndarray) -> float:
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))


def served(result):
    """(voxels, rows) of one answer, in voxel key order."""
    o = _order(result.coords)
    return result.coords[o], np.asarray(result.feats, np.float32)[o]


def compare(window, reference: Reference, params, seed: int,
            limits: Dict[str, float]) -> Dict[str, dict]:
    """Every number compared, with its value and limit."""
    missing = len(window.requests) - len(
        [t for t in window.requests if t in window.results])
    wrong = 0
    for t, r in window.results.items():
        req = window.requests.get(t)
        if req is None:
            wrong += 1
            continue
        want = expected_voxels(reference.ref, reference.model,
                               req.scene.coords)
        got = served(r)[0]
        if got.shape != want.shape or not (
                got == want[_order(want)]).all():
            wrong += 1
    errs = []
    for t in sample(window, seed):
        vox, ref_rows = reference(params, window.requests[t].scene)
        got_vox, got_rows = served(window.results[t])
        if got_vox.shape == vox.shape and (got_vox == vox).all():
            errs.append(rel_err(got_rows, ref_rows))   # else: wrong_voxels
    return {"refused": {"value": window.submit_errors, "limit": 0},
            "missing": {"value": missing, "limit": 0},
            "wrong_voxels": {"value": wrong, "limit": 0},
            "rel_err": {"value": max(errs) if errs else None,
                        "limit": limits["rel_err"]}}


def correct(checks: Dict[str, dict]) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
