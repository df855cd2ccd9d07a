"""Plain reference of Cylinder3D's sparse backbone (Zhu et al., CVPR 2021,
arXiv 2011.10033; ``Asymm_3d_spconv`` of github.com/xinge008/Cylinder3D),
one scene at a time.

With ``act`` LeakyReLU(0.01), ``BN`` the inference affine, ``c_k`` a
submanifold conv of kernel shape ``k`` (axes 0, 1, 2):

* context: ``BN(act(c313(BN(act(c133 x))))) + BN(act(c133(BN(act(c313 x)))))``;
* residual block: the same with the branches' kernels swapped, its sum
  kept as the skip, then a pool: a K=3 strided conv (spconv
  ``SparseConv3d``, padding 1, no BN or act), stride (2, 2, 2) twice, then
  (2, 2, 1).  Its outputs are every multiple of the output stride (per
  axis) within one input stride of an input voxel — a dilation, not a
  floor grid.  Levels are keyed 1, 2, 4, 8, 16 (the x stride; z stops at 4);
* up block: ``BN(act(c333 x))``, the inverse conv of the matching pool
  (each fine voxel ``i`` reads the coarse cell ``i - δ·t`` through
  ``W[δ]``), plus the skip, then c133, c313 and c333, each act then BN;
* gate: ``g = σ(BN(c311 u)) + σ(BN(c131 u)) + σ(BN(c113 u))``, then
  ``[g ⊙ u, u]``; logits: c333 with a bias, of every input voxel.

Kernel offsets of every shape are in product order stably sorted by L1
norm, as the parameters are laid out.  Every map (the asymmetric ones
too) comes from this module's own search on sorted int64 keys; pools and
inverse maps from a brute-force dilation.

Departures from the published network, each also in the configuration's
``assumed``/``reduced``:

* Cartesian voxels (axes 0/1/2 are x/y/z), not the cylindrical
  (ρ, φ, z) partition of 480 x 360 x 32;
* no point feature generator (point MLP, scatter-max, compression): each
  voxel brings 16 features;
* no final ``.dense()`` scatter: logits per voxel;
* the logits bias is the layer's batch-norm affine (a per-channel scale
  times a conv is still a conv);
* the pooling lattice is multiples of the output stride in scene
  coordinates, with no grid edge;
* each conv runs on its own kernel shape (spconv lets convs that share an
  ``indice_key`` reuse the first one's pairs);
* batch norm in inference mode; dropout, off at inference, is left out.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as R
from chipbench.scenes import pack, unpack

#: level id -> tensor stride per axis, and the pool that leaves each level
STRIDES = {1: (1, 1, 1), 2: (2, 2, 2), 4: (4, 4, 4), 8: (8, 8, 4),
           16: (16, 16, 4)}
POOLS = {1: (2, 2, 2), 2: (2, 2, 2), 4: (2, 2, 1), 8: (2, 2, 1)}
SHAPES = {"sub": (3, 3, 3), "sub133": (1, 3, 3), "sub313": (3, 1, 3),
          "sub311": (3, 1, 1), "sub131": (1, 3, 1), "sub113": (1, 1, 3),
          "down": (3, 3, 3), "up": (3, 3, 3)}


def out_stride(model: dict) -> int:
    return 1


def offsets(shape) -> np.ndarray:
    """Offsets of a (kx, ky, kz) kernel, centred, in the parameters' order."""
    offs = np.array(list(itertools.product(
        *[range(-(k // 2), k // 2 + 1) for k in shape])), np.int32)
    return offs[np.argsort(np.abs(offs).sum(1), kind="stable")]


def search(out_coords, in_coords, shape, scale) -> np.ndarray:
    """(n_out, volume): the input row at ``o + δ·scale`` (per axis), or -1."""
    keys = pack(in_coords)
    order = np.argsort(keys, kind="stable")
    d = offsets(shape) * np.asarray(scale)
    q = pack((out_coords[:, None, :] + d[None]).reshape(-1, 3))
    pos = R.lookup(keys[order], q).reshape(len(out_coords), len(d))
    return np.where(pos >= 0, order[np.maximum(pos, 0)], -1).astype(np.int32)


def dilate(coords, t, s) -> np.ndarray:
    """Cells ``c - δ·t`` (δ in {-1, 0, 1}^3) that are multiples of ``t·s``
    on every axis, distinct, in lexicographic order."""
    t, ts = np.asarray(t), np.asarray(t) * np.asarray(s)
    cand = (coords[:, None, :] - offsets((3, 3, 3))[None] * t).reshape(-1, 3)
    cand = cand[(cand % ts == 0).all(1)]
    return unpack(np.unique(pack(cand)))


def layers(model: dict) -> list:
    """(name, map, Cin, Cout, kernel volume) of every conv layer in order."""
    c = model["init_size"]
    w = [c, 2 * c, 4 * c, 8 * c, 16 * c]
    out = []

    def add(name, kind, lvl, cin, cout):
        out.append((name, (kind, lvl), cin, cout,
                    int(np.prod(SHAPES[kind]))))

    def two_branch(prefix, lvl, cin, cout, first, second):
        add(f"{prefix}_s1", first, lvl, cin, cout)
        add(f"{prefix}_s2", second, lvl, cout, cout)
        add(f"{prefix}_r1", second, lvl, cin, cout)
        add(f"{prefix}_r2", first, lvl, cout, cout)

    two_branch("ctx", 1, model["in_channels"], c, "sub133", "sub313")
    for i, lvl in enumerate((1, 2, 4, 8)):
        two_branch(f"enc{i + 1}", lvl, w[i], w[i + 1], "sub313", "sub133")
        add(f"enc{i + 1}_pool", "down", lvl, w[i + 1], w[i + 1])
    cin = w[4]
    for j, lvl in enumerate((16, 8, 4, 2)):
        cout = w[4 - j]
        add(f"up{j}_trans", "sub", lvl, cin, cout)
        add(f"up{j}_inv", "up", lvl // 2, cout, cout)
        add(f"up{j}_c1", "sub133", lvl // 2, cout, cout)
        add(f"up{j}_c2", "sub313", lvl // 2, cout, cout)
        add(f"up{j}_c3", "sub", lvl // 2, cout, cout)
        cin = cout
    for k, kind in enumerate(("sub311", "sub131", "sub113")):
        add(f"recon_{k + 1}", kind, 1, w[1], w[1])
    add("logits", "sub", 1, 2 * w[1], model["num_classes"])
    return out


class Pyramid:
    """One scene's voxels at every level and every map the network runs."""

    def __init__(self, coords: np.ndarray, model: dict):
        self.coords = {1: coords}
        for lvl, p in POOLS.items():
            self.coords[2 * lvl] = dilate(self.coords[lvl], STRIDES[lvl], p)
        self.maps: dict = {}
        for _, (kind, lvl), *_ in layers(model):
            if (kind, lvl) in self.maps:
                continue
            if kind == "down":
                m = search(self.coords[2 * lvl], self.coords[lvl],
                           SHAPES[kind], STRIDES[lvl])
            elif kind == "up":
                m = search(self.coords[lvl], self.coords[2 * lvl],
                           SHAPES[kind], -np.asarray(STRIDES[lvl]))
            else:
                m = search(self.coords[lvl], self.coords[lvl], SHAPES[kind],
                           STRIDES[lvl])
            self.maps[(kind, lvl)] = m

    def pairs(self, ref) -> int:
        return int((self.maps[ref] >= 0).sum())

    def padded(self, cap: int) -> dict:
        """Device arrays of every map (a missing neighbour points at row
        ``cap``, a zero row) and each level's voxel count."""
        out = {}
        for ref, m in self.maps.items():
            a = np.full((cap, m.shape[1]), cap, np.int32)
            a[:len(m)] = np.where(m >= 0, m, cap)
            out[ref] = jnp.asarray(a)
        return {"maps": out, "n": {s: jnp.asarray(len(c), jnp.int32)
                                   for s, c in self.coords.items()}}


def pyramid(coords, model: dict) -> Pyramid:
    return Pyramid(coords, model)


def _leaky(y):
    return jnp.where(y >= 0, y, 0.01 * y)


def forward(params, feats, maps, model: dict, mode: str, cap: int):
    """Logits of every voxel, rows in the scene's own order (padded to
    ``cap``).  ``maps`` is ``Pyramid.padded(cap)``."""
    n, maps = maps["n"], maps["maps"]
    spec = {name: ref for name, ref, *_ in layers(model)}

    def conv(x, name):
        kind, lvl = spec[name]
        y = R.conv(x, params[name]["w"], maps[(kind, lvl)], mode)
        return y, R.valid(n[2 * lvl if kind == "down" else lvl], cap)

    def affine(y, name):
        p = params[f"{name}_bn"]
        return y * p["scale"] + p["bias"]

    def block(x, name):          # conv, act, then BN
        y, mask = conv(x, name)
        return R.store(jnp.where(mask, affine(_leaky(y), name), 0), mode)

    def plain(x, name):          # pools and inverse convs: no BN, no act
        y, mask = conv(x, name)
        return R.store(jnp.where(mask, y, 0), mode)

    def two_branch(x, prefix):
        s = block(block(x, f"{prefix}_s1"), f"{prefix}_s2")
        r = block(block(x, f"{prefix}_r1"), f"{prefix}_r2")
        return R.store(s + r, mode)

    x = two_branch(R.store(feats, mode), "ctx")
    skips = []
    for i in range(1, 5):
        a = two_branch(x, f"enc{i}")
        skips.append(a)
        x = plain(a, f"enc{i}_pool")
    for j in range(4):
        u = plain(block(x, f"up{j}_trans"), f"up{j}_inv")
        u = R.store(u + skips.pop(), mode)
        for k in (1, 2, 3):
            u = block(u, f"up{j}_c{k}")
        x = u
    gate = None
    for k in (1, 2, 3):
        y, mask = conv(x, f"recon_{k}")
        g = R.store(jnp.where(mask, jax.nn.sigmoid(affine(y, f"recon_{k}")),
                              0), mode)
        gate = g if gate is None else R.store(gate + g, mode)
    x = jnp.concatenate([R.store(gate * x, mode), x], axis=1)
    y, mask = conv(x, "logits")
    return jnp.where(mask, affine(y, "logits"), 0)
