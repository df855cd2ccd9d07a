"""Cylinder3D's sparse backbone (Zhu et al., "Cylindrical and Asymmetrical
3D Convolution Networks for LiDAR Segmentation", CVPR 2021;
``network/segmentator_3d_asymm_spconv.py`` ``Asymm_3d_spconv``) on the
sparse-conv engine.

Every block pairs asymmetric kernels — (1, 3, 3) and (3, 1, 3) convs, each
followed by LeakyReLU and then batch norm — in two branches summed:

* a context block (16 → ``init_size``) at the input voxels;
* four residual blocks, each pooled by a K=3 strided conv whose outputs are
  the *dilation* of its inputs (spconv ``SparseConv3d``, padding 1),
  stride (2, 2, 2) twice, then (2, 2, 1), widths up to 16·``init_size``;
* four up blocks: a 3x3x3 conv, the inverse conv of the matching pool
  (its transposed map) back to the skip's voxels, the skip *added*, then
  (1, 3, 3), (3, 1, 3) and 3x3x3 convs;
* a gate: the sum of sigmoids of batch-normed (3, 1, 1), (1, 3, 1) and
  (1, 1, 3) convs times its input, concatenated with that input;
* logits: a 3x3x3 conv with a bias (the layer's batch-norm affine).

The asymmetric kernels run on column subsets of each level's one 3x3x3
submanifold map (``KmapSpec`` kind "slice"); levels are keyed by their x
stride (1, 2, 4, 8, 16), the z stride stopping at 4.  Output: logits of
every input voxel, as MinkUNet's.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax

from repro.core import plan as planlib
from repro.core.kmap import MapCache
from repro.core.plan import (KmapSpec, LayerPlan, ModelDecl, NetworkPlan,
                             compile_plan)
from repro.core.sparse_conv import ConvSpec, TrainDataflowConfig, init_conv
from repro.core.sparse_tensor import SparseTensor, stride_mul

#: the pools' strides, level by level (z is pooled twice only)
POOLS = (2, 2, (2, 2, 1), (2, 2, 1))

#: asymmetric kernel shapes by name, cut from the 3x3x3 map of their level
SHAPES = {"133": (1, 3, 3), "313": (3, 1, 3), "311": (3, 1, 1),
          "131": (1, 3, 1), "113": (1, 1, 3)}


@dataclasses.dataclass(frozen=True)
class Cylinder3DConfig:
    in_channels: int = 16
    num_classes: int = 20
    init_size: int = 32


def _levels():
    """(level id, tensor stride) of the input and every pool's output."""
    out, t = [(1, 1)], 1
    for p in POOLS:
        t = stride_mul(t, p)
        out.append((2 * out[-1][0], t))
    return out


def declare(cfg: Cylinder3DConfig) -> ModelDecl:
    """Layer list + execution program + kernel-map program (see core.plan)."""
    layers, ops = [], []

    def conv(name, cin, cout, level, shape="333", **kw):
        kind = "sub" if shape == "333" else f"sub{shape}"
        layers.append(LayerPlan(
            name, ConvSpec(cin, cout, SHAPES.get(shape, 3)), (kind, level),
            (level, 3, kind), act=kw.pop("act", "leaky_relu"),
            act_first=kw.pop("act_first", True), **kw))
        ops.append(("conv", name))

    def two_branch(prefix, cin, cout, level, first, second):
        # s = second(first(x)), r = first(second(x)); out = s + r
        ops.append(("save", f"{prefix}_in"))
        conv(f"{prefix}_s1", cin, cout, level, first)
        conv(f"{prefix}_s2", cout, cout, level, second)
        ops.extend([("save", f"{prefix}_s"), ("load", f"{prefix}_in")])
        conv(f"{prefix}_r1", cin, cout, level, second)
        conv(f"{prefix}_r2", cout, cout, level, first)
        ops.append(("add", f"{prefix}_s"))

    c = cfg.init_size
    two_branch("ctx", cfg.in_channels, c, 1, "133", "313")
    widths = [c, 2 * c, 4 * c, 8 * c, 16 * c]
    levels = _levels()
    for i, p in enumerate(POOLS):
        lvl = levels[i][0]
        two_branch(f"enc{i + 1}", widths[i], widths[i + 1], lvl, "313", "133")
        ops.append(("save", f"skip{i + 1}"))
        layers.append(LayerPlan(
            f"enc{i + 1}_pool", ConvSpec(widths[i + 1], widths[i + 1], 3,
                                         stride=p),
            ("down", lvl), (lvl, 3, "down"), bn=False, act="none"))
        ops.append(("conv", f"enc{i + 1}_pool"))
    for j in range(len(POOLS)):
        i = len(POOLS) - 1 - j            # up block j undoes pool i
        lvl, fine = levels[i + 1][0], levels[i][0]
        cin, cout = widths[i + 2] if j else widths[-1], widths[i + 1]
        conv(f"up{j}_trans", cin, cout, lvl)
        layers.append(LayerPlan(
            f"up{j}_inv", ConvSpec(cout, cout, 3, stride=POOLS[i],
                                   transposed=True),
            ("up", fine), (fine, 3, "up"), bn=False, act="none"))
        ops.extend([("conv", f"up{j}_inv"), ("add", f"skip{i + 1}")])
        conv(f"up{j}_c1", cout, cout, fine, "133")
        conv(f"up{j}_c2", cout, cout, fine, "313")
        conv(f"up{j}_c3", cout, cout, fine)
    # gate: g = σ(BN(c311 x)) + σ(BN(c131 x)) + σ(BN(c113 x)); [g ⊙ x, x]
    g = widths[1]
    ops.append(("save", "u1"))
    for k, shape in enumerate(("311", "131", "113")):
        if k:
            ops.append(("load", "u1"))
        conv(f"recon_{k + 1}", g, g, 1, shape, act="sigmoid",
             act_first=False)
        if k:
            ops.append(("add", "gate"))
        ops.append(("save", "gate"))
    ops.extend([("mul", "u1"), ("concat", "u1")])
    conv("logits", 2 * g, cfg.num_classes, 1, act="none", act_first=False)
    return ModelDecl(arch="cylinder3d", layers=tuple(layers), ops=tuple(ops),
                     map_specs=map_specs("composed"))


def map_specs(table: str = "sort") -> tuple:
    """A 3x3x3 submanifold map per level with its asymmetric column
    subsets, a dilated K=3 map per pool, and each pool's transposed map."""
    levels = _levels()
    used = {1: ("133", "313", "311", "131", "113")}
    used.update({lvl: ("133", "313") for lvl, _ in levels[1:-1]})

    def level(lvl, t):
        out = [KmapSpec(("sub", lvl), "sub", 3, 1, t, table=table)]
        out += [KmapSpec((f"sub{k}", lvl), "slice", SHAPES[k], 1, t,
                         table=table, slice_of=("sub", lvl))
                for k in used.get(lvl, ())]
        return out

    specs = level(*levels[0])
    for (lvl, t), p, nxt in zip(levels, POOLS, levels[1:]):
        specs.append(KmapSpec(("down", lvl), "down", 3, p, t,
                              adopts_output_table=True, table=table,
                              dilate=True))
        specs += level(*nxt)
    for (lvl, t), p in reversed(list(zip(levels, POOLS))):
        specs.append(KmapSpec(("up", lvl), "up", 3, p, t,
                              transpose_of=("down", lvl), table=table))
    return tuple(specs)


def init_params(cfg: Cylinder3DConfig, key) -> dict:
    decl = declare(cfg)
    keys = jax.random.split(key, len(decl.layers))
    p: dict = {}
    for k, lp in zip(keys, decl.layers):
        p[lp.name] = init_conv(k, lp.spec)
        if lp.bn:
            p[f"{lp.name}_bn"] = planlib.bn_relu_init(lp.spec.out_channels)
    return p


def network_plan(cfg: Cylinder3DConfig,
                 assignment: Optional[Dict[tuple, TrainDataflowConfig]] = None,
                 precision=None) -> NetworkPlan:
    """Compile the execution plan: declare → compile (→ tune → persist)."""
    return compile_plan(declare(cfg), assignment=assignment,
                        precision=precision)


def layer_signatures(cfg: Cylinder3DConfig) -> Dict[str, tuple]:
    return {lp.name: lp.sig for lp in declare(cfg).layers}


def build_maps(st: SparseTensor, cache: Optional[MapCache] = None,
               tables: Optional[dict] = None) -> dict:
    """Every kernel map of the network in one map program; a dilated level
    that overflows its capacity reads ``n_out`` above it
    (``kmap.check_capacity`` raises on that)."""
    return planlib.build_maps_from_specs(map_specs(), st, cache,
                                         tables=tables)


def apply(params, st: SparseTensor, cfg: Cylinder3DConfig,
          maps: Optional[dict] = None,
          assignment: Optional[Dict[tuple, TrainDataflowConfig]] = None,
          bn_mode: str = "batch",
          nplan: Optional[NetworkPlan] = None,
          precision=None) -> jax.Array:
    """Per-voxel class logits (capacity, num_classes)."""
    if nplan is None:
        nplan = network_plan(cfg, assignment=assignment, precision=precision)
    return nplan.apply(params, st, maps, bn_mode=bn_mode)
