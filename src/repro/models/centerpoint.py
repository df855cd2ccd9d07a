"""CenterPoint sparse-conv backbone (SECOND-style 3D detection encoder).

The paper's detection workload (Waymo/nuScenes-CenterPoint).  Only the
SparseConv layers are timed in the paper's detection benchmarks, so this is
the backbone alone: 4 stages of [stride-2 conv + submanifold convs],
channel ladder 16→32→64→128.

Like MinkUNet, the backbone declares its layers (``declare``) and executes
through a compiled ``core.plan.NetworkPlan``; ``apply``/``build_maps``
keep the historical signatures and bit-exact outputs.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax

from repro.core import plan as planlib
from repro.core.kmap import MapCache
from repro.core.plan import (LayerPlan, ModelDecl, NetworkPlan, compile_plan,
                             pyramid_map_specs)
from repro.core.sparse_conv import ConvSpec, TrainDataflowConfig, init_conv
from repro.core.sparse_tensor import SparseTensor
from repro.models.minkunet import _bn_relu_init


@dataclasses.dataclass(frozen=True)
class CenterPointConfig:
    in_channels: int = 5
    channels: tuple = (16, 32, 64, 128)
    sub_convs_per_stage: int = 2
    width: float = 1.0

    def ch(self, c):
        return max(8, int(c * self.width))


def init_params(cfg: CenterPointConfig, key) -> dict:
    keys = iter(jax.random.split(key, 64))
    p = {}
    for lp in declare(cfg).layers:
        p[lp.name] = init_conv(next(keys), lp.spec)
        p[f"{lp.name}_bn"] = _bn_relu_init(lp.spec.out_channels)
    return p


def declare(cfg: CenterPointConfig) -> ModelDecl:
    """Layer list + execution program + kernel-map program (see core.plan)."""
    c0 = cfg.ch(cfg.channels[0])
    layers = [LayerPlan("stem", ConvSpec(cfg.in_channels, c0, 3),
                        ("sub", 1), (1, 3, "sub"))]
    ops = [("conv", "stem")]
    cin, stride = c0, 1
    for i, c in enumerate(cfg.channels):
        c = cfg.ch(c)
        layers.append(LayerPlan(f"down{i}", ConvSpec(cin, c, 2, stride=2),
                                ("down", stride), (stride, 2, "down")))
        ops.append(("conv", f"down{i}"))
        stride *= 2
        for b in range(cfg.sub_convs_per_stage):
            layers.append(LayerPlan(f"sub{i}_{b}", ConvSpec(c, c, 3),
                                    ("sub", stride), (stride, 3, "sub")))
            ops.append(("conv", f"sub{i}_{b}"))
        cin = c
    return ModelDecl(arch="centerpoint", layers=tuple(layers), ops=tuple(ops),
                     map_specs=pyramid_map_specs(len(cfg.channels),
                                                 with_up=False,
                                                 table="composed"))


def network_plan(cfg: CenterPointConfig,
                 assignment: Optional[Dict[tuple, TrainDataflowConfig]] = None,
                 precision=None) -> NetworkPlan:
    """Compile the execution plan: declare → compile (→ tune → persist)."""
    return compile_plan(declare(cfg), assignment=assignment, precision=precision)


def layer_signatures(cfg: CenterPointConfig) -> Dict[str, tuple]:
    return {lp.name: lp.sig for lp in declare(cfg).layers}


def build_maps(st: SparseTensor, cache: Optional[MapCache] = None,
               tables: Optional[dict] = None) -> dict:
    """One ``MapCache`` across the stage ladder: the stem/submanifold and
    strided convs at each stride share a sorted coordinate table, and each
    downsample's declared ``adopts_output_table`` edge seeds the next
    stage's table for free.  A prebuilt warm ``cache`` may be passed
    (serving engine); never reuse one across ``jit`` traces.  ``tables``:
    pre-composed coordinate tables (scene-granular serving reuse; see
    ``plan.build_maps_from_specs``)."""
    return planlib.build_maps_from_specs(pyramid_map_specs(4, with_up=False),
                                         st, cache, tables=tables)


def apply(params, st: SparseTensor, cfg: CenterPointConfig,
          maps: Optional[dict] = None,
          assignment: Optional[Dict[tuple, TrainDataflowConfig]] = None,
          bn_mode: str = "batch",
          nplan: Optional[NetworkPlan] = None,
          precision=None) -> jax.Array:
    if nplan is None:
        nplan = network_plan(cfg, assignment=assignment, precision=precision)
    return nplan.apply(params, st, maps, bn_mode=bn_mode)
