"""MinkUNet (3D semantic segmentation U-Net) on the sparse-conv engine.

The paper's primary segmentation workload (SemanticKITTI-MinkUNet, Fig. 14).
Structure (MinkUNet18-ish, width-scalable): stem → 4 encoder stages
(stride-2 conv + residual submanifold blocks) → 4 decoder stages
(transposed conv reusing the encoder's kernel map + skip concat + blocks).

The model *declares* its layers (``declare`` → ``core.plan.ModelDecl``) and
executes through a compiled ``NetworkPlan``: layer *groups* (paper Fig. 12)
fall out of the declared map-sharing signatures — every submanifold conv at
one stride shares a kernel map; each down/up-sample pair shares the strided
map — and the Sparse Autotuner rebinds the plan's per-group
``TrainDataflowConfig``s.  ``apply``/``build_maps`` keep the pre-plan
call signatures (and bit-exact outputs) for existing callers.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax

from repro.core import plan as planlib
from repro.core.kmap import MapCache
from repro.core.plan import (KmapSpec, LayerPlan, ModelDecl, NetworkPlan,
                             compile_plan, pyramid_map_specs)
from repro.core.sparse_conv import ConvSpec, TrainDataflowConfig, init_conv
from repro.core.sparse_tensor import SparseTensor

# Shared masked-BN init lives with the plan executor; the alias keeps the
# historical name importable (centerpoint).
_bn_relu_init = planlib.bn_relu_init


@dataclasses.dataclass(frozen=True)
class MinkUNetConfig:
    in_channels: int = 4
    num_classes: int = 19
    width: float = 1.0
    enc_channels: tuple = (32, 64, 128, 256)
    dec_channels: tuple = (256, 128, 96, 96)
    blocks_per_stage: int = 2

    def ch(self, c: float) -> int:
        return max(8, int(c * self.width))


def init_params(cfg: MinkUNetConfig, key) -> dict:
    keys = iter(jax.random.split(key, 128))
    p: dict = {}
    for lp in declare(cfg).layers:
        p[lp.name] = init_conv(next(keys), lp.spec)
        p[f"{lp.name}_bn"] = _bn_relu_init(lp.spec.out_channels)
    cin = cfg.ch(cfg.dec_channels[-1])
    p["head"] = {"w": jax.random.normal(next(keys), (cin, cfg.num_classes)) * cin ** -0.5}
    return p


def declare(cfg: MinkUNetConfig) -> ModelDecl:
    """Declare the layer list, execution program and kernel-map program.

    ``compile_plan(declare(cfg))`` is the compiled artifact every consumer
    shares (models, tuner, serving engine, training loop)."""
    w = cfg.ch
    c0 = w(cfg.enc_channels[0])
    layers = [
        LayerPlan("stem1", ConvSpec(cfg.in_channels, c0, 3), ("sub", 1), (1, 3, "sub")),
        LayerPlan("stem2", ConvSpec(c0, c0, 3), ("sub", 1), (1, 3, "sub")),
    ]
    ops = [("conv", "stem1"), ("conv", "stem2"), ("save", "skip0")]

    def res_block(prefix: str, cin_b: int, c: int, sig, ref):
        # ReLU(conv2(conv1(x)) + x); the identity only where widths match
        layers.append(LayerPlan(f"{prefix}_1", ConvSpec(cin_b, c, 3), ref, sig))
        layers.append(LayerPlan(f"{prefix}_2", ConvSpec(c, c, 3), ref, sig,
                                act="none"))
        ops.extend([("save", prefix), ("conv", f"{prefix}_1"),
                    ("conv", f"{prefix}_2")]
                   + ([("add", prefix)] if cin_b == c else [])
                   + [("act", "relu")])

    cin = c0
    stride = 1
    for i, ce in enumerate(cfg.enc_channels):
        ce = w(ce)
        layers.append(LayerPlan(f"down{i}", ConvSpec(cin, ce, 2, stride=2),
                                ("down", stride), (stride, 2, "down")))
        ops.append(("conv", f"down{i}"))
        stride *= 2
        for b in range(cfg.blocks_per_stage):
            res_block(f"enc{i}b{b}", ce, ce, (stride, 3, "sub"), ("sub", stride))
        if i < len(cfg.enc_channels) - 1:
            ops.append(("save", f"skip{i + 1}"))
        cin = ce

    skips = [c0] + [w(c) for c in cfg.enc_channels[:-1]]
    n = len(cfg.dec_channels)
    for i, cd in enumerate(cfg.dec_channels):
        cd = w(cd)
        lvl = n - i - 1            # decoder level i undoes down{lvl}
        s = 2 ** lvl
        layers.append(LayerPlan(f"up{i}", ConvSpec(cin, cd, 2, stride=2, transposed=True),
                                ("up", s), (s, 2, "up")))
        ops.extend([("conv", f"up{i}"), ("concat", f"skip{lvl}")])
        cskip = skips[-(i + 1)]
        for b in range(cfg.blocks_per_stage):
            cin_b = cd + cskip if b == 0 else cd
            res_block(f"dec{i}b{b}", cin_b, cd, (s, 3, "sub"), ("sub", s))
        cin = cd
    ops.append(("head", "head"))

    return ModelDecl(arch="minkunet", layers=tuple(layers), ops=tuple(ops),
                     map_specs=pyramid_map_specs(len(cfg.enc_channels),
                                                 with_up=True,
                                                 table="composed"))


def network_plan(cfg: MinkUNetConfig,
                 assignment: Optional[Dict[tuple, TrainDataflowConfig]] = None,
                 precision=None) -> NetworkPlan:
    """Compile the execution plan: declare → compile (→ tune → persist)."""
    return compile_plan(declare(cfg), assignment=assignment, precision=precision)


def layer_signatures(cfg: MinkUNetConfig) -> Dict[str, tuple]:
    """layer name → map-sharing signature (stride_in, K, kind) for grouping."""
    return {lp.name: lp.sig for lp in declare(cfg).layers}


def build_maps(st: SparseTensor, cache: Optional[MapCache] = None,
               tables: Optional[dict] = None) -> dict:
    """Build every kernel map once (maps are shared within groups) — the
    standard 4-level U-Net map program (``plan.pyramid_map_specs``), with
    the table-adoption edges declared explicitly per ``KmapSpec``.
    ``tables``: pre-composed coordinate tables (scene-granular serving
    reuse; see ``plan.build_maps_from_specs``) — the strided maps then skip
    their unique argsorts and adopt the composed child tables instead."""
    return planlib.build_maps_from_specs(pyramid_map_specs(4, with_up=True),
                                         st, cache, tables=tables)


def apply(params, st: SparseTensor, cfg: MinkUNetConfig,
          maps: Optional[dict] = None,
          assignment: Optional[Dict[tuple, TrainDataflowConfig]] = None,
          bn_mode: str = "batch",
          nplan: Optional[NetworkPlan] = None,
          precision=None) -> jax.Array:
    """Returns per-point class logits (capacity, num_classes).

    Compiles a ``NetworkPlan`` from the declaration (or executes a caller's
    pre-compiled ``nplan``, in which case ``assignment``/``precision`` are
    already baked in) — bit-identical to the historical hand-written
    forward.  ``bn_mode="affine"`` runs inference-mode normalization (see
    ``core.plan.norm_act``) — required by the serving engine so batched and
    per-scene forwards agree bit-for-bit."""
    if nplan is None:
        nplan = network_plan(cfg, assignment=assignment, precision=precision)
    return nplan.apply(params, st, maps, bn_mode=bn_mode)
