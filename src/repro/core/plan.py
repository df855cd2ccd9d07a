"""Unified execution-plan compiler: the `LayerPlan`/`NetworkPlan` IR.

TorchSparse++'s central claim is that a small kernel generator plus a
*whole-network* autotuner beats hand-engineered kernels: the tuner assigns a
dataflow configuration **per layer group for the entire network** (paper §4),
and mixed-precision training is where it wins biggest (§5).  Before this
module, that network-level view existed only implicitly — models hand-plumbed
``apply_conv`` calls, ``DataflowConfig`` dicts, ``MapCache`` handles and
``SplitPlan`` policies, and nothing in the conv stack knew about precision.

The IR makes the network the unit of compilation:

* ``LayerPlan`` — one conv layer: its ``ConvSpec``, which kernel map it runs
  on (``map_ref``), its map-sharing signature and tuner group, its
  ``TrainDataflowConfig`` (fwd/dgrad/wgrad dataflows), and its
  ``PrecisionPolicy``.
* ``KmapSpec`` — one kernel-map build step with the *explicit* dependency
  edges that used to be implicit in ``build_maps`` call order: which tensor
  stride it reads, whether its output table is adopted into the ``MapCache``
  (strided maps seed the next pyramid level's table for free), and which
  forward map a transposed map reuses.
* ``NetworkPlan`` — the compiled artifact every consumer shares: models
  execute through ``NetworkPlan.apply``, the autotuner rebinds per-group
  configs with ``with_assignment``, the serving engine persists/loads it as
  JSON (``serve/plans.PlanRegistry`` schema v2), and the training stack
  threads each layer's precision through the ``sparse_conv_apply``
  custom_vjp.

Lifecycle: **declare → compile → tune → persist → serve/train.**  Models
declare their layer list (a ``ModelDecl``); ``compile_plan`` partitions
tuner groups, binds dataflow assignments and precision policies;
``resolve_tiles`` applies the generator's adaptive tiling (paper §6.2) once
real kernel maps exist; ``PlanTuner``/``TrainingPlanTuner`` (see
``core/autotuner.py`` for the underlying greedy search) produce *tuned
plans* rather than bare config dicts.

A plan compiled with the default FP32 policy executes bit-identically to
the pre-plan per-call path (regression-tested in tests/test_plan.py).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import dataflows as df
from repro.core import generator
from repro.core import precision as prec
from repro.core.autotuner import (Autotuner, TrainingAutotuner,
                                  partition_groups)
from repro.core.hashing import CoordTable
from repro.core.kmap import (MapCache, SceneEntry, check_rows, level_key,
                             make_split_plan, map_strides, prepare_kmap,
                             search_kmaps, slice_kmap, transpose_kmap)
from repro.core.precision import FP32, PrecisionPolicy
from repro.core.sparse_conv import (ConvSpec, TrainDataflowConfig, apply_conv)
from repro.core.sparse_tensor import SparseTensor, stride_name

#: 3: named-value ops and per-layer activations; version-2 plans (stack
#: ops, a ``relu`` flag per layer) still load (``NetworkPlan.from_dict``)
PLAN_VERSION = 3


# ---------------------------------------------------------------------------
# Shared layers: masked batch norm and an activation
# ---------------------------------------------------------------------------

def bn_relu_init(c: int) -> dict:
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


#: activations a layer (``LayerPlan.act``) or an ``act`` op applies;
#: LeakyReLU takes PyTorch's default slope, as Cylinder3D uses it
ACTS = {"none": lambda y: y, "relu": jax.nn.relu,
        "leaky_relu": lambda y: jax.nn.leaky_relu(y, 0.01),
        "sigmoid": jax.nn.sigmoid}


def norm_act(p, st: SparseTensor, act: str = "relu", act_first: bool = False,
             mode: str = "batch") -> SparseTensor:
    """Masked batch norm (stats over valid rows; ``p`` None for none) and
    the activation ``act``, after the norm or, with ``act_first``, before it
    (Cylinder3D's order).

    ``mode="batch"`` (training/eval parity with the seed) normalizes with
    statistics over all valid rows — which couples every row in a *batched*
    tensor.  ``mode="affine"`` is the serving/inference mode: a per-channel
    scale+bias only, so each row's output depends on that row alone and a
    capacity-bucketed batched forward is bit-identical to the per-scene
    forward (the serving engine's correctness contract).  It implements the
    standard deploy-time convention of *folding* BN into an affine op: a
    checkpoint exported for serving is expected to carry running statistics
    pre-folded into ``scale``/``bias`` (this repo trains with batch stats
    and keeps no running stats, so affine-mode outputs are not numerically
    comparable to a ``mode="batch"`` forward of the same raw params).

    Statistics are always computed in fp32; the result is cast back to the
    feature dtype, so bf16 activations stay bf16 across the layer.
    """
    mask = st.valid_mask[:, None]
    y = st.feats.astype(jnp.float32)
    if act_first:
        y = ACTS[act](y)
    if p is not None and mode == "affine":
        y = y * p["scale"] + p["bias"]
    elif p is not None:
        assert mode == "batch", mode
        n = jnp.maximum(st.num_valid, 1).astype(jnp.float32)
        mean = jnp.sum(jnp.where(mask, y, 0), axis=0) / n
        var = jnp.sum(jnp.where(mask, jnp.square(y - mean), 0), axis=0) / n
        y = (y - mean) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    if not act_first:
        y = ACTS[act](y)
    return st.replace_feats(jnp.where(mask, y, 0).astype(st.feats.dtype))


def _tup(v):
    """A JSON list back to the tuple it was (kernel shapes, strides, refs)."""
    return tuple(_tup(x) for x in v) if isinstance(v, list) else v


# ---------------------------------------------------------------------------
# The IR
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """One conv layer's slice of the compiled network plan.

    map_ref:  key into the map dict built by the plan's ``KmapSpec`` program
              (e.g. ``("sub", 4)``) — layers sharing a ref share the map.
    sig:      map-sharing signature ``(stride, kernel, kind)`` — the tuner
              groups layers by this (paper Fig. 12).
    group:    tuner group name, filled by ``compile_plan``.
    dataflow: decoupled fwd/dgrad/wgrad configs (paper Fig. 13).
    precision: numeric policy threaded through all three dataflow kernels.
    bn:       whether the layer is followed by masked BN.
    act:      the activation after it (a key of ``ACTS``), before the BN
              when ``act_first``.
    """

    name: str
    spec: ConvSpec
    map_ref: Tuple
    sig: Tuple
    group: str = ""
    dataflow: TrainDataflowConfig = TrainDataflowConfig()
    precision: PrecisionPolicy = FP32
    bn: bool = True
    act: str = "relu"
    act_first: bool = False

    def __post_init__(self):
        assert self.act in ACTS, self.act

    def to_dict(self) -> dict:
        return {"name": self.name, "spec": dataclasses.asdict(self.spec),
                "map_ref": list(self.map_ref), "sig": list(self.sig),
                "group": self.group, "dataflow": self.dataflow.to_dict(),
                "precision": self.precision.to_dict(),
                "bn": self.bn, "act": self.act, "act_first": self.act_first}

    @staticmethod
    def from_dict(d: dict) -> "LayerPlan":
        d = dict(d)
        if "relu" in d:     # version 2: a ReLU flag in place of ``act``
            d["act"] = "relu" if d.pop("relu") else "none"
        known = {f.name for f in dataclasses.fields(LayerPlan)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown LayerPlan fields: {sorted(unknown)}")
        return LayerPlan(
            name=d["name"],
            spec=ConvSpec(**{k: _tup(v) for k, v in d["spec"].items()}),
            map_ref=_tup(d["map_ref"]), sig=_tup(d["sig"]),
            group=d.get("group", ""),
            dataflow=TrainDataflowConfig.from_dict(d["dataflow"]),
            precision=PrecisionPolicy.from_dict(d["precision"]),
            bn=d.get("bn", True), act=d.get("act", "relu"),
            act_first=d.get("act_first", False))


@dataclasses.dataclass(frozen=True)
class KmapSpec:
    """One kernel-map build step, with explicit dependency edges.

    kind:          "sub" (submanifold), "down" (strided), "up" (transposed),
                   "slice" (the columns of a sub-kernel's offsets in another
                   map, ``slice_of``).
    kernel_size, stride, tensor_stride: ints, or one int per axis.
    tensor_stride: stride of the tensor the map is built on ("up": the fine
                   tensor whose coordinates the inverse conv restores).
    adopts_output_table: a "down" map's strided-unique pass emits the child
                   level's sorted ``CoordTable`` for free; this edge makes
                   the ``MapCache`` adoption — implicit call-order magic
                   before this IR — part of the plan.
    transpose_of:  for "up" maps, the forward map whose pair lists are
                   swapped (decoder layers reuse encoder maps — same group).
    slice_of:      for "slice" maps, the map whose columns they take.
    dilate:        a "down" map's outputs are the dilation of its inputs by
                   its (odd) kernel, as spconv's ``SparseConv3d`` makes
                   them, not their floor grid; such a level may overflow its
                   capacity (``kmap.check_capacity``).
    """

    ref: Tuple
    kind: str
    kernel_size: int | tuple
    stride: int | tuple
    tensor_stride: int | tuple
    adopts_output_table: bool = False
    transpose_of: Optional[Tuple] = None
    table: str = "sort"
    slice_of: Optional[Tuple] = None
    dilate: bool = False

    #: coordinate-table strategies: "sort" rebuilds every table with a fresh
    #: argsort; "composed" allows scene-granular merge-composition of cached
    #: per-scene tables/maps (serving); "incremental" additionally allows
    #: streaming frames to delta-merge their scene table.  A declared,
    #: serializable, tunable axis like dataflow — builders without composed
    #: inputs simply fall back to "sort" semantics.
    TABLE_STRATEGIES = ("sort", "composed", "incremental")

    def __post_init__(self):
        assert self.kind in ("sub", "down", "up", "slice"), self.kind
        assert self.table in self.TABLE_STRATEGIES, self.table
        assert (self.kind == "up") == (self.transpose_of is not None)
        assert (self.kind == "slice") == (self.slice_of is not None)
        assert not self.dilate or self.kind == "down", self.kind

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        return {k: list(v) if isinstance(v, tuple) else v
                for k, v in d.items()}

    @staticmethod
    def from_dict(d: dict) -> "KmapSpec":
        known = {f.name for f in dataclasses.fields(KmapSpec)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown KmapSpec fields: {sorted(unknown)}")
        return KmapSpec(**{k: _tup(v) for k, v in d.items()})


#: Ops of the execution program, over a current tensor and named values.
#: ("conv", layer) runs a LayerPlan on the current tensor; ("save", v) names
#: it, ("load", v) makes a named value current; ("add", v), ("mul", v) and
#: ("concat", v) combine it with a named value on the same voxels (residual
#: sums, skip additions, gates; U-Net skip concatenation); ("act", kind)
#: applies an activation of ``ACTS``; ("head", pname) is a final dense
#: projection.  A program with no head op returns the last features.
OPS = ("conv", "save", "load", "add", "mul", "concat", "act", "head")


@dataclasses.dataclass(frozen=True)
class ModelDecl:
    """What a model module declares: its layers, execution program, and
    kernel-map program.  ``compile_plan`` turns this into a NetworkPlan."""

    arch: str
    layers: Tuple[LayerPlan, ...]
    ops: Tuple[Tuple, ...]
    map_specs: Tuple[KmapSpec, ...]


def pyramid_map_specs(levels: int, with_up: bool,
                      sub_kernel: int = 3, down_kernel: int = 2,
                      table: str = "sort") -> Tuple[KmapSpec, ...]:
    """The standard encoder(/decoder) map program: a submanifold map per
    stride level, a strided map per downsample (adopting its output table),
    and — for U-Nets — transposed maps reusing the forward strided maps.
    ``table`` declares the coordinate-table strategy for the whole program
    (see ``KmapSpec.TABLE_STRATEGIES``)."""
    specs = [KmapSpec(("sub", 1), "sub", sub_kernel, 1, 1, table=table)]
    stride = 1
    for _ in range(levels):
        specs.append(KmapSpec(("down", stride), "down", down_kernel, 2, stride,
                              adopts_output_table=True, table=table))
        stride *= 2
        specs.append(KmapSpec(("sub", stride), "sub", sub_kernel, 1, stride,
                              table=table))
    if with_up:
        for lvl in range(levels - 1, -1, -1):
            s = 2 ** lvl
            specs.append(KmapSpec(("up", s), "up", down_kernel, 2, s,
                                  transpose_of=("down", s), table=table))
    return tuple(specs)


def build_maps_from_specs(specs: Sequence[KmapSpec], st: SparseTensor,
                          cache: Optional[MapCache] = None,
                          tables: Optional[dict] = None) -> dict:
    """Execute a kernel-map program.  One ``MapCache`` spans the pyramid:
    submanifold and strided maps at a stride share one sorted table, and
    each ``adopts_output_table`` edge seeds the next level's table for free.
    A caller-supplied warm ``cache`` (the serving engine) is used as-is;
    never reuse one across ``jit`` traces.

    ``tables``: optional pre-composed coordinate tables, as produced by
    ``kmap.compose_batch_tables`` — {tensor_stride: (sorted_keys, order,
    n_valid)}.  The entry at ``st.stride`` (its row order is required)
    replaces the root argsort; deeper entries (identity order, ``order``
    None) are adopted per out-stride so the strided maps skip their
    floor-grid unique argsorts too.  Levels absent from ``tables`` build
    normally — composition degrades gracefully, never changes results.

    Each spec's table phase (and a transposed or column-subset map) traces
    under ``jax.named_scope("kmap.<kind>_s<s>")`` (``s``: the stride of the
    tensor the map is built on, ``8x8x4`` where the axes differ), and a
    dilated level's output cells under ``kmap.dilate_s<s>`` inside it, so a
    device trace can say which map an op builds; the searches of all specs
    run batched, under ``search/join`` and ``search/compact`` alone.  Each
    column-subset map counts ``kmap.offset_slices`` at trace time.
    """
    if cache is None:   # NOT `or`: an empty caller cache is falsy but wanted
        cache = MapCache.for_tensor(st)
    if tables:
        for s, (keys, order, n) in sorted(tables.items()):
            if s == st.stride:
                assert order is not None, "the root table needs its row order"
                cache.adopt(st.coords, CoordTable(cache.spec, keys, order))
            else:
                cache.adopt_for_stride(s, CoordTable.from_sorted_keys(
                    cache.spec, keys), n)
    # table phase, level by level; then every map's search at once (the
    # searches batch, see ``kmap.search_kmaps``); then the transposed maps
    pending: dict = {}
    tensors = {st.stride: st}
    for ms in specs:
        if ms.kind in ("up", "slice"):
            continue
        with jax.named_scope(f"kmap.{ms.kind}_s{stride_name(ms.tensor_stride)}"):
            p = prepare_kmap(tensors[ms.tensor_stride], ms.kernel_size,
                             1 if ms.kind == "sub" else ms.stride,
                             cache=cache, dilate=ms.dilate)
        pending[ms.ref] = p
        if ms.kind == "down":
            tensors[p.out_stride] = SparseTensor(
                coords=p.out_coords,
                feats=jnp.zeros((p.out_coords.shape[0], 1), st.feats.dtype),
                num_valid=p.n_out, stride=p.out_stride,
                batch_bound=st.batch_bound, spatial_bound=st.spatial_bound)
    built = dict(zip(pending, search_kmaps(list(pending.values()))))
    maps: dict = {}
    for ms in specs:
        scope = f"kmap.{ms.kind}_s{stride_name(ms.tensor_stride)}"
        if ms.kind == "up":
            with jax.named_scope(scope):
                built[ms.ref] = transpose_kmap(built[ms.transpose_of],
                                               tensors[ms.tensor_stride])
        elif ms.kind == "slice":
            obs.count("kmap.offset_slices")
            with jax.named_scope(scope):
                built[ms.ref] = slice_kmap(built[ms.slice_of], ms.kernel_size)
        maps[ms.ref] = built[ms.ref]
    return maps


def scene_entry_arrays(map_specs: Sequence[KmapSpec], st: SparseTensor,
                       root_table: Optional[CoordTable] = None,
                       tables: Optional[dict] = None):
    """The traceable core of a per-scene mapping build: what a batch
    composes from (``kmap.compose_kmaps``) plus the scene's sorted root
    table arrays.  ``st`` is a single-scene tensor (batch column 0, padding
    allowed — the serving engine buckets scene capacities so this jits
    once per rung).

    root_table: an already-merged ``CoordTable`` for ``st`` (streaming
    delta path) — adopted so the build skips the scene's root argsort.
    tables: optional pre-composed deeper-level tables (the incremental
    cell-ladder path) — see ``build_maps_from_specs``.

    Returns ``(arrays, root_keys, root_order)``; ``arrays`` holds
    ``"m_out"`` (ref -> output-stationary map of each "sub"/"down" map),
    ``"coords"`` (``kmap.level_key`` -> each level's coordinates) and
    ``"sizes"`` (``level_key`` -> rows of each level below the root).
    Only the searched maps are built: pair lists, bitmasks, "up" and
    "slice" maps are derived from the composed batch maps.
    """
    cache = MapCache.for_tensor(st)
    if root_table is not None:
        cache.adopt(st.coords, root_table)
    maps = build_maps_from_specs([ms for ms in map_specs
                                  if ms.kind in ("sub", "down")], st, cache,
                                 tables=tables)
    root = cache.table(st)   # cache hit: the table the build sorted/adopted
    downs = [maps[ms.ref] for ms in map_specs if ms.kind == "down"]
    arrays = {"m_out": {ref: km.m_out for ref, km in maps.items()},
              "coords": {level_key(st.stride): st.coords,
                         **{level_key(km.out_stride): km.out_coords
                            for km in downs}},
              "sizes": {level_key(km.out_stride): km.n_out for km in downs}}
    return arrays, root.sorted_keys, root.order


def scene_entry_from_arrays(map_specs: Sequence[KmapSpec], arrays: dict,
                            n: int, root_keys, root_order,
                            root_stride: int = 1) -> SceneEntry:
    """The ``SceneEntry`` of a (possibly padded) scene build: its map and
    level arrays stay on the device; only the per-level row counts and the
    root table come to the host (either may be passed in already fetched),
    the table trimmed to its valid prefix (PAD keys sort last, so the first
    ``n`` entries ARE the exact-size table delta-merge expects).  Raises
    when a dilated level overflowed its capacity (the scene's rung)."""
    sizes, keys, order = jax.device_get((arrays["sizes"], root_keys,
                                         root_order))
    cap = arrays["coords"][level_key(root_stride)].shape[0]
    by_key = {level_key(s): s for s in
              [root_stride] + [map_strides(ms)[1] for ms in map_specs]}
    sizes = {root_stride: n, **{by_key[k]: int(v) for k, v in sizes.items()}}
    for ms in map_specs:
        if ms.dilate:
            check_rows(ms.ref, sizes[map_strides(ms)[1]], cap)
    return SceneEntry(n=n, sizes=sizes,
                      arrays={k: arrays[k] for k in ("m_out", "coords")},
                      root_keys=np.asarray(keys)[:n],
                      root_order=np.asarray(order)[:n])


def build_scene_entry(map_specs: Sequence[KmapSpec], st: SparseTensor,
                      root_table: Optional[CoordTable] = None) -> SceneEntry:
    """Build one scene's cached mapping work for scene-granular composition
    (eager convenience wrapper; the serving engine jits
    ``scene_entry_arrays`` per scene-capacity rung instead)."""
    arrays, keys, order = scene_entry_arrays(map_specs, st, root_table)
    return scene_entry_from_arrays(map_specs, arrays, int(st.num_valid),
                                   keys, order, root_stride=st.stride)


@dataclasses.dataclass(frozen=True)
class NetworkPlan:
    """The compiled, serializable execution plan of one sparse network."""

    arch: str
    layers: Tuple[LayerPlan, ...]
    ops: Tuple[Tuple, ...]
    map_specs: Tuple[KmapSpec, ...]
    version: int = PLAN_VERSION

    # ------------------------------------------------------------ structure
    def layer(self, name: str) -> LayerPlan:
        for lp in self.layers:
            if lp.name == name:
                return lp
        raise KeyError(name)

    def signatures(self) -> Dict[str, tuple]:
        return {lp.name: lp.sig for lp in self.layers}

    def groups(self) -> list:
        """Tuner groups (``GroupInfo``) over this plan's layers."""
        return partition_groups(self.signatures())

    def assignment(self) -> Dict[tuple, TrainDataflowConfig]:
        """Per-signature dataflow assignment (layers in a group share one)."""
        out: Dict[tuple, TrainDataflowConfig] = {}
        for lp in self.layers:
            out.setdefault(lp.sig, lp.dataflow)
        return out

    # ----------------------------------------------------------- rebinding
    def with_assignment(self, assignment: Dict[tuple, TrainDataflowConfig]
                        ) -> "NetworkPlan":
        """Rebind per-group dataflow configs (tuner output → plan)."""
        layers = tuple(dataclasses.replace(lp, dataflow=assignment[lp.sig])
                       if lp.sig in assignment else lp for lp in self.layers)
        return dataclasses.replace(self, layers=layers)

    @property
    def table_strategy(self) -> str:
        """The map program's declared coordinate-table strategy ("sort" /
        "composed" / "incremental") — read off the root spec."""
        return self.map_specs[0].table if self.map_specs else "sort"

    def with_table_strategy(self, strategy: str) -> "NetworkPlan":
        """Rebind the coordinate-table strategy (a tunable axis like
        dataflow) on every map spec of the program."""
        assert strategy in KmapSpec.TABLE_STRATEGIES, strategy
        specs = tuple(dataclasses.replace(ms, table=strategy)
                      for ms in self.map_specs)
        return dataclasses.replace(self, map_specs=specs)

    def with_precision(self, policy) -> "NetworkPlan":
        """Rebind the numeric policy: one policy for the whole network, or a
        ``{sig: policy}`` dict for per-group mixes."""
        if isinstance(policy, dict):
            layers = tuple(dataclasses.replace(lp, precision=prec.resolve(policy[lp.sig]))
                           if lp.sig in policy else lp for lp in self.layers)
        else:
            pol = prec.resolve(policy)
            layers = tuple(dataclasses.replace(lp, precision=pol)
                           for lp in self.layers)
        return dataclasses.replace(self, layers=layers)

    def resolve_tiles(self, maps: dict, threshold_macs: float = 5e8,
                      measure: Optional[Callable[["NetworkPlan"], float]] = None,
                      candidates: Optional[Sequence[tuple]] = None
                      ) -> "NetworkPlan":
        """Adaptive tiling (paper §6.2): once real kernel maps exist, pick
        each implicit-GEMM layer's (tile_m, tile_n).  Tile sizes only matter
        to the Pallas backend's launch geometry — the math is unchanged.

        With ``measure=None`` (default) tiles come from the MAC heuristic
        (``generator.adaptive_tiles``).  With a ``measure(candidate_plan) →
        seconds`` callable, the Pallas implicit-GEMM *groups* are instead
        retiled by greedy measurement — each group tries every ``candidates``
        pair (default: the generator's tile menu) under end-to-end latency,
        mirroring the dataflow tuner's loop — so the kernel tier is a
        searched axis, not a guessed one."""
        def retile(cfg: df.DataflowConfig, kmap, cin, cout):
            if cfg.dataflow != "implicit_gemm":
                return cfg
            tm, tn = generator.adaptive_tiles(kmap, cin, cout,
                                              threshold_macs=threshold_macs)
            return dataclasses.replace(cfg, tile_m=tm, tile_n=tn)

        layers = []
        for lp in self.layers:
            kmap = maps[lp.map_ref]
            cin, cout = lp.spec.in_channels, lp.spec.out_channels
            cfg3 = TrainDataflowConfig(
                fwd=retile(lp.dataflow.fwd, kmap, cin, cout),
                dgrad=retile(lp.dataflow.dgrad, kmap, cout, cin),
                wgrad=retile(lp.dataflow.wgrad, kmap, cin, cout))
            layers.append(dataclasses.replace(lp, dataflow=cfg3))
        plan = dataclasses.replace(self, layers=tuple(layers))
        if measure is None:
            return plan

        # -------- measured mode: greedy per-group tile search (pallas only)
        cands = tuple(candidates if candidates is not None
                      else dict.fromkeys((generator.SMALL_TILES,
                                          generator.LARGE_TILES, (128, 128))))

        def group_tiles(p: "NetworkPlan", sig: tuple, tm: int,
                        tn: int) -> "NetworkPlan":
            def retile3(cfg: df.DataflowConfig) -> df.DataflowConfig:
                if cfg.dataflow != "implicit_gemm":
                    return cfg
                return dataclasses.replace(cfg, tile_m=tm, tile_n=tn)
            new = tuple(
                dataclasses.replace(lp, dataflow=TrainDataflowConfig(
                    fwd=retile3(lp.dataflow.fwd),
                    dgrad=retile3(lp.dataflow.dgrad),
                    wgrad=retile3(lp.dataflow.wgrad)))
                if lp.sig == sig else lp for lp in p.layers)
            return dataclasses.replace(p, layers=new)

        for g in plan.groups():
            rep = plan.layer(g.layer_names[0])
            fwd = rep.dataflow.fwd
            if not (fwd.backend == "pallas" and fwd.dataflow == "implicit_gemm"):
                continue
            results = []
            for tm, tn in cands:
                trial = group_tiles(plan, rep.sig, tm, tn)
                with obs.span("resolve_tiles_candidate", group=g.name,
                              tiles=f"{tm}x{tn}") as sp:
                    lat = measure(trial)
                    sp.set(latency_ms=lat * 1e3)
                results.append((lat, (tm, tn)))
            _, (tm, tn) = min(results, key=lambda r: r[0])
            plan = group_tiles(plan, rep.sig, tm, tn)
        return plan

    # ----------------------------------------------------------- execution
    def cast_params(self, params: dict) -> dict:
        """Cast each conv layer's parameter leaves to its LayerPlan's
        declared storage dtype (``PrecisionPolicy.params``); BN/head params
        are left untouched (normalization statistics and the final
        projection stay fp32 under the mixed policies).  The single home
        for the bench/example/test param-casting rule."""
        out = dict(params)
        for lp in self.layers:
            out[lp.name] = {k: lp.precision.cast_param(v)
                            for k, v in params[lp.name].items()}
        return out

    def build_maps(self, st: SparseTensor, cache: Optional[MapCache] = None,
                   tables: Optional[dict] = None) -> dict:
        return build_maps_from_specs(self.map_specs, st, cache, tables=tables)

    def split_plan_specs(self) -> Tuple[Tuple[tuple, int, bool], ...]:
        """Deduped (map_ref, n_splits, sorted) triples of every layer whose
        forward dataflow consumes a ``SplitPlan`` (pallas implicit GEMM) —
        the executor inputs the serving engine pre-builds/composes so the
        per-batch bitmask argsorts leave the dispatch hot path."""
        out = []
        for lp in self.layers:
            fwd = lp.dataflow.fwd
            if fwd.backend == "pallas" and fwd.dataflow == "implicit_gemm":
                key = (lp.map_ref, fwd.effective_splits, fwd.sorted)
                if key not in out:
                    out.append(key)
        return tuple(out)

    def build_split_plans(self, maps: dict) -> dict:
        """Fresh (traceable) split plans for every ``split_plan_specs()``
        triple — the cold-batch fallback when no per-scene cached orders
        exist to compose."""
        return {(ref, ns, srt): make_split_plan(maps[ref], ns, sort=srt)
                for ref, ns, srt in self.split_plan_specs()}

    def apply(self, params: dict, st: SparseTensor,
              maps: Optional[dict] = None, bn_mode: str = "batch",
              plans: Optional[dict] = None) -> jax.Array:
        """Run the compiled program.  Bit-identical to the models'
        pre-plan hand-written forwards under the FP32 policy.

        plans: optional pre-built split plans keyed ``(map_ref, n_splits,
        sorted)`` (see ``split_plan_specs``); layers without an entry build
        their plan in-trace as before.

        Each conv layer (with its norm) traces under
        ``jax.named_scope("layer.<name>")``.
        """
        if maps is None:
            maps = self.build_maps(st)
        by_name = {lp.name: lp for lp in self.layers}
        x = st
        named: dict = {}
        for kind, *arg in self.ops:
            v = arg[0] if arg else None
            if kind == "conv":
                lp = by_name[v]
                fwd = lp.dataflow.fwd
                plan = (plans or {}).get(
                    (lp.map_ref, fwd.effective_splits, fwd.sorted))
                with jax.named_scope(f"layer.{lp.name}"):
                    x = apply_conv(params[lp.name], x, maps[lp.map_ref],
                                   lp.dataflow, precision=lp.precision,
                                   plan=plan)
                    if lp.bn or lp.act != "none":
                        x = norm_act(params[f"{lp.name}_bn"] if lp.bn
                                     else None, x, lp.act, lp.act_first,
                                     mode=bn_mode)
            elif kind == "save":
                named[v] = x
            elif kind == "load":
                x = named[v]
            elif kind == "add":
                x = x.replace_feats(x.feats + named[v].feats)
            elif kind == "mul":
                x = x.replace_feats(x.feats * named[v].feats)
            elif kind == "concat":
                x = x.replace_feats(jnp.concatenate([x.feats, named[v].feats],
                                                    axis=1))
            elif kind == "act":
                x = x.replace_feats(jnp.where(x.valid_mask[:, None],
                                              ACTS[v](x.feats), 0))
            elif kind == "head":
                return x.feats @ params[v]["w"]
            else:
                raise ValueError(f"unknown plan op {(kind, *arg)!r}")
        return x.feats

    # -------------------------------------------------------- serialization
    def to_dict(self) -> dict:
        return {"version": self.version, "arch": self.arch,
                "layers": [lp.to_dict() for lp in self.layers],
                "ops": [list(op) for op in self.ops],
                "map_specs": [ms.to_dict() for ms in self.map_specs]}

    @staticmethod
    def from_dict(d: dict) -> "NetworkPlan":
        version = d.get("version", PLAN_VERSION)
        if version not in (2, PLAN_VERSION):
            raise ValueError(f"unsupported NetworkPlan version {version!r} "
                             f"(expected {PLAN_VERSION})")
        known = {"version", "arch", "layers", "ops", "map_specs"}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown NetworkPlan fields: {sorted(unknown)}")
        layers = tuple(LayerPlan.from_dict(x) for x in d["layers"])
        ops = tuple(tuple(op) for op in d["ops"])
        return NetworkPlan(
            arch=d["arch"], layers=layers,
            ops=ops if version == PLAN_VERSION else _ops_from_v2(ops, layers),
            map_specs=tuple(KmapSpec.from_dict(x) for x in d["map_specs"]))


def _ops_from_v2(ops: Sequence[tuple], layers: Sequence[LayerPlan]) -> tuple:
    """A version-2 program in named values: its skip stack (``push`` /
    ``concat``) and residual brackets (``res_begin`` / ``res_end``: ReLU of
    the sum, the identity added only where its width matches)."""
    width = {lp.name: lp.spec.out_channels for lp in layers}
    out, stack, c = [], [], None
    for op in ops:
        kind = op[0]
        if kind in ("push", "res_begin"):
            name = f"v{len(out)}"
            stack.append((name, c))
            out.append(("save", name))
            continue
        if kind == "concat":
            name, w = stack.pop()
            out.append(("concat", name))
            c += w
        elif kind == "res_end":
            name, w = stack.pop()
            out += [("add", name)] if w == c else []
            out.append(("act", "relu"))
        else:
            out.append(tuple(op))
            c = width.get(op[1], c)
    return tuple(out)


# ---------------------------------------------------------------------------
# The compiler
# ---------------------------------------------------------------------------

def compile_plan(decl: ModelDecl,
                 assignment: Optional[Dict[tuple, TrainDataflowConfig]] = None,
                 precision=None) -> NetworkPlan:
    """Compile a model declaration into a NetworkPlan.

    Partitions the tuner groups from the layers' map-sharing signatures
    (paper Fig. 12), binds the per-group dataflow ``assignment`` (missing
    groups keep the declaration's default), and binds the numeric policy
    (one policy, a ``{sig: policy}`` dict, or None to keep per-layer
    declarations).  Tile resolution (``resolve_tiles``) is a separate step
    because it needs real kernel maps.
    """
    sigs = {lp.name: lp.sig for lp in decl.layers}
    groups = partition_groups(sigs)
    group_of = {name: g.name for g in groups for name in g.layer_names}
    assignment = assignment or {}
    layers = []
    for lp in decl.layers:
        lp = dataclasses.replace(lp, group=group_of[lp.name])
        if lp.sig in assignment:
            lp = dataclasses.replace(lp, dataflow=assignment[lp.sig])
        layers.append(lp)
    nplan = NetworkPlan(arch=decl.arch, layers=tuple(layers), ops=decl.ops,
                        map_specs=decl.map_specs)
    if precision is not None:
        nplan = nplan.with_precision(precision)
    return nplan


# ---------------------------------------------------------------------------
# Plan-producing tuners (paper §4 on top of the IR)
# ---------------------------------------------------------------------------

class PlanTuner:
    """Greedy group tuner that produces a *tuned NetworkPlan*.

    ``measure(candidate_plan)`` must return end-to-end latency (seconds) of
    the workload executed under the candidate plan — never per-kernel time
    (paper Tables 3 vs 4).  Inference binding: all three kernels share the
    group's config (``bind_all``).

    With ``maps`` given, the dataflow search is followed by a *measured*
    tile resolution pass (``NetworkPlan.resolve_tiles(measure=...)``) over
    the Pallas implicit-GEMM groups of the winning assignment — the kernel
    generator's tile axis joins the search instead of staying a heuristic.
    """

    def __init__(self, nplan: NetworkPlan, space: Sequence[df.DataflowConfig],
                 measure: Callable[[NetworkPlan], float],
                 maps: Optional[dict] = None,
                 tile_candidates: Optional[Sequence[tuple]] = None):
        self.nplan = nplan
        self.space = list(space)
        self.measure = measure
        self.maps = maps
        self.tile_candidates = tile_candidates
        self.groups = nplan.groups()
        self.sig_of = {g.name: nplan.layer(g.layer_names[0]).sig
                       for g in self.groups}
        self.log: list = []

    def _plan_for(self, assign: Dict[str, df.DataflowConfig]) -> NetworkPlan:
        amap = {self.sig_of[k]: TrainDataflowConfig.bind_all(v)
                for k, v in assign.items()}
        return self.nplan.with_assignment(amap)

    def tune(self) -> NetworkPlan:
        tuner = Autotuner(self.groups, self.space,
                          lambda assign: self.measure(self._plan_for(assign)))
        best = tuner.tune()
        self.log = tuner.log
        tuned = self._plan_for(best)
        if self.maps is not None:
            tuned = tuned.resolve_tiles(self.maps, measure=self.measure,
                                        candidates=self.tile_candidates)
        return tuned


class TrainingPlanTuner:
    """Two-pass training tuner (partial binding, paper Fig. 13) over plans.

    ``measure(candidate_plan)`` returns end-to-end train-step latency of the
    candidate.  Returns a plan whose layers carry decoupled fwd/dgrad/wgrad
    configs per group.
    """

    def __init__(self, nplan: NetworkPlan, space: Sequence[df.DataflowConfig],
                 measure: Callable[[NetworkPlan], float],
                 scheme: str = "bind_dgrad_wgrad"):
        self.nplan = nplan
        self.space = list(space)
        self.measure = measure
        self.scheme = scheme
        groups = nplan.groups()
        self.sig_of = {g.name: nplan.layer(g.layer_names[0]).sig
                       for g in groups}
        self._tuner = TrainingAutotuner(groups, self.space, self._measure,
                                        scheme=scheme)

    def _measure(self, assign3: Dict[str, TrainDataflowConfig]) -> float:
        amap = {self.sig_of[k]: v for k, v in assign3.items()}
        return self.measure(self.nplan.with_assignment(amap))

    def tune(self) -> NetworkPlan:
        best = self._tuner.tune()
        amap = {self.sig_of[k]: v for k, v in best.items()}
        return self.nplan.with_assignment(amap)
