"""The sparse serving engine: submit()/flush() over bucketed batched scenes.

Ties the subsystem together (DESIGN: ISSUE 2 tentpole):

* requests (variable-size scenes) queue in a ``SceneBatcher`` and pack FIFO
  into capacity-bucketed batched ``SparseTensor``s with declared bounds —
  every served batch takes the single-argsort packed-key mapping path;
* each bucket capacity owns two pre-jitted stages: a **map builder**
  (``build_maps`` under one trace, so the per-trace ``MapCache`` shares
  sorted tables across the layer pyramid) and an **executor** (the model
  forward in inference-mode normalization).  Static bucket shapes bound jit
  recompiles to one per (bucket, stage) for the engine's lifetime;
* built kernel maps are reused **across requests** at two granularities:
  whole batches are keyed by a content digest of their packed coordinates
  (a small LRU maps digest → device-resident map stack, so exact replays
  skip mapping entirely), and — under the plan's ``"composed"`` /
  ``"incremental"`` table strategies — *scenes* are keyed individually: a
  per-scene store caches each scene's kernel-map stack (on the device)
  and sorted table ladder, and batch maps are **composed** from the cached
  per-scene stacks (on the device, with index offsets; bit-identical to a
  fresh build because batch bits keep scenes disjoint).  Under churning
  batch composition — the common case in real traffic — only cold scenes
  ever build maps, at their own size (Minuet §4 proper).  ``"incremental"``
  additionally lets streaming frames (``submit_delta``) update their scene
  table by an O(r+a) sorted delta-merge instead of a fresh argsort;
* flushes are triggered explicitly, by queue depth (``flush_count``), or by
  a latency deadline (``max_wait_ms`` — the oldest queued scene's age;
  check via ``poll()`` or any ``submit``), with deadline-triggered flushes
  counted in the engine stats;
* flushes run **pipelined**: while batch k executes on device, the host
  builds scene entries, composes maps/plans and packs batch k+1
  (``jax.block_until_ready`` is deferred to result drain, bounded by
  ``max_inflight`` dispatched-but-undrained batches — jax's async dispatch
  makes the overlap real on every backend).  Sorted-dataflow executor
  inputs (``SplitPlan``s) are merge-composed from per-scene cached orders
  the same way kernel maps are, so no per-batch bitmask argsort runs on
  the hot path.  With ``deadline_margin`` set, admission is deadline-aware:
  the engine predicts service time from its own phase medians and flushes
  / drains / cuts batches early when the oldest request's ``max_wait_ms``
  budget is about to be blown;
* the engine executes a compiled ``core.plan.NetworkPlan`` — the same
  artifact the models and the training stack run — loaded from a
  ``PlanRegistry`` at startup when one was persisted (tune once, serve
  forever; v1 assignment-only files recompile the plan from the model
  declaration) and re-tuned in place by ``tune()``;
* latency/throughput stats: per-scene p50/p95, scenes/s, recompile and
  map-cache counters.

The correctness contract — asserted in tests/test_serving.py — is that the
batched engine output is bit-identical to the per-scene forward at the same
bucket capacity: batching only ever adds rows whose keys can't collide with
another scene's (batch index is packed into every voxel key) and
inference-mode normalization keeps every output row a function of its own
scene's rows.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import dataflows as df
from repro.core import hashing
from repro.core.autotuner import timeit_fn
from repro.core.kmap import (SceneEntry, cell_ladder, cell_ladder_delta,
                             check_capacity, compose_blank, compose_finish,
                             compose_kmaps, compose_place,
                             compose_split_plans, ladder_tables)
from repro.core.plan import (KmapSpec, NetworkPlan, PlanTuner,
                             scene_entry_arrays, scene_entry_from_arrays)
from repro.core.sparse_conv import TrainDataflowConfig
from repro.core.sparse_tensor import (INVALID_COORD, SparseTensor,
                                      axis_strides, stride_mul)
from repro.models import centerpoint, cylinder3d, minkunet
from repro.serve.batcher import (PackedBatch, Scene, SceneBatcher, SceneDelta,
                                 SceneResult, apply_delta)
from repro.serve.bucketing import BucketLadder
from repro.serve.plans import PlanRegistry
from repro.serve.service import (STATS_SCHEMA_VERSION, ServiceConfig,
                                 resolve_config)


@dataclasses.dataclass(frozen=True)
class ArchBinding:
    """Everything the engine needs to serve one sparse architecture."""

    name: str
    model: object                       # module: init_params/build_maps/apply/layer_signatures
    default_config: object
    out_stride_of: Callable[[object], int]
    outputs_of: Callable[[object, SparseTensor, dict, jax.Array], tuple]
    in_channels_of: Callable[[object], int]


def _input_voxel_outputs(cfg, st, maps, feats):
    # logits are per input voxel: rows align with the stride-1 input coords
    return st.coords, feats, st.num_valid


def _centerpoint_outputs(cfg, st, maps, feats):
    s = 2 ** len(cfg.channels)
    km = maps[("sub", s)]
    return km.out_coords, feats, km.n_out


def _arch_bindings() -> Dict[str, ArchBinding]:
    from repro.configs import centerpoint_waymo, cylinder3d_kitti, minkunet_kitti

    return {
        "minkunet_kitti": ArchBinding(
            name="minkunet_kitti", model=minkunet,
            default_config=minkunet_kitti.CONFIG_BENCH,
            out_stride_of=lambda cfg: 1,
            outputs_of=_input_voxel_outputs,
            in_channels_of=lambda cfg: cfg.in_channels),
        "centerpoint_waymo": ArchBinding(
            name="centerpoint_waymo", model=centerpoint,
            default_config=centerpoint_waymo.CONFIG_BENCH,
            out_stride_of=lambda cfg: 2 ** len(cfg.channels),
            outputs_of=_centerpoint_outputs,
            in_channels_of=lambda cfg: cfg.in_channels),
        "cylinder3d_kitti": ArchBinding(
            name="cylinder3d_kitti", model=cylinder3d,
            default_config=cylinder3d_kitti.CONFIG_BENCH,
            out_stride_of=lambda cfg: 1,
            outputs_of=_input_voxel_outputs,
            in_channels_of=lambda cfg: cfg.in_channels),
    }


ARCHS = _arch_bindings()

DEFAULT_LADDER = BucketLadder.geometric(base=512, steps=3, max_batch=4)
DEFAULT_SPATIAL_BOUND = 256


#: per-scene latencies kept for percentile stats; bounded so a
#: tune-once-serve-forever process doesn't grow memory with uptime
LATENCY_WINDOW = 8192

#: per-phase duration samples kept per phase name (same rationale)
PHASE_WINDOW = 4096


def _box_coords(n: int) -> np.ndarray:
    """``n`` distinct voxels filling a cube about the origin: a warm-up
    scene whose dilated levels (``kmap._dilate``) hold fewer rows than it."""
    side = int(np.ceil(n ** (1 / 3) - 1e-9))
    axis = np.arange(side, dtype=np.int32) - side // 2
    grid = np.stack(np.meshgrid(axis, axis, axis, indexing="ij"), -1)
    return grid.reshape(-1, 3)[:n]


def _spec_of(leaf):
    """Shape/dtype/placement of a jit argument leaf, without its data."""
    if isinstance(leaf, jax.Array):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=leaf.sharding)
    return leaf


def percentiles_ms(values) -> Tuple[Optional[float], Optional[float]]:
    """(p50, p95) of a latency window — ``(None, None)`` when nothing was
    recorded, so an idle worker is distinguishable from an infinitely fast
    one (the old ``np.zeros(1)`` placeholder fabricated ``0.0`` ms)."""
    if not len(values):
        return (None, None)
    lat = np.asarray(values, dtype=np.float64)
    return (float(np.percentile(lat, 50)), float(np.percentile(lat, 95)))


def summarize_phases(windows: Dict[str, Sequence[float]]) -> Dict[str, dict]:
    """Fold per-phase duration windows into {phase: count/p50/p95} — the
    ``summary()['phases']`` block, shared by Engine and Router stats."""
    out = {}
    for name, window in sorted(windows.items()):
        p50, p95 = percentiles_ms(window)
        out[name] = {"count": len(window), "p50_ms": p50, "p95_ms": p95}
    return out


def scene_stage_compiles(stats: "EngineStats") -> Dict:
    """The scene-granular stages' compiles: the scene and delta builders'
    per scene rung, and the map composer's as ``compose@<bucket>``."""
    return {**stats.scene_compiles,
            **{f"compose@{cap}": n
               for cap, n in stats.compose_compiles.items()}}


@dataclasses.dataclass
class EngineStats:
    submitted: int = 0
    completed: int = 0
    batches: int = 0
    routed_batches: int = 0      # batches assigned by a DeviceRouter
    flushes: int = 0
    busy_s: float = 0.0
    latencies_ms: "collections.deque" = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=LATENCY_WINDOW))
    recompiles: Dict[int, int] = dataclasses.field(default_factory=dict)
    map_compiles: Dict[int, int] = dataclasses.field(default_factory=dict)
    plan_compiles: Dict[int, int] = dataclasses.field(default_factory=dict)
    map_hits: int = 0
    map_misses: int = 0
    # scene-granular reuse (composed/incremental table strategies)
    scene_compiles: Dict[int, int] = dataclasses.field(default_factory=dict)
    scene_hits: int = 0          # batch slots served from the scene store
    scene_misses: int = 0        # cold scenes that built their own stack
    composed_batches: int = 0    # batch map stacks composed, not built
    # the device map composer's compiles, per bucket (its placing stage
    # once per scene rung met there, its finishing stage once)
    compose_compiles: Dict[int, int] = dataclasses.field(default_factory=dict)
    delta_merges: int = 0        # streaming frames that delta-merged a table
    # scene builds (cold or delta): valid rows, and the scene-rung rows the
    # build's neighbour search runs over
    scene_rows: int = 0
    scene_rung_rows: int = 0
    # the same builds' levels below the root: rows they emitted, and the
    # capacity those levels were padded to
    level_rows: int = 0
    level_cap_rows: int = 0
    # bytes the same builds copied device→host (level sizes, root table)
    fetch_bytes: int = 0
    # flush triggers beyond the explicit flush() call
    deadline_flushes: int = 0    # max_wait_ms expiries
    count_flushes: int = 0       # flush_count threshold crossings
    deadline_cuts: int = 0       # batches cut early by deadline admission
    # pipelined-flush accounting (summary()['pipeline'])
    inflight_peak: int = 0       # max dispatched-but-undrained batches seen
    # per-phase duration windows (queue_wait/pack/map/execute/unpack/…) —
    # always on (a perf_counter pair + deque append per phase), independent
    # of whether the tracer is enabled
    phases: Dict[str, "collections.deque"] = dataclasses.field(
        default_factory=dict)
    # SLO accounting: requests measured against the deadline (max_wait_ms)
    slo_deadline_ms: Optional[float] = None
    slo_measured: int = 0
    slo_miss_count: int = 0

    def observe(self, phase: str, ms: float) -> None:
        window = self.phases.get(phase)
        if window is None:
            window = self.phases[phase] = collections.deque(
                maxlen=PHASE_WINDOW)
        window.append(ms)

    def slo_observe(self, latency_ms: float, deadline_ms: float) -> None:
        """Score one completed request against its latency deadline."""
        self.slo_deadline_ms = deadline_ms
        self.slo_measured += 1
        if latency_ms > deadline_ms:
            self.slo_miss_count += 1

    def summary(self) -> dict:
        p50, p95 = percentiles_ms(self.latencies_ms)
        return {
            "schema_version": STATS_SCHEMA_VERSION,
            "scenes": self.completed,
            "batches": self.batches,
            "routed_batches": self.routed_batches,
            "p50_ms": p50,
            "p95_ms": p95,
            "scenes_per_s": self.completed / self.busy_s if self.busy_s else 0.0,
            "recompiles": dict(self.recompiles),
            "map_compiles": dict(self.map_compiles),
            "plan_compiles": dict(self.plan_compiles),
            "map_cache": {"hits": self.map_hits, "misses": self.map_misses},
            "scene_tables": {"hits": self.scene_hits,
                             "misses": self.scene_misses,
                             "composed_batches": self.composed_batches,
                             "delta_merges": self.delta_merges,
                             "rows": self.scene_rows,
                             "rung_rows": self.scene_rung_rows,
                             "fetch_bytes": self.fetch_bytes,
                             "compiles": scene_stage_compiles(self)},
            "level_tables": {"rows": self.level_rows,
                             "cap_rows": self.level_cap_rows},
            "deadline_flushes": self.deadline_flushes,
            "count_flushes": self.count_flushes,
            "deadline_cuts": self.deadline_cuts,
            "pipeline": {"inflight_peak": self.inflight_peak},
            "phases": summarize_phases(self.phases),
            "slo": {"deadline_ms": self.slo_deadline_ms,
                    "measured": self.slo_measured,
                    "misses": self.slo_miss_count,
                    "miss_rate": (self.slo_miss_count / self.slo_measured
                                  if self.slo_measured else None)},
        }


class Engine:
    """Front end: ``submit()`` scenes, ``flush()`` to run queued work.

    arch: "minkunet_kitti" | "centerpoint_waymo" | "cylinder3d_kitti" (see
        ``ARCHS``).
    plans: a PlanRegistry (or path to one) holding tuned per-group dataflow
        assignments; missing entries fall back to the default config.
    map_strategy: coordinate-table strategy override ("sort" / "composed" /
        "incremental"); None follows the plan's declared ``KmapSpec.table``
        axis.  "sort" is the PR-2 whole-batch-digest behavior; "composed"
        adds scene-granular map reuse; "incremental" also enables
        ``submit_delta`` streaming-table merges.
    max_wait_ms / flush_count: latency-deadline and queue-depth triggers for
        automatic flushes (None disables each); auto-flushed results are
        returned by the next ``flush()``/``poll()``.
    scene_cache_size: LRU bound of the per-scene store.  Entries are
        device-resident map stacks (~ searched maps x KD x scene-rung int32
        words each), so size this by device memory (HBM), next to the
        executor's working set.
    scene_cache_bytes: optional byte bound on the same store — eviction by
        the device bytes an entry pins (``SceneEntry.nbytes``), which
        tracks HBM residency far better than an entry count when scene
        sizes span rungs.  Both bounds apply when set.
    max_inflight: dispatched-but-undrained batch window of a pipelined
        flush.  1 restores the strictly serial dispatch→block loop; the
        default 2 double-buffers host mapping/packing against device
        execution.  Outputs are bit-identical at any depth — batches are
        independent and drain in FIFO order.
    deadline_margin: None (default) keeps deadline handling purely
        age-based (flush when the oldest request has waited max_wait_ms).
        A float enables deadline-*aware* admission: the engine predicts
        remaining service time as ``margin ×`` the median of its own
        pack/map/dispatch/execute/unpack phases and (a) auto-flushes early
        so requests finish inside the budget, (b) drains the in-flight
        window before dispatching more when the head batch is about to
        miss, and (c) cuts the first batch of a flush down to the urgent
        scene instead of co-batching it with fresh work.
    device: pin this engine to one jax device — params and every packed
        batch are ``jax.device_put`` there, so each compiled rung's
        executor runs on that device.  None (default) follows jax's default
        placement.  This is how the ``DeviceRouter`` builds one worker per
        device.
    plan_key: the PlanRegistry name to read/write plans under (defaults to
        ``arch``; the router routes per-device entries like ``arch@dev2``
        here — see ``serve.plans.device_key``).

    All behavioral knobs above (ladder, spatial_bound, seed, map_strategy,
    caches, deadlines, …) now live in one serializable ``ServiceConfig`` —
    pass ``config=ServiceConfig(...)``.  The historical per-kwarg spelling
    keeps working through ``resolve_config`` (one DeprecationWarning per
    process); ``model_config`` / ``params`` / ``plans`` / ``precision`` /
    ``device`` stay direct arguments because they are runtime objects, not
    serializable configuration.
    """

    def __init__(self, arch: str, config: Optional[ServiceConfig] = None,
                 model_config=None, params=None,
                 plans: Optional[PlanRegistry] = None,
                 precision=None,
                 device: Optional[jax.Device] = None, **legacy):
        if arch not in ARCHS:
            raise ValueError(f"unknown arch {arch!r}; have {sorted(ARCHS)}")
        if isinstance(config, BucketLadder):   # Engine(arch, ladder) callers
            legacy.setdefault("ladder", config)
            config = None
        self.config = resolve_config(config, legacy)
        cfg_s = self.config
        self.binding = ARCHS[arch]
        self.arch = arch
        self.device = device
        self.cfg = model_config if model_config is not None else self.binding.default_config
        self.params = params if params is not None else self.binding.model.init_params(
            self.cfg, jax.random.PRNGKey(cfg_s.seed))
        if device is not None:
            self.params = jax.device_put(self.params, device)
        self.ladder = cfg_s.ladder()
        self.batcher = SceneBatcher(self.ladder, cfg_s.spatial_bound)
        if isinstance(plans, str):
            plans = PlanRegistry.load(plans)
        self.plans = plans or PlanRegistry()
        self.plan_key = cfg_s.plan_key or arch
        self.assignment = self.plans.get(self.plan_key)
        # The compiled artifact every stage shares: a persisted NetworkPlan
        # is used as-is when it still matches this engine's model config
        # (same layer names + ConvSpecs); otherwise — v1 files, or a plan
        # tuned under a different width/depth — one is recompiled from the
        # model declaration with the registry's assignment.
        nplan = self.plans.network(self.plan_key)
        compiled = self.binding.model.network_plan(self.cfg,
                                                   assignment=self.assignment)
        if nplan is None or [(lp.name, lp.spec) for lp in nplan.layers] != \
                [(lp.name, lp.spec) for lp in compiled.layers]:
            nplan = compiled
        if precision is not None:
            nplan = nplan.with_precision(precision)
        self.nplan: NetworkPlan = nplan
        self.out_stride = self.binding.out_stride_of(self.cfg)
        self.map_strategy = (cfg_s.map_strategy
                             if cfg_s.map_strategy is not None
                             else self.nplan.table_strategy)
        assert self.map_strategy in KmapSpec.TABLE_STRATEGIES, self.map_strategy
        self.max_wait_ms = cfg_s.max_wait_ms
        self.flush_count = cfg_s.flush_count
        assert cfg_s.max_inflight >= 1, cfg_s.max_inflight
        self.max_inflight = cfg_s.max_inflight
        self.deadline_margin = cfg_s.deadline_margin
        self.stats = EngineStats()
        self.maps_cache_size = cfg_s.maps_cache_size
        self.scene_cache_size = cfg_s.scene_cache_size
        self.scene_cache_bytes = cfg_s.scene_cache_bytes
        self._queue: List[tuple] = []       # (ticket, Scene, t_submit)
        self._next_ticket = 0
        self._ready: Dict[int, SceneResult] = {}   # auto-flushed results
        self._map_store: "collections.OrderedDict[str, dict]" = collections.OrderedDict()
        # A DeviceRouter shares ONE scene store — and its lock — across all
        # its workers: an entry lives on the device that built it, and a
        # worker on another device copies it over to compose.  The lock only
        # guards dict mutation, never a build (concurrent builds of the same
        # digest are idempotent: entries are bit-identical).
        self._scene_lock = threading.Lock()
        self._scene_store: "collections.OrderedDict[str, SceneEntry]" = collections.OrderedDict()
        # stream id -> last scene, LRU-bounded: serve-forever processes see
        # ephemeral stream ids, and each entry pins a full host-side Scene
        self._streams: "collections.OrderedDict[str, Scene]" = collections.OrderedDict()
        self.stream_cache_size = 1024
        self._builders: Dict[int, Callable] = {}
        self._executors: Dict[int, Callable] = {}
        self._plan_builders: Dict[int, Callable] = {}
        self._scene_builders: Dict[int, Callable] = {}
        self._scene_delta_builders: Dict[int, Callable] = {}
        #: the map composer's stages: (scene rung, bucket) -> placing
        #: stage, bucket -> finishing stage, and bucket -> the empty batch
        #: map stack the first scene is placed into
        self._placers: Dict[tuple, Callable] = {}
        self._composers: Dict[int, Callable] = {}
        self._compose_blanks: Dict[int, dict] = {}
        #: floor-grid down-map out-strides, ascending — the cell ladder's
        #: levels (a dilated level is no floor grid: it builds afresh)
        self._down_strides = tuple(sorted(
            (stride_mul(ms.tensor_stride, ms.stride)
             for ms in self.nplan.map_specs
             if ms.kind == "down" and not ms.dilate), key=axis_strides))
        #: maps whose level may overflow its capacity (dilated outputs)
        self._dilated = [ms.ref for ms in self.nplan.map_specs
                         if ms.dilate]
        #: (kind, rung) marks queued by trace-time side effects, drained by
        #: the jit wrappers into structured ``compile`` trace events
        self._compile_marks: List[tuple] = []
        #: (kind, rung) → (jitted stage, arg shapes) of its last compile
        self._compiled: Dict[tuple, tuple] = {}
        # per-scene builds jit once per rung of a small capacity ladder
        # (scene sizes vary request to request; exact-size eager builds
        # would recompile every op per distinct size)
        caps = [min(64, self.ladder.capacities[0])]
        while caps[-1] < self.ladder.max_capacity:
            caps.append(caps[-1] * 2)
        self._scene_ladder = BucketLadder(tuple(caps), max_batch=1)

    # -------------------------------------------------------- observability
    @property
    def device_name(self) -> str:
        """The device identity compile events are keyed by (the pinned
        device, or jax's default placement when the engine floats)."""
        d = self.device if self.device is not None else jax.devices()[0]
        return str(d)

    @contextlib.contextmanager
    def _phase(self, name: str, **attrs):
        """Time one phase of the hot path into BOTH sinks: a tracer span
        (rich, nestable, exportable, on the profiler's host plane as
        ``repro.<name>`` — no-op singleton when disabled) and the always-on
        ``EngineStats.phases`` histogram window.

        The phases, nested as they run:

        * ``pack`` (``batch_pack`` inside), then ``map``: the batch's maps,
          from ``compose_kmaps`` (every cold scene's build dispatched, then
          per cold scene ``scene_build``, then the device composer's
          dispatch) and ``compose_plans``, or from a jitted ``map_build``
          and ``plan_build``; then ``dispatch`` of the executor, which
          returns without waiting;
        * ``apply_delta`` (at ``submit_delta``): a streamed frame's new
          scene made from its delta, on the host;
        * ``scene_build`` / ``delta_merge`` (at ``submit_delta``) each hold
          ``scene_wait``, the host blocked on the scene builder's outputs:
          the device's map build plus any device work queued ahead of it
          (an in-flight executor, the batch's earlier builds), and
          ``scene_fetch``, the device→host copies of the scene's level
          sizes and root table into a ``SceneEntry``;
        * ``drain_wait``: the host blocked on a dispatched executor's
          outputs, then ``unpack``.

        ``execute`` (dispatch to ready) and ``queue_wait`` are recorded
        after the fact, not through this context manager."""
        t0 = time.perf_counter()
        with obs.span(name, **attrs) as sp:
            yield sp
        self.stats.observe(name, (time.perf_counter() - t0) * 1e3)

    def _jit_counting(self, fn, kind: str, counter_attr: str,
                      cap: int, rung=None) -> Callable:
        """jit ``fn`` with the trace-time side effect that counts *actual*
        recompiles (not calls) into ``stats.<counter_attr>[cap]``, plus a
        structured ``compile`` trace event carrying (kind, rung, device,
        wall time); ``rung`` (default ``cap``) names the compile in the
        event and in ``compiled_text``.  The side effect fires mid-trace,
        where the compile's duration is unknowable, so it queues a mark;
        the wrapper drains marks after the triggering call returns and
        stamps the event with that call's wall time (trace + compile +
        first execution)."""
        def traced(*args):
            counters = getattr(self.stats, counter_attr)
            counters[cap] = counters.get(cap, 0) + 1
            self._compile_marks.append((kind, cap if rung is None else rung))
            return fn(*args)

        # the XLA module (and the profiler's module events) read jit_<kind>
        traced.__name__ = traced.__qualname__ = kind
        jfn = jax.jit(traced)

        def wrapper(*args):
            n0 = len(self._compile_marks)
            t0 = time.perf_counter()
            out = jfn(*args)
            if len(self._compile_marks) > n0:
                wall_ms = (time.perf_counter() - t0) * 1e3
                marks = self._compile_marks[n0:]
                del self._compile_marks[n0:]
                for k, c in marks:
                    obs.event("compile", kind=k, rung=c,
                              device=self.device_name,
                              wall_ms=round(wall_ms, 3))
                self._compiled[marks[-1]] = (jfn, jax.tree.map(_spec_of,
                                                               args))
            return out

        return wrapper

    def compiled_text(self, kind: str, cap) -> str:
        """Optimized HLO of stage ``kind`` ("executor", "map_builder",
        "plan_builder", "scene_builder", "map_composer", …) as last
        compiled for rung ``cap`` (a (scene rung, bucket) pair for the
        "map_placer") — e.g. to check that a Pallas plan really lowered to
        ``tpu_custom_call`` kernels.  Hits jax's caches: no retrace, and no
        compile counter moves."""
        jfn, specs = self._compiled[(kind, cap)]
        return jfn.lower(*specs).compile().as_text()

    # ------------------------------------------------------------------ jit
    def _builder_for(self, cap: int) -> Callable:
        fn = self._builders.get(cap)
        if fn is None:
            nplan = self.nplan
            fn = self._jit_counting(nplan.build_maps, "map_builder",
                                    "map_compiles", cap)
            self._builders[cap] = fn
        return fn

    def _executor_for(self, cap: int) -> Callable:
        fn = self._executors.get(cap)
        if fn is None:
            binding, cfg, nplan = self.binding, self.cfg, self.nplan

            def run(params, st, maps, plans):
                feats = nplan.apply(params, st, maps, bn_mode="affine",
                                    plans=plans)
                return binding.outputs_of(cfg, st, maps, feats)

            fn = self._jit_counting(run, "executor", "recompiles", cap)
            self._executors[cap] = fn
        return fn

    def _plan_builder_for(self, cap: int) -> Callable:
        """Jitted fresh split-plan build (the cold-batch fallback when no
        per-scene orders exist to compose) — counted separately from map
        compiles so the per-rung map/executor compile contracts hold."""
        fn = self._plan_builders.get(cap)
        if fn is None:
            nplan = self.nplan
            fn = self._jit_counting(nplan.build_split_plans, "plan_builder",
                                    "plan_compiles", cap)
            self._plan_builders[cap] = fn
        return fn

    # ------------------------------------------------------ scene-granular
    def _scene_tensor(self, scene: Scene, cap: int) -> SparseTensor:
        """Single-scene tensor (batch column 0) padded to a scene-ladder
        capacity, with declared bounds matching the packed batches — so its
        KeySpec, and therefore its sorted tables and maps, compose
        bit-identically into batch ones.  Features are irrelevant to
        mapping; a 1-channel zero column keeps the trace tiny."""
        n = scene.num_points
        coords = np.full((cap, 1 + scene.coords.shape[1]), int(INVALID_COORD),
                         np.int32)
        coords[:n, 0] = 0
        coords[:n, 1:] = scene.coords
        st = SparseTensor(coords=jnp.asarray(coords),
                          feats=jnp.zeros((cap, 1), jnp.float32),
                          num_valid=jnp.asarray(n, jnp.int32), stride=1,
                          batch_bound=self.ladder.max_batch,
                          spatial_bound=self.batcher.spatial_bound)
        return st if self.device is None else jax.device_put(st, self.device)

    def _scene_builder_for(self, cap: int) -> Callable:
        fn = self._scene_builders.get(cap)
        if fn is None:
            specs = self.nplan.map_specs
            fn = self._jit_counting(lambda st: scene_entry_arrays(specs, st),
                                    "scene_builder", "scene_compiles", cap)
            self._scene_builders[cap] = fn
        return fn

    def _scene_delta_builder_for(self, cap: int) -> Callable:
        """Like the scene builder, but adopting a delta-merged root table
        (passed as arrays, padded to ``cap``) so the build skips the scene
        argsort — and, when the stream's cell ladder is live, adopting the
        incrementally-updated down-level tables (``lkeys``/``lns``, also
        padded to ``cap``) so no per-level masked-key argsort runs either:
        the whole delta rebuild is lookup joins over adopted tables."""
        fn = self._scene_delta_builders.get(cap)
        if fn is None:
            specs = self.nplan.map_specs

            def build(st, keys, order, lkeys, lns):
                spec = hashing.key_spec_for(st.ndim_space, st.batch_bound,
                                            st.spatial_bound)
                tables = {s: (lkeys[s], None, lns[s]) for s in lkeys}
                maps, k, o = scene_entry_arrays(
                    specs, st, root_table=hashing.CoordTable(spec, keys, order),
                    tables=tables)
                return maps, k, o

            fn = self._jit_counting(build, "scene_delta_builder",
                                    "scene_compiles", cap)
            self._scene_delta_builders[cap] = fn
        return fn

    def _key_spec(self, ndim_space: int) -> hashing.KeySpec:
        """The packed-key spec every scene/batch table of this engine uses
        (bounds are the engine's declared promises)."""
        return hashing.key_spec_for(ndim_space, self.ladder.max_batch,
                                    self.batcher.spatial_bound)

    def _store_scene(self, digest: str, entry: SceneEntry) -> None:
        with self._scene_lock:
            self._scene_store[digest] = entry
            if self.scene_cache_bytes is not None:
                # byte-aware eviction: keep at least the entry just stored
                while (len(self._scene_store) > 1 and
                       sum(e.nbytes for e in self._scene_store.values())
                       > self.scene_cache_bytes):
                    self._scene_store.popitem(last=False)
            while len(self._scene_store) > self.scene_cache_size:
                self._scene_store.popitem(last=False)

    def _fetch_scene_entry(self, built, n: int, cap: int) -> SceneEntry:
        """Wait for a scene builder's outputs (``scene_wait``), fetch its
        level sizes and root table into a ``SceneEntry`` whose maps stay on
        the device (``scene_fetch``; raises when a dilated level
        overflowed), and count the build's valid and scene-rung rows, at
        the root and at the levels below it, and the bytes fetched."""
        with self._phase("scene_wait", cap=cap):
            arrays, keys, order = jax.block_until_ready(built)
        with self._phase("scene_fetch", cap=cap):
            host = jax.device_get((arrays["sizes"], keys, order))
            self.stats.fetch_bytes += sum(np.asarray(x).nbytes
                                          for x in jax.tree.leaves(host))
            ent = scene_entry_from_arrays(self.nplan.map_specs,
                                          {**arrays, "sizes": host[0]}, n,
                                          host[1], host[2])
        self.stats.scene_rows += n
        self.stats.scene_rung_rows += cap
        self.stats.level_rows += sum(ent.sizes.values()) - n
        self.stats.level_cap_rows += cap * (len(ent.sizes) - 1)
        return ent

    def _scene_entries(self, scenes: Sequence[Scene]) -> List[SceneEntry]:
        """The scene-store entries of ``scenes``, building the cold ones: every
        cold scene's build is dispatched before the host waits on any, so
        the device runs them back to back; then each is fetched
        (``scene_build``) and stored."""
        found: Dict[str, SceneEntry] = {}
        cold: Dict[str, tuple] = {}
        for scene in scenes:
            with self._scene_lock:
                ent = self._scene_store.get(scene.digest)
                if ent is not None:
                    self._scene_store.move_to_end(scene.digest)
                    found[scene.digest] = ent
            if ent is not None or scene.digest in cold:
                self.stats.scene_hits += 1
                continue
            self.stats.scene_misses += 1
            cap = self._scene_ladder.select(scene.num_points)
            cold[scene.digest] = (scene, cap, self._scene_builder_for(cap)(
                self._scene_tensor(scene, cap)))
        for digest, (scene, cap, built) in cold.items():
            with self._phase("scene_build", cap=cap, points=scene.num_points):
                ent = self._fetch_scene_entry(built, scene.num_points, cap)
                if self.map_strategy == "incremental":
                    # seed the stream's cell ladder so later deltas propagate
                    # down the pyramid incrementally instead of re-deriving it
                    ent.ladder = cell_ladder(
                        self._key_spec(scene.coords.shape[1]), ent.root_keys,
                        self._down_strides)
            self._store_scene(digest, ent)
            found[digest] = ent
        return [found[s.digest] for s in scenes]

    def _scene_entry(self, scene: Scene) -> SceneEntry:
        return self._scene_entries([scene])[0]

    def _placer_for(self, rung: int, bucket: int) -> Callable:
        """The map composer's placing stage for scenes of ``rung`` in
        ``bucket``, counted in ``stats.compose_compiles[bucket]``."""
        fn = self._placers.get((rung, bucket))
        if fn is None:
            specs = self.nplan.map_specs
            fn = self._jit_counting(
                lambda acc, sc, b, offs: compose_place(specs, acc, sc, b, offs),
                "map_placer", "compose_compiles", bucket, (rung, bucket))
            self._placers[(rung, bucket)] = fn
        return fn

    def _composer_for(self, bucket: int) -> Callable:
        """The map composer's finishing stage for ``bucket``, counted in
        ``stats.compose_compiles[bucket]``."""
        fn = self._composers.get(bucket)
        if fn is None:
            specs = self.nplan.map_specs
            fn = self._jit_counting(
                lambda acc, totals: compose_finish(specs, acc, totals),
                "map_composer", "compose_compiles", bucket)
            self._composers[bucket] = fn
        return fn

    def _compose_blank(self, entry: SceneEntry, bucket: int) -> dict:
        """The empty batch map stack of ``bucket``, on this engine's device,
        made once."""
        blank = self._compose_blanks.get(bucket)
        if blank is None:
            blank = self._compose_blanks[bucket] = jax.device_put(
                compose_blank(entry, bucket), self.device)
        return blank

    def _compose(self, entries: Sequence[SceneEntry], bucket: int):
        """``compose_kmaps`` on this engine's device, through its jitted
        stages (None when composition does not apply)."""
        if self.device is not None:
            # a DeviceRouter's shared store holds other devices' entries
            entries = [dataclasses.replace(
                e, arrays=jax.device_put(e.arrays, self.device))
                for e in entries]

        def place(acc, arrays, b, offs):
            rung = next(iter(arrays["coords"].values())).shape[0]
            return self._placer_for(rung, bucket)(acc, arrays, b, offs)

        return compose_kmaps(self.nplan.map_specs, entries, bucket,
                             place=place, finish=self._composer_for(bucket),
                             blank=self._compose_blank(entries[0], bucket))

    def _maps_for(self, batch: PackedBatch,
                  scenes: Optional[Sequence[Scene]] = None) -> Tuple[dict, dict]:
        """Batch kernel maps + pre-built executor split plans (``{}`` when no
        layer consumes one).  Composed batches also *compose* their plans —
        per-scene stable-sorted bitmask orders merge host-side, so sorted
        dataflows stop paying a per-batch argsort; cold fallbacks build the
        plans jitted alongside the maps."""
        cached = self._map_store.get(batch.digest)
        if cached is not None:
            self.stats.map_hits += 1
            self._map_store.move_to_end(batch.digest)
            return cached
        self.stats.map_misses += 1
        pspecs = self.nplan.split_plan_specs()
        maps = None
        plans: dict = {}
        if scenes is not None and self.map_strategy in ("composed",
                                                        "incremental"):
            # includes nested scene_build spans for any cold scenes
            with self._phase("compose_kmaps", bucket=batch.bucket,
                             scenes=len(scenes)):
                entries = self._scene_entries(scenes)
                maps = self._compose(entries, batch.bucket)
            if maps is not None:
                self.stats.composed_batches += 1
                if pspecs:
                    with self._phase("compose_plans", bucket=batch.bucket):
                        for ref, ns, srt in pspecs:
                            plans[(ref, ns, srt)] = compose_split_plans(
                                self.nplan.map_specs, entries, ref, ns, srt,
                                batch.bucket)
        if maps is None:
            with self._phase("map_build", bucket=batch.bucket):
                maps = self._builder_for(batch.bucket)(batch.st)
                if self._dilated:
                    check_capacity({r: maps[r] for r in self._dilated})
            if pspecs:
                with self._phase("plan_build", bucket=batch.bucket):
                    plans = self._plan_builder_for(batch.bucket)(maps)
        self._map_store[batch.digest] = (maps, plans)
        while len(self._map_store) > self.maps_cache_size:
            self._map_store.popitem(last=False)
        return maps, plans

    # ------------------------------------------------------------------ api
    def submit(self, scene: Scene, stream: Optional[str] = None) -> int:
        """Enqueue one scene; returns a ticket resolved by the next flush.

        stream: optional stream id — remembers the scene as the stream's
        latest frame so later frames can arrive as ``submit_delta`` updates.
        Submitting may trigger an automatic flush (queue depth reaching
        ``flush_count``, or the oldest queued scene exceeding
        ``max_wait_ms``); those results are held for the next ``flush()``
        or ``poll()``.
        """
        if scene.num_points > self.ladder.max_capacity:
            raise ValueError(f"scene of {scene.num_points} rows exceeds the "
                             f"largest bucket ({self.ladder.max_capacity})")
        t = self._next_ticket
        self._next_ticket += 1
        self._queue.append((t, scene, time.perf_counter()))
        self.stats.submitted += 1
        if stream is not None:
            self._streams[stream] = scene
            self._streams.move_to_end(stream)
            while len(self._streams) > self.stream_cache_size:
                self._streams.popitem(last=False)
        self._autoflush()
        return t

    def submit_delta(self, stream: str, delta: SceneDelta) -> int:
        """Enqueue a streaming frame as a delta of the stream's last scene.

        Under the ``"incremental"`` strategy the scene's cached sorted table
        is **delta-merged** (O(r+a) merge, no argsort of the full cloud) and
        the scene's map stack is rebuilt on the merged table, so the frame
        composes into batches like any warm scene; other strategies just
        apply the delta and submit the full scene.
        """
        return self.submit(self._merge_delta(stream, delta), stream=stream)

    def _merge_delta(self, stream: str, delta: SceneDelta) -> Scene:
        """Apply ``delta`` to the stream's last scene and (incremental
        strategy) delta-merge its cached table into a fresh SceneEntry.
        Host-side work only — the router calls this on one worker and the
        resulting store entry composes on every device."""
        prev = self._streams.get(stream)
        if prev is None:
            raise KeyError(f"unknown stream {stream!r}; seed it with "
                           f"submit(scene, stream=...) first")
        if (delta.added_coords.size and
                int(np.abs(delta.added_coords).max()) > self.batcher.spatial_bound):
            # the same declared-bound promise pack() enforces — reject here,
            # BEFORE an out-of-range coord could mis-pack into a cached
            # scene table (host-side np_pack_keys has no PAD sentinel)
            raise ValueError(
                f"delta adds a coord violating declared spatial_bound "
                f"{self.batcher.spatial_bound}: max |coord| = "
                f"{np.abs(delta.added_coords).max()}")
        with self._phase("apply_delta", stream=stream):
            scene = apply_delta(prev, delta)
        if (self.map_strategy == "incremental"
                and scene.digest not in self._scene_store):
            with self._scene_lock:
                prev_ent = self._scene_store.get(prev.digest)
            if prev_ent is not None:
                with self._phase("delta_merge", stream=stream,
                                 added=int(delta.added_coords.shape[0]),
                                 removed=int(delta.removed.shape[0])):
                    spec = self._key_spec(scene.coords.shape[1])
                    rm_rows = np.concatenate(
                        [np.zeros((delta.removed.shape[0], 1), np.int32),
                         delta.removed], 1)
                    ad_rows = np.concatenate(
                        [np.zeros((delta.added_coords.shape[0], 1), np.int32),
                         delta.added_coords], 1)
                    # host-side O(r+a) sorted merge of the cached scene table
                    mkeys, morder = hashing.np_delta_merge(
                        spec, prev_ent.root_keys, prev_ent.root_order,
                        rm_rows, ad_rows)
                    # pad the merged table up to the scene rung — identical to
                    # a fresh build of the padded scene tensor (PAD keys sort
                    # last, pad rows in slot order), so the jitted builder
                    # adopts it transparently
                    n = scene.num_points
                    cap = self._scene_ladder.select(n)
                    pad = (cap - n,) + mkeys.shape[1:]
                    keys = np.concatenate([
                        mkeys, np.full(pad, np.iinfo(np.int32).max, np.int32)])
                    order = np.concatenate([
                        morder, np.arange(n, cap, dtype=np.int32)])
                    # propagate the delta through the cached cell ladder —
                    # every down level's table updates in O(r+a+cells), so
                    # the rebuild below adopts tables at EVERY pyramid level
                    # (no per-level masked-key argsort on the merged root)
                    if prev_ent.ladder:
                        lad = cell_ladder_delta(
                            spec, prev_ent.ladder,
                            hashing.np_pack_keys(rm_rows, spec),
                            hashing.np_pack_keys(ad_rows, spec))
                    else:
                        lad = cell_ladder(spec, mkeys, self._down_strides)
                    tabs = ladder_tables(spec, lad, cap)
                    ent = self._fetch_scene_entry(
                        self._scene_delta_builder_for(cap)(
                            self._scene_tensor(scene, cap), jnp.asarray(keys),
                            jnp.asarray(order),
                            {s: jnp.asarray(t[0]) for s, t in tabs.items()},
                            {s: jnp.asarray(t[2], jnp.int32)
                             for s, t in tabs.items()}),
                        n, cap)
                    ent.ladder = lad
                    self._store_scene(scene.digest, ent)
                    self.stats.delta_merges += 1
        return scene

    def _predicted_service_ms(self) -> float:
        """Predicted service time of one batch: the sum of this engine's own
        median pack/map/dispatch/execute/unpack phase durations (0.0 until
        warm — deadline awareness then degrades to pure age checks)."""
        total = 0.0
        for name in ("pack", "map", "dispatch", "execute", "unpack"):
            window = self.stats.phases.get(name)
            if window:
                total += float(np.median(window))
        return total

    def _deadline_budget_ms(self) -> Optional[float]:
        """The age at which a queued request must start service: plain
        ``max_wait_ms`` by default, shrunk by the predicted service time
        (× ``deadline_margin``) under deadline-aware admission."""
        if self.max_wait_ms is None:
            return None
        if self.deadline_margin is None:
            return self.max_wait_ms
        return self.max_wait_ms - (self.deadline_margin *
                                   self._predicted_service_ms())

    def _deadline_due(self) -> bool:
        budget = self._deadline_budget_ms()
        return (budget is not None and bool(self._queue) and
                (time.perf_counter() - self._queue[0][2]) * 1e3 >= budget)

    def _deadline_cut(self, queue: Sequence[tuple]) -> Optional[int]:
        """Deadline-aware batch cutting: when the oldest request's budget is
        (nearly) blown at flush start, serve it alone instead of co-batching
        it with fresh arrivals — returns the first-group scene cap for
        ``SceneBatcher.plan``."""
        if self.deadline_margin is None or self.max_wait_ms is None:
            return None
        if len(queue) <= 1:
            return None
        age_ms = (time.perf_counter() - queue[0][2]) * 1e3
        if age_ms >= self._deadline_budget_ms():
            self.stats.deadline_cuts += 1
            return 1
        return None

    def _autoflush(self) -> None:
        if self.flush_count is not None and len(self._queue) >= self.flush_count:
            self.stats.count_flushes += 1
            self._ready.update(self._run_queue())
        elif self._deadline_due():
            self.stats.deadline_flushes += 1
            self._ready.update(self._run_queue())

    def poll(self) -> Dict[int, SceneResult]:
        """Deadline hook for timer-driven callers: flush iff the oldest
        queued scene has waited past ``max_wait_ms``, then drain any results
        completed by automatic flushes."""
        if self._deadline_due():
            self.stats.deadline_flushes += 1
            self._ready.update(self._run_queue())
        out, self._ready = self._ready, {}
        return out

    def flush(self) -> Dict[int, SceneResult]:
        """Pack and run everything queued; returns {ticket: SceneResult}
        (including results completed earlier by automatic flushes)."""
        out, self._ready = self._ready, {}
        out.update(self._run_queue())
        return out

    def _dispatch_group(self, scenes: Sequence[Scene]) -> Tuple[PackedBatch, tuple]:
        """Pack ``scenes``, resolve their maps, and dispatch the executor on
        this engine's device *without* blocking — pair with
        ``_finish_group``.  The dispatch/finish split is what lets the
        ``DeviceRouter`` overlap one worker's host-side packing with another
        worker's device execution."""
        with self._phase("pack", scenes=len(scenes)) as sp:
            batch = self.batcher.pack(scenes)
            sp.set(bucket=batch.bucket)
            if self.device is not None:
                batch = dataclasses.replace(
                    batch, st=jax.device_put(batch.st, self.device))
        with self._phase("map", bucket=batch.bucket):
            maps, plans = self._maps_for(batch, scenes)
        with self._phase("dispatch", bucket=batch.bucket,
                         device=self.device_name):
            out = self._executor_for(batch.bucket)(self.params, batch.st,
                                                   maps, plans)
        return batch, out

    def _finish_group(self, batch: PackedBatch, out,
                      t_disp_ns: Optional[int] = None):
        """Block on a dispatched batch and unpack it into per-scene rows.

        ``t_disp_ns`` (pipelined drains) backdates the "execute" span to
        dispatch-return so it covers the device-side window the host
        overlapped — recorded retroactively via ``obs.record_span`` because
        the host was busy with batch k+1 while it ran."""
        t0 = time.perf_counter_ns()
        with self._phase("drain_wait", bucket=batch.bucket):
            out_coords, out_feats, n_out = jax.block_until_ready(out)
        t1 = time.perf_counter_ns()
        start = t0 if t_disp_ns is None else t_disp_ns
        self.stats.observe("execute", (t1 - start) / 1e6)
        obs.record_span("execute", start, t1, bucket=batch.bucket,
                        device=self.device_name)
        with self._phase("unpack", bucket=batch.bucket,
                         scenes=batch.num_scenes):
            per_scene = self.batcher.unpack(batch, out_coords, out_feats,
                                            int(n_out), self.out_stride)
        self.stats.batches += 1
        self.stats.completed += batch.num_scenes
        return per_scene

    def _run_pipeline(self, scene_groups: Sequence[Sequence[Scene]],
                      on_done: Callable,
                      urgent: Optional[Callable[[int], bool]] = None) -> None:
        """Double-buffered group execution: dispatch group k+1 (host pack /
        map compose / executor call — all non-blocking under jax async
        dispatch) while group k executes on device; drain FIFO, bounded by
        ``max_inflight`` dispatched-but-undrained batches.

        Bit-identical to the serial loop at any depth: grouping, packing,
        composition and unpacking are untouched — only the position of
        ``block_until_ready`` moves, and batches are independent.

        on_done(group_index, batch, per_scene) fires at each drain, in
        group order.  urgent(head_group_index) — deadline admission — forces
        draining the oldest in-flight batch before the next dispatch.
        """
        inflight: "collections.deque" = collections.deque()

        def drain_one():
            gi, batch, out, t_disp = inflight.popleft()
            on_done(gi, batch, self._finish_group(batch, out, t_disp))

        for gi, scenes in enumerate(scene_groups):
            while inflight and (len(inflight) >= self.max_inflight or
                                (urgent is not None and urgent(inflight[0][0]))):
                drain_one()
            batch, out = self._dispatch_group(scenes)
            t_disp = time.perf_counter_ns()
            inflight.append((gi, batch, out, t_disp))
            if len(inflight) > self.stats.inflight_peak:
                self.stats.inflight_peak = len(inflight)
        while inflight:
            drain_one()

    def _run_queue(self) -> Dict[int, SceneResult]:
        if not self._queue:
            return {}
        queue, self._queue = self._queue, []
        t0 = time.perf_counter()
        with obs.span("flush", scenes=len(queue), device=self.device_name,
                      max_inflight=self.max_inflight):
            # queue wait = submit → flush start; submit stamped the same
            # monotonic clock the tracer uses, so the interval replays
            # exactly in the trace timeline
            t0_ns = time.perf_counter_ns()
            for ticket, _, t_sub in queue:
                wait_ms = (t0 - t_sub) * 1e3
                self.stats.observe("queue_wait", wait_ms)
                obs.record_span("queue_wait", int(t_sub * 1e9), t0_ns,
                                ticket=ticket)
            results: Dict[int, SceneResult] = {}
            groups = self.batcher.plan([s.num_points for _, s, _ in queue],
                                       cut_first=self._deadline_cut(queue))

            def on_done(gi, batch, per_scene):
                t_done = time.perf_counter()
                t_done_ns = time.perf_counter_ns()
                for slot, i in enumerate(groups[gi]):
                    ticket, _, t_sub = queue[i]
                    results[ticket] = per_scene[slot]
                    lat_ms = (t_done - t_sub) * 1e3
                    self.stats.latencies_ms.append(lat_ms)
                    obs.record_span("request", int(t_sub * 1e9), t_done_ns,
                                    ticket=ticket, bucket=batch.bucket)
                    if self.max_wait_ms is not None:
                        # max_wait_ms doubles as the per-request latency SLO
                        self.stats.slo_observe(lat_ms, self.max_wait_ms)

            urgent = None
            if self.deadline_margin is not None and self.max_wait_ms is not None:
                def urgent(gi):
                    oldest = min(queue[i][2] for i in groups[gi])
                    age_ms = (time.perf_counter() - oldest) * 1e3
                    return age_ms >= self._deadline_budget_ms()

            self._run_pipeline([[queue[i][1] for i in g] for g in groups],
                               on_done, urgent)
        self.stats.busy_s += time.perf_counter() - t0
        self.stats.flushes += 1
        return results

    def serve(self, scenes: Sequence[Scene],
              flush_every: int = 0) -> List[SceneResult]:
        """Convenience driver: submit all, flush (in chunks), return in order."""
        out: Dict[int, SceneResult] = {}
        tickets = []
        for i, s in enumerate(scenes):
            tickets.append(self.submit(s))
            if flush_every and (i + 1) % flush_every == 0:
                out.update(self.flush())
        out.update(self.flush())
        return [out[t] for t in tickets]

    def warmup(self, channels: Optional[int] = None) -> None:
        """Compile every bucket once on synthetic single-scene batches so the
        request stream never pays a trace.  Under the composed/incremental
        strategies this also traces the per-scene builders for every rung of
        the scene-capacity ladder (and the delta builders, for streaming),
        and the map composer's placing stage for every (rung, bucket) pair
        a batch can hold, so batches mixing scene rungs compile nothing."""
        c = channels or self.binding.in_channels_of(self.cfg)
        if self.map_strategy in ("composed", "incremental"):
            specs = self.nplan.map_specs
            prev = 0
            for cap in self._scene_ladder.capacities:
                coords = _box_coords(cap)
                st = self._scene_tensor(
                    Scene(coords=coords,
                          feats=np.zeros((coords.shape[0], c), np.float32)),
                    cap)
                arrays, keys, order = jax.block_until_ready(
                    self._scene_builder_for(cap)(st))
                # the composer's placing stage, in every bucket a scene of
                # this rung (more than ``prev`` rows) can be packed into
                ent = scene_entry_from_arrays(specs, arrays, cap, keys, order)
                offs = {k: (np.int32(0), np.int32(0))
                        for k in ent.arrays["coords"]}
                for bucket in self.ladder.capacities:
                    if prev < bucket:
                        jax.block_until_ready(self._placer_for(cap, bucket)(
                            self._compose_blank(ent, bucket), ent.arrays,
                            np.int32(0), offs))
                prev = cap
                if self.map_strategy == "incremental":
                    # the fresh table doubles as a valid adopted-table input;
                    # derive its cell ladder so the traced pytree structure
                    # matches live delta-merge calls exactly
                    spec = self._key_spec(coords.shape[1])
                    lad = cell_ladder(spec, ent.root_keys, self._down_strides)
                    tabs = ladder_tables(spec, lad, cap)
                    jax.block_until_ready(
                        self._scene_delta_builder_for(cap)(
                            st, keys, order,
                            {s: jnp.asarray(t[0]) for s, t in tabs.items()},
                            {s: jnp.asarray(t[2], jnp.int32)
                             for s, t in tabs.items()}))
        for cap in self.ladder.capacities:
            n = cap   # fill the bucket exactly so every rung compiles
            rng = np.random.default_rng(cap)
            coords = _box_coords(n)
            scene = Scene(coords=coords, feats=rng.normal(size=(n, c)).astype(np.float32))
            # go through the REAL dispatch path: it commits the packed batch
            # to this engine's device, and a warmup executed with any other
            # input placement compiles a *different* executable — the first
            # live batch would silently pay a second compile per rung
            batch, out = self._dispatch_group([scene])
            assert batch.bucket == cap, (batch.bucket, cap)
            jax.block_until_ready(out)

    # ------------------------------------------------------------- autotune
    def tune(self, sample_scenes: Sequence[Scene],
             space: Optional[Sequence[df.DataflowConfig]] = None,
             iters: int = 2, save: bool = True,
             resolve_tiles: bool = False) -> Dict[tuple, TrainDataflowConfig]:
        """Run the group-based Sparse Autotuner on a representative packed
        batch and persist the winning *NetworkPlan* to the PlanRegistry.

        Measurement is end-to-end engine-forward latency of each candidate
        plan (paper §4: never per-kernel time).  Existing executors are
        dropped so the tuned plan takes effect on the next flush.  Returns
        the per-group assignment for inspection; the serialized plan (and
        its v1-compatible assignment block) lands in the registry.

        ``resolve_tiles=True`` adds a measured tile-resolution pass over the
        winner's Pallas implicit-GEMM groups (each candidate (tile_m,
        tile_n) timed end-to-end like the dataflow sweep).  Off by default:
        it multiplies tuning wall-clock by the tile-menu size and only
        matters when the winning assignment uses the Pallas tier.
        """
        space = list(space or df.default_serving_space())
        sample_scenes = list(sample_scenes)
        # measure on the first bucket-fitting FIFO group of the sample
        group = self.batcher.plan([s.num_points for s in sample_scenes])[0]
        group_scenes = [sample_scenes[i] for i in group]
        batch = self.batcher.pack(group_scenes)
        maps, _ = self._maps_for(batch, group_scenes)

        def measure(candidate: NetworkPlan) -> float:
            fn = jax.jit(lambda p, st, m: candidate.apply(p, st, m,
                                                          bn_mode="affine"))
            return timeit_fn(lambda: jax.block_until_ready(
                fn(self.params, batch.st, maps)), warmup=1, iters=iters)

        tuned = PlanTuner(self.nplan, space, measure,
                          maps=maps if resolve_tiles else None).tune()
        self.nplan = tuned
        self.assignment = tuned.assignment()
        self.plans.set(self.plan_key, self.assignment, network=tuned)
        self.plans.set_service(self.plan_key, self.config)
        if save and self.plans.path:
            self.plans.save()
        self._executors.clear()     # recompile with the tuned plan
        self._plan_builders.clear()  # split-plan specs may have changed
        return dict(self.assignment)
