"""Multi-device sharded serving: a device-routed tier above the engine.

The single-device ``Engine`` bounds compile churn with a bucket ladder and
amortizes mapping work across requests; the remaining scaling lever for the
ROADMAP's heavy-traffic north star is putting more devices behind one front
end.  ``DeviceRouter`` owns one bucket-ladder **worker per device** — a
plain ``Engine`` pinned to that device (params and every packed batch land
there via ``jax.device_put``, so each compiled rung's executor is a
per-device artifact: ≤1 compile per (rung, device) after warmup) — and
routes flushed batches between them:

* **load score**: each planned FIFO group is charged at its *padded* row
  count (the bucket capacity it will occupy — what a batch actually costs a
  device) and routed to the worker with the fewest outstanding padded rows;
* **deterministic tie-break**: exact ties fall to a round-robin cursor, so
  a uniform stream degenerates to round-robin and the same stream always
  produces the same device assignment (asserted in tests/test_router.py);
* workers run their assigned batches **concurrently** (one thread per
  worker — XLA execution releases the GIL, so one worker's host-side
  packing/unpacking overlaps another's device compute);
* the **scene store is shared** across workers: an entry stays on the
  device whose worker built it, and a worker on another device copies it
  over to compose, so a scene warmed by any device composes into batches
  on every device;
* each worker resolves its own ``NetworkPlan`` through the
  ``PlanRegistry`` (``arch@devI`` entries when per-device plans were tuned,
  the shared ``arch`` entry otherwise — schema-v2 compatible either way).

Correctness contract (tests/test_router.py): the sharded router's outputs
are **bit-identical** to the single-device engine on the same scene stream
— routing only decides *where* a packed batch executes, never how it is
packed, mapped, or unpacked — and a router with one device degenerates to
the plain engine.

Devices are real accelerators in production; CPU CI shards across
host-platform virtual devices (``XLA_FLAGS=--xla_force_host_platform_
device_count=N`` — see ``launch.mesh.serving_devices``).
"""
from __future__ import annotations

import collections
import concurrent.futures
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro import obs
from repro.launch.mesh import serving_devices
from repro.serve.batcher import Scene, SceneBatcher, SceneDelta, SceneResult
from repro.serve.bucketing import BucketLadder
from repro.serve.engine import (DEFAULT_LADDER, DEFAULT_SPATIAL_BOUND, ARCHS,
                                Engine, EngineStats, PHASE_WINDOW,
                                percentiles_ms, scene_stage_compiles,
                                summarize_phases)
from repro.serve.plans import PlanRegistry, device_key
from repro.serve.service import (STATS_SCHEMA_VERSION, ServiceConfig,
                                 resolve_config)


class RouterStats:
    """Merged view over the per-worker ``EngineStats``.

    ``summary()`` keeps the single-engine schema (``scenes``, ``batches``,
    ``p50_ms``…, so CLI/bench code reads either) and adds a ``devices``
    block: per device, ``routed_batches``, ``queue_depth`` (outstanding
    padded rows right now), and that device's own p50/p95.
    """

    def __init__(self, router: "DeviceRouter"):
        self._router = router
        self.submitted = 0
        self.busy_s = 0.0
        self.flushes = 0
        self.deadline_flushes = 0
        self.count_flushes = 0
        #: (device_index, padded_rows) per routed batch, in routing order —
        #: the determinism contract is over this log
        self.route_log: List[Tuple[int, int]] = []
        # failover accounting: a worker whose shard raises is declared dead
        # and its unfinished groups re-route to the survivors
        self.worker_failures = 0
        self.rerouted_batches = 0
        # router-level phase windows (queue_wait happens before routing, so
        # it belongs to the tier, not to any worker) + SLO accounting
        self.phases: Dict[str, collections.deque] = {}
        self.slo_deadline_ms: Optional[float] = None
        self.slo_measured = 0
        self.slo_miss_count = 0

    def observe(self, phase: str, ms: float) -> None:
        win = self.phases.get(phase)
        if win is None:
            win = self.phases[phase] = collections.deque(maxlen=PHASE_WINDOW)
        win.append(ms)

    def slo_observe(self, latency_ms: float, deadline_ms: float) -> None:
        self.slo_deadline_ms = deadline_ms
        self.slo_measured += 1
        if latency_ms > deadline_ms:
            self.slo_miss_count += 1

    def _merge_counter(self, field: str) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for i, w in enumerate(self._router.workers):
            for cap, n in getattr(w.stats, field).items():
                out[f"d{i}:{cap}"] = n
        return out

    @staticmethod
    def _pctl(lat_deques) -> Tuple[Optional[float], Optional[float]]:
        rows = [np.asarray(d) for d in lat_deques if len(d)]
        if not rows:
            return (None, None)   # idle: report nothing, not a made-up 0.0
        return percentiles_ms(np.concatenate(rows))

    def summary(self) -> dict:
        workers = self._router.workers
        stats: List[EngineStats] = [w.stats for w in workers]
        completed = sum(s.completed for s in stats)
        p50, p95 = self._pctl([s.latencies_ms for s in stats])
        scene_tables = {
            "hits": sum(s.scene_hits for s in stats),
            "misses": sum(s.scene_misses for s in stats),
            "composed_batches": sum(s.composed_batches for s in stats),
            "delta_merges": sum(s.delta_merges for s in stats),
            "rows": sum(s.scene_rows for s in stats),
            "rung_rows": sum(s.scene_rung_rows for s in stats),
            "fetch_bytes": sum(s.fetch_bytes for s in stats),
            "compiles": {f"d{i}:{k}": n for i, w in enumerate(workers)
                         for k, n in scene_stage_compiles(w.stats).items()},
        }
        devices = {}
        for i, w in enumerate(workers):
            dp50, dp95 = self._pctl([w.stats.latencies_ms])
            devices[f"d{i}"] = {
                "device": str(w.device),
                "alive": i not in self._router.dead,
                "routed_batches": w.stats.routed_batches,
                "queue_depth": self._router.outstanding_rows[i],
                "scenes": w.stats.completed,
                "p50_ms": dp50,
                "p95_ms": dp95,
            }
        # per-phase windows merged across the tier: router-level phases
        # (queue_wait) + every worker's (pack/map/execute/unpack/…)
        windows: Dict[str, list] = {}
        for holder in [self] + stats:
            for name, win in holder.phases.items():
                windows.setdefault(name, []).extend(win)
        slo_measured = self.slo_measured + sum(s.slo_measured for s in stats)
        slo_misses = (self.slo_miss_count
                      + sum(s.slo_miss_count for s in stats))
        return {
            "schema_version": STATS_SCHEMA_VERSION,
            "scenes": completed,
            "batches": sum(s.batches for s in stats),
            "routed_batches": sum(s.routed_batches for s in stats),
            "p50_ms": p50,
            "p95_ms": p95,
            "scenes_per_s": completed / self.busy_s if self.busy_s else 0.0,
            "recompiles": self._merge_counter("recompiles"),
            "map_compiles": self._merge_counter("map_compiles"),
            "plan_compiles": self._merge_counter("plan_compiles"),
            "map_cache": {"hits": sum(s.map_hits for s in stats),
                          "misses": sum(s.map_misses for s in stats)},
            "scene_tables": scene_tables,
            "deadline_flushes": self.deadline_flushes,
            "count_flushes": self.count_flushes,
            "deadline_cuts": sum(s.deadline_cuts for s in stats),
            "pipeline": {
                "inflight_peak": max((s.inflight_peak for s in stats),
                                     default=0)},
            "phases": summarize_phases(windows),
            "slo": {
                "deadline_ms": self.slo_deadline_ms,
                "measured": slo_measured,
                "misses": slo_misses,
                "miss_rate": (slo_misses / slo_measured
                              if slo_measured else None),
            },
            "devices": devices,
            "failover": {
                "dead": sorted(f"d{i}" for i in self._router.dead),
                "worker_failures": self.worker_failures,
                "rerouted_batches": self.rerouted_batches,
            },
        }


class DeviceRouter:
    """Engine-compatible front end sharding one request stream over devices.

    devices: an int (take the first N jax devices; raises with the
        ``XLA_FLAGS`` hint when fewer are attached), an explicit device
        sequence, or None for every visible device.
    parallel: run workers' assigned batches in one thread per worker
        (default).  False serializes workers on the caller thread — same
        results, useful for debugging; routing is identical either way.
    max_inflight / deadline_margin / scene_cache_bytes are forwarded to
        every worker: each device runs its assigned shard through the
        engine's double-buffered pipeline, so one worker overlaps its *own*
        host mapping with its own device compute on top of the cross-worker
        thread overlap.
    Remaining behavioral knobs come from ``config=ServiceConfig(...)``
        (legacy per-kwarg spelling still works — see ``Engine``); the
        config is forwarded to every worker with its per-device plan key.
    """

    def __init__(self, arch: str, devices=None,
                 config: Optional[ServiceConfig] = None,
                 model_config=None, params=None,
                 plans: Optional[PlanRegistry] = None,
                 precision=None, parallel: bool = True, **legacy):
        if arch not in ARCHS:
            raise ValueError(f"unknown arch {arch!r}; have {sorted(ARCHS)}")
        if isinstance(config, BucketLadder):   # (arch, devices, ladder) callers
            legacy.setdefault("ladder", config)
            config = None
        self.config = resolve_config(config, legacy)
        cfg_s = self.config
        if isinstance(devices, int) or devices is None:
            devices = serving_devices(devices)
        self.devices = list(devices)
        assert self.devices, "DeviceRouter needs at least one device"
        self.arch = arch
        self.ladder = cfg_s.ladder()
        self.parallel = parallel
        self.max_wait_ms = cfg_s.max_wait_ms
        self.flush_count = cfg_s.flush_count
        self.max_inflight = cfg_s.max_inflight
        self.deadline_margin = cfg_s.deadline_margin
        if isinstance(plans, str):
            plans = PlanRegistry.load(plans)
        self.plans = plans or PlanRegistry()
        binding = ARCHS[arch]
        cfg = model_config if model_config is not None else binding.default_config
        if params is None:
            params = binding.model.init_params(cfg,
                                               jax.random.PRNGKey(cfg_s.seed))
        self.workers: List[Engine] = [
            Engine(arch,
                   config=cfg_s.replace(
                       plan_key=self.plans.resolve_key(arch, i)),
                   model_config=cfg, params=params, plans=self.plans,
                   precision=precision, device=dev)
            for i, dev in enumerate(self.devices)]
        # one scene store (and guard) for the whole tier: any worker's build
        # serves every device (composition copies it to the composing one)
        for w in self.workers[1:]:
            w._scene_store = self.workers[0]._scene_store
            w._scene_lock = self.workers[0]._scene_lock
            w._streams = self.workers[0]._streams
        self._streams = self.workers[0]._streams
        self.batcher: SceneBatcher = self.workers[0].batcher
        self.stats = RouterStats(self)
        self.outstanding_rows = [0] * len(self.workers)
        #: worker indices declared dead by a raising shard — excluded from
        #: routing; their unfinished groups re-route to the survivors
        self.dead: set = set()
        self._rr = 0                       # round-robin cursor for tie-breaks
        self._queue: List[tuple] = []      # (ticket, Scene, t_submit)
        self._next_ticket = 0
        self._ready: Dict[int, SceneResult] = {}
        # Persistent pool, capped at the host's core count: more worker
        # threads than cores just thrash the intra-op pools (measured ~10%
        # slower on a 2-core host), and results don't depend on pool size —
        # routing is fixed before execution starts.
        self._pool: Optional[concurrent.futures.ThreadPoolExecutor] = None
        if self.parallel and len(self.workers) > 1:
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=min(len(self.workers), os.cpu_count() or 1),
                thread_name_prefix="router-worker")

    @property
    def num_devices(self) -> int:
        return len(self.workers)

    # ---------------------------------------------------------------- route
    def _route(self, padded_rows: int) -> int:
        """Worker index for a batch costing ``padded_rows``: least
        outstanding padded rows over *live* workers; exact ties fall to the
        round-robin cursor.  Deterministic in the sequence of routed row
        counts and the liveness state."""
        loads = self.outstanding_rows
        n = len(loads)
        live = [i for i in range(n) if i not in self.dead]
        if not live:
            raise RuntimeError("all router workers are dead")
        lo = min(loads[i] for i in live)
        pick = min((i for i in live if loads[i] == lo),
                   key=lambda i: (i - self._rr) % n)
        obs.event("route", device=f"d{pick}",
                  device_name=str(self.devices[pick]), rows=padded_rows,
                  loads=list(loads))
        self._rr = (pick + 1) % n
        loads[pick] += padded_rows
        self.stats.route_log.append((pick, padded_rows))
        return pick

    # ------------------------------------------------------------------ api
    def submit(self, scene: Scene, stream: Optional[str] = None) -> int:
        """Enqueue one scene (ticket resolved by the next flush); identical
        semantics to ``Engine.submit`` including the auto-flush triggers."""
        if scene.num_points > self.ladder.max_capacity:
            raise ValueError(f"scene of {scene.num_points} rows exceeds the "
                             f"largest bucket ({self.ladder.max_capacity})")
        t = self._next_ticket
        self._next_ticket += 1
        self._queue.append((t, scene, time.perf_counter()))
        self.stats.submitted += 1
        if stream is not None:
            w0 = self.workers[0]
            self._streams[stream] = scene
            self._streams.move_to_end(stream)
            while len(self._streams) > w0.stream_cache_size:
                self._streams.popitem(last=False)
        self._autoflush()
        return t

    def submit_delta(self, stream: str, delta: SceneDelta) -> int:
        """Streaming frame as a delta of the stream's last scene.  The
        delta-merge itself is host-side work on the *shared* scene store, so
        it runs on worker 0's machinery and the refreshed entry composes on
        whichever device the batch is later routed to."""
        scene = self.workers[0]._merge_delta(stream, delta)
        return self.submit(scene, stream=stream)

    def _deadline_due(self) -> bool:
        # worker 0 holds the tier's deadline budget: plain ``max_wait_ms``
        # by default, shrunk by the predicted service time under
        # ``deadline_margin`` (its phase windows are as warm as any worker's)
        budget = self.workers[0]._deadline_budget_ms()
        return (budget is not None and bool(self._queue) and
                (time.perf_counter() - self._queue[0][2]) * 1e3 >= budget)

    def _autoflush(self) -> None:
        if self.flush_count is not None and len(self._queue) >= self.flush_count:
            self.stats.count_flushes += 1
            self._ready.update(self._run_queue())
        elif self._deadline_due():
            self.stats.deadline_flushes += 1
            self._ready.update(self._run_queue())

    def poll(self) -> Dict[int, SceneResult]:
        if self._deadline_due():
            self.stats.deadline_flushes += 1
            self._ready.update(self._run_queue())
        out, self._ready = self._ready, {}
        return out

    def flush(self) -> Dict[int, SceneResult]:
        out, self._ready = self._ready, {}
        out.update(self._run_queue())
        return out

    def _run_queue(self) -> Dict[int, SceneResult]:
        if not self._queue:
            return {}
        queue, self._queue = self._queue, []
        t0 = time.perf_counter()
        with obs.span("flush", scenes=len(queue),
                      devices=len(self.workers)):
            results = self._flush_queue(queue, t0)
        self.stats.busy_s += time.perf_counter() - t0
        self.stats.flushes += 1
        return results

    def _flush_queue(self, queue: List[tuple],
                     t0: float) -> Dict[int, SceneResult]:
        t0_ns = time.perf_counter_ns()
        for ticket, _, t_sub in queue:
            self.stats.observe("queue_wait", (t0 - t_sub) * 1e3)
            obs.record_span("queue_wait", int(t_sub * 1e9), t0_ns,
                            ticket=ticket)
        sizes = [s.num_points for _, s, _ in queue]
        # identical FIFO grouping to the single-device engine (bit-identity
        # contract), then each whole group is routed to one device; worker
        # 0's deadline-cut (margin-aware) caps the head group exactly as the
        # single engine would
        groups = self.batcher.plan(sizes,
                                   cut_first=self.workers[0]._deadline_cut(queue))
        pending = [(group, self.ladder.group_capacity([sizes[i] for i in group]))
                   for group in groups]
        completed: List[tuple] = []     # (group, per_scene, t_done)

        def run_shard(wi: int, items):
            """Run one worker's assigned groups; a raising batch doesn't
            propagate — it declares the worker failed and hands its
            unfinished groups back for re-routing."""
            w = self.workers[wi]
            done = []
            n_done = 0

            def on_done(k, batch, per_scene):
                # fires at each pipeline drain, in shard order: settle the
                # load score the moment the batch's results exist
                nonlocal n_done
                group, rows = items[k]
                self.outstanding_rows[wi] -= rows
                n_done += 1
                w.stats.routed_batches += 1
                done.append((wi, group, per_scene, time.perf_counter()))

            urgent = None
            if self.deadline_margin is not None and self.max_wait_ms is not None:
                def urgent(k):
                    oldest = min(queue[i][2] for i in items[k][0])
                    budget = w._deadline_budget_ms()
                    return (budget is not None and
                            (time.perf_counter() - oldest) * 1e3 >= budget)

            err = None
            try:
                with obs.span("shard", device=f"d{wi}",
                              device_name=str(w.device),
                              batches=len(items)):
                    w._run_pipeline(
                        [[queue[i][1] for i in group] for group, _ in items],
                        on_done, urgent)
            except Exception as e:        # device loss / injected failure
                err = e
            finally:
                # an aborted shard: un-charge every unprocessed group, or
                # the leaked load score would bias routing away from a
                # healthy worker forever
                for _, rows in items[n_done:]:
                    self.outstanding_rows[wi] -= rows
            return done, items[n_done:], err

        while pending:
            shards: List[list] = [[] for _ in self.workers]
            for item in pending:
                shards[self._route(item[1])].append(item)
            pending = []
            active = [wi for wi in range(len(self.workers)) if shards[wi]]
            if self._pool is not None and len(active) > 1:
                finished = list(self._pool.map(
                    lambda wi: run_shard(wi, shards[wi]), active))
            else:
                finished = [run_shard(wi, shards[wi]) for wi in active]
            for wi, (done, failed, err) in zip(active, finished):
                completed.extend(done)
                if err is None:
                    continue
                # failover: declare the worker dead, re-route what it did
                # not finish to the survivors (groups are idempotent —
                # re-execution yields bit-identical rows)
                self.dead.add(wi)
                self.stats.worker_failures += 1
                self.stats.rerouted_batches += len(failed)
                pending.extend(failed)
                obs.event("worker_down", device=f"d{wi}",
                          rerouted=len(failed), error=repr(err))
                if not any(i not in self.dead
                           for i in range(len(self.workers))):
                    raise RuntimeError(
                        f"all router workers dead with {len(pending)} "
                        f"batches outstanding") from err

        results: Dict[int, SceneResult] = {}
        for wi, group, per_scene, t_done in completed:
            for slot, i in enumerate(group):
                ticket, _, t_sub = queue[i]
                results[ticket] = per_scene[slot]
                lat_ms = (t_done - t_sub) * 1e3
                self.workers[wi].stats.latencies_ms.append(lat_ms)
                obs.record_span("request", int(t_sub * 1e9),
                                int(t_done * 1e9), ticket=ticket,
                                device=f"d{wi}")
                if self.max_wait_ms is not None:
                    # max_wait_ms doubles as the per-request latency SLO
                    self.stats.slo_observe(lat_ms, self.max_wait_ms)
        return results

    def serve(self, scenes: Sequence[Scene],
              flush_every: int = 0) -> List[SceneResult]:
        """Submit all, flush (in chunks), return in submission order."""
        out: Dict[int, SceneResult] = {}
        tickets = []
        for i, s in enumerate(scenes):
            tickets.append(self.submit(s))
            if flush_every and (i + 1) % flush_every == 0:
                out.update(self.flush())
        out.update(self.flush())
        return [out[t] for t in tickets]

    def warmup(self, channels: Optional[int] = None) -> None:
        """Compile every (rung, device) once so the request stream never
        pays a trace.  Workers warm concurrently when ``parallel`` — XLA
        compilation releases the GIL too."""
        if self._pool is not None:
            list(self._pool.map(lambda w: w.warmup(channels), self.workers))
        else:
            for w in self.workers:
                w.warmup(channels)

    def tune(self, sample_scenes: Sequence[Scene], space=None, iters: int = 2,
             save: bool = True, per_device: bool = True) -> Dict[int, dict]:
        """Tune each worker on its own device and persist per-device plans.

        per_device: write each worker's tuned ``NetworkPlan`` under its
        ``arch@devI`` registry name (heterogeneous fleets tune apart);
        False re-tunes the shared ``arch`` entry instead (last one wins —
        homogeneous fleets).  Returns {device_index: assignment}.
        """
        out: Dict[int, dict] = {}
        for i, w in enumerate(self.workers):
            w.plan_key = device_key(self.arch, i) if per_device else self.arch
            out[i] = w.tune(sample_scenes, space=space, iters=iters,
                            save=False)
        if save and self.plans.path:
            self.plans.save()
        return out
