"""Serving-engine throughput/latency: bucketed batching + map reuse.

The production question behind the ROADMAP north star: what does the sparse
stack sustain under mixed-size request traffic?  For each arch
(MinkUNet-KITTI segmentation, CenterPoint-Waymo detection) this suite
drives the same synthetic stream through:

* ``batched``   — the serving engine with its bucket ladder (warm, jitted);
* ``unbatched`` — the same engine restricted to one scene per batch
  (the "per-request forward" a naive deployment does);
* ``repeat``    — the stream replayed through the warm engine: identical
  packed batches hit the cross-request map cache, so the second epoch skips
  kernel-map construction entirely (hit rate in the derived column);
* ``pipelined``  — the same warm stream through a depth-2 double-buffered
  engine vs the serial (depth-1) engine, interleaved epochs: host
  scene-build/compose/pack of batch k+1 overlaps device execution of batch
  k, reported with the in-flight peak from ``summary()['pipeline']``
  (the device trace, not a host window, says how much overlapped);
* ``plan_compose`` — the executor-input composition in isolation: batch
  ``_maps_for`` (kernel maps + ``SplitPlan``s for a pallas implicit-GEMM
  assignment) under the composed strategy (host-side merge of cached
  per-scene orders) vs the jitted fallback that argsorts per batch;
* ``saturated``  — overload with a deadline: requests arrive faster than
  they are served against a deadline ≈ 3× the warm batch service time.
  Run twice — legacy age-based flushing, then deadline-aware admission
  (``deadline_margin``), which flushes early enough that service completes
  inside the budget; the two SLO miss rates are the contract;
* ``sharded``   — with ``--devices N`` (or several visible jax devices):
  the replayed stream through a ``DeviceRouter`` sharding the same ladder
  over N devices vs the single-device engine.  CPU CI uses host-platform
  virtual devices (``XLA_FLAGS=--xla_force_host_platform_device_count=N``);
  on one shared CPU the speedup is pipelining (one worker's host packing
  overlapping another's compute), on real accelerators it is parallelism.

Emits scenes/s and p50/p95 per-scene latency.  ``--tiny`` shrinks the
stream and ladder for CI smoke coverage.
"""
from __future__ import annotations

import argparse
import statistics
import time

import jax

from benchmarks import common
from repro import obs
from repro.serve.bucketing import BucketLadder
from repro.serve.engine import ARCHS, Engine, EngineStats
from repro.serve.router import DeviceRouter
from repro.serve.workload import lidar_stream


def _ms(v) -> str:
    """Derived-column formatting for maybe-None millisecond stats."""
    return "none" if v is None else f"{v:.1f}"


def _emit_phases(arch: str, tag: str, s: dict) -> None:
    """One row per recorded phase (median µs) — the per-phase trend lines
    check_regression.py gates on."""
    for name, ph in s.get("phases", {}).items():
        if ph["p50_ms"] is None:
            continue
        common.emit(f"serving/{arch}/{tag}/phase/{name}",
                    ph["p50_ms"] * 1e3,
                    f"count={ph['count']};p95_ms={_ms(ph['p95_ms'])}")


def _drive(arch: str, scenes, bound: int, ladder: BucketLadder,
           flush_every: int, tag: str, epochs: int = 1):
    eng = Engine(arch, ladder=ladder, spatial_bound=bound)
    eng.warmup()
    eng.stats = EngineStats()   # steady state only: warmup compiles excluded,
    for _ in range(epochs):     # so recompiles should stay 0
        eng.serve(scenes, flush_every=flush_every)
    s = eng.stats.summary()
    mc = s["map_cache"]
    hit_rate = mc["hits"] / max(mc["hits"] + mc["misses"], 1)
    derived = (f"scenes_per_s={s['scenes_per_s']:.2f};p95_ms={_ms(s['p95_ms'])};"
               f"recompiles={sum(s['recompiles'].values())};"
               f"map_hit_rate={hit_rate:.2f}")
    common.emit(f"serving/{arch}/{tag}/p50", (s["p50_ms"] or 0.0) * 1e3,
                derived)
    if tag == "batched":
        _emit_phases(arch, tag, s)
    return s


def _pipelined_leg(arch: str, scenes, bound: int, ladder: BucketLadder,
                   reps: int):
    """Warm replayed stream, depth-2 pipelined engine vs the serial
    (depth-1) engine — interleaved alternating-order epochs, best-of
    timing.  The two engines run the identical workload, so scheduler
    noise is strictly additive and the min is the clean estimate of each
    path's cost (medians at this epoch length still wobble a few percent
    either way on a loaded core).  Each epoch submits the full stream and
    flushes once, so every flush holds several groups for the in-flight
    window to overlap."""
    serial = Engine(arch, ladder=ladder, spatial_bound=bound, max_inflight=1)
    pipe = Engine(arch, ladder=ladder, spatial_bound=bound, max_inflight=2)
    for eng in (serial, pipe):
        eng.warmup()
        eng.serve(scenes, flush_every=0)        # warm maps/digests
        eng.stats = EngineStats()
    s_times, p_times = [], []
    for rep in range(max(reps, 11)):
        # alternate within-pair order so frequency/cache drift across the
        # run cancels out of the pair
        pair = ((serial, s_times), (pipe, p_times))
        for eng, sink in (pair if rep % 2 == 0 else pair[::-1]):
            t0 = time.perf_counter()
            eng.serve(scenes, flush_every=0)
            sink.append(time.perf_counter() - t0)
    n = len(scenes)
    s_sps = n / min(s_times)
    p_sps = n / min(p_times)
    ratio = p_sps / s_sps
    s = pipe.stats.summary()
    pl = s["pipeline"]
    common.emit(
        f"serving/{arch}/pipelined/epoch",
        min(p_times) * 1e6,
        f"scenes_per_s={p_sps:.2f};serial_scenes_per_s={s_sps:.2f};"
        f"inflight_peak={pl['inflight_peak']};"
        f"recompiles={sum(s['recompiles'].values())}")
    common.emit(f"serving/{arch}/pipelined_vs_serial", 0.0,
                f"throughput_ratio={ratio:.2f}x")
    _emit_phases(arch, "pipelined", s)
    _emit_phases(arch, "serial", serial.stats.summary())


def _plan_compose_leg(arch: str, scenes, bound: int, ladder: BucketLadder,
                      reps: int):
    """The executor-input composition in isolation: per-batch ``SplitPlan``
    build for a pallas implicit-GEMM assignment, merge-composing the cached
    per-scene stable orders on the host vs the jitted builder that re-runs
    the bitmask argsorts on every batch.  Same composed batch maps for
    both; plans are built, never executed, so the leg runs everywhere."""
    from repro.core import dataflows as df
    from repro.core.kmap import compose_split_plans
    from repro.core.sparse_conv import TrainDataflowConfig
    from repro.serve.plans import PlanRegistry

    reg = PlanRegistry()
    reg.set(arch, {(1, 3, "sub"): TrainDataflowConfig.bind_all(
        df.DataflowConfig("implicit_gemm", n_splits=2, backend="pallas"))})
    eng = Engine(arch, ladder=ladder, spatial_bound=bound, plans=reg,
                 map_strategy="composed")
    specs = eng.nplan.split_plan_specs()
    assert specs, "pallas igemm assignment lost"
    # first bucket-fitting FIFO group, exactly as a flush would form it
    group_idx = eng.batcher.plan([s.num_points for s in scenes])[0]
    group = [scenes[i] for i in group_idx]
    batch = eng.batcher.pack(group)
    entries = eng._scene_entries(group)
    maps = eng._compose(entries, batch.bucket)     # the device composer
    builder = eng._plan_builder_for(batch.bucket)

    def composed():
        return [compose_split_plans(eng.nplan.map_specs, entries, ref, ns,
                                    srt, batch.bucket)
                for ref, ns, srt in specs]

    def jitted():
        return builder(maps)

    jax.block_until_ready(jax.tree.leaves(composed()))  # warm: caches the
    jax.block_until_ready(jax.tree.leaves(jitted()))    # runs / the trace
    # interleaved best-of (timeit convention): both builders are a few
    # hundred µs, where scheduler noise is strictly additive — the min is
    # the clean measurement, and interleaving exposes both paths to the
    # same machine state
    t = {"composed": [], "jitted": []}
    for _ in range(max(reps, 15)):
        for tag, fn in (("composed", composed), ("jitted", jitted)):
            t0 = time.perf_counter()
            jax.block_until_ready(jax.tree.leaves(fn()))
            t[tag].append(time.perf_counter() - t0)
    times = {tag: min(v) for tag, v in t.items()}
    common.emit(
        f"serving/{arch}/plan_compose/batch", times["composed"] * 1e6,
        f"jitted_us={times['jitted'] * 1e6:.1f};"
        f"speedup={times['jitted'] / max(times['composed'], 1e-12):.2f}x;"
        f"specs={len(specs)}")


def _pallas_leg(arch: str, scenes, bound: int, ladder: BucketLadder,
                reps: int):
    """The Pallas kernel tier vs the XLA dataflow on one packed batch's
    stem layer: dense-grid implicit GEMM and the tile-skipping worklist
    variant, with the *effective* backend of each config in the derived
    column.  On CPU containers the Pallas numbers are interpret-mode
    (kernel logic under the Pallas interpreter — orders slower than XLA,
    and the ratio is informational only); the leg's job in CI is to pin
    the tier as measurable and bit-exact everywhere, so the same sweep
    reports real MXU ratios the day it lands on a TPU."""
    from repro.core import dataflows as df
    from repro.kernels.common import default_interpret

    eng = Engine(arch, ladder=ladder, spatial_bound=bound)
    group = eng.batcher.plan([s.num_points for s in scenes])[0]
    gs = [scenes[i] for i in group]
    batch = eng.batcher.pack(gs)
    maps, _ = eng._maps_for(batch, gs)
    lp = eng.nplan.layers[0]
    kmap = maps[lp.map_ref]
    w = eng.params[lp.name]["w"]
    x = batch.st.feats
    tm = 16 if default_interpret() else 128
    cfgs = {
        "xla": df.DataflowConfig("implicit_gemm", n_splits=1),
        "pallas": df.DataflowConfig("implicit_gemm", n_splits=1,
                                    backend="pallas", tile_m=tm),
        "pallas_worklist": df.DataflowConfig("implicit_gemm", n_splits=1,
                                             backend="pallas", tile_m=tm,
                                             worklist=True),
    }
    times = {}
    for tag, cfg in cfgs.items():
        plan = df.plan_for(kmap, cfg)   # eager: worklist needs concrete occ
        call = lambda cfg=cfg, plan=plan: df.sparse_conv_forward(
            x, w, kmap, cfg, plan=plan)
        fn = call if cfg.worklist else jax.jit(call)
        times[tag] = common.time_fn(fn, warmup=1, iters=reps)
        common.emit(f"serving/{arch}/kernel_tier/{tag}", times[tag],
                    f"effective_backend={cfg.effective_backend('fwd')}")
    common.emit(
        f"serving/{arch}/kernel_tier_ratio", 0.0,
        f"pallas_vs_xla={times['xla'] / max(times['pallas'], 1e-9):.2f}x;"
        f"worklist_vs_dense="
        f"{times['pallas'] / max(times['pallas_worklist'], 1e-9):.2f}x;"
        f"interpret={default_interpret()}")


def _drive_deadline(eng: Engine, scenes, deadline_ms: float) -> dict:
    """Poll-driven overload: arrivals every 0.25×deadline, so the queue
    always holds work while a batch is in service and every flush is
    deadline-triggered (no flush_count, no manual flush)."""
    results = {}
    gap_s = 0.25 * deadline_ms / 1e3
    for s in scenes:
        eng.submit(s)
        t_end = time.perf_counter() + gap_s
        while time.perf_counter() < t_end:
            results.update(eng.poll())
            time.sleep(0.02 * deadline_ms / 1e3)
    while len(results) < len(scenes):
        results.update(eng.poll())
        time.sleep(0.05 * deadline_ms / 1e3)
    return results


def _saturating_leg(arch: str, scenes, bound: int, ladder: BucketLadder):
    """Overload against an *achievable* deadline (≈3× the warm batch
    service time), twice: legacy age-based flushing first — the head
    request starts service only once its whole budget is spent, so adding
    service time blows the SLO — then deadline-aware admission
    (``deadline_margin``), which subtracts predicted service from the
    budget and cuts batches for about-to-expire heads.  The pair of miss
    rates is the acceptance contract (aware < legacy)."""
    stats = {}
    for tag, margin in (("saturated", None), ("saturated_margin", 1.5)):
        eng = Engine(arch, ladder=ladder, spatial_bound=bound,
                     deadline_margin=margin)
        eng.warmup()
        eng.serve(scenes, flush_every=0)        # warm maps + phase windows
        deadline_ms = 3.0 * eng._predicted_service_ms()
        eng.max_wait_ms = deadline_ms           # SLO armed after warm-in
        n0, m0 = eng.stats.slo_measured, eng.stats.slo_miss_count
        results = _drive_deadline(eng, scenes, deadline_ms)
        assert len(results) == len(scenes)
        s = eng.stats.summary()
        measured = eng.stats.slo_measured - n0
        misses = eng.stats.slo_miss_count - m0
        miss_rate = misses / max(measured, 1)
        stats[tag] = miss_rate
        common.emit(
            f"serving/{arch}/{tag}/p95",
            (s["p95_ms"] or 0.0) * 1e3,
            f"scenes_per_s={s['scenes_per_s']:.2f};"
            f"slo_deadline_ms={deadline_ms:.1f};"
            f"slo_miss_rate={miss_rate:.2f};"
            f"slo_misses={misses};slo_measured={measured};"
            f"deadline_flushes={s['deadline_flushes']};"
            f"deadline_cuts={s['deadline_cuts']}")
    common.emit(f"serving/{arch}/saturated_margin_vs_legacy", 0.0,
                f"legacy_miss_rate={stats['saturated']:.2f};"
                f"aware_miss_rate={stats['saturated_margin']:.2f}")
    return stats


def _sharded_leg(arch: str, scenes, bound: int, ladder: BucketLadder,
                 n_dev: int, reps: int):
    """Replayed-stream throughput, DeviceRouter over ``n_dev`` devices vs
    the single-device engine at the SAME serving config.

    Both variants are co-resident and their replay epochs interleave
    (engine, router, engine, router, …) with the ratio taken over medians —
    the same drift-cancelling protocol bench_streaming uses; sequential
    whole-variant timing on a shared CPU box swung ±2× run to run.  Each
    epoch submits the full stream and flushes once, so every batch in the
    queue is a routable unit.
    """
    eng = Engine(arch, ladder=ladder, spatial_bound=bound)
    rt = DeviceRouter(arch, devices=n_dev, ladder=ladder, spatial_bound=bound)
    eng.warmup()
    rt.warmup()
    eng.serve(scenes, flush_every=0)    # warm-in replay: scene builds,
    rt.serve(scenes, flush_every=0)     # digest caches, routing state
    eng.stats = EngineStats()           # steady state only below: reported
    for w in rt.workers:                # recompiles/routed_batches cover the
        w.stats = EngineStats()         # measured epochs, not warmup
    rt.stats.busy_s, rt.stats.flushes = 0.0, 0
    rt.stats.route_log.clear()
    e_times, r_times = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        eng.serve(scenes, flush_every=0)
        e_times.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        rt.serve(scenes, flush_every=0)
        r_times.append(time.perf_counter() - t0)
    n = len(scenes)
    e_sps = n / statistics.median(e_times)
    r_sps = n / statistics.median(r_times)
    s = rt.stats.summary()
    routed = ",".join(str(d["routed_batches"]) for d in s["devices"].values())
    common.emit(
        f"serving/{arch}/sharded_d{n_dev}/epoch",
        statistics.median(r_times) * 1e6,
        f"scenes_per_s={r_sps:.2f};single_scenes_per_s={e_sps:.2f};"
        f"recompiles={sum(s['recompiles'].values())};routed_batches={routed}")
    common.emit(f"serving/{arch}/sharded_vs_single", 0.0,
                f"throughput_ratio={r_sps / e_sps:.2f}x;devices={n_dev}")


def run_fleet(tiny: bool = False, hosts: int = 2):
    """Fleet-tier leg (its own suite — it spawns worker *processes*): the
    same replayed warm stream through ``FleetFrontend`` over N localhost
    workers vs a ``DeviceRouter`` vs the single-device ``Engine``, all at
    the same ``ServiceConfig``.  Epochs interleave across the three tiers
    (drift-cancelling, as in the sharded leg) and the fleet results are
    asserted bit-identical to the engine's before any timing is reported —
    the RPC boundary must not change a single row.  On one localhost box
    the fleet ratio prices the wire codec + socket hop; across real hosts
    the same rows measure scale-out."""
    import numpy as np

    from repro.serve.fleet import FleetFrontend, local_tpu_chips
    from repro.serve.service import ServiceConfig

    if local_tpu_chips():
        # the spawned worker holds the chips, so the in-process tiers could
        # only run on the CPU — and a TPU-vs-CPU ratio prices nothing
        raise RuntimeError(
            "fleet_serving compares a spawned worker with in-process Engine "
            "and DeviceRouter tiers, which cannot share this host's TPU "
            "chips with it; run it with JAX_PLATFORMS=cpu (every tier on "
            "the CPU) or on a host without chips")
    arch = "minkunet_kitti"
    if tiny:
        count, n_range = 6, (80, 400)
        ladder = BucketLadder((256, 512), max_batch=3)
        reps = 5
    else:
        count, n_range = 24, (200, 1200)
        ladder = BucketLadder((512, 1024, 2048), max_batch=4)
        reps = 3
    channels = ARCHS[arch].in_channels_of(ARCHS[arch].default_config)
    scenes, bound = lidar_stream(0, count, channels, n_range=n_range)
    cfg = ServiceConfig.from_ladder(ladder, spatial_bound=bound)
    eng = Engine(arch, config=cfg)
    rt = DeviceRouter(arch, devices=jax.device_count(), config=cfg)
    fl = FleetFrontend(arch, hosts=hosts, config=cfg)
    try:
        warm = {}
        for tag, svc in (("engine", eng), ("router", rt), ("fleet", fl)):
            svc.warmup()
            warm[tag] = svc.serve(scenes, flush_every=0)
        for a, b in zip(warm["fleet"], warm["engine"]):
            np.testing.assert_array_equal(a.coords, b.coords)
            np.testing.assert_array_equal(a.feats, b.feats)
        times = {"engine": [], "router": [], "fleet": []}
        for _ in range(reps):
            for tag, svc in (("engine", eng), ("router", rt), ("fleet", fl)):
                t0 = time.perf_counter()
                svc.serve(scenes, flush_every=0)
                times[tag].append(time.perf_counter() - t0)
        n = len(scenes)
        sps = {tag: n / statistics.median(v) for tag, v in times.items()}
        s = fl.stats.summary()
        common.emit(
            f"serving/{arch}/fleet_h{hosts}/epoch",
            statistics.median(times["fleet"]) * 1e6,
            f"scenes_per_s={sps['fleet']:.2f};"
            f"router_scenes_per_s={sps['router']:.2f};"
            f"engine_scenes_per_s={sps['engine']:.2f};"
            f"schema_version={s['schema_version']};"
            f"live_hosts={s['fleet']['live']};"
            f"failovers={s['fleet']['failovers']}")
        common.emit(
            f"serving/{arch}/fleet_vs_router_vs_engine", 0.0,
            f"fleet_vs_engine={sps['fleet'] / sps['engine']:.2f}x;"
            f"fleet_vs_router={sps['fleet'] / sps['router']:.2f}x;"
            f"hosts={hosts};bit_identical=True")
        _emit_phases(arch, f"fleet_h{hosts}", s)
    finally:
        fl.close()


def run(tiny: bool = False, devices: int = 0):
    if tiny:
        count, n_range, ladder = 6, (80, 400), BucketLadder((256, 512), max_batch=3)
        flush_every = 3
    else:
        count, n_range = 24, (200, 1200)
        ladder = BucketLadder((512, 1024, 2048), max_batch=4)
        flush_every = 8

    for arch in sorted(ARCHS):
        channels = ARCHS[arch].in_channels_of(ARCHS[arch].default_config)
        scenes, bound = lidar_stream(0, count, channels, n_range=n_range)
        batched = _drive(arch, scenes, bound, ladder, flush_every, "batched")
        single = BucketLadder(ladder.capacities, max_batch=1)
        unbatched = _drive(arch, scenes, bound, single, 1, "unbatched")
        speedup = (batched["scenes_per_s"] /
                   max(unbatched["scenes_per_s"], 1e-9))
        common.emit(f"serving/{arch}/batched_vs_unbatched", 0.0,
                    f"throughput_ratio={speedup:.2f}x")

        _drive(arch, scenes, bound, ladder, flush_every, "repeat", epochs=2)

        _pipelined_leg(arch, scenes, bound, ladder, reps=17 if tiny else 7)
        _plan_compose_leg(arch, scenes, bound, ladder, reps=7 if tiny else 5)
        _pallas_leg(arch, scenes, bound, ladder, reps=2 if tiny else 3)

        _saturating_leg(arch, scenes, bound, ladder)

        n_dev = devices if devices else jax.device_count()
        if n_dev > 1:
            if jax.device_count() < n_dev:
                raise RuntimeError(
                    f"--devices {n_dev} needs XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={n_dev}")
            # the sharded leg replays the stream in the warm-traffic regime
            # the router targets (maps cached, executors hot), one scene
            # per batch: the batch is the routing granularity, so this is
            # the request-parallel deployment a device fleet serves
            _sharded_leg(arch, scenes, bound, single, n_dev,
                         reps=5 if tiny else 3)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="reduced stream for CI smoke runs")
    ap.add_argument("--devices", type=int, default=0,
                    help="run the sharded leg across N devices "
                         "(0 = every visible device; sharded leg is skipped "
                         "when only one is attached)")
    ap.add_argument("--trace", default=None, metavar="OUT",
                    help="trace the benchmark run: Chrome trace-event JSON "
                         "(Perfetto) or .jsonl event log")
    args = ap.parse_args()
    if args.trace:
        obs.enable()
    print("name,us_per_call,derived")
    run(tiny=args.tiny, devices=args.devices)
    if args.trace:
        path = obs.export(obs.get_tracer(), args.trace)
        snap = obs.get_tracer().snapshot()
        print(f"# trace: {snap['spans']} spans + {snap['events']} events "
              f"-> {path}")
