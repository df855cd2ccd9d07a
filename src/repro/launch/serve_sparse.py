"""Sparse serving launcher: bucketed batched point-cloud inference.

    python -m repro.launch.serve_sparse --arch minkunet_kitti
    python -m repro.launch.serve_sparse --arch centerpoint_waymo \
        --tune --plans plans.json     # tune once…
    python -m repro.launch.serve_sparse --arch centerpoint_waymo \
        --plans plans.json            # …serve forever
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    python -m repro.launch.serve_sparse --arch minkunet_kitti --devices 4
    python -m repro.launch.serve_sparse --arch minkunet_kitti --hosts 2

Drives a mixed-size synthetic request stream through one of the three
``SparseService`` tiers — the single-device ``Engine``, the sharded
``DeviceRouter`` (``--devices N``), or the cross-host ``FleetFrontend``
(``--hosts N`` spawns N localhost worker processes) — and prints
latency/throughput stats (p50/p95 per scene, scenes/s, jit recompile and
map-cache counters; per-device / per-host routing counters when sharded).
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax

from repro import obs
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.engine import ARCHS, Engine
from repro.serve.fleet import FleetFrontend
from repro.serve.plans import PlanRegistry
from repro.serve.router import DeviceRouter
from repro.serve.service import ServiceConfig
from repro.serve.workload import lidar_stream


def build_service(arch: str, buckets, max_batch: int, spatial_bound: int,
                  plans_path=None, seed: int = 0, map_strategy=None,
                  devices: int = 1, hosts: int = 1, max_wait_ms=None,
                  replication: str = "lazy"):
    """One ``SparseService`` front end, picked from deployment shape alone:
    a plain ``Engine`` for a single device, a ``DeviceRouter`` sharding the
    same ladder across ``devices`` workers, or — with ``hosts > 1`` — a
    ``FleetFrontend`` spawning that many localhost worker processes
    (identical submit/flush/serve API, bit-identical outputs)."""
    config = ServiceConfig(buckets=tuple(buckets), max_batch=max_batch,
                           spatial_bound=spatial_bound, seed=seed,
                           map_strategy=map_strategy,
                           max_wait_ms=max_wait_ms)
    if hosts > 1:
        # the fleet forwards the plans *path* — worker processes load it
        return FleetFrontend(arch, hosts=hosts, config=config,
                             plans=plans_path, replication=replication,
                             respawn=True, devices_per_host=devices)
    plans = PlanRegistry.load(plans_path) if plans_path else None
    if devices > 1:
        return DeviceRouter(arch, devices=devices, config=config, plans=plans)
    return Engine(arch, config=config, plans=plans)


def fmt_ms(v) -> str:
    """Format a maybe-None millisecond value (idle stats report None)."""
    return "-" if v is None else f"{v:.1f} ms"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--scenes", type=int, default=24)
    ap.add_argument("--buckets", default="512,1024,2048",
                    help="comma-separated capacity ladder")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--min-points", type=int, default=200)
    ap.add_argument("--max-points", type=int, default=1200)
    ap.add_argument("--epochs", type=int, default=2,
                    help="replay the stream N times; epochs > 1 exercise "
                         "cross-request map reuse on repeated batches")
    ap.add_argument("--flush-every", type=int, default=8,
                    help="scenes per flush (0 = one flush at the end)")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard serving across the first N jax devices "
                         "(CPU smoke: set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N); with "
                         "--hosts, devices per spawned worker")
    ap.add_argument("--hosts", type=int, default=1,
                    help="fleet tier: spawn N localhost worker processes "
                         "behind a FleetFrontend (RPC boundary + failover "
                         "+ weighted routing)")
    ap.add_argument("--replication", default="lazy",
                    choices=["lazy", "gossip"],
                    help="fleet scene-store replication policy: push every "
                         "admitted scene to all hosts (gossip) or let hosts "
                         "warm from routed traffic (lazy)")
    ap.add_argument("--plans", default=None,
                    help="PlanRegistry JSON (loaded at startup; --tune writes it)")
    ap.add_argument("--tune", action="store_true",
                    help="run the Sparse Autotuner on a sample batch and "
                         "persist the assignment before serving (per-device "
                         "plan entries when --devices > 1)")
    ap.add_argument("--map-strategy", default=None,
                    choices=["sort", "composed", "incremental"],
                    help="coordinate-table strategy override (default: the "
                         "plan's declared KmapSpec.table axis)")
    ap.add_argument("--tiny", action="store_true",
                    help="reduced stream/ladder for smoke runs")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="OUT",
                    help="write a trace of the serving run: Chrome "
                         "trace-event JSON (open in Perfetto) or a flat "
                         "event log when OUT ends in .jsonl; also captures "
                         "an XLA-level profile to OUT.xprof/")
    ap.add_argument("--max-wait-ms", type=float, default=None,
                    help="latency deadline: flush when the oldest queued "
                         "scene exceeds this age; doubles as the per-request "
                         "SLO reported in summary()['slo']")
    args = ap.parse_args(argv)
    if args.hosts > 1:
        # a fleet front end only packs and unpacks: keep this process off
        # the accelerator so its spawned worker can claim the chip
        jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()

    if args.tiny:
        args.scenes, args.buckets = 6, "256,512"
        args.min_points, args.max_points, args.flush_every = 80, 400, 3
    buckets = [int(b) for b in args.buckets.split(",")]

    binding = ARCHS[args.arch]
    channels = binding.in_channels_of(binding.default_config)
    scenes, bound = lidar_stream(args.seed, args.scenes, channels,
                                 n_range=(args.min_points, args.max_points))
    engine = build_service(args.arch, buckets, args.max_batch, bound,
                           plans_path=args.plans, seed=args.seed,
                           map_strategy=args.map_strategy,
                           devices=args.devices, hosts=args.hosts,
                           max_wait_ms=args.max_wait_ms,
                           replication=args.replication)
    sharded = isinstance(engine, DeviceRouter)
    fleet = isinstance(engine, FleetFrontend)
    if args.trace:
        obs.enable()

    if args.tune:
        sample = scenes[:min(2, len(scenes))]
        assignment = engine.tune(sample)   # persists when --plans was given
        n_groups = (sum(len(a) for a in assignment.values())
                    if (sharded or fleet) else len(assignment))
        print(f"tuned {n_groups} groups"
              + (f" across {engine.num_devices} devices" if sharded else "")
              + (f" across {engine.num_hosts} hosts" if fleet else "")
              + (f" -> {args.plans}" if args.plans else " (not persisted)"))
    elif not (sharded or fleet) and engine.assignment:
        print(f"loaded {len(engine.assignment)} tuned groups from {args.plans}")

    t0 = time.perf_counter()
    engine.warmup()
    warm_s = time.perf_counter() - t0
    warm = engine.stats.summary()
    # --trace also brackets the serve epochs with the XLA-level profiler
    # (TensorBoard/XProf artifact next to our own Chrome trace)
    profiler = (obs.jax_profile(args.trace + ".xprof")
                if args.trace else contextlib.nullcontext())
    with profiler:
        for _ in range(max(1, args.epochs)):
            results = engine.serve(scenes, flush_every=args.flush_every)

    s = engine.stats.summary()
    print(f"arch={args.arch} buckets={buckets} max_batch={args.max_batch}"
          + (f" devices={engine.num_devices}" if sharded else "")
          + (f" hosts={engine.num_hosts}" if fleet else ""))
    print(f"scenes: {s['scenes']} in {s['batches']} batches "
          f"({s['scenes_per_s']:.1f} scenes/s)")
    print(f"latency: p50 {fmt_ms(s['p50_ms'])}  p95 {fmt_ms(s['p95_ms'])}")
    print(f"jit: {sum(s['recompiles'].values())} executor + "
          f"{sum(s['map_compiles'].values())} map-builder compiles "
          f"across {len(buckets)} buckets "
          f"({sum(warm['recompiles'].values())} during a {warm_s:.1f} s "
          f"warmup)")
    print(f"map cache: {s['map_cache']['hits']} hits / "
          f"{s['map_cache']['misses']} misses")
    sc = s["scene_tables"]
    strategy = (engine.config.map_strategy or "plan-default" if fleet
                else engine.workers[0].map_strategy if sharded
                else engine.map_strategy)
    print(f"scene store [{strategy}]: "
          f"{sc['hits']} hits / "
          f"{sc['misses']} misses, {sc['composed_batches']} composed batches, "
          f"{sc['delta_merges']} delta merges")
    if sharded:
        for name, d in s["devices"].items():
            print(f"  {name} [{d['device']}]: {d['routed_batches']} batches, "
                  f"{d['scenes']} scenes, p50 {fmt_ms(d['p50_ms'])} "
                  f"p95 {fmt_ms(d['p95_ms'])}, queue_depth {d['queue_depth']}")
    if fleet:
        fl = s["fleet"]
        print(f"fleet: {fl['live']}/{fl['hosts']} hosts live, "
              f"replication={fl['replication']}, "
              f"{fl['failovers']} failovers, "
              f"{fl['rerouted_batches']} rerouted batches, "
              f"{fl['respawns']} respawns")
        for name, h in s["hosts"].items():
            print(f"  {name} [{h['addr']}]"
                  f"{'' if h['alive'] else ' (dead)'}: "
                  f"{h['routed_batches']} batches, {h['scenes']} scenes, "
                  f"weight {h['weight']:.2f}, p50 {fmt_ms(h['p50_ms'])} "
                  f"p95 {fmt_ms(h['p95_ms'])}")
    if s["phases"]:
        print("phases: " + "  ".join(
            f"{name} p50 {fmt_ms(ph['p50_ms'])}"
            for name, ph in s["phases"].items()))
    if s["slo"]["measured"]:
        slo = s["slo"]
        print(f"slo: deadline {slo['deadline_ms']:.1f} ms, "
              f"{slo['misses']}/{slo['measured']} misses "
              f"({100 * slo['miss_rate']:.1f}%), "
              f"{s['deadline_flushes']} deadline flushes")
    out = results[0]
    print(f"sample result: {out.feats.shape[0]} rows x {out.feats.shape[1]} ch "
          f"@ stride {out.stride}")
    if args.trace:
        path = obs.export(obs.get_tracer(), args.trace)
        tr = obs.get_tracer().snapshot()
        print(f"trace: {tr['spans']} spans + {tr['events']} events -> {path}"
              + f" (+ XLA profile in {args.trace}.xprof/)")
    if fleet:
        engine.close()


if __name__ == "__main__":
    main()
