"""Prove the main paths run on the chip: serve full-width MinkUNet through
``Engine`` and train it, on one TPU; or route one stream over four.

    python chip_smoke.py             # one chip: serve phase + train phase
    python chip_smoke.py --chips 4   # DeviceRouter over 4 chips vs one Engine

Serve phase: ``Engine("minkunet_kitti")`` at ``CONFIG_1X`` (width 1.0, two
blocks per stage, channels up to 256) on a (16384, 32768)-row ladder serves
8 synthetic LiDAR scenes for 2 epochs, once under the default XLA plan and
once with every layer group on the Pallas implicit-GEMM kernel.  Every
scene's output is checked against a float32 per-scene forward at
``highest`` matmul precision on the gather-scatter XLA dataflow.

Train phase: 3 bf16 AdamW steps of ``examples/train_minkunet.py`` at
``CONFIG_1X`` with Pallas forward and wgrad kernels; loss and grad norm
must be finite.

Any failed check raises, so the script exits non-zero.  It also exits
non-zero, before any phase, when jax sees no TPU.  The last stdout line is
one JSON object naming the device it ran on.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax

ROOT = os.path.dirname(os.path.abspath(__file__))

#: Max |engine − reference| over max |reference|, per scene.  The reference
#: is float32 at ``highest`` precision; the served XLA plan runs at jax's
#: default float32 matmul precision, which on the TPU MXU is one bf16 pass
#: (operands rounded to 8 mantissa bits, float32 accumulation).  Running
#: the whole network under the bf16 policy (which also rounds activations)
#: moves 1x MinkUNet logits by ~0.5% of their max on CPU; 2% leaves 4x
#: headroom while still catching a wrong map, offset or weight slice
#: (errors of order 100%).
REL_TOL = 2e-2

SERVE_BUCKETS = (16384, 32768)
SCENE_POINTS = (12000, 30000)
TRAIN_POINTS = 16384


def log(msg: str) -> None:
    print(msg, flush=True)


def scene_errors(results, refs) -> list:
    """Per scene, max |out − ref| / max |ref| (coords must match exactly)."""
    import numpy as np
    errs = []
    for res, (ref_coords, ref_feats) in zip(results, refs):
        np.testing.assert_array_equal(res.coords, ref_coords)
        out = np.asarray(res.feats, np.float32)
        errs.append(float(np.abs(out - ref_feats).max()
                          / max(float(np.abs(ref_feats).max()), 1e-30)))
    return errs


def reference_outputs(params, cfg, config, scenes) -> list:
    """Per-scene float32 forward on the gather-scatter XLA dataflow at
    ``highest`` precision, each scene packed alone at its own rung."""
    import numpy as np
    from repro.core import dataflows as df
    from repro.core.sparse_conv import TrainDataflowConfig
    from repro.models import minkunet
    from repro.serve.batcher import SceneBatcher
    from repro.serve.engine import ARCHS

    batcher = SceneBatcher(config.ladder(), config.spatial_bound)
    outputs_of = ARCHS["minkunet_kitti"].outputs_of

    gs = TrainDataflowConfig.bind_all(df.DataflowConfig("gather_scatter"))
    nplan = minkunet.network_plan(cfg)
    nplan = nplan.with_assignment({sig: gs for sig in nplan.assignment()})

    @jax.jit
    def forward(params, st):
        maps = nplan.build_maps(st)
        feats = nplan.apply(params, st, maps, bn_mode="affine")
        return outputs_of(cfg, st, maps, feats)

    refs = []
    with jax.default_matmul_precision("highest"):
        for scene in scenes:
            coords, feats, n = forward(params, batcher.pack([scene]).st)
            n = int(n)
            refs.append((np.asarray(coords)[:n, 1:],
                         np.asarray(feats, np.float32)[:n]))
    return refs


def serve_plan(name, cfg, config, params, assignment, scenes, refs) -> None:
    """Warm up and serve ``scenes`` for 2 epochs under one plan (the
    default XLA plan when ``assignment`` is None); check outputs, compile
    counts and, for an assignment, that the executor lowered to Pallas
    kernels — a Pallas request that ran XLA fails here."""
    from repro.serve.engine import Engine
    from repro.serve.plans import PlanRegistry

    plans = PlanRegistry()
    if assignment is not None:
        plans.set("minkunet_kitti", assignment)
    eng = Engine("minkunet_kitti", config=config, model_config=cfg,
                 params=params, plans=plans)
    t0 = time.perf_counter()
    eng.warmup()
    warm_s = time.perf_counter() - t0
    errs = []
    for _ in range(2):
        errs += scene_errors(eng.serve(scenes, flush_every=4), refs)
    s = eng.stats.summary()
    for counter in ("recompiles", "map_compiles", "plan_compiles"):
        assert all(n <= 1 for n in s[counter].values()), (counter, s[counter])
    assert all(n <= 1 for n in s["scene_tables"]["compiles"].values()), \
        s["scene_tables"]["compiles"]
    kernels = {cap: eng.compiled_text("executor", cap).count("tpu_custom_call")
               for cap in config.buckets}
    log(f"serve[{name}]: warmup+compile {warm_s:.1f} s; "
        f"executor compiles {s['recompiles']}, map-builder compiles "
        f"{s['map_compiles']}, scene-builder compiles "
        f"{s['scene_tables']['compiles']}; tpu_custom_call per executor "
        f"{kernels}")
    log(f"serve[{name}]: {s['scenes']} scenes in {s['batches']} batches, "
        f"p50 {s['p50_ms']:.1f} ms/scene; max rel err vs reference "
        f"{max(errs):.3e} (tol {REL_TOL:.0e}); per scene "
        + " ".join(f"{e:.2e}" for e in errs[:len(refs)]))
    assert max(errs) <= REL_TOL, (name, errs)
    if assignment is not None:
        assert all(kernels.values()), f"Pallas plan ran no kernel: {kernels}"


def serve_phase(seed: int) -> None:
    from repro.configs import minkunet_kitti
    from repro.core import dataflows as df
    from repro.core.sparse_conv import TrainDataflowConfig
    from repro.models import minkunet
    from repro.serve.service import ServiceConfig
    from repro.serve.workload import lidar_stream

    cfg = minkunet_kitti.CONFIG_1X
    scenes, bound = lidar_stream(seed, 8, cfg.in_channels,
                                 n_range=SCENE_POINTS)
    log(f"serve: {len(scenes)} scenes of "
        f"{[s.num_points for s in scenes]} voxels, spatial_bound {bound}")
    config = ServiceConfig(buckets=SERVE_BUCKETS, max_batch=2,
                           spatial_bound=bound, seed=seed)
    params = minkunet.init_params(cfg, jax.random.PRNGKey(seed))
    t0 = time.perf_counter()
    refs = reference_outputs(params, cfg, config, scenes)
    log(f"reference: {len(refs)} per-scene forwards in "
        f"{time.perf_counter() - t0:.1f} s")
    serve_plan("xla", cfg, config, params, None, scenes, refs)
    pallas = TrainDataflowConfig.bind_all(df.DataflowConfig(
        "implicit_gemm", backend="pallas", tile_m=128, tile_n=128))
    groups = minkunet.network_plan(cfg).assignment()
    serve_plan("pallas", cfg, config, params,
               {sig: pallas for sig in groups}, scenes, refs)


def train_phase(seed: int) -> None:
    import numpy as np
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    from train_minkunet import build_trainer

    from repro.configs import minkunet_kitti
    from repro.core import dataflows as df
    from repro.core import precision as prec
    from repro.core.sparse_conv import TrainDataflowConfig

    cfg3 = TrainDataflowConfig.bind_all(
        df.DataflowConfig("implicit_gemm", backend="pallas"))
    params, state, step, data = build_trainer(
        minkunet_kitti.CONFIG_1X, prec.POLICIES["bf16"], cfg3,
        points=TRAIN_POINTS, capacity=TRAIN_POINTS, seed=seed)
    batches = data()
    for i in range(3):
        batch = next(batches)
        t0 = time.perf_counter()
        params, state, m = step(params, state, batch)
        loss, gn = float(m["loss"]), float(m["grad_norm"])
        log(f"train step {i}: loss {loss:.4f} grad_norm {gn:.4f} "
            f"({time.perf_counter() - t0:.1f} s)")
        assert np.isfinite(loss) and np.isfinite(gn), (i, loss, gn)
    n = step.lower(params, state, batch).compile().as_text().count(
        "tpu_custom_call")
    log(f"train: bf16 CONFIG_1X step holds {n} tpu_custom_call kernels")
    assert n > 0, "Pallas train plan ran no kernel"


def router_phase(seed: int, chips: int) -> None:
    """DeviceRouter over ``chips`` chips vs one Engine on chip 0, same
    stream: bit-identical outputs, every device routed, no failures."""
    import numpy as np
    from repro.configs import minkunet_kitti
    from repro.models import minkunet
    from repro.serve.engine import Engine
    from repro.serve.router import DeviceRouter
    from repro.serve.service import ServiceConfig
    from repro.serve.workload import lidar_stream

    cfg = minkunet_kitti.CONFIG_1X
    scenes, bound = lidar_stream(seed, 8, cfg.in_channels,
                                 n_range=SCENE_POINTS)
    config = ServiceConfig(buckets=SERVE_BUCKETS, max_batch=2,
                           spatial_bound=bound, seed=seed)
    params = minkunet.init_params(cfg, jax.random.PRNGKey(seed))
    router = DeviceRouter("minkunet_kitti", devices=chips, config=config,
                          model_config=cfg, params=params)
    single = Engine("minkunet_kitti", config=config, model_config=cfg,
                    params=params, device=jax.devices()[0])
    t0 = time.perf_counter()
    router.warmup()
    single.warmup()
    log(f"router: warmup+compile {time.perf_counter() - t0:.1f} s")
    for epoch in range(2):
        got = router.serve(scenes, flush_every=4)
        want = single.serve(scenes, flush_every=4)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g.coords, w.coords)
            np.testing.assert_array_equal(g.feats, w.feats)
    s = router.stats.summary()
    routed = {d: v["routed_batches"] for d, v in s["devices"].items()}
    failures = s["failover"]["worker_failures"]
    log(f"router: {s['scenes']} scenes bit-identical to one Engine; routed "
        f"batches {routed}; worker_failures {failures}; executor compiles "
        f"{s['recompiles']}")
    assert all(n >= 1 for n in routed.values()), routed
    assert failures == 0, s["failover"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the DeviceRouter-over-4-chips check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {enable_compile_cache()}")

    if args.chips == 1:
        serve_phase(args.seed)
        train_phase(args.seed)
    else:
        router_phase(args.seed, args.chips)
    # count: the chips this run used, not every chip the host shows
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
