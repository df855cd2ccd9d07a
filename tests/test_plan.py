"""Execution-plan IR: compile/serialize round-trips, plan-vs-per-call
bit-identity, mixed-precision policies through all three dataflows, the
plan-producing tuners, and the v2 PlanRegistry with its v1 shim."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dataflows as df
from repro.core import generator
from repro.core import kmap as km
from repro.core import plan as planlib
from repro.core import precision as prec
from repro.core.plan import (NetworkPlan, PlanTuner, TrainingPlanTuner,
                             compile_plan)
from repro.core.sparse_conv import TrainDataflowConfig, apply_conv
from repro.data.synthetic import lidar_scene
from repro.models import centerpoint, minkunet
from repro.serve import Engine, PlanRegistry
from repro.serve.bucketing import BucketLadder
from tests.test_kmap import random_tensor


def det_scene(n=300, cap=512):
    """The deterministic CenterPoint detection scene (benchmarks.common
    parameters at CI scale)."""
    return lidar_scene(jax.random.PRNGKey(0), n, cap, 5, extent=75.0, voxel=0.8)


MU_CFG = minkunet.MinkUNetConfig(in_channels=4, num_classes=5, width=0.25,
                                 blocks_per_stage=1)
CP_CFG = centerpoint.CenterPointConfig(width=0.5)


# ---------------------------------------------------------------------------
# Compile: structure
# ---------------------------------------------------------------------------

def test_compile_partitions_groups_and_binds_assignment():
    amap = {(1, 3, "sub"): TrainDataflowConfig.bind_all(
        df.DataflowConfig("gather_scatter"))}
    nplan = minkunet.network_plan(MU_CFG, assignment=amap)
    assert all(lp.group for lp in nplan.layers)
    # layers in one group share a signature and a config
    for g in nplan.groups():
        sigs = {nplan.layer(n).sig for n in g.layer_names}
        cfgs = {nplan.layer(n).dataflow for n in g.layer_names}
        assert len(sigs) == 1 and len(cfgs) == 1
    assert nplan.layer("stem1").dataflow.fwd.dataflow == "gather_scatter"
    assert nplan.layer("down0").dataflow == TrainDataflowConfig()
    # the signature view matches the historical layer_signatures
    assert nplan.signatures() == minkunet.layer_signatures(MU_CFG)
    # map program declares the adoption edges explicitly
    down_specs = [ms for ms in nplan.map_specs if ms.kind == "down"]
    assert down_specs and all(ms.adopts_output_table for ms in down_specs)
    up_specs = [ms for ms in nplan.map_specs if ms.kind == "up"]
    assert up_specs and all(ms.transpose_of == ("down", ms.ref[1])
                            for ms in up_specs)


def test_resolve_tiles_uses_generator_adaptive_tiling():
    stx = random_tensor(0, n=150, cap=256, channels=5, extent=16)
    nplan = centerpoint.network_plan(CP_CFG)
    maps = nplan.build_maps(stx)
    small = nplan.resolve_tiles(maps, threshold_macs=1e18)
    large = nplan.resolve_tiles(maps, threshold_macs=1.0)
    for lp in small.layers:
        assert (lp.dataflow.fwd.tile_m, lp.dataflow.fwd.tile_n) == generator.SMALL_TILES
    for lp in large.layers:
        assert (lp.dataflow.fwd.tile_m, lp.dataflow.fwd.tile_n) == generator.LARGE_TILES
    # non-implicit-gemm configs are left alone
    gs = nplan.with_assignment({lp.sig: TrainDataflowConfig.bind_all(
        df.DataflowConfig("gather_scatter")) for lp in nplan.layers})
    assert gs.resolve_tiles(maps, threshold_macs=1.0).layers == gs.layers


# ---------------------------------------------------------------------------
# Serialize → load → bit-identical forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model,cfg,scene", [
    (minkunet, MU_CFG, dict(n=200, cap=256, channels=4, extent=16)),
    (centerpoint, CP_CFG, dict(n=200, cap=256, channels=5, extent=20)),
])
def test_network_plan_json_roundtrip_bit_identical(model, cfg, scene):
    amap = {(1, 3, "sub"): TrainDataflowConfig.bind_fwd_dgrad(
        df.DataflowConfig("implicit_gemm", n_splits=2, tile_m=64),
        df.DataflowConfig("fetch_on_demand"))}
    nplan = model.network_plan(cfg, assignment=amap, precision="bf16")
    loaded = NetworkPlan.from_dict(json.loads(json.dumps(nplan.to_dict())))
    assert loaded == nplan  # full structural equality incl. precision
    # and the fp32 variant executes bit-identically after the round trip
    nplan32 = nplan.with_precision("fp32")
    loaded32 = NetworkPlan.from_dict(json.loads(json.dumps(nplan32.to_dict())))
    stx = random_tensor(4, **scene)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    maps = loaded32.build_maps(stx)
    np.testing.assert_array_equal(
        np.asarray(nplan32.apply(params, stx, maps)),
        np.asarray(loaded32.apply(params, stx, maps)))


def test_plan_rejects_unknown_fields_and_versions():
    nplan = centerpoint.network_plan(CP_CFG)
    d = nplan.to_dict()
    with pytest.raises(ValueError):
        NetworkPlan.from_dict({**d, "bogus": 1})
    with pytest.raises(ValueError):
        NetworkPlan.from_dict({**d, "version": 99})
    with pytest.raises(ValueError):
        TrainDataflowConfig.from_dict({**TrainDataflowConfig().to_dict(),
                                       "bogus": {}})
    with pytest.raises(ValueError):
        prec.PrecisionPolicy.from_dict({"compute": "bfloat16", "bogus": 1})
    assert prec.PrecisionPolicy.from_dict(prec.BF16.to_dict()) == prec.BF16
    # autocast-style policy: bf16 compute numerics, fp32 storage, no master
    assert prec.BF16_AMP.compute == "bfloat16"
    assert not prec.BF16_AMP.master_weights
    p32 = jnp.ones((4,), jnp.float32)
    assert prec.BF16_AMP.cast_param(p32) is p32       # params left fp32
    assert prec.BF16.cast_param(p32).dtype == jnp.bfloat16
    assert prec.bf16_training_policy("cpu") == prec.BF16_AMP
    assert prec.bf16_training_policy("tpu") == prec.BF16


# ---------------------------------------------------------------------------
# Plan-driven execution ≡ pre-refactor per-call path
# ---------------------------------------------------------------------------

def _precall_centerpoint(params, st, cfg, maps, bn_mode="batch"):
    """The pre-plan hand-written CenterPoint forward, verbatim."""
    x = apply_conv(params["stem"], st, maps[("sub", 1)])
    x = planlib.norm_act(params["stem_bn"], x, mode=bn_mode)
    stride = 1
    for i in range(len(cfg.channels)):
        x = apply_conv(params[f"down{i}"], x, maps[("down", stride)])
        x = planlib.norm_act(params[f"down{i}_bn"], x, mode=bn_mode)
        stride *= 2
        for b in range(cfg.sub_convs_per_stage):
            x = apply_conv(params[f"sub{i}_{b}"], x, maps[("sub", stride)])
            x = planlib.norm_act(params[f"sub{i}_{b}_bn"], x, mode=bn_mode)
    return x.feats


def test_plan_equals_precall_path_on_deterministic_scene():
    stx = det_scene()
    params = centerpoint.init_params(CP_CFG, jax.random.PRNGKey(0))
    nplan = centerpoint.network_plan(CP_CFG)
    maps = nplan.build_maps(stx)
    for bn_mode in ("batch", "affine"):
        ref = _precall_centerpoint(params, stx, CP_CFG, maps, bn_mode=bn_mode)
        got = nplan.apply(params, stx, maps, bn_mode=bn_mode)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # module-level apply (the historical entry point) matches too
    np.testing.assert_array_equal(
        np.asarray(centerpoint.apply(params, stx, CP_CFG, maps)),
        np.asarray(_precall_centerpoint(params, stx, CP_CFG, maps)))


# ---------------------------------------------------------------------------
# Mixed precision: bf16 fwd/dgrad/wgrad vs fp32 on all three dataflows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flow", df.DATAFLOWS)
def test_bf16_policy_close_to_fp32_all_kernels(flow):
    stx = random_tensor(1, n=80, cap=96, channels=8, extent=8)
    kmap = km.build_kmap(stx, 3, 1)
    cfg = df.DataflowConfig(flow)
    w = jax.random.normal(jax.random.PRNGKey(2), (27, 8, 16)) * 0.2
    dy = jax.random.normal(jax.random.PRNGKey(3), (kmap.capacity, 16))
    xb, wb, dyb = (stx.feats.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                   dy.astype(jnp.bfloat16))

    y32 = df.sparse_conv_forward(stx.feats, w, kmap, cfg)
    ybf = df.sparse_conv_forward(xb, wb, kmap, cfg, precision=prec.BF16)
    assert ybf.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(ybf, np.float32), np.asarray(y32),
                               rtol=5e-2, atol=5e-2)

    dx32 = df.sparse_conv_dgrad(dy, w, kmap, cfg, in_capacity=stx.capacity)
    dxbf = df.sparse_conv_dgrad(dyb, wb, kmap, cfg, in_capacity=stx.capacity,
                                precision=prec.BF16)
    assert dxbf.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(dxbf, np.float32), np.asarray(dx32),
                               rtol=5e-2, atol=5e-2)

    dw32 = df.sparse_conv_wgrad(stx.feats, dy, kmap, cfg)
    dwbf = df.sparse_conv_wgrad(xb, dyb, kmap, cfg, precision=prec.BF16)
    assert dwbf.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(dwbf, np.float32), np.asarray(dw32),
                               rtol=5e-2, atol=0.3)


def test_fp32_policy_is_bit_identical_to_default():
    stx = random_tensor(2, n=60, cap=64, channels=4, extent=8)
    kmap = km.build_kmap(stx, 3, 1)
    w = jax.random.normal(jax.random.PRNGKey(4), (27, 4, 8)) * 0.2
    dy = jax.random.normal(jax.random.PRNGKey(5), (kmap.capacity, 8))
    for flow in df.DATAFLOWS:
        cfg = df.DataflowConfig(flow)
        np.testing.assert_array_equal(
            np.asarray(df.sparse_conv_forward(stx.feats, w, kmap, cfg)),
            np.asarray(df.sparse_conv_forward(stx.feats, w, kmap, cfg,
                                              precision=prec.FP32)))
        np.testing.assert_array_equal(
            np.asarray(df.sparse_conv_dgrad(dy, w, kmap, cfg)),
            np.asarray(df.sparse_conv_dgrad(dy, w, kmap, cfg,
                                            precision=prec.FP32)))
        np.testing.assert_array_equal(
            np.asarray(df.sparse_conv_wgrad(stx.feats, dy, kmap, cfg)),
            np.asarray(df.sparse_conv_wgrad(stx.feats, dy, kmap, cfg,
                                            precision=prec.FP32)))


def test_bf16_plan_trains_with_master_weights():
    """End-to-end mixed-precision training: bf16 conv params + fp32 master
    weights descend on the segmentation toy problem."""
    from repro.train import optimizer as opt

    stx = lidar_scene(jax.random.PRNGKey(0), 300, 256, 4, extent=20.0, voxel=0.5)
    nplan = minkunet.network_plan(MU_CFG, precision="bf16")
    params = nplan.cast_params(minkunet.init_params(MU_CFG, jax.random.PRNGKey(1)))
    maps = nplan.build_maps(stx)
    labels = jax.random.randint(jax.random.PRNGKey(2), (256,), 0, 5)
    ocfg = opt.AdamWConfig(lr=3e-3, weight_decay=0.0, master_weights=True)
    state = opt.init_opt_state(params, ocfg)
    assert "master" in state
    assert all(x.dtype == jnp.float32
               for x in jax.tree.leaves(state["master"]))

    @jax.jit
    def step(params, state):
        def loss(p):
            lg = nplan.apply(p, stx, maps).astype(jnp.float32)
            ls = jax.nn.log_softmax(lg)[jnp.arange(256), labels]
            return -jnp.sum(jnp.where(stx.valid_mask, ls, 0)) / jnp.maximum(stx.num_valid, 1)

        l, g = jax.value_and_grad(loss)(params)
        p2, s2, _ = opt.adamw_update(params, g, state, ocfg)
        return p2, s2, l

    losses = []
    for _ in range(8):
        params, state, l = step(params, state)
        losses.append(float(l))
    assert params["stem1"]["w"].dtype == jnp.bfloat16
    assert losses[-1] < losses[0] * 0.9, losses


def test_master_weights_accumulate_sub_ulp_updates():
    """Updates smaller than one bf16 ulp vanish without the fp32 master."""
    from repro.train import optimizer as opt

    p = {"w": jnp.ones((4,), jnp.bfloat16)}
    g = {"w": jnp.full((4,), 1e-3, jnp.bfloat16)}
    for master, moved in ((False, False), (True, True)):
        cfg = opt.AdamWConfig(lr=1e-4, weight_decay=0.0, master_weights=master)
        params, state = p, opt.init_opt_state(p, cfg)
        for _ in range(20):
            params, state, _ = opt.adamw_update(params, g, state, cfg)
        changed = bool(jnp.any(params["w"] != p["w"]))
        assert changed == moved, (master, np.asarray(params["w"], np.float32))


# ---------------------------------------------------------------------------
# Plan-producing tuners
# ---------------------------------------------------------------------------

def _cost_measure(table):
    """Synthetic end-to-end cost: Σ per-group cost of the assigned fwd
    dataflow (reads the candidate plan, no jit)."""
    def measure(nplan: NetworkPlan) -> float:
        seen = {}
        for lp in nplan.layers:
            seen.setdefault(lp.sig, lp.dataflow.fwd.dataflow)
        return 1.0 + sum(table[sig][flow] for sig, flow in seen.items())

    return measure


def test_plan_tuner_returns_tuned_network_plan():
    nplan = centerpoint.network_plan(CP_CFG)
    space = [df.DataflowConfig("gather_scatter"),
             df.DataflowConfig("implicit_gemm", n_splits=1)]
    rng = np.random.default_rng(0)
    sigs = sorted({lp.sig for lp in nplan.layers}, key=str)
    table = {sig: {c.dataflow: float(rng.uniform(1, 10)) for c in space}
             for sig in sigs}
    tuned = PlanTuner(nplan, space, _cost_measure(table)).tune()
    assert isinstance(tuned, NetworkPlan)
    for sig in sigs:
        best_flow = min(table[sig], key=table[sig].get)
        got = tuned.assignment()[sig]
        assert got.fwd.dataflow == best_flow
        assert got == TrainDataflowConfig.bind_all(got.fwd)  # inference binding
    # the input plan is immutable — tuning returns a new artifact
    assert nplan.assignment() != tuned.assignment()


def test_training_plan_tuner_binds_decoupled_configs():
    nplan = centerpoint.network_plan(CP_CFG)
    space = [df.DataflowConfig("gather_scatter"),
             df.DataflowConfig("implicit_gemm", n_splits=1)]

    # fwd/dgrad prefer implicit, wgrad prefers gather (paper Fig. 13 shape)
    def measure(candidate: NetworkPlan) -> float:
        t = 0.0
        for sig, c3 in candidate.assignment().items():
            t += 1.0 if c3.fwd.dataflow == "implicit_gemm" else 2.0
            t += 1.0 if c3.dgrad.dataflow == "implicit_gemm" else 2.0
            t += 1.0 if c3.wgrad.dataflow == "gather_scatter" else 3.0
        return t

    tuned = TrainingPlanTuner(nplan, space, measure, "bind_fwd_dgrad").tune()
    for c3 in tuned.assignment().values():
        assert c3.fwd.dataflow == "implicit_gemm"
        assert c3.dgrad.dataflow == "implicit_gemm"
        assert c3.wgrad.dataflow == "gather_scatter"


# ---------------------------------------------------------------------------
# PlanRegistry v2 + v1 shim + engine integration
# ---------------------------------------------------------------------------

def test_plan_registry_v2_persists_network_plans(tmp_path):
    nplan = centerpoint.network_plan(
        CP_CFG, assignment={(1, 3, "sub"): TrainDataflowConfig.bind_all(
            df.DataflowConfig("gather_scatter"))})
    reg = PlanRegistry()
    reg.set("centerpoint_waymo", nplan.assignment(), network=nplan)
    path = reg.save(str(tmp_path / "plans.json"))
    doc = json.loads(open(path).read())
    assert doc["version"] == 2
    loaded = PlanRegistry.load(path)
    assert loaded.get("centerpoint_waymo") == nplan.assignment()
    assert loaded.network("centerpoint_waymo") == nplan
    assert loaded.network("never_tuned") is None


def test_plan_registry_v1_shim_loads_pr2_files(tmp_path):
    """A persisted v1 plans JSON (PR 2 schema) still loads: assignments are
    read and the engine recompiles its NetworkPlan from the declaration."""
    cfg3 = TrainDataflowConfig.bind_all(df.DataflowConfig("gather_scatter"))
    v1 = {"version": 1,
          "plans": {"minkunet_kitti": {"1:3:sub": cfg3.to_dict()}}}
    path = tmp_path / "plans_v1.json"
    path.write_text(json.dumps(v1))
    reg = PlanRegistry.load(str(path))
    assert reg.get("minkunet_kitti") == {(1, 3, "sub"): cfg3}
    assert reg.network("minkunet_kitti") is None
    # engine startup on the v1 file: assignment lands in the compiled plan
    eng = Engine("minkunet_kitti", ladder=BucketLadder((256,), max_batch=2),
                 spatial_bound=64, plans=str(path))
    assert eng.assignment == {(1, 3, "sub"): cfg3}
    assert eng.nplan.layer("stem1").dataflow == cfg3
    assert eng.nplan.layer("down0").dataflow == TrainDataflowConfig()


def test_engine_prefers_persisted_network_plan(tmp_path):
    binding_cfg = None
    from repro.serve.engine import ARCHS

    cfg = ARCHS["centerpoint_waymo"].default_config
    nplan = centerpoint.network_plan(cfg).with_assignment(
        {(1, 3, "sub"): TrainDataflowConfig.bind_all(
            df.DataflowConfig("fetch_on_demand"))})
    reg = PlanRegistry()
    reg.set("centerpoint_waymo", nplan.assignment(), network=nplan)
    path = reg.save(str(tmp_path / "plans.json"))
    eng = Engine("centerpoint_waymo", ladder=BucketLadder((256,), max_batch=2),
                 spatial_bound=64, plans=path)
    assert eng.nplan == nplan


def test_measured_resolve_tiles_searches_pallas_groups():
    """With a measure callable, resolve_tiles runs a greedy per-group tile
    search over the Pallas implicit-GEMM groups (end-to-end latency, like
    the dataflow tuner) instead of trusting the MAC heuristic; XLA groups
    keep the heuristic tiles (tile choice can't matter to them)."""
    stx = random_tensor(0, n=150, cap=256, channels=5, extent=16)
    nplan = centerpoint.network_plan(CP_CFG)
    maps = nplan.build_maps(stx)
    sigs = sorted({lp.sig for lp in nplan.layers}, key=str)
    pallas_sig, xla_sig = sigs[0], sigs[1]
    nplan = nplan.with_assignment({
        pallas_sig: TrainDataflowConfig.bind_all(
            df.DataflowConfig("implicit_gemm", n_splits=1, backend="pallas")),
        xla_sig: TrainDataflowConfig.bind_all(
            df.DataflowConfig("implicit_gemm", n_splits=1))})

    calls = []

    def measure(p: NetworkPlan) -> float:
        fwd = p.assignment()[pallas_sig].fwd
        calls.append((fwd.tile_m, fwd.tile_n))
        return 1.0 + 0.01 * abs(fwd.tile_m - 64) + 0.01 * abs(fwd.tile_n - 128)

    resolved = nplan.resolve_tiles(maps, measure=measure)
    got = resolved.assignment()[pallas_sig].fwd
    assert (got.tile_m, got.tile_n) == (64, 128)
    # the search actually tried the generator's tile menu
    assert set(calls) >= {generator.SMALL_TILES, generator.LARGE_TILES}
    # the xla group took the MAC heuristic, not a measured pick
    heur = nplan.resolve_tiles(maps).assignment()[xla_sig].fwd
    assert resolved.assignment()[xla_sig].fwd == heur
    # no measure → pure heuristic, unchanged behavior
    assert nplan.resolve_tiles(maps).assignment()[pallas_sig].fwd.tile_m in (
        generator.SMALL_TILES[0], generator.LARGE_TILES[0])


def test_plan_tuner_measures_pallas_axis_and_resolves_tiles():
    """End-to-end: PlanTuner with maps searches the dataflow×backend space
    (including the worklist variant) and follows with measured tile
    resolution on the winning Pallas groups."""
    stx = random_tensor(0, n=150, cap=256, channels=5, extent=16)
    nplan = centerpoint.network_plan(CP_CFG)
    maps = nplan.build_maps(stx)
    space = [df.DataflowConfig("gather_scatter"),
             df.DataflowConfig("implicit_gemm", n_splits=1, backend="pallas",
                               worklist=True)]

    def measure(p: NetworkPlan) -> float:
        t = 1.0
        for _, c3 in p.assignment().items():
            fwd = c3.fwd
            t += 1.0 if fwd.effective_backend("fwd") == "pallas" else 5.0
            if fwd.dataflow == "implicit_gemm":
                t += 0.01 * abs(fwd.tile_m - 64)
        return t

    tuned = PlanTuner(nplan, space, measure, maps=maps).tune()
    for _, c3 in tuned.assignment().items():
        assert c3.fwd.backend == "pallas" and c3.fwd.worklist
        assert c3.fwd.effective_backend("fwd") == "pallas"
        assert c3.fwd.tile_m == 64
    # worklist configs demand pre-built split plans on the executor side
    assert tuned.split_plan_specs()


def test_tuned_pallas_plan_roundtrips_registry(tmp_path):
    """A tuned plan carrying pallas assignments (worklist variant included)
    and measured tiles survives PlanRegistry JSON round-trip bit-exactly —
    including the derived effective_backend stamp in the serialized form."""
    stx = random_tensor(0, n=150, cap=256, channels=5, extent=16)
    nplan = centerpoint.network_plan(CP_CFG)
    maps = nplan.build_maps(stx)
    space = [df.DataflowConfig("implicit_gemm", n_splits=2, backend="pallas",
                               worklist=True),
             df.DataflowConfig("gather_scatter", backend="pallas")]

    def measure(p: NetworkPlan) -> float:
        return sum(1.0 if c3.fwd.dataflow == "implicit_gemm" else 2.0
                   for c3 in p.assignment().values())

    tuned = PlanTuner(nplan, space, measure, maps=maps).tune()
    reg = PlanRegistry()
    reg.set("centerpoint_waymo", tuned.assignment(), network=tuned)
    path = reg.save(str(tmp_path / "plans.json"))
    doc = json.loads(open(path).read())
    blob = json.dumps(doc)
    assert '"worklist": true' in blob
    assert '"effective_backend": "pallas"' in blob
    loaded = PlanRegistry.load(path)
    assert loaded.network("centerpoint_waymo") == tuned
    assert loaded.get("centerpoint_waymo") == tuned.assignment()
