"""Serving subsystem: bucket selection, batcher round-trip, plan
persistence, and the engine's end-to-end correctness contract (batched ≡
per-scene, bounded recompiles, cross-request map reuse)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import dataflows as df
from repro.core.kmap import MapCache
from repro.core.sparse_conv import TrainDataflowConfig, apply_conv, init_conv, ConvSpec
from repro.core.kmap import build_kmap
from repro.models import centerpoint, minkunet
from repro.serve import (BucketLadder, Engine, PlanRegistry, Scene,
                         SceneBatcher, scene_from_tensor)
from repro.serve.workload import lidar_stream

RNG = np.random.default_rng(0)


def _mk_scene(n, channels, bound=60, seed=None):
    rng = np.random.default_rng(seed) if seed is not None else RNG
    coords = np.unique(
        rng.integers(-bound, bound, size=(n, 3), dtype=np.int32), axis=0)
    return Scene(coords=coords,
                 feats=rng.normal(size=(coords.shape[0], channels)).astype(np.float32))


# ---------------------------------------------------------------- buckets

def test_bucket_selection_smallest_fit_deterministic():
    ladder = BucketLadder((128, 512, 2048), max_batch=4)
    assert ladder.select(1) == 128
    assert ladder.select(128) == 128
    assert ladder.select(129) == 512
    assert ladder.select(2048) == 2048
    # deterministic: same input, same bucket, every time
    assert all(ladder.select(300) == 512 for _ in range(5))
    with pytest.raises(ValueError):
        ladder.select(2049)


def test_bucket_ladder_validation():
    with pytest.raises(AssertionError):
        BucketLadder((512, 128))          # must ascend
    with pytest.raises(AssertionError):
        BucketLadder(())
    geo = BucketLadder.geometric(256, 3)
    assert geo.capacities == (256, 512, 1024)


def test_batcher_plan_fifo_respects_bucket_and_batch_limits():
    ladder = BucketLadder((256, 512), max_batch=2)
    b = SceneBatcher(ladder, spatial_bound=64)
    groups = b.plan([100, 200, 300, 50, 50, 50])
    # FIFO: scene order preserved; limits: ≤512 rows and ≤2 scenes per group
    assert [i for g in groups for i in g] == list(range(6))
    for g in groups:
        assert len(g) <= 2
        assert sum([100, 200, 300, 50, 50, 50][i] for i in g) <= 512
    assert groups == b.plan([100, 200, 300, 50, 50, 50])  # deterministic
    with pytest.raises(ValueError):
        b.plan([513])


# ---------------------------------------------------------------- batcher

def test_pack_unpack_roundtrip_identity():
    """pack K scenes → 'identity model' → unpack reproduces every scene."""
    ladder = BucketLadder((256,), max_batch=3)
    b = SceneBatcher(ladder, spatial_bound=64)
    scenes = [_mk_scene(n, 4, seed=n) for n in (40, 70, 25)]
    batch = b.pack(scenes)
    assert batch.bucket == 256
    assert int(batch.st.num_valid) == sum(s.num_points for s in scenes)
    assert batch.st.batch_bound == 3 and batch.st.spatial_bound == 64
    out = b.unpack(batch, batch.st.coords, batch.st.feats,
                   int(batch.st.num_valid), out_stride=1)
    assert len(out) == 3
    for scene, res in zip(scenes, out):
        np.testing.assert_array_equal(res.coords, scene.coords)
        np.testing.assert_array_equal(res.feats, scene.feats)


def test_pack_rejects_bound_violation():
    b = SceneBatcher(BucketLadder((256,)), spatial_bound=16)
    bad = Scene(coords=np.array([[0, 0, 40]], np.int32),
                feats=np.zeros((1, 4), np.float32))
    with pytest.raises(ValueError):
        b.pack([bad])


def test_pack_digest_is_content_keyed():
    b = SceneBatcher(BucketLadder((256,), max_batch=2), spatial_bound=64)
    s1, s2 = _mk_scene(30, 4, seed=1), _mk_scene(30, 4, seed=2)
    s1_copy = Scene(coords=s1.coords.copy(), feats=s1.feats.copy())
    assert b.pack([s1]).digest == b.pack([s1_copy]).digest
    assert b.pack([s1]).digest != b.pack([s2]).digest
    assert b.pack([s1, s2]).digest != b.pack([s2, s1]).digest


# ------------------------------------------------------------------ plans

def test_plan_registry_save_load_identical(tmp_path):
    reg = PlanRegistry()
    assignment = {
        (1, 3, "sub"): TrainDataflowConfig.bind_all(
            df.DataflowConfig("gather_scatter")),
        (2, 2, "down"): TrainDataflowConfig.bind_fwd_dgrad(
            df.DataflowConfig("implicit_gemm", n_splits=2, tile_m=64),
            df.DataflowConfig("fetch_on_demand")),
    }
    reg.set("minkunet_kitti", assignment)
    path = reg.save(str(tmp_path / "plans.json"))
    loaded = PlanRegistry.load(path)
    assert loaded.get("minkunet_kitti") == assignment
    assert loaded.archs() == ["minkunet_kitti"]
    # unknown arch → empty assignment, not an error
    assert loaded.get("never_tuned") == {}


def test_plan_registry_rejects_bad_version(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text('{"version": 99, "plans": {}}')
    with pytest.raises(ValueError):
        PlanRegistry.load(str(p))


def test_default_serving_space_spans_dataflows_and_backends():
    """The tuner's default space searches all three dataflows on both
    backends (Pallas in interpret mode on CPU), or the XLA triple alone
    when the Pallas axis is switched off."""
    forced = df.default_serving_space(include_pallas=True)
    assert len(forced) == 6
    assert {c.dataflow for c in forced} == set(df.DATAFLOWS)
    assert {c.backend for c in forced} == {"xla", "pallas"}
    # the worklist variant runs eagerly only, so the jitted served
    # executor's tuner never searches it
    assert not any(c.worklist for c in forced)
    xla_only = df.default_serving_space(include_pallas=False)
    assert len(xla_only) == 3
    assert all(c.backend == "xla" for c in xla_only)
    assert {c.dataflow for c in xla_only} == set(df.DATAFLOWS)
    assert df.default_serving_space() == forced


def test_pallas_assignment_roundtrips_plan_registry(tmp_path):
    """A tuner pick on the Pallas axis persists through ``PlanRegistry``
    and reloads into an engine intact — including the split-plan demand it
    creates on the executor-input side."""
    reg = PlanRegistry()
    assignment = {(1, 3, "sub"): TrainDataflowConfig.bind_all(
        df.DataflowConfig("implicit_gemm", n_splits=2, backend="pallas"))}
    reg.set("minkunet_kitti", assignment)
    path = reg.save(str(tmp_path / "plans.json"))
    eng = Engine("minkunet_kitti", ladder=BucketLadder((256,), max_batch=2),
                 spatial_bound=64, plans=path)
    assert eng.assignment == assignment
    assert eng.assignment[(1, 3, "sub")].fwd.backend == "pallas"
    # the pallas implicit-GEMM choice declares pre-built executor split
    # plans on the compiled plan (composed per batch by the serving engine)
    specs = eng.nplan.split_plan_specs()
    assert specs and all(ns == 2 and srt for _, ns, srt in specs)


def test_dataflow_config_dict_roundtrip():
    cfg = df.DataflowConfig("fetch_on_demand", n_splits=0, tile_m=32,
                            tile_n=64, backend="pallas")
    assert df.DataflowConfig.from_dict(cfg.to_dict()) == cfg
    with pytest.raises(ValueError):
        df.DataflowConfig.from_dict({"dataflow": "implicit_gemm", "bogus": 1})


def test_serialized_config_stamps_effective_backend():
    """A "pallas" request only *runs* Pallas for dataflows that have a
    kernel; serialized configs (and therefore tuner sweep logs and plan
    registries) carry the derived ``effective_backend`` so sweep records
    say what actually executed."""
    # gather_scatter has no pallas forward kernel: requested != effective
    gs = df.DataflowConfig("gather_scatter", backend="pallas")
    assert gs.to_dict()["effective_backend"] == "xla"
    assert gs.effective_backend("fwd") == "xla"
    ig = df.DataflowConfig("implicit_gemm", backend="pallas")
    assert ig.to_dict()["effective_backend"] == "pallas"
    assert ig.effective_backend("dgrad") == "xla"   # dgrad is always XLA scan
    assert ig.effective_backend("wgrad") == "pallas"
    assert df.DataflowConfig("implicit_gemm").to_dict()["effective_backend"] == "xla"
    # the stamp is derived, not state: it round-trips away cleanly
    assert df.DataflowConfig.from_dict(gs.to_dict()) == gs


# ----------------------------------------------------------------- engine

def _reference_forward(eng, scene):
    """Per-scene forward through the public model API at the same bucket."""
    single = eng.batcher.pack([scene])
    maps = eng.binding.model.build_maps(single.st)
    feats = eng.binding.model.apply(eng.params, single.st, eng.cfg, maps,
                                    assignment=eng.assignment, bn_mode="affine")
    coords, out_feats, n_out = eng.binding.outputs_of(eng.cfg, single.st,
                                                      maps, feats)
    coords, out_feats = np.asarray(coords), np.asarray(out_feats)
    valid = np.arange(coords.shape[0]) < int(n_out)
    return coords[valid][:, 1:], out_feats[valid]


@pytest.mark.parametrize("arch,channels", [("minkunet_kitti", 4),
                                           ("centerpoint_waymo", 5)])
def test_batched_engine_bit_identical_to_per_scene(arch, channels):
    """The acceptance contract: a mixed-size request stream served batched
    produces, per scene, exactly the bits of the per-scene forward."""
    eng = Engine(arch, ladder=BucketLadder((256, 512), max_batch=3),
                 spatial_bound=64)
    scenes = [_mk_scene(n, channels, seed=n) for n in (50, 120, 30, 200, 80)]
    results = eng.serve(scenes, flush_every=3)
    assert len(results) == len(scenes)
    for scene, res in zip(scenes, results):
        ref_coords, ref_feats = _reference_forward(eng, scene)
        np.testing.assert_array_equal(res.coords, ref_coords)
        assert res.feats.dtype == ref_feats.dtype
        np.testing.assert_array_equal(res.feats, ref_feats)  # bit-identical


def test_engine_recompile_bound_and_map_reuse():
    """≤1 jit compile per bucket per stage after warmup, and replayed
    batches skip map construction via the content-keyed cross-request
    cache.  Under the default "composed" strategy batch maps are composed
    on the device from per-scene stacks, so the map-builder stage is never
    traced at all, and warmup compiles the composer for every scene rung a
    bucket can hold — batches mixing rungs trace nothing new; the "sort"
    strategy keeps the PR-2 one-trace-per-bucket bound."""
    eng = Engine("centerpoint_waymo",
                 ladder=BucketLadder((256, 512), max_batch=3), spatial_bound=64)
    assert eng.map_strategy == "composed"    # the plan-declared default
    eng.warmup()
    warm_exec = dict(eng.stats.recompiles)
    assert warm_exec == {256: 1, 512: 1}     # one executor trace per bucket
    assert eng.stats.map_compiles == {}      # composed: no builder traces
    assert eng.stats.composed_batches == 2   # one composed batch per bucket
    warm_compose = dict(eng.stats.compose_compiles)
    warm_scene = dict(eng.stats.scene_compiles)
    # per bucket: the finishing stage, and a placing stage per scene rung
    # (64..512) whose smallest scene fits the bucket
    assert warm_compose == {256: 1 + 3, 512: 1 + 4}

    # each flush is one batch pairing a rung-64 scene with a rung-256 one
    scenes = [_mk_scene(n, 5, seed=100 + n) for n in (60, 150, 40, 220)]
    assert [eng._scene_ladder.select(s.num_points) for s in scenes] == \
        [64, 256, 64, 256]
    eng.serve(scenes, flush_every=2)
    hits0 = eng.stats.map_hits
    eng.serve(scenes, flush_every=2)         # replay: identical batches
    # no new traces in steady state — the ≤1-per-bucket guarantee
    assert eng.stats.recompiles == warm_exec
    assert eng.stats.map_compiles == {}
    assert eng.stats.compose_compiles == warm_compose
    assert eng.stats.scene_compiles == warm_scene
    # replayed epoch's batches all hit the whole-batch map cache
    assert eng.stats.map_hits >= hits0 + 2
    s = eng.stats.summary()
    assert s["scenes"] == 8 and s["p95_ms"] >= s["p50_ms"] > 0

    # the "sort" override restores the PR-2 jitted builder path exactly
    eng2 = Engine("centerpoint_waymo",
                  ladder=BucketLadder((256, 512), max_batch=3),
                  spatial_bound=64, map_strategy="sort")
    eng2.warmup()
    assert eng2.stats.map_compiles == {256: 1, 512: 1}
    assert eng2.stats.composed_batches == 0 and eng2.stats.scene_misses == 0


def test_cold_composed_batches_fetch_only_sizes_and_root_table():
    """Every batch of fresh scenes composes on the device; a cold scene's
    build sends the host only its level sizes and its root table (keys and
    order, one int32 word each a rung row), never its maps."""
    eng = Engine("centerpoint_waymo",
                 ladder=BucketLadder((256, 512), max_batch=2),
                 spatial_bound=64)
    scenes = [_mk_scene(n, 5, seed=300 + n) for n in (60, 150, 40, 220, 90)]
    eng.serve(scenes, flush_every=2)
    st = eng.stats
    assert st.composed_batches == st.batches == st.map_misses == 3
    assert st.scene_misses == len(scenes)
    levels = sum(ms.kind == "down" for ms in eng.nplan.map_specs)
    rungs = [eng._scene_ladder.select(s.num_points) for s in scenes]
    assert st.fetch_bytes == sum(4 * (2 * r + levels) for r in rungs)
    assert st.summary()["scene_tables"]["fetch_bytes"] == st.fetch_bytes
    # the maps themselves stayed on the device
    for ent in eng._scene_store.values():
        assert all(isinstance(a, jax.Array)
                   for a in jax.tree.leaves(ent.arrays))
        assert st.fetch_bytes < ent.nbytes


def test_scene_store_byte_bound_evicts_by_device_bytes():
    """``scene_cache_bytes`` bounds the device bytes the scene store pins:
    entries are evicted oldest first until the rest fit."""
    scenes = [_mk_scene(100, 5, seed=400 + i) for i in range(5)]
    probe = Engine("centerpoint_waymo",
                   ladder=BucketLadder((256,), max_batch=1), spatial_bound=64)
    size = probe._scene_entry(scenes[0]).nbytes
    assert size == sum(a.nbytes for a in jax.tree.leaves(
        probe._scene_store[scenes[0].digest].arrays))
    eng = Engine("centerpoint_waymo",
                 ladder=BucketLadder((256,), max_batch=1), spatial_bound=64,
                 scene_cache_bytes=int(2.5 * size))
    eng.serve(scenes, flush_every=1)
    store = eng._scene_store
    assert list(store) == [s.digest for s in scenes[-2:]]
    assert sum(e.nbytes for e in store.values()) <= eng.scene_cache_bytes
    assert all(e.nbytes == size for e in store.values())


def test_engine_rejects_oversize_scene():
    eng = Engine("minkunet_kitti", ladder=BucketLadder((128,), max_batch=2),
                 spatial_bound=64)
    with pytest.raises(ValueError):
        eng.submit(Scene(coords=np.zeros((129, 3), np.int32),
                         feats=np.zeros((129, 4), np.float32)))


def test_engine_loads_plans_at_startup(tmp_path):
    reg = PlanRegistry()
    assignment = {(1, 3, "sub"): TrainDataflowConfig.bind_all(
        df.DataflowConfig("gather_scatter"))}
    reg.set("minkunet_kitti", assignment)
    path = reg.save(str(tmp_path / "plans.json"))
    eng = Engine("minkunet_kitti", ladder=BucketLadder((256,), max_batch=2),
                 spatial_bound=64, plans=path)
    assert eng.assignment == assignment


def test_scene_from_tensor_and_workload_bounds():
    scenes, bound = lidar_stream(0, 3, 4, n_range=(50, 120))
    assert len(scenes) == 3
    for s in scenes:
        assert s.num_points > 0
        assert int(np.abs(s.coords).max()) <= bound
    # distinct sizes exist in a mixed stream (not all padded equal)
    assert len({s.num_points for s in scenes}) > 1


# ---------------------------------------------------- core serving hooks

def test_mapcache_content_key_hits_across_array_objects():
    st = scene_st = None
    scenes, bound = lidar_stream(1, 1, 4, n_range=(60, 60))
    b = SceneBatcher(BucketLadder((128,)), spatial_bound=bound)
    batch1 = b.pack(scenes)
    batch2 = b.pack([Scene(coords=scenes[0].coords.copy(),
                           feats=scenes[0].feats.copy())])
    cache = MapCache.for_tensor(batch1.st)
    t1 = cache.table(batch1.st, key=batch1.digest)
    t2 = cache.table(batch2.st, key=batch2.digest)   # different arrays, same content
    assert t1 is t2
    assert cache.hits == 1 and cache.misses == 1
    cache.clear()
    assert len(cache) == 0


def test_build_maps_populates_caller_supplied_empty_cache():
    """Regression: an empty MapCache is falsy (__len__), so `cache or ...`
    would silently discard it — the caller's cache must still be warmed."""
    scenes, bound = lidar_stream(3, 1, 4, n_range=(60, 60))
    st = SceneBatcher(BucketLadder((128,)), spatial_bound=bound).pack(scenes).st
    for model in (minkunet, centerpoint):
        cache = MapCache.for_tensor(st)
        assert len(cache) == 0 and not cache   # falsy when empty
        model.build_maps(st, cache=cache)
        assert len(cache) > 0
        assert cache.misses > 0


def test_bounds_propagate_through_apply_conv():
    scenes, bound = lidar_stream(2, 1, 4, n_range=(80, 80))
    b = SceneBatcher(BucketLadder((128,), max_batch=2), spatial_bound=bound)
    st = b.pack(scenes).st
    kmap = build_kmap(st, 2, 2)
    params = init_conv(jax.random.PRNGKey(0), ConvSpec(4, 8, 2, stride=2))
    out = apply_conv(params, st, kmap)
    assert out.batch_bound == st.batch_bound
    assert out.spatial_bound == st.spatial_bound
