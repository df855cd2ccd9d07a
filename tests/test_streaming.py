"""Composable coordinate tables and streaming kernel maps.

The contract under test (ISSUE 4): composed-batch tables
(``hashing.compose_tables``), delta-merged tables
(``CoordTable.delta_merge``) and every kernel map built from them — through
``build_maps_from_specs(tables=...)`` pre-adoption and through
``kmap.compose_kmaps`` device composition of scene stacks — are
**bit-identical** to fresh full builds, across negative coords,
multi-batch packing, strided table adoption and transposed (up) edges, for
all three key-spec modes.
Plus the serving-engine integration: scene-granular hits where the PR-2
whole-batch digest scores misses, streaming delta submits, and the
deadline-/count-triggered flush satellites.

Property tests use ``hypothesis`` when installed and fall back to the
deterministic samples otherwise (``conftest.property_test``).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import property_test

from repro.core import hashing
from repro.core import kmap as km
from repro.core import plan as planlib
from repro.core.plan import KmapSpec, pyramid_map_specs
from repro.core.sparse_tensor import INVALID_COORD, SparseTensor
from repro.serve import (BucketLadder, Engine, Scene, SceneBatcher,
                         SceneDelta, apply_delta)
from repro.serve.workload import churned_stream

KMAP_FIELDS = ("m_out", "out_coords", "n_out", "ws_in", "ws_out", "ws_count",
               "bitmask")


def _spec_of_kind(kind):
    """One spec per packing mode (cf. test_mapping_engine): single int32
    word, packed [hi, lo] pair, raw no-range-limit fallback."""
    if kind == "one":
        spec = hashing.key_spec_for(3, batch_bound=4, spatial_bound=60)
        assert spec.words == 1 and not spec.raw
    elif kind == "two":
        spec = hashing.key_spec_for(3, batch_bound=500, spatial_bound=12000)
        assert spec.words == 2 and not spec.raw
    else:
        spec = hashing.key_spec_for(3)
        assert spec.raw
    return spec


def _mk_scene_coords(rng, n, lo=-50, hi=50):
    """(n', 4) unique batch-0 voxel rows (exercises negative coords)."""
    c = np.unique(np.concatenate(
        [np.zeros((2 * n, 1), np.int32),
         rng.integers(lo, hi, size=(2 * n, 3), dtype=np.int32)], axis=1),
        axis=0)
    return c[:n]


def _pack_batch(scene_coords, capacity):
    """Batch-major packed coords + tensor, as SceneBatcher lays rows out."""
    batch = np.full((capacity, 4), int(INVALID_COORD), np.int32)
    off = 0
    for b, c in enumerate(scene_coords):
        cb = c.copy()
        cb[:, 0] = b
        batch[off:off + len(c)] = cb
        off += len(c)
    st = SparseTensor(coords=jnp.asarray(batch), feats=jnp.zeros((capacity, 1)),
                      num_valid=jnp.asarray(off, jnp.int32), stride=1,
                      batch_bound=4, spatial_bound=64)
    return batch, st, off


def assert_kmaps_equal(a, b, ctx=""):
    for f in KMAP_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"{ctx}:{f}")


# ---------------------------------------------------------------------------
# compose_tables ≡ fresh batch build (tables bit-identical, incl. pad tail)
# ---------------------------------------------------------------------------

@property_test(
    "seed,sizes,spec_kind",
    cases=[(0, (17, 9, 23), "one"), (1, (40, 1, 12), "two"),
           (2, (8, 30), "raw"), (3, (25,), "one"), (4, (6, 6, 6, 6), "two")],
    strategies=lambda st: dict(
        seed=st.integers(0, 10_000),
        sizes=st.lists(st.integers(1, 40), min_size=1, max_size=4).map(tuple),
        spec_kind=st.sampled_from(["one", "two", "raw"])),
    max_examples=20)
def test_property_composed_table_bit_identical(seed, sizes, spec_kind):
    rng = np.random.default_rng(seed)
    spec = _spec_of_kind(spec_kind)
    scenes = [_mk_scene_coords(rng, n) for n in sizes]
    cap = sum(len(c) for c in scenes) + 11   # uneven pad tail
    batch, bst, total = _pack_batch(scenes, cap)
    fresh = hashing.CoordTable.build(bst.coords, bst.valid_mask, spec)
    off = 0
    parts = []
    for b, c in enumerate(scenes):
        t = hashing.CoordTable.build(jnp.asarray(c), jnp.ones((len(c),), bool),
                                     spec)
        parts.append((np.asarray(t.sorted_keys), np.asarray(t.order), b, off))
        off += len(c)
    keys, order = hashing.compose_tables(spec, parts, cap)
    np.testing.assert_array_equal(keys, np.asarray(fresh.sorted_keys))
    np.testing.assert_array_equal(order, np.asarray(fresh.order))


# ---------------------------------------------------------------------------
# delta_merge ≡ fresh build of the updated scene
# ---------------------------------------------------------------------------

@property_test(
    "seed,n,r,a,spec_kind",
    cases=[(0, 40, 5, 7, "one"), (1, 30, 1, 1, "two"), (2, 25, 4, 0, "raw"),
           (3, 20, 0, 6, "one"), (4, 50, 12, 12, "two"), (5, 15, 15, 3, "raw")],
    strategies=lambda st: dict(
        seed=st.integers(0, 10_000), n=st.integers(2, 50),
        r=st.integers(0, 10), a=st.integers(0, 10),
        spec_kind=st.sampled_from(["one", "two", "raw"])),
    max_examples=20)
def test_property_delta_merged_table_bit_identical(seed, n, r, a, spec_kind):
    rng = np.random.default_rng(seed)
    spec = _spec_of_kind(spec_kind)
    coords = _mk_scene_coords(rng, n)
    n = len(coords)
    r = min(r, n)
    table = hashing.CoordTable.build(jnp.asarray(coords),
                                     jnp.ones((n,), bool), spec)
    rm_idx = rng.choice(n, size=r, replace=False)
    removed = coords[rm_idx]
    kept = np.delete(coords, rm_idx, axis=0)
    taken = set(map(tuple, kept))
    added = []
    while len(added) < a:
        cand = np.concatenate([[0], rng.integers(-50, 50, size=3)]).astype(np.int32)
        if tuple(cand) not in taken:
            taken.add(tuple(cand))
            added.append(cand)
    added = (np.asarray(added, np.int32) if added
             else np.zeros((0, 4), np.int32))
    new_coords = np.concatenate([kept, added])
    fresh = hashing.CoordTable.build(jnp.asarray(new_coords),
                                     jnp.ones((len(new_coords),), bool), spec)
    merged = table.delta_merge(jnp.asarray(removed), jnp.asarray(added))
    np.testing.assert_array_equal(np.asarray(merged.sorted_keys),
                                  np.asarray(fresh.sorted_keys))
    np.testing.assert_array_equal(np.asarray(merged.order),
                                  np.asarray(fresh.order))
    # the host-side numpy twin (the engine's streaming hot path) agrees too
    nk, no = hashing.np_delta_merge(spec, np.asarray(table.sorted_keys),
                                    np.asarray(table.order), removed, added)
    np.testing.assert_array_equal(nk, np.asarray(fresh.sorted_keys))
    np.testing.assert_array_equal(no, np.asarray(fresh.order))


def test_delta_merged_table_builds_identical_kmaps():
    """Maps built on a delta-merged table (pre-adopted through the tables=
    hook, root level) equal maps built from scratch on the updated scene."""
    rng = np.random.default_rng(7)
    spec = _spec_of_kind("one")
    coords = _mk_scene_coords(rng, 60)
    prev = Scene(coords=coords[:, 1:],
                 feats=rng.normal(size=(len(coords), 4)).astype(np.float32))
    delta = SceneDelta(removed=coords[rng.choice(len(coords), 6,
                                                 replace=False), 1:],
                       added_coords=np.asarray([[51, 52, 53], [54, 55, 56]],
                                               np.int32),
                       added_feats=np.zeros((2, 4), np.float32))
    new = apply_delta(prev, delta)
    c01 = np.concatenate([np.zeros((new.num_points, 1), np.int32),
                          new.coords], axis=1)
    st = SparseTensor(coords=jnp.asarray(c01),
                      feats=jnp.asarray(new.feats),
                      num_valid=jnp.asarray(new.num_points, jnp.int32),
                      stride=1, batch_bound=4, spatial_bound=60)
    prev01 = np.concatenate([np.zeros((prev.num_points, 1), np.int32),
                             prev.coords], axis=1)
    table = hashing.CoordTable.build(jnp.asarray(prev01),
                                     jnp.ones((prev.num_points,), bool), spec)
    merged = table.delta_merge(
        np.concatenate([np.zeros((6, 1), np.int32), delta.removed], 1),
        np.concatenate([np.zeros((2, 1), np.int32), delta.added_coords], 1))
    specs = pyramid_map_specs(2, with_up=True)
    fresh = planlib.build_maps_from_specs(specs, st)
    n = jnp.asarray(new.num_points, jnp.int32)
    via_delta = planlib.build_maps_from_specs(
        specs, st, tables={1: (merged.sorted_keys, merged.order, n)})
    for ref in fresh:
        assert_kmaps_equal(fresh[ref], via_delta[ref], ctx=str(ref))


# ---------------------------------------------------------------------------
# Composed tables / composed kernel maps ≡ fresh batch map builds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec_kind", ["one", "two", "raw"])
@pytest.mark.parametrize("with_up", [False, True])
def test_composed_tables_build_identical_maps(spec_kind, with_up):
    """build_maps over pre-composed table ladders (root order + identity
    child tables through the strided adoption edges, incl. transpose) is
    bit-identical to a fresh batch build, for every key-spec mode."""
    rng = np.random.default_rng(11)
    scenes = [_mk_scene_coords(rng, n) for n in (40, 25, 33)]
    cap = 128
    batch, bst, total = _pack_batch(scenes, cap)
    if spec_kind != "one":   # re-declare bounds to force the other specs
        bb, sb = (500, 12000) if spec_kind == "two" else (0, 0)
        bst = SparseTensor(coords=bst.coords, feats=bst.feats,
                           num_valid=bst.num_valid, stride=1,
                           batch_bound=bb, spatial_bound=sb)
    spec = km.MapCache.for_tensor(bst).spec
    specs = pyramid_map_specs(4, with_up=with_up, table="composed")
    down_strides = sorted({ms.tensor_stride * ms.stride for ms in specs
                           if ms.kind == "down"})
    fresh = planlib.build_maps_from_specs(specs, bst)
    ladders = [km.scene_table_ladder(c, spec, down_strides) for c in scenes]
    tables = km.compose_batch_tables(spec, ladders, cap)
    assert sorted(tables) == [1] + down_strides   # every level composed
    composed = planlib.build_maps_from_specs(specs, bst, tables=tables)
    for ref in fresh:
        assert_kmaps_equal(fresh[ref], composed[ref], ctx=str(ref))


def _scene_entries(specs, scenes, caps):
    """One scene entry per batch-0 coordinate block, each built at its own
    scene capacity (rung)."""
    entries = []
    for c, cap in zip(scenes, caps):
        rows = np.full((cap, 4), int(INVALID_COORD), np.int32)
        rows[:len(c)] = c
        st = SparseTensor(coords=jnp.asarray(rows),
                          feats=jnp.zeros((cap, 1)),
                          num_valid=jnp.asarray(len(c), jnp.int32), stride=1,
                          batch_bound=4, spatial_bound=64)
        entries.append(planlib.build_scene_entry(specs, st))
    return entries


def _assert_composes_to_fresh(specs, scenes, caps, cap):
    """The device composer — op by op, and jitted as the engine runs it —
    gives exactly the fresh batch build's maps."""
    batch, bst, total = _pack_batch(scenes, cap)
    fresh = planlib.build_maps_from_specs(specs, bst)
    entries = _scene_entries(specs, scenes, caps)
    jitted = dict(place=jax.jit(lambda *a: km.compose_place(specs, *a)),
                  finish=jax.jit(lambda *a: km.compose_finish(specs, *a)))
    for composed in (km.compose_kmaps(specs, entries, cap),
                     km.compose_kmaps(specs, entries, cap, **jitted)):
        assert composed is not None and set(composed) == set(fresh)
        for ref in fresh:
            assert_kmaps_equal(fresh[ref], composed[ref], ctx=str(ref))
            assert composed[ref].out_stride == fresh[ref].out_stride
            assert composed[ref].kernel_size == fresh[ref].kernel_size
    return entries


@pytest.mark.parametrize("with_up", [False, True])
def test_composed_scene_kmap_stacks_bit_identical(with_up):
    """compose_kmaps: per-scene cached kernel-map stacks compose on the
    device into the exact batch map stack (Minuet §4 proper) — m_out/ws/
    bitmask and the up-map transpose edges included.  Scene rows are
    shuffled: client scenes arrive in arbitrary row order, and the up-map
    pair lists follow the forward map's coarse-row order (transpose_kmap),
    not fine-row order — a regression the sorted rows np.unique produces
    would mask."""
    rng = np.random.default_rng(13)
    scenes = [_mk_scene_coords(rng, n) for n in (40, 25, 33)]
    for c in scenes:
        rng.shuffle(c)
    cap = 128
    specs = pyramid_map_specs(4, with_up=with_up, table="composed")
    entries = _assert_composes_to_fresh(specs, scenes, [c.shape[0]
                                                        for c in scenes], cap)
    # degenerate inputs fall back instead of mis-composing
    assert km.compose_kmaps(specs, [], cap) is None
    assert km.compose_kmaps(specs, entries, entries[0].n - 1) is None


@pytest.mark.parametrize("arch", ["minkunet", "centerpoint"])
@pytest.mark.parametrize("sizes,caps,cap", [
    ((90, 40), (128, 64), 256),      # two scene rungs in one bucket
    ((40, 90), (64, 128), 256),      # the other order
    ((70,), (128,), 128),            # a single-scene batch
    ((90,), (128,), 96),             # a scene rung taller than the bucket
])
def test_device_composer_matches_batch_build_for_model_specs(arch, sizes,
                                                             caps, cap):
    """MinkUNet's and CenterPoint's own map programs compose bit-identically
    to a fresh batch build, whatever the scenes' rungs and the batch size."""
    from repro.configs import centerpoint_waymo, minkunet_kitti
    from repro.models import centerpoint, minkunet
    specs = (minkunet.declare(minkunet_kitti.CONFIG_BENCH) if arch ==
             "minkunet" else centerpoint.declare(
                 centerpoint_waymo.CONFIG_BENCH)).map_specs
    rng = np.random.default_rng(sum(sizes))
    scenes = [_mk_scene_coords(rng, n, -20, 20) for n in sizes]
    _assert_composes_to_fresh(specs, scenes, caps, cap)


def test_device_composer_declines_what_it_cannot_compose():
    """An empty scene, or a level with more rows than the bucket, gives
    None (the engine then builds the batch's maps afresh)."""
    rng = np.random.default_rng(4)
    specs = pyramid_map_specs(2, with_up=True, table="composed")
    scenes = [_mk_scene_coords(rng, n) for n in (30, 20)]
    entries = _scene_entries(specs, scenes, (32, 32))
    assert km.compose_kmaps(specs, entries, 50) is not None
    assert km.compose_kmaps(specs, entries, 49) is None
    empty = _scene_entries(specs, [scenes[0][:0]], (32,))[0]
    assert empty.n == 0
    assert km.compose_kmaps(specs, [entries[0], empty], 64) is None
    # only the level sizes and the root table reached the host
    assert set(entries[0].arrays) == {"m_out", "coords"}
    for a in jax.tree.leaves(entries[0].arrays):
        assert isinstance(a, jax.Array)
    assert isinstance(entries[0].root_keys, np.ndarray)
    assert entries[0].nbytes == sum(a.nbytes for a in
                                    jax.tree.leaves(entries[0].arrays))


# ---------------------------------------------------------------------------
# KmapSpec "table" strategy: a declared, serializable, rebindable axis
# ---------------------------------------------------------------------------

def test_kmap_spec_table_strategy_axis():
    ms = KmapSpec(("sub", 1), "sub", 3, 1, 1, table="incremental")
    assert KmapSpec.from_dict(ms.to_dict()) == ms
    # missing key (pre-PR files) defaults to the sort strategy
    d = ms.to_dict()
    del d["table"]
    assert KmapSpec.from_dict(d).table == "sort"
    with pytest.raises(AssertionError):
        KmapSpec(("sub", 1), "sub", 3, 1, 1, table="bogus")

    from repro.models import centerpoint, minkunet
    from repro.configs import centerpoint_waymo
    nplan = centerpoint.network_plan(centerpoint_waymo.CONFIG_TINY
                                     if hasattr(centerpoint_waymo, "CONFIG_TINY")
                                     else centerpoint_waymo.CONFIG_BENCH)
    assert nplan.table_strategy == "composed"    # models declare composition
    re = nplan.with_table_strategy("incremental")
    assert re.table_strategy == "incremental"
    assert all(ms.table == "incremental" for ms in re.map_specs)
    # round-trips through the serialized plan
    from repro.core.plan import NetworkPlan
    assert NetworkPlan.from_dict(re.to_dict()).table_strategy == "incremental"


# ---------------------------------------------------------------------------
# Engine integration: scene-granular reuse, streaming deltas, deadline flush
# ---------------------------------------------------------------------------

def _mk_scene(rng, n, channels, bound=60):
    coords = np.unique(rng.integers(-bound, bound, size=(n, 3),
                                    dtype=np.int32), axis=0)
    return Scene(coords=coords,
                 feats=rng.normal(size=(coords.shape[0], channels))
                 .astype(np.float32))


def _reference_forward(eng, scene):
    single = eng.batcher.pack([scene])
    maps = eng.binding.model.build_maps(single.st)
    feats = eng.binding.model.apply(eng.params, single.st, eng.cfg, maps,
                                    assignment=eng.assignment,
                                    bn_mode="affine")
    coords, out_feats, n_out = eng.binding.outputs_of(eng.cfg, single.st,
                                                      maps, feats)
    coords, out_feats = np.asarray(coords), np.asarray(out_feats)
    valid = np.arange(coords.shape[0]) < int(n_out)
    return coords[valid][:, 1:], out_feats[valid]


def test_engine_scene_granular_hits_where_digest_misses():
    """Churned batch composition: every flush's packed batch differs (the
    PR-2 whole-batch digest always misses) but the unchanged scenes hit the
    per-scene store, and the composed outputs stay bit-identical to the
    per-scene reference forward."""
    rng = np.random.default_rng(3)
    eng = Engine("centerpoint_waymo", ladder=BucketLadder((512,), max_batch=4),
                 spatial_bound=64)
    assert eng.map_strategy == "composed"
    pool = [_mk_scene(rng, n, 5) for n in (60, 70, 50, 40)]
    # three flushes over rotating scene subsets: batches never repeat
    batches = [pool[:3], [pool[3]] + pool[1:3], pool[:2] + [pool[3]]]
    results = []
    for group in batches:
        tickets = [eng.submit(s) for s in group]
        out = eng.flush()
        results.extend((s, out[t]) for s, t in zip(group, tickets))
    assert eng.stats.map_hits == 0 and eng.stats.map_misses == 3
    assert eng.stats.composed_batches == 3
    assert eng.stats.scene_misses == 4         # each unique scene built once
    assert eng.stats.scene_hits == 5           # every repeat slot composed
    for scene, res in results:
        ref_coords, ref_feats = _reference_forward(eng, scene)
        np.testing.assert_array_equal(res.coords, ref_coords)
        np.testing.assert_array_equal(res.feats, ref_feats)  # bit-identical


def test_engine_streaming_deltas_bit_identical():
    """submit_delta under the incremental strategy: frames delta-merge the
    scene table (counted), compose into batches, and every frame's output
    equals the reference forward of the full updated scene."""
    eng = Engine("centerpoint_waymo", ladder=BucketLadder((512,), max_batch=4),
                 spatial_bound=64, map_strategy="incremental")
    frames, bound = churned_stream(5, streams=3, frames=4, channels=5,
                                   n_range=(40, 80), extent=16.0, voxel=0.4)
    assert bound <= 64
    served = []
    for frame in frames:
        tickets = []
        for sid, scene, delta in frame:
            if delta is not None:
                tickets.append((scene, eng.submit_delta(sid, delta)))
            else:
                tickets.append((scene, eng.submit(scene, stream=sid)))
        out = eng.flush()
        served.extend((s, out[t]) for s, t in tickets)
    assert eng.stats.delta_merges > 0
    assert eng.stats.scene_hits > 0            # unchanged streams composed
    assert eng.stats.composed_batches == eng.stats.map_misses
    for scene, res in served:
        ref_coords, ref_feats = _reference_forward(eng, scene)
        np.testing.assert_array_equal(res.coords, ref_coords)
        np.testing.assert_array_equal(res.feats, ref_feats)


def test_engine_unknown_stream_delta_raises():
    eng = Engine("centerpoint_waymo", ladder=BucketLadder((256,)),
                 spatial_bound=64, map_strategy="incremental")
    with pytest.raises(KeyError):
        eng.submit_delta("nope", SceneDelta(removed=np.zeros((0, 3), np.int32),
                                            added_coords=np.zeros((0, 3), np.int32),
                                            added_feats=np.zeros((0, 5), np.float32)))
    # an added coord outside the declared bound must be rejected loudly —
    # BEFORE it could mis-pack into a cached scene table (np_pack_keys has
    # no PAD sentinel) and alias another scene's voxel
    rng = np.random.default_rng(1)
    eng.submit(_mk_scene(rng, 30, 5), stream="s")
    eng.flush()
    with pytest.raises(ValueError):
        eng.submit_delta("s", SceneDelta(
            removed=np.zeros((0, 3), np.int32),
            added_coords=np.asarray([[200, 0, 0]], np.int32),
            added_feats=np.zeros((1, 5), np.float32)))


def test_apply_delta_layout_and_validation():
    prev = Scene(coords=np.asarray([[0, 0, 0], [1, 1, 1], [2, 2, 2]], np.int32),
                 feats=np.arange(6, dtype=np.float32).reshape(3, 2))
    delta = SceneDelta(removed=np.asarray([[1, 1, 1]], np.int32),
                       added_coords=np.asarray([[3, 3, 3]], np.int32),
                       added_feats=np.asarray([[9.0, 9.0]], np.float32))
    new = apply_delta(prev, delta)
    np.testing.assert_array_equal(new.coords,
                                  [[0, 0, 0], [2, 2, 2], [3, 3, 3]])
    np.testing.assert_array_equal(new.feats, [[0, 1], [4, 5], [9, 9]])
    with pytest.raises(ValueError):
        apply_delta(prev, SceneDelta(removed=np.asarray([[7, 7, 7]], np.int32),
                                     added_coords=np.zeros((0, 3), np.int32),
                                     added_feats=np.zeros((0, 2), np.float32)))


def test_deadline_and_count_triggered_flushes():
    """The async-batching first step: submits flush automatically when the
    queue hits flush_count or the oldest scene ages past max_wait_ms, with
    both triggers counted and results drained by the next flush()/poll()."""
    rng = np.random.default_rng(9)
    scenes = [_mk_scene(rng, 40, 5) for _ in range(4)]

    eng = Engine("centerpoint_waymo", ladder=BucketLadder((256,), max_batch=2),
                 spatial_bound=64, flush_count=2)
    t0 = eng.submit(scenes[0])
    assert eng.stats.count_flushes == 0        # below threshold: queued
    t1 = eng.submit(scenes[1])
    assert eng.stats.count_flushes == 1        # threshold reached: ran
    out = eng.flush()                          # drains the auto-flushed pair
    assert set(out) == {t0, t1}
    assert eng.flush() == {}

    eng2 = Engine("centerpoint_waymo", ladder=BucketLadder((256,), max_batch=2),
                  spatial_bound=64, max_wait_ms=1e6)
    ta = eng2.submit(scenes[2])
    assert eng2.poll() == {}                   # deadline far away
    assert eng2.stats.deadline_flushes == 0
    eng2.max_wait_ms = 0.0                     # expire the oldest instantly
    out2 = eng2.poll()
    assert set(out2) == {ta} and eng2.stats.deadline_flushes == 1
    # a submit can also trip the deadline of an already-queued scene
    eng2.max_wait_ms = 1e6
    tb = eng2.submit(scenes[3])
    eng2.max_wait_ms = 0.0
    tc = eng2.submit(scenes[2])
    assert eng2.stats.deadline_flushes == 2
    assert set(eng2.flush()) == {tb, tc}
    s = eng2.stats.summary()
    assert s["deadline_flushes"] == 2
