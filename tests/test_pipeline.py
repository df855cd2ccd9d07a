"""Pipelined serving (double-buffered flushes): bit-identity to the serial
path on mixed-size and streaming-delta streams, bounded in-flight depth,
trace-visible host/device overlap, and the composed executor inputs —
merge-composed ``SplitPlan``s and the incremental down-ladder — matching
their from-scratch builds bit-for-bit."""
import numpy as np
import pytest

from conftest import property_test

from repro import obs
from repro.core import dataflows as df
from repro.core import hashing
from repro.core.kmap import (cell_ladder, cell_ladder_delta,
                             compose_split_plans, ladder_tables,
                             make_split_plan)
from repro.core.sparse_conv import TrainDataflowConfig
from repro.serve import BucketLadder, Engine, PlanRegistry, Scene
from repro.serve.workload import churned_stream


@pytest.fixture(autouse=True)
def _tracer_off():
    obs.disable()
    yield
    obs.disable()


def _mk_scene(n, channels, seed, bound=60):
    rng = np.random.default_rng(seed)
    coords = np.unique(rng.integers(-bound, bound, size=(n, 3),
                                    dtype=np.int32), axis=0)
    return Scene(coords=coords,
                 feats=rng.normal(size=(coords.shape[0], channels)).astype(np.float32))


def _engine(max_inflight, **kw):
    kw.setdefault("ladder", BucketLadder((256, 512), max_batch=2))
    return Engine("minkunet_kitti", spatial_bound=64,
                  max_inflight=max_inflight, **kw)


# ------------------------------------------------------- bit-identity

@property_test(
    "sizes,seed",
    cases=[((50, 120, 30, 200, 80, 60), 0),
           ((40, 45, 240, 10, 90, 200, 35), 1),
           ((200, 30, 150, 60, 20), 2)],
    strategies=lambda st: {
        "sizes": st.lists(st.integers(min_value=10, max_value=250),
                          min_size=3, max_size=8).map(tuple),
        "seed": st.integers(min_value=0, max_value=2**16)},
    max_examples=5)
def test_pipelined_bit_identical_to_serial_mixed_sizes(sizes, seed):
    """The tentpole contract: only the position of block_until_ready moves,
    so a depth-3 pipeline serves exactly the bits of the depth-1 (serial)
    engine on the same mixed-size stream — same params, same grouping, same
    ≤1-executor-compile-per-rung bound."""
    serial, pipe = _engine(1), _engine(3)
    scenes = [_mk_scene(n, 4, seed=seed * 1000 + i)
              for i, n in enumerate(sizes)]
    r0 = serial.serve(scenes)           # one flush at the end → many groups
    r1 = pipe.serve(scenes)
    assert serial.stats.inflight_peak == 1
    for a, b in zip(r0, r1):
        np.testing.assert_array_equal(a.coords, b.coords)
        assert a.feats.dtype == b.feats.dtype
        np.testing.assert_array_equal(a.feats, b.feats)   # bit-identical
    # pipelining never costs extra traces
    assert pipe.stats.recompiles == serial.stats.recompiles
    assert all(n == 1 for n in pipe.stats.recompiles.values())


def test_pipelined_bit_identical_on_streaming_deltas():
    """Same contract under the incremental strategy: delta-merged frames
    composed into pipelined batches equal the serial engine's outputs."""
    kw = dict(ladder=BucketLadder((512,), max_batch=2), spatial_bound=64,
              map_strategy="incremental")
    serial = Engine("centerpoint_waymo", max_inflight=1, **kw)
    pipe = Engine("centerpoint_waymo", max_inflight=2, **kw)
    frames, bound = churned_stream(7, streams=4, frames=3, channels=5,
                                   n_range=(40, 80), extent=16.0, voxel=0.4)
    assert bound <= 64
    for frame in frames:
        tickets = []
        for sid, scene, delta in frame:
            for eng in (serial, pipe):
                if delta is not None:
                    t = eng.submit_delta(sid, delta)
                else:
                    t = eng.submit(scene, stream=sid)
            tickets.append(t)           # same submission order → same tickets
        out_s, out_p = serial.flush(), pipe.flush()
        for t in tickets:
            np.testing.assert_array_equal(out_s[t].coords, out_p[t].coords)
            np.testing.assert_array_equal(out_s[t].feats, out_p[t].feats)
    assert serial.stats.delta_merges > 0 and pipe.stats.delta_merges > 0
    assert pipe.stats.inflight_peak == 2


# ------------------------------------------------- depth bound + overlap

def test_inflight_window_bounded_by_max_inflight():
    """Never more than ``max_inflight`` dispatched-but-undrained batches,
    and the window actually fills when the stream is deep enough."""
    eng = _engine(2, ladder=BucketLadder((256,), max_batch=1))
    scenes = [_mk_scene(60, 4, seed=i) for i in range(6)]   # 6 groups
    eng.serve(scenes)
    assert eng.stats.inflight_peak == 2
    s = eng.stats.summary()["pipeline"]
    assert s["inflight_peak"] == 2


def test_overlap_host_spans_inside_prior_execute_span():
    """Trace evidence of the double-buffer: batch k+1's host-side pack/map
    spans are time-contained within the device ``execute`` span of batch k
    (the execute span runs dispatch-return → drain-ready, and the window
    only drains after the next dispatch when depth permits)."""
    tr = obs.enable()
    try:
        eng = _engine(2, ladder=BucketLadder((256,), max_batch=1))
        eng.serve([_mk_scene(60, 4, seed=10 + i) for i in range(4)])
    finally:
        obs.disable()
    execs = [s for s in tr.spans() if s.name == "execute"]
    hosts = [s for s in tr.spans() if s.name in ("pack", "map")]
    assert execs and hosts
    contained = [(e, h) for e in execs for h in hosts
                 if e.t0_ns < h.t0_ns and h.t1_ns <= e.t1_ns]
    # strict <: batch k's own pack/map end before its dispatch returns, so
    # any contained host span belongs to a *later* batch
    assert contained, "no host span overlapped a device execute span"


def test_serial_depth_one_reproduces_legacy_span_order():
    """max_inflight=1 is the serial engine: every batch drains before the
    next dispatch, so no host span can sit inside a foreign execute span."""
    tr = obs.enable()
    try:
        eng = _engine(1, ladder=BucketLadder((256,), max_batch=1))
        eng.serve([_mk_scene(60, 4, seed=20 + i) for i in range(3)])
    finally:
        obs.disable()
    execs = [s for s in tr.spans() if s.name == "execute"]
    hosts = [s for s in tr.spans() if s.name in ("pack", "map")]
    assert not [(e, h) for e in execs for h in hosts
                if e.t0_ns < h.t0_ns and h.t1_ns <= e.t1_ns]


# ------------------------------------------- composed executor inputs

def _pallas_igemm_engine(n_splits, map_strategy, tmp_path):
    reg = PlanRegistry()
    assignment = {(1, 3, "sub"): TrainDataflowConfig.bind_all(
        df.DataflowConfig("implicit_gemm", n_splits=n_splits,
                          backend="pallas"))}
    reg.set("minkunet_kitti", assignment)
    path = reg.save(str(tmp_path / "plans.json"))
    return Engine("minkunet_kitti", ladder=BucketLadder((256, 512),
                                                        max_batch=3),
                  spatial_bound=64, plans=path, map_strategy=map_strategy)


@pytest.mark.parametrize("n_splits", [1, 2, 4])
def test_composed_split_plans_match_jitted_build(n_splits, tmp_path):
    """compose_split_plans (host-side merge of cached per-scene stable
    orders) is bit-identical to make_split_plan on the composed batch map —
    the per-batch argsort leaves the hot path without changing a bit."""
    eng = _pallas_igemm_engine(n_splits, "composed", tmp_path)
    specs = eng.nplan.split_plan_specs()
    assert specs and all(ns == n_splits and srt for _, ns, srt in specs)
    scenes = [_mk_scene(n, 4, seed=30 + n) for n in (50, 120, 80)]
    batch = eng.batcher.pack(scenes)
    maps, plans = eng._maps_for(batch, scenes)
    assert eng.stats.composed_batches == 1
    assert set(plans) == {(ref, ns, srt) for ref, ns, srt in specs}
    for (ref, ns, srt), sp in plans.items():
        ref_sp = make_split_plan(maps[ref], ns, sort=srt)
        assert sp.ranges == ref_sp.ranges and sp.sorted_ == ref_sp.sorted_
        np.testing.assert_array_equal(np.asarray(sp.order),
                                      np.asarray(ref_sp.order))
        np.testing.assert_array_equal(np.asarray(sp.inv_order),
                                      np.asarray(ref_sp.inv_order))
    # replay: whole-batch cache returns the identical (maps, plans) pair
    maps2, plans2 = eng._maps_for(eng.batcher.pack(scenes), scenes)
    assert plans2 is plans and eng.stats.map_hits == 1
    # composition is pure host work: no plan-builder traces
    assert eng.stats.plan_compiles == {}


def test_fallback_plan_builder_traces_once_per_rung(tmp_path):
    """The cold path ("sort" strategy) builds plans jitted next to the maps:
    one plan-builder trace per rung, counted separately so the exact
    map-compile contracts stay intact."""
    eng = _pallas_igemm_engine(2, "sort", tmp_path)
    scenes = [_mk_scene(n, 4, seed=40 + n) for n in (50, 120)]
    batch = eng.batcher.pack(scenes)
    maps, plans = eng._maps_for(batch, scenes)
    assert set(plans) == set(
        (ref, ns, srt) for ref, ns, srt in eng.nplan.split_plan_specs())
    assert eng.stats.plan_compiles == {256: 1}
    for (ref, ns, srt), sp in plans.items():
        ref_sp = make_split_plan(maps[ref], ns, sort=srt)
        np.testing.assert_array_equal(np.asarray(sp.order),
                                      np.asarray(ref_sp.order))
    # a second distinct batch at the same rung reuses the traced builder
    more = [_mk_scene(n, 4, seed=50 + n) for n in (60, 110)]
    eng._maps_for(eng.batcher.pack(more), more)
    assert eng.stats.plan_compiles == {256: 1}


# ------------------------------------------- incremental down-ladder

def _packed_rows(spec, coords):
    rows = np.concatenate(
        [np.zeros((coords.shape[0], 1), np.int32), coords], axis=1)
    keys = hashing.np_pack_keys(rows, spec)
    order = (np.argsort(keys, kind="stable") if keys.ndim == 1
             else hashing.lex_argsort_np(keys))
    return keys[order]


def test_cell_ladder_delta_matches_fresh_derivation():
    """Propagating a root delta through the cell ladder yields exactly the
    ladder a fresh derivation of the merged cloud produces — per level the
    same sorted unique cells and the same per-cell occupancy counts."""
    rng = np.random.default_rng(3)
    pool = np.unique(rng.integers(-60, 60, size=(500, 3), dtype=np.int32),
                     axis=0)
    scene, added = pool[:300], pool[300:360]
    removed, kept = scene[:40], scene[40:]
    spec = hashing.key_spec_for(3, 4, 64)
    assert not spec.raw
    down = (2, 4, 8)
    lad0 = cell_ladder(spec, _packed_rows(spec, scene), down)
    assert set(lad0) == set(down)
    lad_delta = cell_ladder_delta(spec, lad0,
                                  _packed_rows(spec, removed),
                                  _packed_rows(spec, added))
    merged = np.concatenate([kept, added])
    lad_fresh = cell_ladder(spec, _packed_rows(spec, merged), down)
    for s in down:
        np.testing.assert_array_equal(lad_delta[s][0], lad_fresh[s][0])
        np.testing.assert_array_equal(lad_delta[s][1], lad_fresh[s][1])
        assert int(lad_fresh[s][1].sum()) == merged.shape[0]
    # unfolded adoption tables agree too (PAD-padded, sorted, exact n)
    t_d, t_f = (ladder_tables(spec, l, 512) for l in (lad_delta, lad_fresh))
    for s in down:
        np.testing.assert_array_equal(t_d[s][0], t_f[s][0])
        assert t_d[s][2] == t_f[s][2] == lad_fresh[s][0].shape[0]


def test_cell_ladder_counts_track_cells_exactly():
    """A cell leaves a level exactly when its last root row leaves: remove
    every row of one stride-8 cell and the delta ladder drops that cell."""
    rng = np.random.default_rng(11)
    scene = np.unique(rng.integers(-60, 60, size=(200, 3), dtype=np.int32),
                      axis=0)
    spec = hashing.key_spec_for(3, 4, 64)
    lad0 = cell_ladder(spec, _packed_rows(spec, scene), (8,))
    cell_of = scene >> 3                     # stride-8 grid cell per row
    target = cell_of[0]
    removed = scene[(cell_of == target).all(axis=1)]
    lad = cell_ladder_delta(spec, lad0, _packed_rows(spec, removed),
                            _packed_rows(spec, np.zeros((0, 3), np.int32)))
    assert lad[8][0].shape[0] == lad0[8][0].shape[0] - 1
    assert int(lad[8][1].sum()) == scene.shape[0] - removed.shape[0]
