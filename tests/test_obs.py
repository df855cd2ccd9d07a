"""The observability layer (repro.obs): span nesting and thread
attribution, the disabled-tracer no-op fast path (bounded overhead, zero
retained allocations), exporter schema round-trips, SLO accounting, and
the serving integration contract — exactly one ``compile`` event per
(rung, stage) on a cold stream and none on the warm replay, with
``summary()`` phases reconciling against the recorded spans."""
import gc
import json
import sys
import threading
import time

import numpy as np
import pytest

from conftest import property_test
from repro import obs
from repro.obs.trace import Tracer


@pytest.fixture(autouse=True)
def _fresh_default_tracer():
    """Every test starts and ends with the process default DISABLED — an
    enabled global leaking across tests would slow the whole suite."""
    obs.disable()
    yield
    obs.disable()


# ------------------------------------------------------------------- tracer

def test_span_nesting_depth_and_containment():
    tr = Tracer()
    with tr.span("outer", kind="a"):
        with tr.span("inner"):
            with tr.span("leaf"):
                pass
    spans = {s.name: s for s in tr.spans()}
    assert [spans[n].depth for n in ("outer", "inner", "leaf")] == [0, 1, 2]
    # exit order: leaf records first
    assert [s.name for s in tr.spans()] == ["leaf", "inner", "outer"]
    # time containment: children lie inside the parent interval
    assert spans["outer"].t0_ns <= spans["inner"].t0_ns
    assert spans["inner"].t1_ns <= spans["outer"].t1_ns
    assert spans["outer"].attrs == {"kind": "a"}
    assert spans["leaf"].dur_ms >= 0.0


@property_test(
    "depths",
    cases=[[1, 3, 2], [5], [2, 2, 2, 2]],
    strategies=lambda st: {"depths": st.lists(
        st.integers(min_value=1, max_value=6), min_size=1, max_size=5)})
def test_span_depths_reset_between_roots(depths):
    """Each root-level nest starts back at depth 0, however deep the
    previous one went (per-thread stack pops what it pushes)."""
    tr = Tracer()
    for d in depths:
        ctxs = [tr.span(f"level{i}") for i in range(d)]
        for c in ctxs:
            c.__enter__()
        for c in reversed(ctxs):
            c.__exit__(None, None, None)
    recorded = [s.depth for s in tr.spans()]
    expected = [d for want in depths for d in reversed(range(want))]
    assert recorded == expected


def test_spans_attribute_to_their_thread():
    tr = Tracer()

    def worker():
        with tr.span("work"):
            with tr.span("inner"):
                pass

    threads = [threading.Thread(target=worker, name=f"w{i}")
               for i in range(3)]
    with tr.span("main"):
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    by_thread = {}
    for s in tr.spans():
        by_thread.setdefault(s.thread, []).append(s)
    assert set(by_thread) == {"w0", "w1", "w2", "MainThread"}
    for name in ("w0", "w1", "w2"):
        # each worker's stack is independent: its root span is depth 0
        # even while the main thread holds an open span
        assert sorted(s.depth for s in by_thread[name]) == [0, 1]
    # the main thread's tid is distinct from every worker's (worker idents
    # may be reused between workers once a thread exits, so no exact count)
    main_tid = threading.main_thread().ident
    assert {s.tid for s in by_thread["MainThread"]} == {main_tid}
    assert main_tid not in {s.tid for name in ("w0", "w1", "w2")
                            for s in by_thread[name]}


def test_record_span_retroactive_interval():
    tr = Tracer()
    t0 = time.perf_counter_ns()
    t1 = t0 + 5_000_000   # 5 ms measured elsewhere
    tr.record_span("queue_wait", t0, t1, ticket=7)
    (s,) = tr.spans()
    assert (s.t0_ns, s.t1_ns, s.attrs) == (t0, t1, {"ticket": 7})
    assert s.dur_ms == pytest.approx(5.0)


def test_set_attaches_mid_span_attrs():
    tr = Tracer()
    with tr.span("tune", group="g0") as sp:
        sp.set(latency_ms=12.5)
    (s,) = tr.spans()
    assert s.attrs == {"group": "g0", "latency_ms": 12.5}


def test_bounded_storage_counts_drops():
    tr = Tracer(max_records=3)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
        tr.event(f"e{i}")
    assert len(tr.spans()) == 3 and len(tr.events()) == 3
    # keep-earliest: the interesting part of a trace is its start
    assert [s.name for s in tr.spans()] == ["s0", "s1", "s2"]
    assert tr.dropped == 4
    assert tr.snapshot()["dropped"] == 4


def test_counters_gauges_snapshot_and_clear():
    tr = Tracer(enabled=False)     # counters/gauges stay live when disabled
    tr.count("requests")
    tr.count("requests", 2)
    tr.gauge("queue_depth", 7.0)
    snap = tr.snapshot()
    assert snap["counters"] == {"requests": 3}
    assert snap["gauges"] == {"queue_depth": 7.0}
    assert snap["spans"] == 0 and snap["events"] == 0
    tr.clear()
    assert tr.snapshot()["counters"] == {}


def test_phase_summary_percentiles():
    tr = Tracer()
    base = time.perf_counter_ns()
    for i in range(10):
        tr.record_span("phase", base, base + (i + 1) * 1_000_000)
    s = tr.phase_summary()["phase"]
    assert s["count"] == 10
    assert s["p50_ms"] == pytest.approx(6.0)    # sorted-index percentile
    assert s["p95_ms"] == pytest.approx(10.0)
    assert s["total_ms"] == pytest.approx(55.0)


# ------------------------------------------------- disabled-tracer fast path

def test_disabled_span_is_the_noop_singleton():
    assert obs.span("anything", a=1) is obs.NOOP_SPAN
    assert obs.get_tracer().span("x") is obs.NOOP_SPAN
    with obs.span("x") as sp:
        assert sp.set(k=2) is obs.NOOP_SPAN
    obs.event("x", a=1)            # all no-ops, nothing recorded
    obs.record_span("x", 0, 1)
    assert obs.get_tracer().spans() == []
    assert obs.get_tracer().events() == []


def test_enabled_span_writes_a_profiler_annotation(tmp_path):
    """Under a jax profiler trace an enabled span is a ``repro.<name>``
    event on a host plane of the ``.xplane.pb``; a disabled one is the
    no-op singleton and writes nothing there."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with obs.span("quiet"):         # default tracer: disabled
            jnp.ones(3).block_until_ready()
        assert obs.span("quiet") is obs.NOOP_SPAN
        obs.enable()
        with obs.span("x", bucket=512):
            jnp.ones(3).block_until_ready()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = {(p.name, e.name) for p in ProfileData.from_file(str(path)).planes
             for line in p.lines for e in line.events}
    assert any(n == "repro.x" and p.startswith("/host:") for p, n in names)
    assert not any(n == "repro.quiet" for _, n in names)
    assert [s.name for s in obs.get_tracer().spans()] == ["x"]


def test_disabled_span_retains_zero_allocations():
    def burst(n):
        for _ in range(n):
            with obs.span("hot", bucket=512):
                pass
    burst(100)                      # warm any lazy interpreter state
    gc.collect()
    before = sys.getallocatedblocks()
    burst(1000)
    gc.collect()
    after = sys.getallocatedblocks()
    # transient kwargs dicts are freed; nothing is retained per call
    assert after - before <= 5, f"leaked {after - before} blocks"


def test_disabled_span_overhead_is_negligible():
    n = 20_000

    def noop_pass():
        for _ in range(n):
            pass

    def instrumented():
        for _ in range(n):
            with obs.span("hot"):
                pass

    noop_pass(); instrumented()     # warmup
    t0 = time.perf_counter(); instrumented(); dt = time.perf_counter() - t0
    per_call_us = dt / n * 1e6
    # a truthiness check + context-manager protocol on a preallocated
    # singleton: single-digit µs even on a loaded shared CPU runner
    assert per_call_us < 20.0, f"{per_call_us:.2f}µs per disabled span"


# ---------------------------------------------------------------- exporters

def _sample_tracer() -> Tracer:
    tr = Tracer()
    with tr.span("flush", scenes=2):
        with tr.span("pack", bucket=512):
            pass
    tr.event("compile", kind="executor", rung=512, device="cpu:0")
    tr.count("flushes")
    tr.gauge("depth", 1.0)
    return tr


def test_chrome_trace_schema(tmp_path):
    tr = _sample_tracer()
    path = obs.export_chrome(tr, str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    assert {m["name"] for m in meta} >= {"process_name", "thread_name"}
    complete = {e["name"]: e for e in events if e["ph"] == "X"}
    assert set(complete) == {"flush", "pack"}
    for e in complete.values():
        assert e["dur"] >= 0 and e["cat"] == "phase"
        assert isinstance(e["ts"], float)
    # nesting renders by time containment within one tid
    assert complete["flush"]["ts"] <= complete["pack"]["ts"]
    assert complete["flush"]["tid"] == complete["pack"]["tid"]
    (inst,) = [e for e in events if e["ph"] == "i"]
    assert inst["name"] == "compile" and inst["args"]["rung"] == 512
    assert doc["otherData"]["counters"] == {"flushes": 1}


def test_jsonl_round_trip(tmp_path):
    tr = _sample_tracer()
    path = obs.export_jsonl(tr, str(tmp_path / "trace.jsonl"))
    lines = [json.loads(l) for l in open(path)]
    assert [l["type"] for l in lines] == ["span", "span", "event", "snapshot"]
    spans = {l["name"]: l for l in lines if l["type"] == "span"}
    originals = {s.name: s for s in tr.spans()}
    for name, s in originals.items():
        assert spans[name]["t0_ns"] == s.t0_ns
        assert spans[name]["t1_ns"] == s.t1_ns
        assert spans[name]["depth"] == s.depth
        assert spans[name]["attrs"] == s.attrs
    assert lines[-1]["counters"] == {"flushes": 1}


def test_export_dispatches_on_extension(tmp_path):
    tr = _sample_tracer()
    chrome = obs.export(tr, str(tmp_path / "t.json"))
    jsonl = obs.export(tr, str(tmp_path / "t.jsonl"))
    assert "traceEvents" in json.load(open(chrome))
    assert json.loads(open(jsonl).readline())["type"] == "span"


def test_jax_profile_noop_path(tmp_path):
    # the bracket captures an XLA-level trace of the enclosed region
    import jax.numpy as jnp
    with obs.jax_profile(str(tmp_path / "prof")):
        jnp.ones(3).block_until_ready()
    assert list((tmp_path / "prof").glob("plugins/profile/*/*.xplane.pb"))


# --------------------------------------------------------- stats & SLO math

def test_idle_summary_reports_none_not_zero():
    from repro.serve.engine import EngineStats
    s = EngineStats().summary()
    assert s["p50_ms"] is None and s["p95_ms"] is None
    assert s["slo"] == {"deadline_ms": None, "measured": 0, "misses": 0,
                        "miss_rate": None}
    assert s["phases"] == {}


def test_slo_observe_counts_misses():
    from repro.serve.engine import EngineStats
    st = EngineStats()
    for lat in (5.0, 15.0, 25.0):
        st.slo_observe(lat, 10.0)
    s = st.summary()["slo"]
    assert s == {"deadline_ms": 10.0, "measured": 3, "misses": 2,
                 "miss_rate": pytest.approx(2 / 3)}


def test_phase_windows_are_bounded():
    from repro.serve.engine import PHASE_WINDOW, EngineStats
    st = EngineStats()
    for i in range(PHASE_WINDOW + 10):
        st.observe("pack", float(i))
    ph = st.summary()["phases"]["pack"]
    assert ph["count"] == PHASE_WINDOW
    assert ph["p50_ms"] is not None


def test_router_pctl_idle_is_none():
    from repro.serve.router import RouterStats
    assert RouterStats._pctl([]) == (None, None)
    import collections
    assert RouterStats._pctl([collections.deque()]) == (None, None)
    p50, p95 = RouterStats._pctl([collections.deque([1.0, 2.0, 3.0])])
    assert p50 == pytest.approx(2.0)


# --------------------------------------------------- serving integration

@pytest.fixture(scope="module")
def traced_serving():
    """One tiny cold-then-warm serving run under an enabled tracer; the
    assertions below all read this single (expensive) run."""
    from repro.serve.batcher import Scene
    from repro.serve.bucketing import BucketLadder
    from repro.serve.engine import Engine

    tracer = obs.enable()
    try:
        ladder = BucketLadder((256, 512), max_batch=2)
        eng = Engine("minkunet_kitti", ladder=ladder, spatial_bound=64,
                     max_wait_ms=50.0)
        rng = np.random.default_rng(0)

        def scene(n):
            coords = np.unique(rng.integers(-60, 60, size=(2 * n, 3),
                                            dtype=np.int32), axis=0)[:n]
            feats = rng.normal(size=(coords.shape[0], 4)).astype(np.float32)
            return Scene(coords=coords, feats=feats)

        scenes = [scene(100), scene(200), scene(150)]
        eng.serve(scenes)                       # cold epoch: compiles
        cold_compiles = list(tracer.events("compile"))
        eng.serve(scenes)                       # warm replay
        yield {"engine": eng, "tracer": tracer,
               "cold_compiles": cold_compiles}
    finally:
        obs.disable()


def test_exactly_one_compile_event_per_rung_and_stage(traced_serving):
    tracer = traced_serving["tracer"]
    keys = [(e.attrs["kind"], e.attrs["rung"], e.attrs["device"])
            for e in traced_serving["cold_compiles"]]
    assert len(keys) == len(set(keys)), f"duplicate compiles: {keys}"
    for e in tracer.events("compile"):
        assert e.attrs["wall_ms"] > 0
    # the warm replay re-traced NOTHING
    assert len(tracer.events("compile")) == len(keys)


def test_request_phases_are_spanned_and_nested(traced_serving):
    tracer = traced_serving["tracer"]
    by_name = {}
    for s in tracer.spans():
        by_name.setdefault(s.name, []).append(s)
    for phase in ("flush", "queue_wait", "request", "batch_plan", "pack",
                  "batch_pack", "map", "dispatch", "execute", "unpack"):
        assert phase in by_name, f"no {phase!r} spans recorded"
    # per-request phases nest under their flush (time containment, one tid)
    flushes = by_name["flush"]
    for phase in ("pack", "map", "execute", "unpack"):
        for s in by_name[phase]:
            assert s.depth >= 1
            assert any(f.t0_ns <= s.t0_ns and s.t1_ns <= f.t1_ns
                       for f in flushes), f"{phase} span outside any flush"
    # batch_pack nests inside the engine's pack phase
    assert all(s.depth >= 2 for s in by_name["batch_pack"])


def test_summary_reconciles_with_trace(traced_serving):
    eng, tracer = traced_serving["engine"], traced_serving["tracer"]
    s = eng.stats.summary()
    phase_counts = {}
    for rec in tracer.spans():
        phase_counts[rec.name] = phase_counts.get(rec.name, 0) + 1
    # every stats phase window was fed by the same code path as its spans
    for name in ("pack", "map", "execute", "unpack", "queue_wait"):
        assert s["phases"][name]["count"] == phase_counts[name], name
        assert s["phases"][name]["p50_ms"] is not None
        assert s["phases"][name]["p95_ms"] >= s["phases"][name]["p50_ms"]
    # every completed request was scored against the max_wait_ms SLO
    assert s["slo"]["deadline_ms"] == 50.0
    assert s["slo"]["measured"] == s["scenes"] == 6
    assert phase_counts["request"] == 6


@pytest.fixture(scope="module")
def traced_streaming():
    """A tiny incremental engine under an enabled tracer: cold scenes on
    the first frame, then streaming frames rebuilt through the delta
    builder.  Records what each build should count, from the traffic."""
    from repro.serve.bucketing import BucketLadder
    from repro.serve.engine import Engine
    from repro.serve.workload import churned_stream

    tracer = obs.enable()
    try:
        eng = Engine("centerpoint_waymo",
                     ladder=BucketLadder((512,), max_batch=4),
                     spatial_bound=64, map_strategy="incremental")
        frames, _ = churned_stream(5, streams=3, frames=3, channels=5,
                                   n_range=(40, 80), extent=16.0, voxel=0.4)
        built = []          # (valid rows, scene-rung capacity) per build
        for t, frame in enumerate(frames):
            for sid, scene, delta in frame:
                if t == 0 or delta is not None:
                    n = scene.num_points
                    built.append((n, eng._scene_ladder.select(n)))
                if delta is not None:
                    eng.submit_delta(sid, delta)
                else:
                    eng.submit(scene, stream=sid)
            eng.flush()
        yield {"engine": eng, "tracer": tracer, "built": built}
    finally:
        obs.disable()


def test_scene_wait_and_fetch_nest_once_per_build(traced_streaming):
    """Every scene build, cold (``scene_build``) or delta
    (``delta_merge``), records one ``scene_wait`` and one ``scene_fetch``
    inside it, in that order."""
    eng, tracer = traced_streaming["engine"], traced_streaming["tracer"]
    spans = tracer.spans()
    parents = [s for s in spans if s.name in ("scene_build", "delta_merge")]
    assert sum(p.name == "delta_merge" for p in parents) == \
        eng.stats.delta_merges > 0
    assert sum(p.name == "scene_build" for p in parents) == \
        eng.stats.scene_misses > 0
    for p in parents:
        inside = {c.name: c for c in spans
                  if c.name in ("scene_wait", "scene_fetch")
                  and c.tid == p.tid and p.t0_ns <= c.t0_ns
                  and c.t1_ns <= p.t1_ns}
        assert sorted(inside) == ["scene_fetch", "scene_wait"], p.name
        assert inside["scene_wait"].t1_ns <= inside["scene_fetch"].t0_ns
        assert inside["scene_wait"].depth == p.depth + 1
    assert sum(s.name == "apply_delta" for s in spans) == \
        eng.stats.delta_merges
    for name in ("scene_wait", "scene_fetch"):
        assert sum(s.name == name for s in spans) == len(parents)
        assert eng.stats.summary()["phases"][name]["count"] == len(parents)


def test_scene_row_counters_count_every_build(traced_streaming):
    """``scene_tables.rows`` / ``rung_rows``: the valid rows and the
    scene-ladder capacities of every build, cold and delta."""
    built = traced_streaming["built"]
    st = traced_streaming["engine"].stats.summary()["scene_tables"]
    assert len(built) == st["misses"] + st["delta_merges"]
    assert st["rows"] == sum(n for n, _ in built)
    assert st["rung_rows"] == sum(c for _, c in built)
    assert st["rows"] < st["rung_rows"]


def test_stage_modules_and_scopes_are_named(traced_streaming):
    """Each jitted stage compiles to a module named for its kind, and its
    ops carry the map-build and layer scopes."""
    eng = traced_streaming["engine"]
    cap = eng._scene_ladder.select(traced_streaming["built"][0][0])
    text = eng.compiled_text("scene_builder", cap)
    assert text.startswith("HloModule jit_scene_builder")
    for scope in ("kmap.sub_s1/table", "kmap.sub_s1/search",
                  "kmap.down_s1/"):
        assert scope in text, scope
    assert eng.compiled_text("scene_delta_builder", cap).startswith(
        "HloModule jit_scene_delta_builder")
    # the device map composer: its finishing stage per bucket, its placing
    # stage per (scene rung, bucket), both under the kmap.compose scope
    for kind, key in (("map_composer", 512), ("map_placer", (cap, 512))):
        text = eng.compiled_text(kind, key)
        assert text.startswith(f"HloModule jit_{kind}")
        assert "kmap.compose" in text, kind
    text = eng.compiled_text("executor", 512)
    assert text.startswith("HloModule jit_executor")
    assert "layer." in text


def test_tuner_spans_carry_measured_latency():
    from repro.core import dataflows as df
    from repro.core.autotuner import Autotuner, GroupInfo

    tracer = obs.enable()
    try:
        groups = [GroupInfo("g0", ["a"]), GroupInfo("g1", ["b"])]
        space = [df.DataflowConfig("gather_scatter"),
                 df.DataflowConfig("implicit_gemm", n_splits=1)]
        Autotuner(groups, space, measure=lambda a: 0.001 * len(a)).tune()
        spans = [s for s in tracer.spans() if s.name == "tune_candidate"]
        assert len(spans) == len(groups) * len(space)
        for s in spans:
            assert s.attrs["group"] in ("g0", "g1")
            assert s.attrs["latency_ms"] == pytest.approx(2.0)
    finally:
        obs.disable()


def test_train_loop_emits_step_spans():
    import jax.numpy as jnp

    from repro.train.loop import LoopConfig, train_loop

    tracer = obs.enable()
    try:
        def step(params, opt, batch):
            return params + batch, opt, {"loss": jnp.float32(0.0)}

        data = iter([jnp.float32(1.0)] * 3)
        train_loop(step, jnp.float32(0.0), None, data,
                   LoopConfig(total_steps=3, ckpt_dir=None))
        steps = [s for s in tracer.spans() if s.name == "train_step"]
        assert [s.attrs["step"] for s in steps] == [0, 1, 2]
    finally:
        obs.disable()


# ----------------------------------------------------------- CI perf gate

def test_check_regression_classification():
    from benchmarks.check_regression import compare
    baseline = {"a": 1000.0, "b": 1000.0, "c": 1000.0, "tiny": 50.0,
                "gone": 400.0}
    current = {"a": 1100.0, "b": 2500.0, "c": 9000.0, "tiny": 500.0,
               "new": 300.0}
    r = compare(current, baseline, min_us=200.0, warn_ratio=2.0,
                fail_ratio=3.0)
    assert [e[0] for e in r["ok"]] == ["a"]
    assert [e[0] for e in r["warn"]] == ["b"]
    assert [e[0] for e in r["fail"]] == ["c"]
    assert r["skipped"] == 1                    # 'tiny' is under the floor
    assert r["only_current"] == ["new"]
    assert r["only_baseline"] == ["gone"]


def test_check_regression_refresh_and_gate(tmp_path):
    from benchmarks.check_regression import main
    artifact = {"meta": {"tiny": True}, "suites": {"s": {"rows": [
        {"name": "serving/x/p50", "us_per_call": 5000.0, "derived": ""},
        {"name": "ratio_row", "us_per_call": 0.0, "derived": "r=2x"},
    ]}}}
    cur = tmp_path / "BENCH_CI.json"
    base = tmp_path / "baseline.json"
    cur.write_text(json.dumps(artifact))
    assert main(["--current", str(cur), "--baseline", str(base),
                 "--refresh"]) == 0
    saved = json.loads(base.read_text())
    assert saved["rows"] == {"serving/x/p50": 5000.0}   # ratio rows excluded
    # identical re-run passes the gate
    assert main(["--current", str(cur), "--baseline", str(base)]) == 0
    # a >3x cliff hard-fails
    artifact["suites"]["s"]["rows"][0]["us_per_call"] = 20000.0
    cur.write_text(json.dumps(artifact))
    assert main(["--current", str(cur), "--baseline", str(base)]) == 1
    # missing baseline: warn-only, never red
    assert main(["--current", str(cur),
                 "--baseline", str(tmp_path / "absent.json")]) == 0
