"""Cross-host serving fleet (serve/fleet.py, serve/wire.py, serve/service.py).

The contracts under test:

* **wire codec**: every value that crosses the fleet boundary round-trips
  bit-identically — scalars, nested containers, ndarrays of every serving
  dtype including ``bfloat16`` — and malformed frames (bad magic, newer
  version, trailing bytes, overflowing ints) raise ``WireError`` instead
  of mis-parsing;
* **ServiceConfig**: dict round-trip rejects unknown keys, persists
  alongside plans in ``PlanRegistry``, and the legacy per-kwarg
  constructor path folds into it with exactly one DeprecationWarning per
  process;
* **SparseService conformance**: Engine, DeviceRouter and FleetFrontend
  all satisfy the protocol and produce **bit-identical** results on the
  same stream;
* **failover loses zero requests**: an injected worker exception
  (router) or a killed worker process mid-stream (fleet) re-routes every
  un-acked batch to the survivors, outputs stay bit-identical to the
  single-device engine, and — with ``respawn`` — a replacement host comes
  back re-warmed.

The fleet cases spawn real localhost worker subprocesses (each with its
own jax runtime), so they are the slowest in the tier-1 suite; scene
counts and the bucket ladder are kept minimal.
"""
import threading
import warnings

import jax
import numpy as np
import pytest

from repro.serve import (BucketLadder, DeviceRouter, Engine, PlanRegistry,
                         Scene)
from repro.serve.batcher import SceneBatcher, SceneDelta, apply_delta
from repro.serve.fleet import (FleetFrontend, FleetStats, FleetWorker,
                               HostHandle)
from repro.serve import service as service_mod
from repro.serve import wire
from repro.serve.service import (STATS_SCHEMA_VERSION, ServiceConfig,
                                 SparseService, resolve_config)
from repro.serve.workload import lidar_stream

from conftest import property_test

ARCH = "minkunet_kitti"
SCENES, BOUND = lidar_stream(0, 6, 4, n_range=(40, 100))
CFG = ServiceConfig(buckets=(128, 256), max_batch=2, spatial_bound=BOUND)

try:
    import ml_dtypes
    HAS_BF16 = True
except ImportError:             # pragma: no cover - jax ships ml_dtypes
    HAS_BF16 = False


def _assert_results_equal(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a.coords, b.coords)
        np.testing.assert_array_equal(a.feats, b.feats)
        assert a.stride == b.stride


# ------------------------------------------------------------------ wire codec

@property_test(
    "value",
    [None, True, False, 0, -1, 2**62, 1.5, -0.0,
     "", "héllo", b"\x00\xff", [1, [2, "x"], None],
     {"a": 1, 2: [True, b"z"], "n": {"d": 3.5}}],
    lambda st: {"value": st.recursive(
        st.none() | st.booleans() |
        st.integers(min_value=-2**63, max_value=2**63 - 1) |
        st.floats(allow_nan=False) | st.text(max_size=20) |
        st.binary(max_size=20),
        lambda leaf: st.lists(leaf, max_size=4) |
        st.dictionaries(st.text(max_size=5), leaf, max_size=4),
        max_leaves=10)})
def test_wire_scalar_tree_roundtrip(value):
    assert wire.decode(wire.encode(value)) == value


@pytest.mark.parametrize("dtype", ["int32", "int64", "uint8", "float32",
                                   "float64", "bool"])
@pytest.mark.parametrize("shape", [(0, 3), (5,), (4, 4), ()])
def test_wire_ndarray_roundtrip(dtype, shape):
    rng = np.random.default_rng(0)
    a = np.asarray(rng.random(shape) * 100).astype(dtype)
    b = wire.decode(wire.encode(a))
    assert b.dtype == a.dtype and b.shape == a.shape
    np.testing.assert_array_equal(a, b)


@pytest.mark.skipif(not HAS_BF16, reason="ml_dtypes unavailable")
def test_wire_bfloat16_bit_identical():
    a = np.linspace(-3.0, 3.0, 16).astype(ml_dtypes.bfloat16).reshape(4, 4)
    b = wire.decode(wire.encode(a))
    assert b.dtype == a.dtype
    np.testing.assert_array_equal(a.view(np.uint16), b.view(np.uint16))


def test_wire_rejects_malformed():
    frame = wire.pack_frame(wire.encode({"op": "ping"}))
    with pytest.raises(wire.WireError, match="magic"):
        wire.unpack_header(b"XX" + frame[2:wire.HEADER_SIZE])
    with pytest.raises(wire.WireError, match="version"):
        wire.unpack_header(bytes([frame[0], frame[1], 99])
                           + frame[3:wire.HEADER_SIZE])
    with pytest.raises(wire.WireError, match="trailing"):
        wire.decode(wire.encode(1) + b"\x00")
    with pytest.raises(wire.WireError, match="truncated"):
        wire.decode(wire.encode("hello")[:-2])
    with pytest.raises(wire.WireError, match="overflow"):
        wire.encode(2**70)
    with pytest.raises(wire.WireError, match="unencodable"):
        wire.encode(object())


def test_wire_socket_roundtrip():
    import socket
    a, b = socket.socketpair()
    try:
        msg = {"op": "execute", "scenes": [wire.scene_to_wire(SCENES[0])]}
        wire.send_msg(a, msg)
        got = wire.recv_msg(b)
        assert got["op"] == "execute"
        s = wire.scene_from_wire(got["scenes"][0])
        np.testing.assert_array_equal(s.coords, SCENES[0].coords)
        assert s.digest == SCENES[0].digest
    finally:
        a.close()
        b.close()


def test_wire_serving_object_roundtrips():
    s = SCENES[0]
    s2 = wire.scene_from_wire(wire.decode(wire.encode(wire.scene_to_wire(s))))
    assert s2.digest == s.digest
    np.testing.assert_array_equal(s2.feats, s.feats)

    D = s.coords.shape[1]
    d = SceneDelta(removed=s.coords[:3], added_coords=np.zeros((0, D), np.int32),
                   added_feats=np.zeros((0, s.feats.shape[1]), s.feats.dtype))
    d2 = wire.delta_from_wire(
        wire.decode(wire.encode(wire.delta_to_wire(d))))
    np.testing.assert_array_equal(d2.removed, d.removed)
    np.testing.assert_array_equal(apply_delta(s, d2).coords,
                                  apply_delta(s, d).coords)

    # PackedBatch: declared bounds survive the trip (the key-bit budget)
    batcher = SceneBatcher(CFG.ladder(), CFG.spatial_bound)
    batch = batcher.pack(SCENES[:2])
    b2 = wire.packed_batch_from_wire(
        wire.decode(wire.encode(wire.packed_batch_to_wire(batch))))
    assert b2.st.batch_bound == batch.st.batch_bound
    assert b2.st.spatial_bound == batch.st.spatial_bound
    assert b2.st.stride == batch.st.stride
    assert int(b2.st.num_valid) == int(batch.st.num_valid)
    assert b2.scene_sizes == batch.scene_sizes
    assert b2.bucket == batch.bucket and b2.digest == batch.digest
    np.testing.assert_array_equal(np.asarray(b2.st.coords),
                                  np.asarray(batch.st.coords))


# -------------------------------------------------------------- ServiceConfig

def test_service_config_dict_roundtrip_rejects_unknown():
    d = CFG.to_dict()
    assert ServiceConfig.from_dict(d) == CFG
    import json
    assert ServiceConfig.from_dict(json.loads(json.dumps(d))) == CFG
    with pytest.raises(ValueError, match="unknown ServiceConfig keys"):
        ServiceConfig.from_dict({**d, "warp_factor": 9})


def test_service_config_persists_in_plan_registry(tmp_path):
    reg = PlanRegistry()
    reg.set(ARCH, {})
    reg.set_service(ARCH, CFG)
    path = reg.save(str(tmp_path / "plans.json"))
    loaded = PlanRegistry.load(path)
    assert loaded.service(ARCH) == CFG
    assert loaded.service("never_tuned") is None


def test_legacy_kwargs_warn_once_and_typo_raises():
    old = service_mod._LEGACY_WARNED[0]
    service_mod._LEGACY_WARNED[0] = False
    try:
        with pytest.warns(DeprecationWarning, match="ServiceConfig"):
            cfg = resolve_config(None, {"ladder": BucketLadder((128, 256),
                                                               max_batch=2),
                                        "spatial_bound": BOUND})
        assert cfg == CFG
        with warnings.catch_warnings():     # second use: silent
            warnings.simplefilter("error")
            resolve_config(None, {"max_wait_ms": 5.0})
    finally:
        service_mod._LEGACY_WARNED[0] = old
    with pytest.raises(TypeError, match="unexpected serving kwargs"):
        resolve_config(None, {"ladderr": None})


def test_engine_legacy_and_config_paths_identical():
    eng = Engine(ARCH, config=CFG)
    legacy = Engine(ARCH, ladder=CFG.ladder(), spatial_bound=BOUND)
    assert eng.config == legacy.config == CFG
    _assert_results_equal(legacy.serve(SCENES[:2]), eng.serve(SCENES[:2]))


# --------------------------------------------------- SparseService conformance

@pytest.fixture(scope="module")
def engine_ref():
    return Engine(ARCH, config=CFG).serve(SCENES, flush_every=3)


@pytest.fixture(scope="module")
def fleet():
    fl = FleetFrontend(ARCH, hosts=2, config=CFG)
    yield fl
    fl.close()


@pytest.fixture
def service(request, fleet):
    if request.param == "engine":
        return Engine(ARCH, config=CFG)
    if request.param == "router":
        dev = jax.devices()[0]
        return DeviceRouter(ARCH, devices=[dev] * 2, config=CFG)
    return fleet


@pytest.mark.parametrize("service", ["engine", "router", "fleet"],
                         indirect=True)
def test_sparse_service_conformance(service, engine_ref):
    assert isinstance(service, SparseService)
    assert service.config == CFG
    got = service.serve(SCENES, flush_every=3)
    _assert_results_equal(got, engine_ref)
    # submit/flush ticketing: monotone tickets, flush resolves exactly them
    t0 = service.submit(SCENES[0])
    t1 = service.submit(SCENES[1])
    assert t1 == t0 + 1
    out = service.flush()
    assert set(out) >= {t0, t1}
    _assert_results_equal([out[t0], out[t1]], engine_ref[:2])
    # streaming: a delta resolves like the full scene it denotes
    service.submit(SCENES[2], stream="s0")
    service.flush()
    D = SCENES[2].coords.shape[1]
    delta = SceneDelta(removed=SCENES[2].coords[:4],
                       added_coords=np.zeros((0, D), np.int32),
                       added_feats=np.zeros((0, SCENES[2].feats.shape[1]),
                                            SCENES[2].feats.dtype))
    td = service.submit_delta("s0", delta)
    got_d = service.flush()[td]
    want_d = Engine(ARCH, config=CFG).serve([apply_delta(SCENES[2], delta)])[0]
    _assert_results_equal([got_d], [want_d])
    s = service.stats.summary()
    assert s["schema_version"] == STATS_SCHEMA_VERSION
    assert s["scenes"] >= len(SCENES)
    assert s["p50_ms"] is None or s["p50_ms"] > 0


# ---------------------------------------------------------------- fleet stats

def test_fleet_stats_blocks(fleet, engine_ref):
    fleet.serve(SCENES, flush_every=3)
    s = fleet.stats.summary()
    assert s["schema_version"] == STATS_SCHEMA_VERSION
    assert set(s["hosts"]) == {"h0", "h1"}
    for h in s["hosts"].values():
        assert h["alive"] and h["weight"] >= 1.0
        assert ":" in h["addr"]
    f = s["fleet"]
    assert f["hosts"] == 2 and f["live"] == 2
    assert f["replication"] == "lazy"
    assert f["failovers"] == 0
    assert sum(h["routed_batches"] for h in s["hosts"].values()) \
        == s["routed_batches"] > 0
    # both hosts actually took traffic (round-robin over uniform groups)
    assert all(h["routed_batches"] >= 1 for h in s["hosts"].values())


def test_fleet_gossip_replication(fleet):
    scenes, _ = lidar_stream(7, 2, 4, n_range=(40, 80))
    fleet.set_replication("gs", "gossip")
    before = fleet.stats.gossip_scenes
    fleet.submit(scenes[0], stream="gs")
    fleet.flush()
    live = fleet.live_hosts
    assert fleet.stats.gossip_scenes == before + len(live)
    for h in live:
        assert scenes[0].digest in h.warmed
    # lazy stream: no admit-time fan-out
    before = fleet.stats.gossip_scenes
    fleet.submit(scenes[1], stream="other")
    fleet.flush()
    assert fleet.stats.gossip_scenes == before


# ------------------------------------------------------- routing (unit level)

def _bare_frontend(weights):
    """A FleetFrontend with fake host handles — exercises ``_route``
    without any worker processes."""
    fl = FleetFrontend.__new__(FleetFrontend)
    fl.hosts = []
    fl.outstanding_score = []
    fl._rr = 0
    fl._lock = threading.Lock()
    fl.stats = FleetStats(fl)
    for i, w in enumerate(weights):
        h = HostHandle(i, ("127.0.0.1", 0), None)
        h.alive = True
        h.weight = w
        fl.hosts.append(h)
        fl.outstanding_score.append(0.0)
    return fl


def test_fleet_route_uniform_round_robin():
    fl = _bare_frontend([1.0, 1.0, 1.0])
    counts = [0, 0, 0]
    for _ in range(9):
        counts[fl._route(128)] += 1
    assert counts == [3, 3, 3]


def test_fleet_route_weighted_prefers_fast_host():
    # host 1 calibrated 2x slower: its score grows twice as fast, so the
    # fast host absorbs ~2/3 of a uniform stream
    fl = _bare_frontend([1.0, 2.0])
    counts = [0, 0]
    for _ in range(9):
        counts[fl._route(128)] += 1
    assert counts[0] > counts[1] >= 1, counts
    log = [i for i, _ in fl.stats.route_log]
    fl2 = _bare_frontend([1.0, 2.0])
    for _ in range(9):
        fl2._route(128)
    assert [i for i, _ in fl2.stats.route_log] == log   # deterministic


def test_fleet_route_skips_dead_hosts():
    fl = _bare_frontend([1.0, 1.0])
    fl.hosts[0].alive = False
    assert all(fl._route(64) == 1 for _ in range(3))
    fl.hosts[1].alive = False
    with pytest.raises(RuntimeError, match="no live fleet hosts"):
        fl._route(64)


# ----------------------------------------------------- one process per chip

@pytest.fixture
def chip_host(monkeypatch):
    """Pretend this host has TPU chips; spawning a worker fails the test."""
    from repro.serve import fleet as fleet_mod

    def spawn(self):
        raise AssertionError("spawned a worker on a refused chip host")
    monkeypatch.setattr(fleet_mod, "local_tpu_chips", lambda: 4)
    monkeypatch.setattr(FleetFrontend, "_spawn_worker", spawn)


class _Spawned(Exception):
    pass


def test_fleet_refuses_local_workers_sharing_chips(chip_host):
    with pytest.raises(RuntimeError, match="would all claim the same chips"):
        FleetFrontend("minkunet_kitti", hosts=2,
                      config=service_mod.ServiceConfig(buckets=(256,)))


@pytest.mark.parametrize("platforms", ["", "tpu", "cpu,tpu"])
def test_fleet_refuses_when_front_end_holds_the_tpu(chip_host, monkeypatch,
                                                    platforms):
    from repro.serve import fleet as fleet_mod
    monkeypatch.setattr(fleet_mod, "_front_end_platforms", lambda: platforms)
    with pytest.raises(RuntimeError, match="may hold the TPU"):
        FleetFrontend("minkunet_kitti", hosts=1,
                      config=service_mod.ServiceConfig(buckets=(256,)))


def test_fleet_spawns_one_worker_from_a_cpu_front_end(chip_host, monkeypatch):
    """A front end configured off the TPU spawns its one worker, and the
    constructor leaves the process's jax configuration as it found it."""
    def spawn(self):
        raise _Spawned
    monkeypatch.setattr(FleetFrontend, "_spawn_worker", spawn)
    before = jax.config.jax_platforms
    assert "tpu" not in before.split(",")
    with pytest.raises(_Spawned):
        FleetFrontend("minkunet_kitti", hosts=1,
                      config=service_mod.ServiceConfig(buckets=(256,)))
    assert jax.config.jax_platforms == before


def test_local_tpu_chips_reads_the_pci_bus(tmp_path, monkeypatch):
    from repro.serve import fleet as fleet_mod
    for name, vendor, device in [("a", "0x1ae0", "0x0063"),   # v5e chip
                                 ("b", "0x1ae0", "0x0063"),
                                 ("c", "0x1ae0", "0x0001"),   # not a TPU
                                 ("d", "0x8086", "0x0063")]:  # not Google
        (tmp_path / name).mkdir()
        (tmp_path / name / "vendor").write_text(vendor + "\n")
        (tmp_path / name / "device").write_text(device + "\n")
    (tmp_path / "e").mkdir()                                  # no ids
    monkeypatch.setattr(fleet_mod.glob, "glob",
                        lambda _: sorted(str(p) for p in tmp_path.iterdir()))
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert fleet_mod.local_tpu_chips() == 2
    # spawned workers inherit JAX_PLATFORMS: kept off the TPU, they claim
    # no chip
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert fleet_mod.local_tpu_chips() == 0


def test_fleet_bench_refuses_on_a_chip_host(chip_host):
    from benchmarks import bench_serving
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        bench_serving.run_fleet(tiny=True)


# --------------------------------------------------- worker ops (in-process)

def test_fleet_worker_handle_ops(engine_ref):
    w = FleetWorker(ARCH, CFG.replace(max_wait_ms=3.0, flush_count=2))
    # admission knobs are stripped: the front end owns flushing
    assert w.config.max_wait_ms is None and w.config.flush_count is None
    assert w.handle({"op": "nope"}) == {"ok": False,
                                        "error": "unknown op 'nope'"}
    assert w.handle({"op": "ping"})["ok"]
    r = w.handle({"op": "hello"})
    assert r["ok"] and r["arch"] == ARCH
    # execute one front-end-formed group: bit-identical to the engine
    group = [wire.scene_to_wire(s) for s in SCENES[:2]]
    r = w.handle({"op": "execute", "scenes": group})
    assert r["ok"]
    got = [wire.result_from_wire(d) for d in r["results"]]
    _assert_results_equal(got, engine_ref[:2])
    # a raising op reports, never kills the loop
    r = w.handle({"op": "execute", "scenes": [{"bad": "payload"}]})
    assert not r["ok"] and "error" in r


# ------------------------------------------------------- per-host swimlanes

def test_chrome_trace_per_host_swimlanes():
    from repro.obs import Tracer, chrome_trace
    tr = Tracer()
    with tr.span("host_rpc", host="h0", rows=128):
        pass
    with tr.span("host_rpc", host="h1", rows=128):
        pass
    tr.event("host_down", host="h1", why="execute")
    doc = chrome_trace(tr)
    events = doc["traceEvents"]
    lanes = {e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"host h0", "host h1"} <= lanes
    pids = {e["args"]["host"]: e["pid"] for e in events if e["ph"] == "X"}
    assert pids["h0"] != pids["h1"]       # one synthetic process per host
    (down,) = [e for e in events if e["ph"] == "i"]
    assert down["pid"] == pids["h1"]      # events land in their host's lane


# ----------------------------------------------------------- router failover

def test_router_injected_failure_zero_loss(engine_ref):
    dev = jax.devices()[0]
    r = DeviceRouter(ARCH, devices=[dev] * 2, config=CFG)

    boom = {"armed": False}
    orig = r.workers[1]._run_pipeline

    def failing(groups, on_done, urgent=None):
        if boom["armed"]:
            raise RuntimeError("injected device loss")
        return orig(groups, on_done, urgent)

    r.workers[1]._run_pipeline = failing
    got = r.serve(SCENES[:3], flush_every=0)
    _assert_results_equal(got, engine_ref[:3])
    boom["armed"] = True                      # dies mid-stream
    got = r.serve(SCENES[3:], flush_every=0)
    _assert_results_equal(got, engine_ref[3:])
    s = r.stats.summary()
    assert s["failover"]["dead"] == ["d1"]
    assert s["failover"]["worker_failures"] == 1
    assert s["failover"]["rerouted_batches"] >= 1
    assert not s["devices"]["d1"]["alive"] and s["devices"]["d0"]["alive"]
    # the survivor carries on alone
    got = r.serve(SCENES[:2], flush_every=0)
    _assert_results_equal(got, engine_ref[:2])


def test_router_all_workers_dead_raises():
    dev = jax.devices()[0]
    r = DeviceRouter(ARCH, devices=[dev], config=CFG)

    def failing(groups, on_done, urgent=None):
        raise RuntimeError("injected")

    r.workers[0]._run_pipeline = failing
    with pytest.raises(RuntimeError, match="dead"):
        r.serve(SCENES[:2])


# ------------------------------------------------------------ fleet failover

def test_fleet_kill_worker_mid_stream_zero_loss(engine_ref):
    """The acceptance contract: kill a worker process mid-stream, lose
    zero requests, outputs bit-identical to the single-device engine, and
    (respawn=True) a re-warmed replacement rejoins the fleet."""
    fl = FleetFrontend(ARCH, hosts=2, config=CFG, respawn=True,
                       heartbeat_s=0.2)
    try:
        out = {}
        tickets = [fl.submit(s) for s in SCENES[:3]]
        out.update(fl.flush())

        victim = fl.hosts[0]
        victim.proc.kill()
        victim.proc.wait(timeout=10)

        tickets += [fl.submit(s) for s in SCENES[3:]]
        out.update(fl.flush())            # detects the death, re-routes

        assert sorted(out) == tickets     # zero lost requests
        got = [out[t] for t in tickets]
        _assert_results_equal(got, engine_ref)

        s = fl.stats.summary()
        assert s["fleet"]["failovers"] >= 1
        assert s["fleet"]["respawns"] >= 1
        assert s["fleet"]["live"] == 2    # replacement joined
        assert all(h.alive for h in fl.hosts)
        # the respawned host was re-warmed from the front end's digest store
        assert fl.hosts[0].warmed >= set(fl._digest_store)
        # and the fleet still serves bit-identically after recovery
        _assert_results_equal(fl.serve(SCENES, flush_every=3), engine_ref)
    finally:
        fl.close()
