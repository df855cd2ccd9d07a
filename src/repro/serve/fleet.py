"""Cross-host serving fleet: an RPC front end over per-host serving workers.

The third serving tier (ROADMAP item 2).  ``Engine`` serves one device,
``DeviceRouter`` the devices of one process; ``FleetFrontend`` puts whole
*hosts* behind one ``SparseService`` front end, speaking the length-prefixed
binary protocol in serve/wire.py over plain sockets:

* **workers** are separate processes (``python -m repro.serve.fleet
  --worker``), each running its own engine (so its own jax runtime,
  devices, compile cache).  A worker listens on localhost and answers
  framed ops: ``execute`` (a FIFO scene group → per-scene results),
  ``warm`` (admit scenes into the worker's scene-digest store), ``warmup``
  (compile every rung, return a calibration timing), ``stats``, ``ping``,
  ``tune``, ``shutdown``.  ``--hosts N`` in launch/serve_sparse.py spawns
  N of them on localhost; production would point the front end at real
  host:port addresses instead — the protocol is the same;
* **routing** happens at batch granularity and in two levels, host then
  device: the front end runs the SAME deterministic FIFO grouping as the
  single engine (`SceneBatcher.plan`), charges each group at its padded
  row count **× the host's calibrated weight** (warmup timings of a slow
  host scale its scores up, so heterogeneous fleets balance by actual
  capacity, not batch count), and sends it to the host with the least
  outstanding weighted rows (round-robin tie-break).  Inside the worker,
  the engine (or a DeviceRouter, when the worker has several devices)
  routes to a device as before;
* **failover**: a worker death is detected three ways — a socket
  error/EOF on its data connection, an in-flight timeout on an un-acked
  batch, or a missed heartbeat on the control connection.  Its un-acked
  and still-queued batches are re-routed to the surviving hosts and
  re-executed (groups are self-contained and idempotent: re-running one
  yields bit-identical rows), so a mid-stream kill loses zero requests.
  With ``respawn=True`` the front end then spawns a replacement process
  and **re-warms** it from the front end's scene-digest store before it
  takes traffic;
* **replication policy** per stream: ``"gossip"`` pushes every admitted
  scene's digest+payload to all live hosts at submit time (any host can
  then merge-compose batches containing it from its local scene store —
  the right call for streams that will be served repeatedly), while
  ``"lazy"`` (default) lets each host warm up from the traffic it is
  actually routed (no admit-time fan-out cost).

Correctness contract (tests/test_fleet.py): fleet outputs are
**bit-identical** to the single-device ``Engine`` on the same stream —
grouping and packing decisions all happen in the front end exactly as the
engine makes them, workers only execute — and killing a worker mid-stream
loses zero requests.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.serve import wire
from repro.serve.batcher import (Scene, SceneBatcher, SceneDelta, SceneResult,
                                 apply_delta)
from repro.serve.engine import LATENCY_WINDOW, PHASE_WINDOW, percentiles_ms, \
    summarize_phases
from repro.serve.plans import (PlanRegistry, _assignment_from_json,
                               _assignment_to_json)
from repro.serve.service import (STATS_SCHEMA_VERSION, ServiceConfig,
                                 resolve_config)

REPLICATION_POLICIES = ("lazy", "gossip")

#: scenes the front end remembers (digest → Scene) for gossip and re-warm
DIGEST_STORE_SIZE = 1024


class HostFailure(Exception):
    """One host's connection died mid-operation; carries the host index."""

    def __init__(self, index: int, cause: BaseException):
        super().__init__(f"fleet host h{index} failed: {cause!r}")
        self.index = index
        self.cause = cause


# ---------------------------------------------------------------------------
# Worker side (runs in its own process)
# ---------------------------------------------------------------------------

class FleetWorker:
    """One host's serving loop: an engine/router behind a socket.

    Accepts any number of connections (the front end opens two: data for
    the heavy ops, control for ping/stats so liveness checks never queue
    behind a batch) and answers one framed request per received frame.
    Engine-touching ops serialize on one lock; ``ping``/``stats`` don't,
    so a heartbeat gets answered while a batch executes.
    """

    def __init__(self, arch: str, config: ServiceConfig,
                 plans: Optional[str] = None, devices: int = 1):
        # the front end owns admission; a worker must never auto-flush
        # or cut batches on its own or bit-identity breaks
        cfg = config.replace(max_wait_ms=None, flush_count=None,
                             deadline_margin=None)
        self.config = cfg
        if devices > 1:
            from repro.serve.router import DeviceRouter
            self.engine = DeviceRouter(arch, devices=devices, config=cfg,
                                       plans=plans)
        else:
            from repro.serve.engine import Engine
            self.engine = Engine(arch, config=cfg, plans=plans)
        self._elock = threading.Lock()

    # ------------------------------------------------------------------- ops
    def handle(self, msg: dict) -> dict:
        op = msg.get("op")
        fn = getattr(self, f"_op_{op}", None)
        if fn is None:
            return {"ok": False, "error": f"unknown op {op!r}"}
        try:
            return {"ok": True, **fn(msg)}
        except Exception as e:     # report, don't kill the worker loop
            return {"ok": False, "error": f"{type(e).__name__}: {e}"}

    def _op_hello(self, msg) -> dict:
        import jax
        return {"pid": os.getpid(), "device_count": jax.device_count(),
                "arch": self.engine.arch}

    def _op_ping(self, msg) -> dict:
        return {"t_ns": time.perf_counter_ns()}

    def _op_warmup(self, msg) -> dict:
        """Compile every rung; returns the warmup wall time and the median
        warm execute phase — the calibration number weighted routing uses."""
        with self._elock:
            t0 = time.perf_counter()
            self.engine.warmup(msg.get("channels"))
            wall_ms = (time.perf_counter() - t0) * 1e3
        phases = self.engine.stats.summary().get("phases", {})
        execute = phases.get("execute", {})
        return {"warmup_ms": wall_ms, "calib_ms": execute.get("p50_ms")}

    def _op_execute(self, msg) -> dict:
        """Run one front-end-formed FIFO group; returns per-scene results
        in group order.  The group fits one batch by construction, so the
        worker's own plan() re-derives exactly that single group and the
        result rows are bit-identical to any other host running it."""
        scenes = [wire.scene_from_wire(d) for d in msg["scenes"]]
        with self._elock:
            results = self.engine.serve(scenes, flush_every=0)
        return {"results": [wire.result_to_wire(r) for r in results]}

    def _op_warm(self, msg) -> dict:
        """Admit scenes into the scene-digest store ahead of traffic (the
        gossip replication path, and the re-warm of a respawned worker)."""
        scenes = [wire.scene_from_wire(d) for d in msg["scenes"]]
        eng = self.engine
        if hasattr(eng, "workers"):           # DeviceRouter: shared store
            eng = eng.workers[0]
        stored = 0
        with self._elock:
            for s in scenes:
                if eng.map_strategy in ("composed", "incremental"):
                    eng._scene_entry(s)
                    stored += 1
        return {"stored": stored}

    def _op_stats(self, msg) -> dict:
        return {"summary": self.engine.stats.summary()}

    def _op_tune(self, msg) -> dict:
        from repro.core import dataflows as df
        scenes = [wire.scene_from_wire(d) for d in msg["scenes"]]
        space = msg.get("space")
        if space is not None:
            space = [df.DataflowConfig.from_dict(d) for d in space]
        with self._elock:
            assignment = self.engine.tune(scenes, space=space,
                                          iters=int(msg.get("iters", 2)),
                                          save=False)
        return {"assignment": _assignment_to_json(assignment)}

    def _op_shutdown(self, msg) -> dict:
        return {"bye": True}

    # ------------------------------------------------------------- serve loop
    def serve_forever(self, port: int = 0, announce=print) -> None:
        """Bind localhost, announce ``FLEET_WORKER_PORT=<port>`` (the spawn
        handshake), then answer frames until a ``shutdown`` op."""
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(("127.0.0.1", port))
        srv.listen(8)
        announce(f"FLEET_WORKER_PORT={srv.getsockname()[1]}", flush=True)
        done = threading.Event()

        def conn_loop(conn: socket.socket) -> None:
            try:
                while not done.is_set():
                    msg = wire.recv_msg(conn)
                    reply = self.handle(msg)
                    wire.send_msg(conn, reply)
                    if msg.get("op") == "shutdown":
                        done.set()
            except (ConnectionError, OSError, wire.WireError):
                pass               # front end went away; keep serving others
            finally:
                conn.close()

        srv.settimeout(0.25)
        try:
            while not done.is_set():
                try:
                    conn, _ = srv.accept()
                except socket.timeout:
                    continue
                threading.Thread(target=conn_loop, args=(conn,),
                                 daemon=True).start()
        finally:
            srv.close()


def worker_main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="fleet worker process (spawned by FleetFrontend / "
                    "serve_sparse --hosts)")
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--config", required=True,
                    help="ServiceConfig as JSON (ServiceConfig.to_dict)")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--plans", default=None)
    ap.add_argument("--devices", type=int, default=1)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg = ServiceConfig.from_dict(json.loads(args.config))
    FleetWorker(args.arch, cfg, plans=args.plans,
                devices=args.devices).serve_forever(args.port)


# ---------------------------------------------------------------------------
# Front end side
# ---------------------------------------------------------------------------

class HostHandle:
    """Front-end state for one worker host: process + two connections."""

    def __init__(self, index: int, addr: Tuple[str, int],
                 proc: Optional[subprocess.Popen]):
        self.index = index
        self.label = f"h{index}"
        self.addr = addr
        self.proc = proc
        self.data: Optional[socket.socket] = None
        self.ctrl: Optional[socket.socket] = None
        self.data_lock = threading.Lock()
        self.ctrl_lock = threading.Lock()
        self.alive = False
        self.weight = 1.0
        self.calib_ms: Optional[float] = None
        self.warmed: set = set()            # scene digests pushed via gossip
        self.last_summary: Optional[dict] = None

    def close(self) -> None:
        for s in (self.data, self.ctrl):
            if s is not None:
                try:
                    s.close()
                except OSError:
                    pass
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self.proc.kill()


class FleetStats:
    """Fleet-level stats: the RouterStats schema with ``hosts`` in place of
    ``devices`` plus a ``fleet`` robustness block, aggregated from the
    front end's own windows and each live worker's reported summary."""

    def __init__(self, frontend: "FleetFrontend"):
        self._frontend = frontend
        self.submitted = 0
        self.completed = 0
        self.busy_s = 0.0
        self.flushes = 0
        self.deadline_flushes = 0
        self.count_flushes = 0
        self.failovers = 0           # hosts declared dead
        self.rerouted_batches = 0    # un-acked/queued batches re-routed
        self.respawns = 0
        self.heartbeat_misses = 0
        self.gossip_scenes = 0
        self.latencies_ms = collections.deque(maxlen=LATENCY_WINDOW)
        self.route_log: List[Tuple[int, int]] = []
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

    def summary(self) -> dict:
        fr = self._frontend
        host_sums = fr._host_summaries()
        live = [h for h in fr.hosts if h.alive]

        def total(*path, default=0):
            out = 0
            for s in host_sums.values():
                v = s
                for p in path:
                    v = v.get(p, {}) if isinstance(v, dict) else {}
                out += v if isinstance(v, (int, float)) else default
            return out

        merged_compiles: Dict[str, Dict[str, int]] = {
            k: {} for k in ("recompiles", "map_compiles", "plan_compiles")}
        for h in fr.hosts:
            s = host_sums.get(h.label)
            if not s:
                continue
            for field, sink in merged_compiles.items():
                for cap, n in s.get(field, {}).items():
                    sink[f"{h.label}:{cap}"] = n
        p50, p95 = percentiles_ms(self.latencies_ms)
        hosts = {}
        for h in fr.hosts:
            s = host_sums.get(h.label) or {}
            hosts[h.label] = {
                "addr": f"{h.addr[0]}:{h.addr[1]}",
                "alive": h.alive,
                "weight": h.weight,
                "calib_ms": h.calib_ms,
                "routed_batches": sum(1 for i, _ in self.route_log
                                      if i == h.index),
                "queue_depth": fr.outstanding_score[h.index],
                "scenes": s.get("scenes", 0),
                "batches": s.get("batches", 0),
                "p50_ms": s.get("p50_ms"),
                "p95_ms": s.get("p95_ms"),
            }
        return {
            "schema_version": STATS_SCHEMA_VERSION,
            "scenes": self.completed,
            "batches": len(self.route_log),
            "routed_batches": len(self.route_log),
            "p50_ms": p50,
            "p95_ms": p95,
            "scenes_per_s": self.completed / self.busy_s if self.busy_s else 0.0,
            "recompiles": merged_compiles["recompiles"],
            "map_compiles": merged_compiles["map_compiles"],
            "plan_compiles": merged_compiles["plan_compiles"],
            "map_cache": {"hits": total("map_cache", "hits"),
                          "misses": total("map_cache", "misses")},
            "scene_tables": {
                "hits": total("scene_tables", "hits"),
                "misses": total("scene_tables", "misses"),
                "composed_batches": total("scene_tables", "composed_batches"),
                "delta_merges": total("scene_tables", "delta_merges")},
            "deadline_flushes": self.deadline_flushes,
            "count_flushes": self.count_flushes,
            "phases": summarize_phases(self.phases),
            "slo": {"deadline_ms": self.slo_deadline_ms,
                    "measured": self.slo_measured,
                    "misses": self.slo_miss_count,
                    "miss_rate": (self.slo_miss_count / self.slo_measured
                                  if self.slo_measured else None)},
            "hosts": hosts,
            "fleet": {
                "schema_version": STATS_SCHEMA_VERSION,
                "hosts": len(fr.hosts),
                "live": len(live),
                "replication": fr.replication,
                "weights": {h.label: h.weight for h in fr.hosts},
                "failovers": self.failovers,
                "rerouted_batches": self.rerouted_batches,
                "respawns": self.respawns,
                "heartbeat_misses": self.heartbeat_misses,
                "gossip_scenes": self.gossip_scenes,
            },
        }


def _src_pythonpath() -> str:
    """PYTHONPATH for spawned workers: this repro's src root first.
    ``repro`` is a namespace package (no __init__), so the root comes from
    its ``__path__`` rather than ``__file__``."""
    import repro
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    current = os.environ.get("PYTHONPATH", "")
    return src + (os.pathsep + current if current else "")


#: PCI vendor id of Google devices, and the device ids of its TPU chips
#: (v3, v4, v5p, v5e, v6e, 7x), as the host's PCI bus lists them.
_GOOGLE_PCI_VENDOR = "0x1ae0"
_TPU_PCI_DEVICES = frozenset(
    {"0x0027", "0x005e", "0x0062", "0x0063", "0x006f", "0x0076"})


def local_tpu_chips() -> int:
    """TPU chips a locally spawned worker would claim, counted on the PCI bus
    without starting a jax backend — 0 where ``JAX_PLATFORMS`` (which
    spawned workers inherit) keeps the workers off the TPU."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    chips = 0
    for dev in glob.glob("/sys/bus/pci/devices/*"):
        try:
            with open(os.path.join(dev, "vendor")) as f:
                vendor = f.read().strip()
            with open(os.path.join(dev, "device")) as f:
                device = f.read().strip()
        except OSError:
            continue
        chips += vendor == _GOOGLE_PCI_VENDOR and device in _TPU_PCI_DEVICES
    return chips


def _front_end_platforms() -> str:
    """The jax platforms this process is configured for ("" = any)."""
    import jax
    return jax.config.jax_platforms or ""


def _check_local_spawn(hosts: int) -> None:
    """One process per chip.  A TPU chip belongs to the first process that
    starts a jax backend on it; a second one fails or hangs.  On a host with
    chips, refuse more than one local worker, and refuse unless this front
    end — which only packs and unpacks — is configured off the TPU, so the
    chips are left to the one spawned worker.  Nothing here changes this
    process's jax configuration: the caller moves it to the CPU at its entry
    point (``serve_sparse --hosts`` does)."""
    chips = local_tpu_chips()
    if not chips:
        return
    if hosts > 1:
        raise RuntimeError(
            f"{hosts} local fleet workers on a host with {chips} TPU chips "
            f"would all claim the same chips; spawn one worker (it routes "
            f"over the host's chips with devices_per_host) or start workers "
            f"on separate hosts and pass their addresses")
    platforms = _front_end_platforms()
    if not platforms or "tpu" in platforms.split(","):
        raise RuntimeError(
            f"this process may hold the TPU its spawned fleet worker needs "
            f"(jax_platforms={platforms!r}); before any jax computation, "
            f"call jax.config.update('jax_platforms', 'cpu') in the front "
            f"end only, or start the worker elsewhere and pass its address")


class FleetFrontend:
    """Host-level ``SparseService``: route scene groups to worker hosts.

    arch: model architecture, as for ``Engine``.
    hosts: an int N — spawn N localhost worker processes — or a list of
        ``(host, port)`` addresses of already-running workers.  On a host
        with TPU chips only one local worker may be spawned, from a front
        end configured off the TPU (see ``_check_local_spawn``).
    config: the ``ServiceConfig`` every worker serves with (shipped to
        spawned workers as JSON; remote workers must be started with the
        same config or bit-identity is forfeit).
    plans: optional PlanRegistry JSON *path*, forwarded to workers.
    replication: default scene replication policy ("lazy" | "gossip");
        per-stream overrides via ``set_replication(stream, policy)``.
    respawn: spawn + re-warm a replacement when a spawned host dies
        (address-only hosts are never respawned — we didn't start them).
    heartbeat_s: control-connection ping interval (None disables).
    inflight_timeout_s: per-operation data-socket timeout — the in-flight
        detector for a host that accepted a batch and hung.
    devices_per_host: devices each spawned worker routes over (>1 runs a
        DeviceRouter inside the worker: host-level then device-level
        routing).
    """

    def __init__(self, arch: str, hosts=2, config: Optional[ServiceConfig] = None,
                 plans: Optional[str] = None, replication: str = "lazy",
                 respawn: bool = False, heartbeat_s: Optional[float] = None,
                 inflight_timeout_s: float = 300.0, devices_per_host: int = 1,
                 seed: Optional[int] = None, **legacy):
        if seed is not None:
            legacy["seed"] = seed
        self.config = resolve_config(config, legacy)
        assert replication in REPLICATION_POLICIES, replication
        self.arch = arch
        self.plans_path = plans
        self.replication = replication
        self.respawn = respawn
        self.heartbeat_s = heartbeat_s
        self.inflight_timeout_s = inflight_timeout_s
        self.devices_per_host = devices_per_host
        self.ladder = self.config.ladder()
        self.batcher = SceneBatcher(self.ladder, self.config.spatial_bound)
        self.max_wait_ms = self.config.max_wait_ms
        self.flush_count = self.config.flush_count
        self.stats = FleetStats(self)
        self.hosts: List[HostHandle] = []
        self.outstanding_score: List[float] = []
        self._rr = 0
        self._queue: List[tuple] = []
        self._next_ticket = 0
        self._ready: Dict[int, SceneResult] = {}
        self._streams: "collections.OrderedDict[str, Scene]" = collections.OrderedDict()
        self.stream_cache_size = 1024
        self._replication_overrides: Dict[str, str] = {}
        self._digest_store: "collections.OrderedDict[str, Scene]" = collections.OrderedDict()
        self._lock = threading.Lock()       # host liveness + score mutation
        self._closed = False
        if isinstance(hosts, int):
            assert hosts >= 1, hosts
            _check_local_spawn(hosts)
            procs = [self._spawn_worker() for _ in range(hosts)]
            for proc in procs:
                self._attach(self._handshake(proc))
        else:
            for addr in hosts:
                h, p = (addr.rsplit(":", 1) if isinstance(addr, str)
                        else addr)
                handle = HostHandle(len(self.hosts), (h, int(p)), proc=None)
                self._connect(handle)
                self._attach(handle)
        self._hb_stop = threading.Event()
        self._hb_thread = None
        if heartbeat_s:
            self._hb_thread = threading.Thread(
                target=self._heartbeat_loop, daemon=True,
                name="fleet-heartbeat")
            self._hb_thread.start()

    # -------------------------------------------------------------- lifecycle
    def _spawn_worker(self) -> subprocess.Popen:
        # -c instead of -m: runpy re-executing an already-imported
        # submodule of repro.serve would warn on every worker start
        cmd = [sys.executable, "-c",
               "from repro.serve.fleet import worker_main; worker_main()",
               "--worker",
               "--arch", self.arch, "--port", "0",
               "--config", json.dumps(self.config.to_dict())]
        if self.plans_path:
            cmd += ["--plans", self.plans_path]
        if self.devices_per_host > 1:
            cmd += ["--devices", str(self.devices_per_host)]
        env = os.environ.copy()
        env["PYTHONPATH"] = _src_pythonpath()
        return subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                                env=env)

    def _handshake(self, proc: subprocess.Popen,
                   timeout_s: float = 120.0) -> HostHandle:
        """Read the worker's announced port off its stdout and connect."""
        deadline = time.monotonic() + timeout_s
        port = None
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                raise RuntimeError(
                    f"fleet worker exited during startup "
                    f"(rc={proc.poll()})")
            if line.startswith("FLEET_WORKER_PORT="):
                port = int(line.strip().split("=", 1)[1])
                break
        if port is None:
            raise RuntimeError("fleet worker never announced its port")
        handle = HostHandle(len(self.hosts), ("127.0.0.1", port), proc)
        self._connect(handle)
        return handle

    def _connect(self, handle: HostHandle) -> None:
        handle.data = socket.create_connection(handle.addr, timeout=120.0)
        handle.data.settimeout(self.inflight_timeout_s)
        handle.ctrl = socket.create_connection(handle.addr, timeout=120.0)
        handle.ctrl.settimeout(30.0)
        hello = self._request(handle, {"op": "hello"})
        handle.alive = True
        obs.event("host_up", host=handle.label, pid=hello.get("pid"),
                  devices=hello.get("device_count"))

    def _attach(self, handle: HostHandle) -> None:
        handle.index = len(self.hosts)
        handle.label = f"h{handle.index}"
        self.hosts.append(handle)
        self.outstanding_score.append(0.0)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._hb_stop.set()
        for h in self.hosts:
            if h.alive:
                try:
                    with h.data_lock:
                        wire.send_msg(h.data, {"op": "shutdown"})
                        wire.recv_msg(h.data)
                except (OSError, wire.WireError):
                    pass
            h.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    @property
    def num_hosts(self) -> int:
        return len(self.hosts)

    @property
    def live_hosts(self) -> List[HostHandle]:
        return [h for h in self.hosts if h.alive]

    # --------------------------------------------------------------- plumbing
    def _request(self, handle: HostHandle, msg: dict, ctrl: bool = False) -> dict:
        """One framed request/response on a host connection; socket failures
        and worker-reported errors surface as ``HostFailure``."""
        sock = handle.ctrl if ctrl else handle.data
        lock = handle.ctrl_lock if ctrl else handle.data_lock
        try:
            with lock:
                wire.send_msg(sock, msg)
                reply = wire.recv_msg(sock)
        except (OSError, ConnectionError, socket.timeout,
                wire.WireError) as e:
            raise HostFailure(handle.index, e) from e
        if not reply.get("ok"):
            raise HostFailure(handle.index,
                              RuntimeError(reply.get("error", "worker error")))
        return reply

    def _mark_dead(self, handle: HostHandle, why: str) -> None:
        with self._lock:
            if not handle.alive:
                return
            handle.alive = False
            self.stats.failovers += 1
        obs.event("host_down", host=handle.label, why=why)

    def _heartbeat_loop(self) -> None:
        while not self._hb_stop.wait(self.heartbeat_s):
            for h in list(self.hosts):
                if not h.alive:
                    continue
                try:
                    self._request(h, {"op": "ping"}, ctrl=True)
                except HostFailure:
                    self.stats.heartbeat_misses += 1
                    self._mark_dead(h, "heartbeat")

    def _host_summaries(self) -> Dict[str, dict]:
        out = {}
        for h in self.hosts:
            if h.alive:
                try:
                    h.last_summary = self._request(
                        h, {"op": "stats"}, ctrl=True)["summary"]
                except HostFailure:
                    self._mark_dead(h, "stats")
            if h.last_summary is not None:
                out[h.label] = h.last_summary
        return out

    # ---------------------------------------------------------------- routing
    def _route(self, rows: int) -> int:
        """Host index for a batch of ``rows`` padded rows: least outstanding
        *weighted* rows over live hosts; exact ties fall to a round-robin
        cursor.  Deterministic in the routed sequence and liveness state."""
        live = [h.index for h in self.hosts if h.alive]
        if not live:
            raise RuntimeError("no live fleet hosts")
        lo = min(self.outstanding_score[i] for i in live)
        n = len(self.hosts)
        pick = min((i for i in live if self.outstanding_score[i] == lo),
                   key=lambda i: (i - self._rr) % n)
        self._rr = (pick + 1) % n
        self.outstanding_score[pick] += rows * self.hosts[pick].weight
        self.stats.route_log.append((pick, rows))
        obs.event("route", host=self.hosts[pick].label, rows=rows,
                  weight=self.hosts[pick].weight)
        return pick

    def _uncharge(self, host_index: int, rows: int) -> None:
        with self._lock:
            self.outstanding_score[host_index] = max(
                0.0, self.outstanding_score[host_index]
                - rows * self.hosts[host_index].weight)

    # -------------------------------------------------------------------- api
    def set_replication(self, stream: str, policy: str) -> None:
        assert policy in REPLICATION_POLICIES, policy
        self._replication_overrides[stream] = policy

    def _admit(self, scene: Scene, stream: Optional[str]) -> None:
        self._digest_store[scene.digest] = scene
        self._digest_store.move_to_end(scene.digest)
        while len(self._digest_store) > DIGEST_STORE_SIZE:
            self._digest_store.popitem(last=False)
        policy = (self._replication_overrides.get(stream, self.replication)
                  if stream is not None else self.replication)
        if policy != "gossip":
            return
        payload = wire.scene_to_wire(scene)
        for h in self.live_hosts:
            if scene.digest in h.warmed:
                continue
            try:
                self._request(h, {"op": "warm", "scenes": [payload]})
                h.warmed.add(scene.digest)
                self.stats.gossip_scenes += 1
            except HostFailure:
                self._mark_dead(h, "gossip")

    def submit(self, scene: Scene, stream: Optional[str] = None) -> int:
        """Enqueue one scene; ticket resolved by the next flush — identical
        semantics to ``Engine.submit`` including the auto-flush triggers."""
        if scene.num_points > self.ladder.max_capacity:
            raise ValueError(f"scene of {scene.num_points} rows exceeds the "
                             f"largest bucket ({self.ladder.max_capacity})")
        t = self._next_ticket
        self._next_ticket += 1
        self._queue.append((t, scene, time.perf_counter()))
        self.stats.submitted += 1
        if stream is not None:
            self._streams[stream] = scene
            self._streams.move_to_end(stream)
            while len(self._streams) > self.stream_cache_size:
                self._streams.popitem(last=False)
        self._admit(scene, stream)
        self._autoflush()
        return t

    def submit_delta(self, stream: str, delta: SceneDelta) -> int:
        """Streaming frame as a delta of the stream's last scene.  The
        front end applies the delta host-side (it holds the stream's last
        full scene) and ships the full scene; workers on the incremental
        strategy still delta-merge locally from their own stores."""
        prev = self._streams.get(stream)
        if prev is None:
            raise KeyError(f"unknown stream {stream!r}; seed it with "
                           f"submit(scene, stream=...) first")
        return self.submit(apply_delta(prev, delta), stream=stream)

    def _deadline_due(self) -> bool:
        return (self.max_wait_ms is not None and bool(self._queue) and
                (time.perf_counter() - self._queue[0][2]) * 1e3
                >= self.max_wait_ms)

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

    # ------------------------------------------------------------------ flush
    def _run_queue(self) -> Dict[int, SceneResult]:
        if not self._queue:
            return {}
        queue, self._queue = self._queue, []
        t0 = time.perf_counter()
        with obs.span("flush", scenes=len(queue), hosts=len(self.hosts)):
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
        # identical FIFO grouping to the single-device engine: the
        # bit-identity contract — a worker only ever sees whole groups
        groups = self.batcher.plan(sizes)
        pending = [(gi, group, self.ladder.group_capacity(
            [sizes[i] for i in group])) for gi, group in enumerate(groups)]
        done: Dict[int, Tuple[List[SceneResult], float]] = {}

        while pending:
            shards: Dict[int, list] = {}
            with self._lock:
                for item in pending:
                    shards.setdefault(self._route(item[2]), []).append(item)
            pending = []
            failures: List[Tuple[HostHandle, list]] = []
            lock = threading.Lock()

            def run_host(hi: int, items: list) -> None:
                handle = self.hosts[hi]
                for k, (gi, group, rows) in enumerate(items):
                    payload = {"op": "execute",
                               "scenes": [wire.scene_to_wire(queue[i][1])
                                          for i in group]}
                    t_rpc = time.perf_counter()
                    try:
                        with obs.span("host_rpc", host=handle.label,
                                      rows=rows, scenes=len(group)):
                            reply = self._request(handle, payload)
                    except HostFailure:
                        self._mark_dead(handle, "execute")
                        with lock:
                            failures.append((handle, items[k:]))
                        return
                    self.stats.observe("rpc", (time.perf_counter() - t_rpc) * 1e3)
                    self._uncharge(hi, rows)
                    res = [wire.result_from_wire(d)
                           for d in reply["results"]]
                    with lock:
                        done[gi] = (res, time.perf_counter())

            threads = [threading.Thread(target=run_host, args=(hi, items),
                                        name=f"fleet-{self.hosts[hi].label}")
                       for hi, items in shards.items()]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for handle, lost in failures:
                for _, _, rows in lost:
                    self._uncharge(handle.index, rows)
                self.stats.rerouted_batches += len(lost)
                obs.event("reroute", host=handle.label, batches=len(lost))
                pending.extend(lost)
            if pending and not self.live_hosts:
                raise RuntimeError(
                    f"all fleet hosts died with {len(pending)} batches "
                    f"outstanding")

        results: Dict[int, SceneResult] = {}
        for gi, group in enumerate(groups):
            per_scene, t_done = done[gi]
            for slot, i in enumerate(group):
                ticket, _, t_sub = queue[i]
                results[ticket] = per_scene[slot]
                lat_ms = (t_done - t_sub) * 1e3
                self.stats.latencies_ms.append(lat_ms)
                obs.record_span("request", int(t_sub * 1e9),
                                int(t_done * 1e9), ticket=ticket)
                if self.max_wait_ms is not None:
                    self.stats.slo_observe(lat_ms, self.max_wait_ms)
        self.stats.completed += len(queue)
        if self.respawn:
            self._respawn_dead()
        return results

    # --------------------------------------------------------------- recovery
    def _respawn_dead(self) -> None:
        for h in list(self.hosts):
            if not h.alive and h.proc is not None:
                self.respawn_host(h.index)

    def respawn_host(self, index: int) -> HostHandle:
        """Replace a dead spawned host with a fresh worker process and
        re-warm its scene store from the front end's digest store."""
        old = self.hosts[index]
        assert old.proc is not None, \
            "cannot respawn a host this front end did not spawn"
        old.close()
        proc = self._spawn_worker()
        handle = self._handshake(proc)
        handle.index = index
        handle.label = f"h{index}"
        handle.weight = old.weight
        handle.calib_ms = old.calib_ms
        with self._lock:
            self.hosts[index] = handle
            self.outstanding_score[index] = 0.0
        scenes = [wire.scene_to_wire(s) for s in self._digest_store.values()]
        if scenes:
            try:
                stored = self._request(
                    handle, {"op": "warm", "scenes": scenes})["stored"]
                handle.warmed.update(self._digest_store.keys())
                obs.event("rewarm", host=handle.label, scenes=stored)
            except HostFailure:
                self._mark_dead(handle, "rewarm")
        self.stats.respawns += 1
        return handle

    # ------------------------------------------------------------ maintenance
    def warmup(self, channels: Optional[int] = None) -> None:
        """Warm every host (compile all rungs) and calibrate routing
        weights from the reported warm timings: a host 2× slower than the
        fastest carries weight 2.0, so its outstanding-rows score grows
        2× per routed row and it receives proportionally less work."""
        calib: Dict[int, float] = {}

        def warm_one(h: HostHandle) -> None:
            try:
                r = self._request(h, {"op": "warmup", "channels": channels})
            except HostFailure:
                self._mark_dead(h, "warmup")
                return
            ms = r.get("calib_ms") or r.get("warmup_ms")
            if ms:
                calib[h.index] = float(ms)

        threads = [threading.Thread(target=warm_one, args=(h,))
                   for h in self.live_hosts]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if calib:
            fastest = min(calib.values())
            for i, ms in calib.items():
                self.hosts[i].calib_ms = ms
                self.hosts[i].weight = ms / fastest if fastest > 0 else 1.0

    def tune(self, sample_scenes: Sequence[Scene], space=None, iters: int = 2,
             save: bool = True) -> Dict[str, dict]:
        """Tune every live host's engine on the sample and return
        {host_label: assignment}.  With ``save`` and a plans path, host 0's
        winning assignment is persisted under the shared arch entry (a
        homogeneous fleet serves one plan; heterogeneous fleets should
        tune per host out of band and pass per-host plan files)."""
        payload = {"op": "tune", "iters": iters,
                   "scenes": [wire.scene_to_wire(s) for s in sample_scenes],
                   "space": ([c.to_dict() for c in space]
                             if space is not None else None)}
        out: Dict[str, dict] = {}
        for h in self.live_hosts:
            try:
                r = self._request(h, payload)
            except HostFailure:
                self._mark_dead(h, "tune")
                continue
            out[h.label] = _assignment_from_json(r["assignment"])
        if save and self.plans_path and out:
            reg = PlanRegistry.load(self.plans_path)
            first = next(iter(out))
            reg.set(self.arch, out[first])
            reg.set_service(self.arch, self.config)
            reg.save(self.plans_path)
        return out


if __name__ == "__main__":
    worker_main()
