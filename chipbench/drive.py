"""Drive an ``Engine`` with a run's requests: set-up flushes, then one
measured window, closed or open loop, with an optional profiled stretch.

Every call into the program is bracketed by a ``TraceAnnotation`` of the
harness's own (``chipbench.submit``, ``.submit_delta``, ``.poll``,
``.flush``, and ``.wait`` while the open loop sleeps), so a device trace
can say what the host was doing in each idle gap.
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import tempfile
import time
from typing import Dict, List, Optional

import jax

from chipbench import system

#: seconds of the window the profiler records, from a third of the way in
TRACE_SECONDS = 6.0

#: seconds an open loop waits past its last arrival for the last results
DRAIN_SECONDS = 60.0

ann = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Request:
    ticket: int
    scene: object            # scenes.SceneData the request carries
    due: float               # perf_counter seconds
    done: Optional[float] = None


@dataclasses.dataclass
class Traced:
    """The profiled stretch: the tickets answered inside it, and the
    profiler's output directory."""

    tickets: List[int]
    logdir: str


@dataclasses.dataclass
class Window:
    t0: float
    t1: float = 0.0
    requests: Dict[int, Request] = dataclasses.field(default_factory=dict)
    results: Dict[int, object] = dataclasses.field(default_factory=dict)
    submit_errors: int = 0
    lateness_ms: List[float] = dataclasses.field(default_factory=list)
    traced: Optional[Traced] = None
    stats0: dict = dataclasses.field(default_factory=dict)
    stats1: dict = dataclasses.field(default_factory=dict)
    phases: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def completed(self) -> int:
        return len(self.results)


class _Profiler:
    """Starts the profiler a third of the way into the window and stops
    it ``TRACE_SECONDS`` later, each at a point the loop offers (a flush
    boundary in a closed loop)."""

    def __init__(self, on: bool, t0: float, seconds: float):
        self.on = on
        self.start_at = t0 + seconds / 3
        self.logdir = None
        self.t0 = None
        self.traced: Optional[Traced] = None
        self._window = None

    @property
    def active(self) -> bool:
        return self.t0 is not None and self.traced is None

    def step(self, now: float, done_tickets) -> None:
        if not self.on or self.traced is not None:
            return
        if self.t0 is None:
            if now >= self.start_at:
                self.logdir = tempfile.mkdtemp(prefix="chipbench_trace_")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.logdir, profiler_options=opts)
                self._window = ann("chipbench.window")
                self._window.__enter__()
                self.t0 = time.perf_counter()
                self.tickets: List[int] = []
            return
        self.tickets.extend(done_tickets)
        if now >= self.t0 + TRACE_SECONDS:
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.traced = Traced(self.tickets, self.logdir)


def _submit(eng, w: Window, scene, sdata, due: float, stream=None,
            delta=None) -> None:
    try:
        if delta is not None:
            with ann("chipbench.submit_delta"):
                t = eng.submit_delta(stream, delta)
        else:
            with ann("chipbench.submit"):
                t = eng.submit(scene, stream=stream)
    except Exception:   # a refused request counts as failed, not fatal
        w.submit_errors += 1
        return
    w.requests[t] = Request(t, sdata, due)


def _record(w: Window, res: dict, now: float) -> List[int]:
    for t, r in res.items():
        w.results[t] = r
        if t in w.requests:
            w.requests[t].done = now
    return list(res)


def warm_up(eng, req) -> None:
    """Set-up: compile every rung the window will use, through the same
    calls the window makes, and seed the sensor streams (frame 0)."""
    for batch in req.warm:
        for s in batch:
            eng.submit(system.to_scene(s))
        eng.flush()
    for j, (s, d) in enumerate(req.warm_deltas):
        eng.submit(system.to_scene(s), stream=f"warm{j}")
        eng.flush()
        eng.submit_delta(f"warm{j}", system.to_delta(d))
        eng.flush()
    for i, s in enumerate(req.streams):
        eng.submit(system.to_scene(s), stream=f"s{i}")
    if req.streams:
        eng.flush()


def _snapshot(eng) -> tuple:
    return eng.stats.summary(), {k: len(v) for k, v in
                                 eng.stats.phases.items()}


def _finish(eng, w: Window, marks: dict, prof: _Profiler) -> Window:
    from repro import obs
    w.stats1 = eng.stats.summary()
    for k, v in eng.stats.phases.items():
        if len(v) == v.maxlen:
            raise RuntimeError(f"phase window {k!r} overflowed")
        w.phases[k] = list(v)[marks.get(k, 0):]
    w.spans = [s for s in obs.get_tracer().spans()
               if s.name == "batch_pack"]
    w.traced = prof.traced
    return w


def closed_loop(eng, req, mix: dict, seconds: float, trace: bool) -> Window:
    """Queue a backlog (fresh scenes) or a whole frame (streams), flush,
    repeat until the window's time is up; the window ends at the flush
    that crosses it, so it holds whole flushes only."""
    from repro import obs
    if trace:
        obs.enable()
    stats0, marks = _snapshot(eng)
    frames = _frame_scenes(req)
    w = Window(t0=time.perf_counter(), stats0=stats0)
    prof = _Profiler(trace, w.t0, seconds)
    i = 0
    while True:
        now = time.perf_counter()
        if req.streams:
            if i >= len(frames):
                raise RuntimeError(f"all {i} frames sent before the window "
                                   "closed: raise the mix's 'frames'")
            for k, (d, sdata, scene) in enumerate(frames[i]):
                _submit(eng, w, scene, sdata, now, stream=f"s{k}",
                        delta=None if d is None else system.to_delta(d))
        else:
            for k in range(i, i + mix["backlog"]):
                sdata = req.fresh_at(k)
                _submit(eng, w, system.to_scene(sdata), sdata, now)
        i += 1 if req.streams else mix["backlog"]
        with ann("chipbench.flush"):
            res = eng.flush()
        now = time.perf_counter()
        done = _record(w, res, now)
        prof.step(now, done)
        if now >= w.t0 + seconds and not prof.active:
            break
    w.t1 = now
    return _finish(eng, w, marks, prof)


def _frame_scenes(req) -> list:
    """Per frame, per stream: (delta, expected scene, program Scene), one
    program Scene per distinct scene so unchanged streams resubmit the
    same object."""
    made: dict = {}
    out = []
    for row in req.frames:
        out.append([])
        for d, s in row:
            if id(s) not in made:
                made[id(s)] = system.to_scene(s)
            out[-1].append((d, s, made[id(s)]))
    return out


def open_loop(eng, req, seconds: float, trace: bool) -> Window:
    """Submit each scene when it is due, whatever is still in flight; poll
    for results (the engine flushes on its queue depth or deadline) until
    every request has an answer or ``DRAIN_SECONDS`` pass."""
    from repro import obs
    if trace:
        obs.enable()
    stats0, marks = _snapshot(eng)
    scenes_in = [system.to_scene(s) for s in req.fresh]
    w = Window(t0=time.perf_counter(), stats0=stats0)
    prof = _Profiler(trace, w.t0, seconds)
    due = w.t0 + req.arrivals
    k, n = 0, len(scenes_in)
    last = w.t0
    while True:
        now = time.perf_counter()
        if k < n and now >= due[k]:
            w.lateness_ms.append((now - due[k]) * 1e3)
            _submit(eng, w, scenes_in[k], req.fresh[k], due[k])
            k += 1
            continue
        with ann("chipbench.poll"):
            res = eng.poll()
        now = time.perf_counter()
        done = _record(w, res, now)
        if done:
            last = now
        prof.step(now, done)
        pending = len(w.requests) - len(w.results)
        if k >= n and ((pending <= 0 and not prof.active)
                       or now > due[-1] + DRAIN_SECONDS):
            break
        if not done:
            nxt = due[k] if k < n else now + 0.002
            with ann("chipbench.wait"):
                time.sleep(max(0.0, min(nxt - now, 0.002)))
    w.t1 = last
    return _finish(eng, w, marks, prof)


def drop_trace(w: Window) -> None:
    if w.traced is not None:
        shutil.rmtree(w.traced.logdir, ignore_errors=True)


def trace_file(w: Window) -> str:
    for root, _, files in os.walk(w.traced.logdir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(root, f)
    raise FileNotFoundError(f"no .xplane.pb under {w.traced.logdir}")
