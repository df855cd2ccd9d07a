"""Phase-level tracing + metrics: the repo's zero-dependency observability
core.

The paper's Sparse Autotuner picks dataflows purely from measurements
(PAPER.md §4), and TorchSparse's own gather/GEMM/scatter cost breakdowns
are per-phase visibility — this module gives the *system* that same
visibility at request granularity.  One ``Tracer`` holds:

* **spans** — nestable ``span("phase", **attrs)`` context managers on
  monotonic clocks (``time.perf_counter_ns``), with a per-thread span
  stack so router worker threads interleave correctly: every record
  carries its thread id/name and its nesting depth *within that thread*.
  A live span also enters a ``jax.profiler.TraceAnnotation`` named
  ``repro.<name>``, so while a jax profiler trace is recording, every
  span sits on the profiler's host plane on the same clock as the
  device's operations (jax is imported by the first enabled span).
  ``record_span`` retroactively records an interval measured elsewhere
  (queue waits: the submit timestamp predates the flush that observes
  it); those stay host-only, since the profiler cannot be told about an
  interval after it ended;
* **instant events** — ``event("compile", rung=..., device=...)`` for
  point-in-time facts like jit recompiles, routing decisions, checkpoint
  writes;
* **counters / gauges** — monotonically accumulated / last-value metrics,
  readable as one ``snapshot()`` dict;
* **phase histograms** — ``phase_summary()`` folds recorded spans into
  per-name count/p50/p95/total.

A process-global default tracer starts **disabled** and compiles to
no-ops: the disabled ``span()`` fast path returns one preallocated
singleton, so instrumented hot paths pay a truthiness check, retain
zero allocations and create no profiler annotation (asserted in
tests/test_obs.py).  Enable it with
``enable()`` (or install your own via ``set_tracer``), export with
``repro.obs.export`` (Chrome trace-event JSON for Perfetto /
``chrome://tracing``, or a flat JSONL event log).

Storage is bounded: past ``max_records`` spans/events the tracer keeps
the earliest records (a trace's interesting part is usually its start —
compiles, warmup) and counts the rest in ``dropped``.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class SpanRecord:
    """One finished span: a named interval on the monotonic clock."""

    name: str
    t0_ns: int
    t1_ns: int
    tid: int
    thread: str
    depth: int      # nesting depth within this thread's span stack
    attrs: dict

    @property
    def dur_ms(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e6


@dataclasses.dataclass(frozen=True)
class EventRecord:
    """One instant event (a point, not an interval)."""

    name: str
    t_ns: int
    tid: int
    thread: str
    attrs: dict


class _NoopSpan:
    """The disabled fast path: one preallocated singleton, no state."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


NOOP_SPAN = _NoopSpan()


class _Span:
    """A live span context manager (enabled tracer only)."""

    __slots__ = ("_tracer", "name", "attrs", "_t0", "_depth", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> "_Span":
        """Attach attributes discovered mid-span (e.g. a measured latency)
        — must be called before the span closes."""
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        stack = self._tracer._stack()
        self._depth = len(stack)
        stack.append(self)
        # imported here, not at module level: repro.obs imports no jax
        from jax.profiler import TraceAnnotation
        self._ann = TraceAnnotation("repro." + self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:     # out-of-order exit: drop through to self
            del stack[stack.index(self):]
        th = threading.current_thread()
        self._tracer._add_span(SpanRecord(
            name=self.name, t0_ns=self._t0, t1_ns=t1, tid=th.ident or 0,
            thread=th.name, depth=self._depth, attrs=self.attrs))
        return False


class Tracer:
    """Thread-safe span/event/metric collector (see module docstring).

    enabled:     a disabled tracer records nothing; its ``span()`` returns
                 the no-op singleton (counters/gauges stay live — they are
                 cheap and callers rely on them for stats).
    max_records: bound on stored spans and on stored events (separately);
                 excess records are counted in ``dropped``, never stored.
    """

    def __init__(self, enabled: bool = True, max_records: int = 200_000):
        self.enabled = enabled
        self.max_records = int(max_records)
        self.dropped = 0
        self._lock = threading.Lock()
        self._spans: List[SpanRecord] = []
        self._events: List[EventRecord] = []
        self._counters: "collections.Counter" = collections.Counter()
        self._gauges: Dict[str, float] = {}
        self._tls = threading.local()

    # ------------------------------------------------------------- plumbing
    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _add_span(self, rec: SpanRecord) -> None:
        with self._lock:
            if len(self._spans) < self.max_records:
                self._spans.append(rec)
            else:
                self.dropped += 1

    # ------------------------------------------------------------ recording
    def span(self, name: str, **attrs):
        """Context manager timing a named phase; no-op when disabled."""
        if not self.enabled:
            return NOOP_SPAN
        return _Span(self, name, attrs)

    def record_span(self, name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
        """Record an interval measured elsewhere (both ends in
        ``time.perf_counter_ns`` time) — e.g. a queue wait whose start
        predates the flush that observes it."""
        if not self.enabled:
            return
        th = threading.current_thread()
        self._add_span(SpanRecord(
            name=name, t0_ns=int(t0_ns), t1_ns=int(t1_ns),
            tid=th.ident or 0, thread=th.name,
            depth=len(self._stack()), attrs=attrs))

    def event(self, name: str, **attrs) -> None:
        """Record an instant event; no-op when disabled."""
        if not self.enabled:
            return
        th = threading.current_thread()
        rec = EventRecord(name=name, t_ns=time.perf_counter_ns(),
                          tid=th.ident or 0, thread=th.name, attrs=attrs)
        with self._lock:
            if len(self._events) < self.max_records:
                self._events.append(rec)
            else:
                self.dropped += 1

    def count(self, name: str, n: int = 1) -> None:
        """Bump a monotonic counter (live even when tracing is disabled)."""
        with self._lock:
            self._counters[name] += n

    def gauge(self, name: str, value: float) -> None:
        """Set a last-value gauge (live even when tracing is disabled)."""
        with self._lock:
            self._gauges[name] = value

    # -------------------------------------------------------------- reading
    def spans(self) -> List[SpanRecord]:
        with self._lock:
            return list(self._spans)

    def events(self, name: Optional[str] = None) -> List[EventRecord]:
        with self._lock:
            evs = list(self._events)
        return evs if name is None else [e for e in evs if e.name == name]

    def snapshot(self) -> dict:
        """Counters + gauges + record bookkeeping, one JSON-able dict."""
        with self._lock:
            return {"counters": dict(self._counters),
                    "gauges": dict(self._gauges),
                    "spans": len(self._spans), "events": len(self._events),
                    "dropped": self.dropped}

    def phase_summary(self) -> Dict[str, dict]:
        """Per span name: count, p50/p95/total milliseconds (pure python —
        percentiles by sorted index, no numpy dependency here)."""
        by_name: Dict[str, List[float]] = {}
        for rec in self.spans():
            by_name.setdefault(rec.name, []).append(rec.dur_ms)
        out = {}
        for name, durs in sorted(by_name.items()):
            durs.sort()
            n = len(durs)
            out[name] = {"count": n,
                         "p50_ms": durs[min(n - 1, int(0.50 * n))],
                         "p95_ms": durs[min(n - 1, int(0.95 * n))],
                         "total_ms": sum(durs)}
        return out

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._events.clear()
            self._counters.clear()
            self._gauges.clear()
            self.dropped = 0


# ---------------------------------------------------------------------------
# The process-global default tracer
# ---------------------------------------------------------------------------

_default = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _default


def set_tracer(tracer: Tracer) -> Tracer:
    """Install ``tracer`` as the process default; returns it."""
    global _default
    _default = tracer
    return tracer


def enable(max_records: int = 200_000) -> Tracer:
    """Install and return a fresh enabled default tracer."""
    return set_tracer(Tracer(enabled=True, max_records=max_records))


def disable() -> Tracer:
    """Install and return a fresh disabled default tracer."""
    return set_tracer(Tracer(enabled=False))


def span(name: str, **attrs):
    """Module-level span on the default tracer — THE instrumentation entry
    point for hot paths: when disabled it returns the preallocated no-op
    singleton (no tracer state touched, nothing retained)."""
    t = _default
    if not t.enabled:
        return NOOP_SPAN
    return _Span(t, name, attrs)


def event(name: str, **attrs) -> None:
    t = _default
    if t.enabled:
        t.event(name, **attrs)


def record_span(name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
    t = _default
    if t.enabled:
        t.record_span(name, t0_ns, t1_ns, **attrs)


def count(name: str, n: int = 1) -> None:
    _default.count(name, n)


def gauge(name: str, value: float) -> None:
    _default.gauge(name, value)
