"""Reduce a profiler trace (``.xplane.pb``) to device busy time, the
top device operations and the longest idle gaps.

The traced stretch is the host annotation ``chipbench.window``.  Device
operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, clipped to that stretch, and grouped by
instruction name without its number and by output type (``fusion
bf16[32768,4]``).  Busy time is the
union of their intervals, averaged over the chips that ran anything.  An
idle gap is a stretch between two operations of one chip; it is named by
the innermost ``chipbench.*`` host annotation that covers its midpoint
(what the harness was waiting on), or ``host`` where none does.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

WINDOW = "chipbench.window"
PREFIX = "chipbench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over chips with any op
    chips: int
    device_ops: List[Tuple[str, float]]  # top ops by total seconds
    idle_gaps: List[Tuple[str, float]]   # longest gaps, named by the host

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _union(ivs: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_group(hlo: str) -> str:
    """``%fusion.597 = bf16[32768,4]{...} fusion(...)`` -> ``fusion
    bf16[32768,4]``: the instruction's name without its number, and the
    type it writes."""
    name, _, rest = hlo.partition(" = ")
    name = re.sub(r"\.\d+$", "", name.lstrip("%"))
    out = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return f"{name} {out.group(1)}" if out else name


def events(path: str):
    """(plane name, line name, event name, start ns, end ns) of every
    event of the trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                yield (plane.name, line.name, e.name, float(e.start_ns),
                       float(e.start_ns + e.duration_ns))


def reduce(rows) -> Reduced:
    """``rows``: the output of ``events`` (or a recorded list of it)."""
    rows = list(rows)
    wins = [(a, b) for p, _, n, a, b in rows
            if not p.startswith(DEVICE_PREFIX) and n == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW!r} annotation, found "
                         f"{len(wins)}")
    w0, w1 = wins[0]
    host = sorted(((a, b, n) for p, _, n, a, b in rows
                   if not p.startswith(DEVICE_PREFIX) and n != WINDOW
                   and n.startswith(PREFIX)), key=lambda r: r[0])
    per_chip: Dict[str, List[Tuple[float, float, str]]] = {}
    for p, line, n, a, b in rows:
        if p.startswith(DEVICE_PREFIX) and line == OPS_LINE:
            a, b = max(a, w0), min(b, w1)
            if b > a:
                per_chip.setdefault(p, []).append((a, b, n))
    if not per_chip:
        raise ValueError("no device operation inside the traced window")
    op_time: Dict[str, float] = {}
    busy = 0.0
    gaps: List[Tuple[str, float]] = []
    for ops in per_chip.values():
        for a, b, n in ops:
            g = op_group(n)
            op_time[g] = op_time.get(g, 0.0) + (b - a)
        merged = _union([(a, b) for a, b, _ in ops])
        busy += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_name_at(host, (a + b) / 2), (b - a) / 1e9))
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduced(window_s=(w1 - w0) / 1e9,
                   busy_s=busy / len(per_chip) / 1e9, chips=len(per_chip),
                   device_ops=[(n, t / 1e9) for n, t in top],
                   idle_gaps=sorted(gaps, key=lambda g: -g[1])[:TOP])


def _name_at(host, t: float) -> str:
    """The innermost (latest-starting) harness annotation covering t."""
    name = "host"
    for a, b, n in host:
        if a > t:
            break
        if b >= t:
            name = n
    return name
