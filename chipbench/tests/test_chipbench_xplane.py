"""The trace reduction, on a small trace recorded on a TPU v5e (four
matmul-tanh calls, each inside ``chipbench.flush``, in one
``chipbench.window``) and on hand-made events."""
from __future__ import annotations

import os

import pytest

import chipbench_helpers as H  # noqa: F401  (import paths)
from chipbench import xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_small.xplane.pb")


def test_recorded_trace():
    r = xplane.reduce(xplane.events(FIXTURE))
    assert r.chips == 1
    assert r.window_s == pytest.approx(0.01289314)
    # three of the four calls start inside the window (the device clock
    # reads about 1.4 ms behind the host's, so the first one falls before)
    assert r.busy_s == pytest.approx(9.53e-06)
    assert 0 < r.idle_share < 1
    name, secs = r.device_ops[0]
    assert name == "fusion f32[512,512]" and secs == pytest.approx(9.484e-06)
    assert len(r.idle_gaps) <= xplane.TOP
    names = {n for n, _ in r.idle_gaps}
    assert names <= {"host", "chipbench.flush"} and "chipbench.flush" in names
    assert r.idle_gaps == sorted(r.idle_gaps, key=lambda g: -g[1])


def _row(name, a, b, plane="/device:TPU:0", line=xplane.OPS_LINE):
    return (plane, line, name, float(a), float(b))


def test_union_gaps_and_names():
    host = "/host:CPU"
    rows = [_row(xplane.WINDOW, 0, 1000, host, "python"),
            _row("chipbench.flush", 100, 400, host, "python"),
            _row("chipbench.poll", 600, 900, host, "python"),
            _row("op_a", -50, 100),       # clipped to the window: 100 ns
            _row("op_b", 50, 150),        # overlaps op_a
            _row("op_a", 500, 600),
            _row("op_c", 2000, 3000),     # after the window
            _row("ignored", 0, 1000, line="XLA Modules")]
    r = xplane.reduce(rows)
    assert r.window_s == pytest.approx(1e-6)
    assert r.busy_s == pytest.approx(250e-9)        # [0,150] + [500,600]
    assert dict(r.device_ops) == pytest.approx({"op_a": 200e-9,
                                                "op_b": 100e-9})
    assert r.idle_gaps == [("chipbench.flush", pytest.approx(350e-9)),
                           ("chipbench.poll", pytest.approx(400e-9))][::-1]


def test_no_window_or_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce([_row("op", 0, 1)])
    with pytest.raises(ValueError):
        xplane.reduce([_row(xplane.WINDOW, 0, 10, "/host:CPU", "python")])
