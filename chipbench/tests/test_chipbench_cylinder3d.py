"""Cylinder3D through the harness on the CPU at a small size: the served
path against ``ref_cylinder3d`` (voxels exact, rows to float32 rounding),
the configuration's ``rel_err`` limit between the bf16 program and the
fp8 control, a whole run of a cell, and the ``level_pad_row_share``
reader."""
from __future__ import annotations

import json

import numpy as np
import pytest

import chipbench_helpers as H
from chipbench import check, context, drive, run, scenes, system
from chipbench.control import CONTROL

NAME = "cylinder3d-kitti-1x"


def tiny_c3d(precision: str = "fp32") -> dict:
    """A quarter of the published width (``init_size`` 8) on 200-400-voxel
    scenes packed into a 3 m square: dense enough that each dilated level
    holds fewer rows than its scene's rung, as the full-size scenes do."""
    cfg = system.load_json("configs", NAME)
    cfg["model"]["init_size"] = 8
    cfg.update(extent=3.0, margin=1.0, voxels_per_scene=[200, 400],
               precision=precision)
    cfg["serving"] = {**cfg["serving"], "buckets": [1024]}
    return cfg


def _serve(precision):
    cfg = tiny_c3d(precision)
    ref = system.load_module("configs", cfg["reference"])
    params = system.init_params(cfg, ref, 4)
    eng = system.engine(cfg, cfg["serving"], params, 4)
    geo = scenes.Geometry.of(cfg)
    ss = [scenes.scene(9, i, n, geo) for i, n in enumerate((200, 380, 260))]
    res = eng.serve([system.to_scene(s) for s in ss])
    return cfg, ref, params, eng, ss, res


@pytest.fixture(scope="module")
def fp32():
    return _serve("fp32")


def test_reference_matches_the_served_path(fp32):
    """Every input voxel answered, rows equal to float32 rounding, through
    composed batch maps (dilated pools, column-subset maps)."""
    cfg, ref, params, eng, ss, res = fp32
    assert eng.stats.composed_batches >= 1
    reference = check.Reference(ref, cfg)
    for s, r in zip(ss, res):
        vox, want = reference(params, s)
        got_vox, got = check.served(r)
        np.testing.assert_array_equal(got_vox, vox)
        np.testing.assert_array_equal(
            vox, s.coords[np.argsort(scenes.pack(s.coords))])
        assert check.rel_err(got, want) < 1e-5


def test_level_rows_are_the_reference_levels(fp32):
    """The engine's ``level_tables`` counters: the rows of every dilated
    level the reference's own pooling finds, and four levels at each
    scene's rung."""
    cfg, ref, _, eng, ss, _ = fp32
    rows = sum(len(c) for s in ss
               for lvl, c in ref.pyramid(s.coords, cfg["model"]).coords.items()
               if lvl > 1)
    rungs = sum(eng._scene_ladder.select(s.num_points) for s in ss)
    summary = eng.stats.summary()["level_tables"]
    assert summary == {"rows": rows, "cap_rows": 4 * rungs}


def test_control_fails_the_limit_the_program_meets():
    """The configuration's own limit: the served bf16 path reads under
    it, the fp8 control over it."""
    limit = system.load_json("configs", NAME)["limits"]["rel_err"]
    cfg, ref, params, _, ss, res = _serve("bf16")
    reference = check.Reference(ref, cfg)
    prog, ctrl = [], []
    for s, r in zip(ss, res):
        _, want = reference(params, s)
        prog.append(check.rel_err(check.served(r)[1], want))
        ctrl.append(check.rel_err(reference(params, s, CONTROL["bf16"])[1],
                                  want))
    assert max(prog) < limit < min(ctrl), (prog, limit, ctrl)


def test_a_cell_runs_correct(tmp_path, monkeypatch, capsys):
    cfg = tiny_c3d()
    # sizes clear of the 256-row rung, whose level 2 a 225-voxel scene
    # would overflow (the engine raises on that: test_cylinder3d.py)
    cfg["voxels_per_scene"] = [300, 440]
    cfg["limits"] = {"rel_err": 1e-3}
    root = H.bench_tree(
        str(tmp_path), [{"name": "tiny.c3d", "config": "tiny-c3d",
                         "traffic": "backlog", "chips": 1, "why": "test"}],
        {"tiny-c3d": cfg},
        {"backlog": {"loop": "closed", "backlog": 4, "pool": 400,
                     "streams": 0}})
    monkeypatch.setattr(run, "device_or_exit", lambda chips: H.FakeDevice())
    monkeypatch.setattr(run, "compile_cache", lambda: None)
    assert run.main(["--workload", "tiny.c3d", "--seed", str(2 ** 31 + 3),
                     "--seconds", "0.5"], root=root) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0


def _reader_run(stats0: dict, stats1: dict):
    w = drive.Window(t0=0.0, t1=1.0, stats0=stats0, stats1=stats1)
    return context.Run(config={}, ref=None, peak={}, window=w, setup_s=1.0)


def test_level_pad_row_share_reader():
    read = system.load_module("metrics", "level_pad_row_share").read
    # 8 scenes of 11750..22250 voxels: four levels each at 16384- and
    # 32768-row rungs, about 20 thousand level rows a scene
    before = {"level_tables": {"rows": 500, "cap_rows": 4096}}
    after = {"level_tables": {"rows": 500 + 160000, "cap_rows": 4096 + 786432}}
    assert read(_reader_run(before, after)) == pytest.approx(
        100 * (1 - 160000 / 786432))
    assert read(_reader_run(before, before)) is None       # no scene build
    assert read(_reader_run({}, {"scene_tables": {}})) is None   # no counter
