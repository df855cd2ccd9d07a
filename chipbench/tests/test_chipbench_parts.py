"""The harness's parts on the CPU: the scene and traffic generator, the
plain reference against the served path, the reference's independence,
the work counts, the peaks table and the chip check."""
from __future__ import annotations

import ast
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_helpers as H
from chipbench import check, counts, reference as R, scenes, system, traffic

CONFIGS = ("minkunet-kitti-1x", "centerpoint-waymo-1x")


def test_scenes_are_deterministic_in_the_seed():
    geo = scenes.Geometry(0.4, 50.0, 8.0, 32, 4)
    a, b = scenes.scene(7, 3, 2000, geo), scenes.scene(7, 3, 2000, geo)
    c = scenes.scene(8, 3, 2000, geo)
    np.testing.assert_array_equal(a.coords, b.coords)
    np.testing.assert_array_equal(a.feats, b.feats)
    assert a.num_points == c.num_points == 2000    # an exact voxel count
    assert (a.coords != c.coords).any()
    keys = scenes.pack(a.coords)
    assert (np.diff(keys) > 0).all()          # unique, lexicographic order
    assert np.abs(a.coords).max() <= geo.spatial_bound
    np.testing.assert_array_equal(scenes.unpack(keys), a.coords)


def test_delta_round_trip():
    geo = scenes.Geometry(0.4, 50.0, 8.0, 32, 4)
    s = scenes.scene(1, 0, 3000, geo)
    d = scenes.delta(np.random.default_rng(0), s, 0.1, geo)
    t = scenes.apply(s, d)
    assert t.num_points == s.num_points
    assert not np.isin(scenes.pack(d.added_coords), scenes.pack(s.coords)).any()
    assert not np.isin(scenes.pack(d.removed), scenes.pack(t.coords)).any()


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_every_seed_gets_the_same_work(seed, monkeypatch):
    cfg = H.tiny_config("minkunet-kitti-1x")
    mix = {"loop": "open", "rate": 3.0, "streams": 0}
    a = traffic.build(mix, cfg, seed, 10.0)
    b = traffic.build(mix, cfg, seed + 1, 10.0)
    assert len(a.fresh) == len(b.fresh) == 30
    def gaps(r):   # the gaps between arrivals, and the last one to the end
        return np.sort(np.diff(np.append(r.arrivals, 10.0)))
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-9)
    assert a.arrivals[0] == 0.0 and a.arrivals[-1] < 10.0
    lo, hi = cfg["voxels_per_scene"]
    # the voxels asked of each scene: the same set for every seed
    monkeypatch.setattr(traffic.scenes, "scene", lambda s, i, n, g: n)
    pa = traffic.build(mix, cfg, seed, 10.0).fresh
    pb = traffic.build(mix, cfg, seed + 1, 10.0).fresh
    assert sorted(pa) == sorted(pb) and pa != pb
    # a closed loop: every flush's backlog one permutation of one grid
    grid = traffic.size_grid(lo, hi, 4)
    rng = np.random.default_rng(seed)
    blocks = traffic.sizes(rng, lo, hi, 12, 4).reshape(3, -1)
    for blk in blocks:
        np.testing.assert_array_equal(np.sort(blk), grid)
    with pytest.raises(ValueError):
        traffic.sizes(rng, lo, hi, 10, 4)


def test_moved_pool_scenes_keep_their_maps():
    """Past the pool, a closed loop resends it moved by multiples of
    ``ALIGN``: new voxels, inside the bound, and at every stride up to
    ``ALIGN`` the same down-sampled cells, moved."""
    cfg = H.tiny_config("minkunet-kitti-1x")
    mix = {"loop": "closed", "backlog": 2, "pool": 4, "streams": 0}
    req = traffic.build(mix, cfg, 11, 10.0)
    bound = scenes.Geometry.of(cfg).spatial_bound
    assert len(req.moves) > 1 and not req.moves[0].any()
    seen = set()
    for k in range(4 * len(req.moves)):
        s, base = req.fresh_at(k), req.fresh[k % 4]
        off = req.moves[k // 4]
        assert np.abs(s.coords).max() <= bound
        np.testing.assert_array_equal(s.coords - off, base.coords)
        assert (np.diff(scenes.pack(s.coords)) > 0).all()
        for stride in (2, 4, 8, traffic.ALIGN):
            np.testing.assert_array_equal(
                R.downsample(s.coords, stride),
                R.downsample(base.coords, stride) + off)
        seen.add(scenes.pack(s.coords).tobytes())
    assert len(seen) == 4 * len(req.moves)
    with pytest.raises(RuntimeError):
        req.fresh_at(4 * len(req.moves))


def _serve_and_reference(name, precision):
    cfg = H.tiny_config(name, precision)
    ref = system.load_module("configs", cfg["reference"])
    params = system.init_params(cfg, ref, 4)
    eng = system.engine(cfg, {**cfg["serving"], "buckets": [1024]}, params, 4)
    geo = scenes.Geometry.of(cfg)
    ss = [scenes.scene(9, i, n, geo) for i, n in enumerate((200, 380, 260))]
    res = eng.serve([system.to_scene(s) for s in ss])
    return cfg, ref, params, ss, res


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_the_served_path(name):
    """Voxels exact (MinkUNet's every input voxel, CenterPoint's stride-16
    cells), rows to float32 rounding on the CPU."""
    cfg, ref, params, ss, res = _serve_and_reference(name, "fp32")
    reference = check.Reference(ref, cfg)
    for s, r in zip(ss, res):
        vox, want = reference(params, s)
        got_vox, got = check.served(r)
        np.testing.assert_array_equal(got_vox, vox)
        np.testing.assert_array_equal(
            vox, check.expected_voxels(ref, cfg["model"], s.coords)[
                np.argsort(scenes.pack(vox))])
        assert check.rel_err(got, want) < 1e-5


@pytest.mark.parametrize("name", CONFIGS)
def test_control_fails_the_limit_the_program_meets(name):
    """The configuration's own ``rel_err`` limit, at a tiny size on the
    CPU: the served bf16 path reads under it, the fp8 control over it."""
    from chipbench.control import CONTROL
    limit = system.load_json("configs", name)["limits"]["rel_err"]
    cfg, ref, params, ss, res = _serve_and_reference(name, "bf16")
    reference = check.Reference(ref, cfg)
    prog, ctrl = [], []
    for s, r in zip(ss, res):
        _, want = reference(params, s)
        prog.append(check.rel_err(check.served(r)[1], want))
        ctrl.append(check.rel_err(reference(params, s, CONTROL["bf16"])[1],
                                  want))
    assert max(prog) < limit < min(ctrl), (prog, limit, ctrl)


def _imports(path):
    tree = ast.parse(open(path).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""


def test_reference_is_independent_of_the_program():
    """The reference and what it builds on import nothing of the program:
    not its maps, hashing, plans or kernels, nor anything else of it."""
    here = system.HERE
    files = [os.path.join(here, f) for f in ("reference.py", "scenes.py",
                                             "counts.py")]
    files += [os.path.join(here, "configs", f)
              for f in os.listdir(os.path.join(here, "configs"))
              if f.endswith(".py")]
    for f in files:
        for mod in _imports(f):
            assert not mod.startswith("repro"), (f, mod)
    code = ("import sys; sys.path.insert(0, %r); "
            "from chipbench import check, reference, scenes, system; "
            "[system.load_module('configs', n) for n in "
            "('ref_minkunet', 'ref_centerpoint')]; "
            "print(sorted(m for m in sys.modules if m.startswith('repro')))"
            % H.ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


def test_work_count_by_hand():
    """One stage of CenterPoint (8 channels, 5 in) on three voxels: two
    adjacent ones at the origin and one at (5, 5, 5)."""
    ref = system.load_module("configs", "ref_centerpoint")
    model = {"in_channels": 5, "channels": [8], "sub_convs_per_stage": 2,
             "width": 1.0}
    coords = np.array([[0, 0, 0], [1, 0, 0], [5, 5, 5]], np.int32)
    peak = {"flops": {"bf16": 1e12}, "hbm_bytes_per_s": 1e9}
    w = counts.scene_work(ref, model, coords, "bf16", peak)
    # stem: 3 centres + 2 neighbour pairs; down: 3 voxels into cells
    # (0,0,0) and (4,4,4); each stride-2 sub conv: the 2 centres only
    flops = 2 * (5 * 5 * 8 + 3 * 8 * 8 + 2 * 8 * 8 + 2 * 8 * 8)
    assert w.flops == flops
    by = (3 * 5 * 2 + 27 * 5 * 8 * 2 + 3 * 8 * 2,      # stem
          3 * 8 * 2 + 8 * 8 * 8 * 2 + 2 * 8 * 2,       # down0
          2 * 8 * 2 + 27 * 8 * 8 * 2 + 2 * 8 * 2,      # sub0_0
          2 * 8 * 2 + 27 * 8 * 8 * 2 + 2 * 8 * 2)      # sub0_1
    fl = (2 * 5 * 5 * 8, 2 * 3 * 8 * 8, 2 * 2 * 8 * 8, 2 * 2 * 8 * 8)
    want = sum(max(f / 1e12, b / 1e9) for f, b in zip(fl, by))
    assert w.min_s == pytest.approx(want, rel=1e-12)
    assert w.compute_bound_s == 0.0          # all bound by bytes here


def test_unknown_device_kind_is_an_error():
    assert counts.peaks("TPU v5 lite")["flops"]["bf16"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks("TPU v4")


def test_run_refuses_a_machine_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, os.path.join(H.ROOT, "chipbench", "run.py"),
         "--workload", "mink1x.fresh.backlog", "--seed", "1",
         "--seconds", "1"], capture_output=True, text=True, env=env,
        timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_metric_readers_on_a_made_up_run():
    """Every metric file reads a run record: shares in percent, a reader
    with nothing to read returns nothing."""
    from types import SimpleNamespace as NS
    from chipbench import context, drive, xplane
    cfg = H.tiny_config("minkunet-kitti-1x", "bf16")
    ref = system.load_module("configs", cfg["reference"])
    geo = scenes.Geometry.of(cfg)
    sc = scenes.scene(1, 0, 300, geo)
    w = drive.Window(t0=0.0, t1=2.0)
    for t in range(4):
        w.requests[t] = drive.Request(t, sc, due=0.1 * t, done=1.0 + 0.2 * t)
        w.results[t] = object()
    w.phases = {"queue_wait": [1.0, 3.0], "execute": [10.0, 20.0, 30.0],
                "map": [5.0, 7.0]}
    w.stats0 = {"scene_tables": {"hits": 1, "misses": 1}}
    w.stats1 = {"scene_tables": {"hits": 4, "misses": 2}}
    w.spans = [NS(attrs={"rows": 300, "bucket": 1024}),
               NS(attrs={"rows": 700, "bucket": 1024})]
    w.traced = drive.Traced(tickets=[0, 1], logdir="")
    trace = xplane.Reduced(window_s=1.0, busy_s=0.25, chips=1,
                           device_ops=[], idle_gaps=[])
    run = context.Run(config=cfg, ref=ref, peak=counts.peaks("TPU v5 lite"),
                      window=w, setup_s=12.5, trace=trace)
    read = {n[:-3]: system.load_module("metrics", n[:-3]).read(run)
            for n in os.listdir(os.path.join(system.HERE, "metrics"))
            if n.endswith(".py")}
    work = counts.scene_work(ref, cfg["model"], sc.coords, "bf16",
                             counts.peaks("TPU v5 lite"))
    assert read["scenes_per_s"] == 2.0
    assert read["setup_s"] == 12.5
    assert read["p50_latency_ms"] == pytest.approx(1150.0)   # 1.0 .. 1.3 s
    assert read["p95_latency_ms"] == pytest.approx(1285.0)
    assert read["queue_wait_ms.open"] == 2.0
    assert read["execute_ms"] == read["execute_ms.open"] == 20.0
    assert read["map_ms_per_scene"] == 3.0
    assert read["scene_hit_share"] == pytest.approx(75.0)
    assert read["pad_row_share"] == pytest.approx(100 * (1 - 1000 / 2048))
    assert read["device_idle_share"] == pytest.approx(75.0)
    assert read["step_mfu"] == pytest.approx(100 * 2 * work.flops / 197e12)
    assert read["conv_roofline"] == pytest.approx(100 * 2 * work.min_s / 0.25)
    assert 0 < read["conv_roofline"] < 100 and 0 < read["step_mfu"] < 100
    run.trace, w.spans, w.phases = None, [], {}
    for name in ("step_mfu", "conv_roofline", "device_idle_share",
                 "pad_row_share", "execute_ms", "map_ms_per_scene"):
        assert system.load_module("metrics", name).read(run) is None
