"""Cylinder3D's mapping and plan on the CPU: dilated pooling and its
transpose against brute-force numpy, asymmetric maps as column subsets
against direct searches, the overflow guard, composed batch maps against a
fresh batch build, and plan serialization (tuple kernels, named-value ops,
and version-2 plans written before them)."""
from __future__ import annotations

import itertools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import kmap as km
from repro.core import plan as planlib
from repro.core.plan import NetworkPlan
from repro.core.sparse_tensor import (INVALID_COORD, SparseTensor, axis_strides,
                                      stride_mul)
from repro.models import cylinder3d, minkunet

SHAPES = [(1, 3, 3), (3, 1, 3), (3, 1, 1), (1, 3, 1), (1, 1, 3)]
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def _tensor(coords: np.ndarray, cap: int, stride=1, bound: int = 64):
    """A batched tensor of (b, x, y, z) rows, padded to ``cap``."""
    n = coords.shape[0]
    c = np.full((cap, 4), int(INVALID_COORD), np.int32)
    c[:n] = coords
    return SparseTensor(coords=jnp.asarray(c), feats=jnp.zeros((cap, 1)),
                        num_valid=jnp.asarray(n, jnp.int32), stride=stride,
                        batch_bound=2, spatial_bound=bound)


def _cloud(rng, n: int, t, extent: int = 6):
    """``n`` distinct rows over two batches, each coordinate a multiple of
    the tensor stride ``t`` (per axis)."""
    t = np.asarray(axis_strides(t))
    rows = set()
    while len(rows) < n:
        b = int(rng.integers(0, 2))
        xyz = rng.integers(-extent, extent, size=3) * t
        rows.add((b, *map(int, xyz)))
    return np.array(sorted(rows), np.int32)


def _offsets(shape):
    offs = np.array(list(itertools.product(
        *[range(-(k // 2), k // 2 + 1) for k in shape])), np.int32)
    return offs[np.argsort(np.abs(offs).sum(1), kind="stable")]


def _brute_map(out_rows, in_rows, shape, scale) -> np.ndarray:
    """(n_out, volume): index of the input row at ``o + δ·scale``, or -1."""
    index = {tuple(r): i for i, r in enumerate(in_rows)}
    d = _offsets(shape) * np.asarray(scale)
    return np.array([[index.get((o[0], *(o[1:] + dk)), -1) for dk in d]
                     for o in out_rows], np.int32).reshape(len(out_rows), -1)


def _brute_pool(rows, t, s) -> np.ndarray:
    """Cells ``c - δ·t`` that are multiples of ``t·s``, sorted."""
    t = np.asarray(axis_strides(t))
    ts = t * np.asarray(axis_strides(s))
    cells = {(r[0], *(r[1:] - d * t)) for r in rows for d in _offsets((3, 3, 3))
             if ((r[1:] - d * t) % ts == 0).all()}
    return np.array(sorted(cells), np.int32)


POOL_CASES = [(1, 2), (4, (2, 2, 1)), ((8, 8, 4), (2, 2, 1))]


@pytest.mark.parametrize("t,stride", POOL_CASES)
def test_dilated_pool_and_transpose_match_brute_force(t, stride):
    rng = np.random.default_rng(3)
    rows = _cloud(rng, 70, t)
    cap = 512
    x = _tensor(rows, cap, stride=t)
    fwd = km.build_kmap(x, 3, stride, cache=km.MapCache.for_tensor(x),
                        dilate=True)
    n = int(fwd.n_out)
    want = _brute_pool(rows, t, stride)
    np.testing.assert_array_equal(np.asarray(fwd.out_coords)[:n], want)
    assert fwd.out_stride == stride_mul(t, stride)
    np.testing.assert_array_equal(np.asarray(fwd.m_out)[:n],
                                  _brute_map(want, rows, (3, 3, 3), axis_strides(t)))
    assert (np.asarray(fwd.m_out)[n:] == -1).all()
    inv = km.transpose_kmap(fwd, x)
    np.testing.assert_array_equal(
        np.asarray(inv.m_out)[:len(rows)],
        _brute_map(rows, want, (3, 3, 3), -np.asarray(axis_strides(t))))


@pytest.mark.parametrize("shape", SHAPES)
def test_asymmetric_map_is_a_column_subset_and_a_direct_search(shape):
    rng = np.random.default_rng(5)
    t = (4, 4, 2)
    rows = _cloud(rng, 90, t, extent=4)
    x = _tensor(rows, 256, stride=t)
    full = km.build_kmap(x, 3)
    cut = km.slice_kmap(full, shape)
    direct = km.build_kmap(x, shape)
    for f in ("m_out", "ws_in", "ws_out", "ws_count", "bitmask", "n_out"):
        np.testing.assert_array_equal(getattr(cut, f), getattr(direct, f), f)
    assert cut.kernel_size == direct.kernel_size == shape
    np.testing.assert_array_equal(np.asarray(direct.m_out)[:len(rows)],
                                  _brute_map(rows, rows, shape, t))


def test_kernel_offsets_keep_the_cubic_order():
    np.testing.assert_array_equal(km.kernel_offsets(3, 3),
                                  km.kernel_offsets((3, 3, 3), 3))
    np.testing.assert_array_equal(km.kernel_offsets(2, 3),
                                  km.kernel_offsets((2, 2, 2), 3))
    for shape in SHAPES:
        cols = km.offset_columns(shape, 3, 3)
        assert (np.diff(cols) > 0).all()      # sub-kernel order = cube order
        np.testing.assert_array_equal(km.kernel_offsets(3, 3)[cols],
                                      km.kernel_offsets(shape, 3))


def test_an_overflowing_level_raises():
    """Isolated voxels at odd coordinates each reach 8 cells of the stride-2
    lattice: the pooled level needs more rows than its capacity, and the
    guard raises instead of dropping cells."""
    rows = np.array([(0, 4 * i + 1, 4 * j + 1, 1) for i in range(4)
                     for j in range(4)], np.int32)
    x = _tensor(rows, 64)
    fwd = km.build_kmap(x, 3, 2, dilate=True)
    assert int(fwd.n_out) == 16 * 8 > fwd.capacity
    with pytest.raises(ValueError, match="capacity"):
        km.check_capacity({("down", 1): fwd})
    # the same scene's build for serving raises too
    specs = cylinder3d.map_specs()
    st = _tensor(rows[:, :], 64)
    arrays, keys, order = planlib.scene_entry_arrays(specs, st)
    with pytest.raises(ValueError, match="capacity"):
        planlib.scene_entry_from_arrays(specs, arrays, len(rows), keys, order)


def _slices_counted() -> int:
    return obs.get_tracer().snapshot()["counters"].get("kmap.offset_slices",
                                                       0)


def _dense_scene(rng, n, side=7):
    pts = set()
    while len(pts) < n:
        pts.add(tuple(int(v) for v in rng.integers(-side, side, size=3)))
    return np.array(sorted(pts), np.int32)


@pytest.mark.parametrize("sizes,rungs,bucket", [
    ((300, 420), (512, 512), 1024),
    ((420, 300), (1024, 512), 1024),     # two scene rungs in one bucket
    ((300,), (512,), 512),               # a single-scene batch
])
def test_composed_maps_equal_a_fresh_batch_build(sizes, rungs, bucket):
    """Per-scene map stacks composed on the device into a batch (dilated
    levels, their transposes and the column subsets), op by op and jitted
    as the engine runs it, equal the batch's own build."""
    rng = np.random.default_rng(11)
    specs = cylinder3d.map_specs("composed")
    scenes_ = [_dense_scene(rng, n) for n in sizes]
    entries = []
    for sc, rung in zip(scenes_, rungs):
        rows = np.concatenate([np.zeros((len(sc), 1), np.int32), sc], 1)
        arrays, keys, order = planlib.scene_entry_arrays(specs,
                                                         _tensor(rows, rung))
        entries.append(planlib.scene_entry_from_arrays(specs, arrays,
                                                       len(sc), keys, order))
    batch = np.concatenate([np.concatenate(
        [np.full((len(sc), 1), b, np.int32), sc], 1)
        for b, sc in enumerate(scenes_)])
    before = _slices_counted()
    fresh = planlib.build_maps_from_specs(specs, _tensor(batch, bucket))
    # every column-subset map counted once, as the batch build cuts it
    assert _slices_counted() - before == 11 == sum(
        ms.kind == "slice" for ms in specs)
    jitted = dict(place=jax.jit(lambda *a: km.compose_place(specs, *a)),
                  finish=jax.jit(lambda *a: km.compose_finish(specs, *a)))
    for composed in (km.compose_kmaps(specs, entries, bucket),
                     km.compose_kmaps(specs, entries, bucket, **jitted)):
        assert set(composed) == set(fresh)
        for ref in fresh:
            for f in ("m_out", "out_coords", "n_out", "ws_in", "ws_out",
                      "ws_count", "bitmask"):
                np.testing.assert_array_equal(getattr(composed[ref], f),
                                              getattr(fresh[ref], f),
                                              (ref, f))
            assert composed[ref].out_stride == fresh[ref].out_stride
            assert composed[ref].kernel_size == fresh[ref].kernel_size
    # a dilated level larger than the bucket declines composition
    assert km.compose_kmaps(specs, entries,
                            max(e.sizes[2] for e in entries) - 1) is None


def test_plan_with_tuple_kernels_and_named_ops_round_trips():
    nplan = cylinder3d.network_plan(cylinder3d.Cylinder3DConfig(init_size=8),
                                    precision="bf16")
    loaded = NetworkPlan.from_dict(json.loads(json.dumps(nplan.to_dict())))
    assert loaded == nplan
    kinds = {op[0] for op in nplan.ops}
    assert {"save", "load", "add", "mul", "concat", "conv"} <= kinds
    assert {lp.spec.kernel_size for lp in loaded.layers} >= set(SHAPES)
    assert {lp.act for lp in loaded.layers} == {"leaky_relu", "sigmoid",
                                                "none"}
    assert any(ms.kind == "slice" for ms in loaded.map_specs)
    assert len(nplan.layers) == 48


def test_a_version2_plan_still_loads_and_runs_bit_identically():
    """A MinkUNet plan written with the stack ops (push/concat/res_begin/
    res_end) and per-layer ReLU flags runs as the plan compiled now."""
    with open(os.path.join(FIXTURES, "minkunet_plan_v2.json")) as f:
        old = NetworkPlan.from_dict(json.load(f))
    from repro.configs.minkunet_kitti import CONFIG_BENCH
    new = minkunet.network_plan(CONFIG_BENCH)
    assert [(lp.name, lp.spec, lp.act) for lp in old.layers] == \
        [(lp.name, lp.spec, lp.act) for lp in new.layers]
    assert old.map_specs == new.map_specs
    rng = np.random.default_rng(2)
    sc = _dense_scene(rng, 300, side=20)
    rows = np.concatenate([np.zeros((len(sc), 1), np.int32), sc], 1)
    st = _tensor(rows, 512, bound=64)
    st = st.replace_feats(jnp.asarray(rng.normal(size=(512, 4)), jnp.float32))
    params = minkunet.init_params(CONFIG_BENCH, jax.random.PRNGKey(0))
    maps = new.build_maps(st)
    a = old.apply(params, st, maps, bn_mode="affine")
    b = new.apply(params, st, maps, bn_mode="affine")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
