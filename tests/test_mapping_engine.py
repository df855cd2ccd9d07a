"""Packed-key mapping engine: equivalence with brute-force numpy references
(the ``engine="legacy"`` multi-word oracle was deleted after its A/B window
closed — see ROADMAP), cross-layer table caching, dgrad capacity, and
bitmask dtype invariants.

Property tests use ``hypothesis`` when installed (requirements-dev.txt) and
fall back to a deterministic sample otherwise (``conftest.property_test``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import property_test

from repro import obs
from repro.core import dataflows as df
from repro.core import hashing
from repro.core import kmap as km
from repro.core.sparse_conv import sparse_conv_apply
from repro.core.sparse_tensor import INVALID_COORD, SparseTensor, make_sparse_tensor

KMAP_FIELDS = ("m_out", "out_coords", "n_out", "ws_in", "ws_out", "ws_count",
               "bitmask")


def random_tensor(seed, n=100, cap=128, channels=8, extent=8, batch=1, d=3,
                  lo=0, bounds=False):
    """Random unique voxel cloud; ``lo < 0`` exercises negative coordinates,
    ``batch > 1`` duplicate spatial coords across batches."""
    rng = np.random.default_rng(seed)
    coords = rng.integers(lo, extent, size=(n, d))
    b = rng.integers(0, batch, size=(n, 1))
    coords = np.unique(np.concatenate([b, coords], axis=1), axis=0)
    n = coords.shape[0]
    feats = rng.standard_normal((cap, channels)).astype(np.float32)
    pad = np.zeros((cap - n, d + 1), np.int32)
    kw = dict(batch_bound=batch, spatial_bound=max(abs(lo), extent)) if bounds else {}
    return make_sparse_tensor(jnp.asarray(np.concatenate([coords, pad])),
                              jnp.asarray(feats), n, **kw)


def assert_kmaps_equal(a: km.KernelMap, b: km.KernelMap):
    for f in KMAP_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


# ---------------------------------------------------------------------------
# Brute-force numpy references (the oracles the engine is tested against)
# ---------------------------------------------------------------------------

def np_bitmask(hits: np.ndarray) -> np.ndarray:
    """Reference for km._bitmask: exact for KD ≤ 31, composite above."""
    kd = hits.shape[-1]
    if kd <= 31:
        return (hits * (1 << np.arange(kd))).sum(axis=-1).astype(np.int32)
    pop = hits.sum(axis=-1).astype(np.int64)
    low = (hits[..., :24] * (1 << np.arange(24))).sum(axis=-1).astype(np.int64)
    return ((pop << 24) | low).astype(np.int32)


def np_build_kmap(stx, kernel: int, stride: int = 1, out_capacity=None) -> dict:
    """O(N·K^D) dict-based reference for build_kmap's full contract:
    output-stationary map, lex-sorted strided unique coords, hits-first
    pair lists, bitmasks, and all the padding conventions."""
    coords = np.asarray(stx.coords)
    n_valid = int(stx.num_valid)
    t = stx.stride
    cap_in = coords.shape[0]
    offs = np.asarray(km.kernel_offsets(kernel, stx.ndim_space))
    kd = offs.shape[0]
    lut = {tuple(c): i for i, c in enumerate(coords[:n_valid])}

    if stride == 1:
        out_coords = coords.copy()
        n_out = n_valid
        cap_out = out_capacity or cap_in
        out_coords = out_coords[:cap_out]
        out_stride = t
    else:
        out_stride = t * stride
        grid = coords[:n_valid].copy()
        grid[:, 1:] = (grid[:, 1:] // out_stride) * out_stride
        uniq = np.unique(grid, axis=0)        # lexicographic ascending
        n_out = uniq.shape[0]
        cap_out = out_capacity or cap_in
        out_coords = np.full((cap_out, coords.shape[1]), int(INVALID_COORD),
                             np.int32)
        out_coords[:min(n_out, cap_out)] = uniq[:cap_out]
        n_out = min(n_out, cap_out)

    m_out = -np.ones((cap_out, kd), np.int32)
    for i in range(n_out):
        c = out_coords[i]
        for k, off in enumerate(offs):
            q = (c[0],) + tuple(c[1:] + off * t)
            m_out[i, k] = lut.get(q, -1)

    ws_in = -np.ones((kd, cap_out), np.int32)
    ws_out = -np.ones((kd, cap_out), np.int32)
    ws_count = np.zeros((kd,), np.int32)
    for k in range(kd):
        rows = np.nonzero(m_out[:, k] >= 0)[0]
        ws_count[k] = len(rows)
        ws_in[k, :len(rows)] = m_out[rows, k]
        ws_out[k, :len(rows)] = rows

    bm = np.zeros((cap_out,), np.int32)
    bm[:n_out] = np_bitmask(m_out[:n_out] >= 0)
    return dict(m_out=m_out, out_coords=out_coords.astype(np.int32),
                n_out=np.int32(n_out), ws_in=ws_in, ws_out=ws_out,
                ws_count=ws_count, bitmask=bm)


def assert_kmap_matches_ref(kmap: km.KernelMap, ref: dict):
    for f in KMAP_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(kmap, f)), ref[f],
                                      err_msg=f)


# ---------------------------------------------------------------------------
# Packed lookup ≡ brute-force dict lookup (all three key-spec modes)
# ---------------------------------------------------------------------------

def _bisect_count() -> int:
    return obs.get_tracer().snapshot()["counters"].get("kmap.search_bisect", 0)


def _spec_of_kind(kind, batch, lo, extent):
    """One spec per packing mode: single int32 word, packed [hi, lo] pair,
    and the raw no-range-limit fallback (default when bounds are unknown)."""
    if kind == "one":
        spec = hashing.key_spec_for(3, batch_bound=batch,
                                    spatial_bound=max(abs(lo), extent))
        assert spec.words == 1 and not spec.raw
    elif kind == "two":
        spec = hashing.key_spec_for(3, batch_bound=500, spatial_bound=12000)
        assert spec.words == 2 and not spec.raw
    else:
        spec = hashing.key_spec_for(3)  # unknown bounds → raw columns
        assert spec.raw and spec.words == 4
    return spec


@property_test(
    "seed,extent,lo,batch,spec_kind",
    cases=[(0, 8, 0, 1, "one"), (1, 16, -8, 1, "one"), (2, 6, -5, 3, "one"),
           (3, 20, 0, 2, "two"), (4, 10, -12, 4, "two"), (5, 3, -2, 1, "two"),
           (6, 18, -9, 3, "raw"), (7, 5, 0, 1, "raw"), (8, 12, -12, 4, "raw")],
    strategies=lambda st: dict(seed=st.integers(0, 10_000),
                               extent=st.integers(3, 20),
                               lo=st.integers(-12, 0),
                               batch=st.integers(1, 4),
                               spec_kind=st.sampled_from(["one", "two", "raw"])),
    max_examples=24)
def test_property_packed_lookup_matches_bruteforce(seed, extent, lo, batch,
                                                   spec_kind):
    stx = random_tensor(seed, n=80, cap=96, extent=extent, lo=lo, batch=batch)
    spec = _spec_of_kind(spec_kind, batch, lo, extent)
    packed = hashing.CoordTable.build(stx.coords, stx.valid_mask, spec)
    rng = np.random.default_rng(seed + 1)
    # half perturbed copies of table rows (some present), half random
    q1 = np.asarray(stx.coords)[rng.integers(0, stx.capacity, 64)]
    q1 = q1 + rng.integers(-1, 2, size=q1.shape)
    q2 = np.concatenate([rng.integers(0, batch, (64, 1)),
                         rng.integers(lo - 2, extent + 2, (64, 3))], axis=1)
    q = np.concatenate([q1, q2]).astype(np.int32)
    lut = {tuple(c): i for i, c in
           enumerate(np.asarray(stx.coords)[: int(stx.num_valid)])}
    ref = np.asarray([lut.get(tuple(row), -1) for row in q], np.int32)
    before = _bisect_count()
    np.testing.assert_array_equal(np.asarray(packed.lookup(jnp.asarray(q))), ref)
    # one-word keys join; pair and raw keys bisect, and say so
    assert (_bisect_count() - before) == (spec_kind != "one")


def test_pack_unpack_roundtrip_with_negatives():
    spec = hashing.key_spec_for(3, batch_bound=4, spatial_bound=30)
    rng = np.random.default_rng(0)
    coords = np.concatenate([rng.integers(0, 4, (200, 1)),
                             rng.integers(-30, 31, (200, 3))], axis=1)
    keys = hashing.pack_keys(jnp.asarray(coords, jnp.int32), spec)
    back = hashing.unpack_keys(keys, spec)
    np.testing.assert_array_equal(np.asarray(back), coords)
    # packing is order-isomorphic to lexicographic row order
    order_packed = np.asarray(hashing.sort_keys(keys)[0])
    order_lex = np.asarray(hashing.lex_argsort(jnp.asarray(coords, jnp.int32)))
    np.testing.assert_array_equal(np.lexsort(coords.T[::-1]), order_lex)
    np.testing.assert_array_equal(coords[order_packed], coords[order_lex])


def test_undeclared_bounds_have_no_range_limit():
    """Regression: a coordinate far outside any packed bit budget, on a
    tensor with NO declared bounds, must still appear in the kernel map
    (the raw-spec fallback keeps the seed's no-range-limit contract)."""
    coords = np.zeros((8, 4), np.int32)
    coords[:, 1] = np.arange(8) * 20000          # |x| up to 140000
    coords[:, 2] = -70000 + np.arange(8) * 100
    stx = make_sparse_tensor(jnp.asarray(coords), jnp.ones((8, 4)), 8)
    assert stx.spatial_bound == 0  # nothing declared
    for kernel, stride in [(3, 1), (2, 2)]:
        assert_kmap_matches_ref(km.build_kmap(stx, kernel, stride),
                                np_build_kmap(stx, kernel, stride))
    # self-hit at the center offset for every valid row
    m = np.asarray(km.build_kmap(stx, 3, 1).m_out)
    np.testing.assert_array_equal(m[:8, 0], np.arange(8))


def test_huge_declared_bounds_fall_back_instead_of_crashing():
    spec = hashing.key_spec_for(3, batch_bound=2, spatial_bound=20000)
    assert spec.raw  # too wide for two words → raw, not an AssertionError
    stx = make_sparse_tensor(
        jnp.asarray([[0, 20000, -20000, 3], [1, 5, 5, 5]], jnp.int32),
        jnp.ones((2, 4)), 2, batch_bound=2, spatial_bound=20000)
    assert_kmap_matches_ref(km.build_kmap(stx, 2, 2), np_build_kmap(stx, 2, 2))


def test_no_valid_key_aliases_pad_sentinel():
    """Regression: a 31-bit single-word layout would pack the maximal
    in-field row to exactly int32 max (the PAD sentinel), silently dropping
    it from strided dedup.  Word budgets are capped at 30 bits, so this spec
    must spill to two words and the row must survive a downsample."""
    spec = hashing.key_spec_for(3, batch_bound=2, spatial_bound=447)
    assert spec.total_bits == 31 and spec.words == 2
    coords = jnp.asarray([[1, 511, 511, 511], [0, 0, 0, 0]], jnp.int32)
    keys = hashing.pack_keys(coords, spec, valid=jnp.ones((2,), bool))
    assert (np.asarray(keys) != np.iinfo(np.int32).max).any(axis=-1).all()
    table = hashing.CoordTable.build(coords, jnp.ones((2,), bool), spec)
    np.testing.assert_array_equal(np.asarray(table.lookup(coords)), [0, 1])
    uniq = km._unique_from_keys(table, 2, 2)
    assert uniq is not None and int(uniq[1]) == 2


def test_out_of_range_queries_miss():
    spec = hashing.key_spec_for(3, batch_bound=1, spatial_bound=10)
    stx = random_tensor(0, extent=8)
    table = hashing.CoordTable.build(stx.coords, stx.valid_mask, spec)
    q = jnp.asarray([[0, 1000, 0, 0], [0, 0, -1000, 0], [2, 0, 0, 0],
                     [0, 0x3FFFFFF, 0x3FFFFFF, 0x3FFFFFF]], jnp.int32)
    assert (np.asarray(table.lookup(q)) == -1).all()


# ---------------------------------------------------------------------------
# Sort-merge join lookup and sort compaction ≡ numpy brute force
# ---------------------------------------------------------------------------

@property_test(
    "seed,rows,pad,extent,lo,batch,queries",
    cases=[(0, 80, 16, 8, 0, 1, "mixed"), (1, 60, 36, 16, -8, 3, "dupes"),
           (2, 50, 14, 6, -5, 2, "all_miss"), (3, 1, 0, 4, -2, 1, "dupes"),
           (4, 0, 16, 8, 0, 1, "mixed"), (5, 0, 0, 8, 0, 1, "mixed"),
           (6, 40, 0, 10, -12, 4, "dupes"), (7, 1, 5, 5, -3, 2, "mixed")],
    strategies=lambda st: dict(seed=st.integers(0, 10_000),
                               rows=st.integers(0, 60),
                               pad=st.integers(0, 20),
                               extent=st.integers(4, 20),
                               lo=st.integers(-12, 0),
                               batch=st.integers(1, 4),
                               queries=st.sampled_from(
                                   ["mixed", "dupes", "all_miss"])),
    max_examples=24)
def test_property_join_lookup_matches_bruteforce(seed, rows, pad, extent, lo,
                                                 batch, queries):
    """One-word lookups take the sort-merge join, and it answers as a dict
    does: duplicate and MISS queries, PAD table rows (``pad`` of them),
    all-miss query sets, 1-row, all-PAD and n = 0 tables, negative
    coordinates and several batches."""
    rng = np.random.default_rng(seed)
    spec = hashing.key_spec_for(3, batch_bound=batch,
                                spatial_bound=max(abs(lo), extent) + 8)
    assert spec.words == 1 and not spec.raw
    cand = np.concatenate([rng.integers(0, batch, (4 * rows + 4, 1)),
                           rng.integers(lo, extent, (4 * rows + 4, 3))], 1)
    cand = np.unique(cand, axis=0)
    table_rows = cand[rng.permutation(len(cand))[:rows]]
    rows = len(table_rows)
    coords = np.concatenate([table_rows, np.full((pad, 4), int(INVALID_COORD))])
    valid = np.arange(rows + pad) < rows
    table = hashing.CoordTable.build(jnp.asarray(coords, jnp.int32),
                                     jnp.asarray(valid), spec)
    miss = np.full((8, 4), int(INVALID_COORD))
    if queries == "dupes" and rows:
        q = np.concatenate([table_rows[rng.integers(0, rows, 96)], miss])
    elif queries == "all_miss":
        far = np.concatenate([rng.integers(0, batch, (64, 1)),
                              rng.integers(extent + 1, extent + 8, (64, 3))], 1)
        q = np.concatenate([far, miss])
    else:
        near = coords[rng.integers(0, max(len(coords), 1), 48)] \
            if len(coords) else np.zeros((0, 4), np.int64)
        near = near + rng.integers(-1, 2, size=near.shape)
        rand = np.concatenate([rng.integers(0, batch, (48, 1)),
                               rng.integers(lo - 2, extent + 2, (48, 3))], 1)
        q = np.concatenate([near, rand, miss])
    q = q.astype(np.int32)
    lut = {tuple(c): i for i, c in enumerate(table_rows)}
    ref = np.asarray([lut.get(tuple(row), -1) for row in q], np.int32)
    if queries == "all_miss":
        assert (ref == -1).all()
    before = _bisect_count()
    got = np.asarray(table.lookup(jnp.asarray(q)))
    assert _bisect_count() == before
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("seed,cap,kd", [(0, 64, 27), (1, 1, 8), (2, 200, 8),
                                         (3, 33, 3)])
def test_compact_ws_matches_per_column_nonzero(seed, cap, kd):
    """The sort compaction gives each column's hits in row order, then -1,
    bit for bit, all-hit and no-hit columns included."""
    rng = np.random.default_rng(seed)
    m_out = np.where(rng.random((cap, kd)) < 0.4,
                     rng.integers(0, 1000, (cap, kd)), -1).astype(np.int32)
    m_out[:, 0] = rng.integers(0, 1000, cap)   # every row a hit
    m_out[:, -1] = -1                          # no hit
    ws_in, ws_out, ws_count = km._compact_ws(jnp.asarray(m_out))
    ref_in = np.full((kd, cap), -1, np.int32)
    ref_out = np.full((kd, cap), -1, np.int32)
    ref_count = np.zeros((kd,), np.int32)
    for k in range(kd):
        rows = np.nonzero(m_out[:, k] >= 0)[0]
        ref_count[k] = len(rows)
        ref_in[k, :len(rows)] = m_out[rows, k]
        ref_out[k, :len(rows)] = rows
    np.testing.assert_array_equal(np.asarray(ws_in), ref_in)
    np.testing.assert_array_equal(np.asarray(ws_out), ref_out)
    np.testing.assert_array_equal(np.asarray(ws_count), ref_count)


def _search_whiles(hlo: str) -> list:
    """The ``while`` ops of a compiled module whose ``op_name`` lies in a
    ``search`` scope."""
    return [ln for ln in hlo.splitlines()
            if " while(" in ln and "/search/" in ln]


@pytest.mark.parametrize("arch", ["minkunet", "centerpoint"])
def test_scene_builds_search_without_while_loops(arch):
    """The engine's scene builder and delta builder (``scene_entry_arrays``
    on a fresh scene, and on adopted root and level tables) compile with no
    ``while`` op in any ``search`` scope, and no lookup takes the bisect."""
    from repro.configs import centerpoint_waymo, minkunet_kitti
    from repro.core.plan import scene_entry_arrays
    from repro.models import centerpoint, minkunet
    specs = (minkunet.declare(minkunet_kitti.CONFIG_1X) if arch == "minkunet"
             else centerpoint.declare(centerpoint_waymo.CONFIG)).map_specs
    cap, n = 256, 200
    rng = np.random.default_rng(0)
    cells = np.unique(rng.integers(-40, 40, (2 * n, 3)), axis=0)[:n]
    coords = np.full((cap, 4), int(INVALID_COORD), np.int32)
    coords[:n, 0] = 0
    coords[:n, 1:] = cells
    st = SparseTensor(coords=jnp.asarray(coords),
                      feats=jnp.zeros((cap, 1), jnp.float32),
                      num_valid=jnp.asarray(n, jnp.int32), stride=1,
                      batch_bound=2, spatial_bound=147)
    spec = hashing.key_spec_for(3, 2, 147)
    assert spec.words == 1
    root = hashing.CoordTable.build(st.coords, st.valid_mask, spec)
    downs = [ms.tensor_stride * 2 for ms in specs if ms.kind == "down"]
    lkeys = {s: root.sorted_keys for s in downs}
    lns = {s: jnp.asarray(n, jnp.int32) for s in downs}

    def delta_build(st, keys, order, lkeys, lns):
        tables = {s: (lkeys[s], None, lns[s]) for s in lkeys}
        return scene_entry_arrays(
            specs, st, root_table=hashing.CoordTable(spec, keys, order),
            tables=tables)

    before = _bisect_count()
    fresh = jax.jit(lambda st: scene_entry_arrays(specs, st)).lower(st)
    delta = jax.jit(delta_build).lower(st, root.sorted_keys, root.order,
                                       lkeys, lns)
    assert _bisect_count() == before
    for lowered in (fresh, delta):
        hlo = lowered.compile().as_text()
        assert "/search/join/" in hlo and "/search/compact/" in hlo
        assert _search_whiles(hlo) == []


# ---------------------------------------------------------------------------
# build_kmap ≡ numpy reference, with and without the MapCache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("kernel,stride", [(3, 1), (2, 2), (3, 2)])
@pytest.mark.parametrize("bounds", [False, True])
def test_build_kmap_matches_bruteforce(seed, kernel, stride, bounds):
    stx = random_tensor(seed, extent=16, lo=-4, batch=2, bounds=bounds)
    assert_kmap_matches_ref(km.build_kmap(stx, kernel, stride),
                            np_build_kmap(stx, kernel, stride))


def test_cached_table_reuse_and_adoption():
    stx = random_tensor(3, extent=16, bounds=True)
    cache = km.MapCache.for_tensor(stx)
    sub = km.build_kmap(stx, 3, 1, cache=cache)
    down = km.build_kmap(stx, 2, 2, cache=cache)
    assert_kmap_matches_ref(sub, np_build_kmap(stx, 3, 1))
    assert_kmap_matches_ref(down, np_build_kmap(stx, 2, 2))
    # the downsample adopted its output table: the child submanifold map
    # must come out identical to a from-scratch build
    cur = SparseTensor(coords=down.out_coords,
                       feats=jnp.zeros((down.capacity, 1)),
                       num_valid=down.n_out, stride=down.out_stride)
    child = km.build_kmap(cur, 3, 1, cache=cache)
    assert_kmap_matches_ref(child, np_build_kmap(cur, 3, 1))
    # exactly two tables live in the cache: stx's and the adopted child's
    assert len(cache._tables) == 2
    assert cache.hits >= 2   # the down reused stx's table; the child hit too


def test_transpose_kmap_equivalent_under_cached_table():
    stx = random_tensor(4, extent=16, bounds=True)
    cache = km.MapCache.for_tensor(stx)
    fwd_cached = km.build_kmap(stx, 2, 2, cache=cache)
    fwd_fresh = km.build_kmap(stx, 2, 2)
    assert_kmaps_equal(km.transpose_kmap(fwd_cached, stx),
                       km.transpose_kmap(fwd_fresh, stx))


def test_build_kmap_inside_jit_with_cache():
    stx = random_tensor(5, extent=16, bounds=True)

    @jax.jit
    def build():
        cache = km.MapCache.for_tensor(stx)
        a = km.build_kmap(stx, 3, 1, cache=cache)
        b = km.build_kmap(stx, 2, 2, cache=cache)
        return a, b

    a, b = build()
    assert_kmap_matches_ref(a, np_build_kmap(stx, 3, 1))
    assert_kmap_matches_ref(b, np_build_kmap(stx, 2, 2))


@pytest.mark.parametrize("with_up", [False, True])
def test_map_program_matches_map_by_map_builds(with_up):
    """A map program searches all its maps in batched joins and one
    compaction; each map equals a lone ``build_kmap`` on the same level."""
    from repro.core.plan import build_maps_from_specs, pyramid_map_specs
    stx = random_tensor(7, n=200, cap=256, extent=24, lo=-24, batch=2,
                        bounds=True)
    specs = pyramid_map_specs(3, with_up=with_up)
    maps = build_maps_from_specs(specs, stx)
    assert list(maps) == [ms.ref for ms in specs]
    cur = stx
    for ms in specs:
        if ms.kind == "up":
            continue
        one = km.build_kmap(cur, ms.kernel_size,
                            1 if ms.kind == "sub" else ms.stride)
        assert_kmaps_equal(maps[ms.ref], one)
        if ms.kind == "down":
            cur = SparseTensor(coords=one.out_coords, feats=jnp.zeros(
                (one.capacity, 1)), num_valid=one.n_out,
                stride=one.out_stride, batch_bound=stx.batch_bound,
                spatial_bound=stx.spatial_bound)


def test_lookup_batched_matches_lookup_keys():
    """Batched lookups give each pair what its own ``lookup_keys`` gives:
    tables shared by several query sets, query sets of other lengths padded
    into one batched join, an n = 0 table and a two-word table among them."""
    one = hashing.key_spec_for(3, batch_bound=2, spatial_bound=30)
    two = hashing.key_spec_for(3, batch_bound=500, spatial_bound=12000)
    tables = []
    for seed, spec, cap in [(0, one, 96), (1, one, 96), (2, one, 40),
                            (3, two, 96)]:
        stx = random_tensor(seed, n=cap - 16, cap=cap, extent=10, lo=-10,
                            batch=2)
        tables.append((stx, hashing.CoordTable.build(stx.coords,
                                                     stx.valid_mask, spec)))
    empty = hashing.CoordTable.build(jnp.zeros((0, 4), jnp.int32),
                                     jnp.zeros((0,), bool), one)
    rng = np.random.default_rng(9)
    requests = []
    for (stx, table), m in zip(tables + tables[:2], [64, 128, 32, 50, 17, 9]):
        q = np.asarray(stx.coords)[rng.integers(0, stx.capacity, m)]
        q = q + rng.integers(-1, 2, size=q.shape)
        requests.append((table, hashing.pack_keys(jnp.asarray(q, jnp.int32),
                                                  table.spec, query=True)))
    requests.append((empty, requests[0][1]))
    got = hashing.lookup_batched(requests)
    for (table, q), g in zip(requests, got):
        np.testing.assert_array_equal(np.asarray(g),
                                      np.asarray(table.lookup_keys(q)))


# ---------------------------------------------------------------------------
# All dataflows bit-identical on cached-table maps vs fresh maps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel,stride", [(3, 1), (2, 2)])
def test_dataflows_bit_identical_on_cached_maps(kernel, stride):
    stx = random_tensor(6, n=60, cap=64, channels=4, extent=10, bounds=True)
    cache = km.MapCache.for_tensor(stx)
    cached = km.build_kmap(stx, kernel, stride, cache=cache)
    fresh = km.build_kmap(stx, kernel, stride)
    assert_kmaps_equal(cached, fresh)
    kd = kernel ** 3
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (kd, 4, 8)) * 0.3
    dy = jax.random.normal(key, (fresh.capacity, 8))
    for flow in df.DATAFLOWS:
        cfg = df.DataflowConfig(flow)
        y_new = df.sparse_conv_forward(stx.feats, w, cached, cfg)
        y_old = df.sparse_conv_forward(stx.feats, w, fresh, cfg)
        np.testing.assert_array_equal(np.asarray(y_new), np.asarray(y_old))
        dx_new = df.sparse_conv_dgrad(dy, w, cached, cfg, in_capacity=stx.capacity)
        dx_old = df.sparse_conv_dgrad(dy, w, fresh, cfg, in_capacity=stx.capacity)
        np.testing.assert_array_equal(np.asarray(dx_new), np.asarray(dx_old))
        dw_new = df.sparse_conv_wgrad(stx.feats, dy, cached, cfg)
        dw_old = df.sparse_conv_wgrad(stx.feats, dy, fresh, cfg)
        np.testing.assert_array_equal(np.asarray(dw_new), np.asarray(dw_old))


# ---------------------------------------------------------------------------
# dgrad accumulator capacity (regression: out_capacity != cap_in)
# ---------------------------------------------------------------------------

def test_dgrad_respects_input_capacity():
    stx = random_tensor(7, n=100, cap=128, channels=4, extent=16)
    out_cap = 64
    kmap = km.build_kmap(stx, 2, 2, out_capacity=out_cap)
    assert kmap.capacity == out_cap != stx.capacity
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 6)) * 0.3
    dy = jax.random.normal(jax.random.PRNGKey(2), (out_cap, 6))
    dx = df.sparse_conv_dgrad(dy, w, kmap, in_capacity=stx.capacity)
    assert dx.shape == (stx.capacity, 4)
    # brute-force pair-list reference
    ws_in, ws_out = np.asarray(kmap.ws_in), np.asarray(kmap.ws_out)
    ref = np.zeros((stx.capacity, 4), np.float32)
    wn, dyn = np.asarray(w), np.asarray(dy)
    for k in range(kmap.volume):
        for i_in, i_out in zip(ws_in[k], ws_out[k]):
            if i_in >= 0:
                ref[i_in] += dyn[i_out] @ wn[k].T
    np.testing.assert_allclose(np.asarray(dx), ref, rtol=1e-5, atol=1e-5)
    # input rows beyond the pair capacity must receive gradient too
    assert (np.abs(ref[out_cap:]).sum() > 0), "regression scene too small"


def test_custom_vjp_dgrad_shape_with_mismatched_capacities():
    stx = random_tensor(8, n=100, cap=128, channels=4, extent=16)
    kmap = km.build_kmap(stx, 2, 2, out_capacity=64)
    w = jax.random.normal(jax.random.PRNGKey(3), (8, 4, 6)) * 0.3

    def loss(feats, w):
        return jnp.sum(sparse_conv_apply(feats, w, kmap) ** 2)

    dx, dw = jax.grad(loss, argnums=(0, 1))(stx.feats, w)
    assert dx.shape == stx.feats.shape
    assert dw.shape == w.shape
    assert float(jnp.abs(dx[64:]).sum()) > 0


# ---------------------------------------------------------------------------
# bitmask dtype + composite path (K^D > 31)
# ---------------------------------------------------------------------------

def test_bitmask_is_int32_exact_below_32():
    stx = random_tensor(9)
    kmap = km.build_kmap(stx, 3, 1)
    assert kmap.bitmask.dtype == jnp.int32
    m = np.asarray(kmap.m_out)
    bm = np.asarray(kmap.bitmask)
    for i in range(int(stx.num_valid)):
        assert bm[i] == sum(1 << k for k in range(27) if m[i, k] >= 0)


def test_bitmask_composite_path_above_31():
    rng = np.random.default_rng(0)
    hit = jnp.asarray(rng.integers(0, 2, size=(50, 64)).astype(bool))
    bm = km._bitmask(hit)
    assert bm.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(bm), np_bitmask(np.asarray(hit)))
    # K=4 (even) in 3D has volume 64 → exercises the composite path end-to-end
    stx = random_tensor(10, extent=16)
    kmap = km.build_kmap(stx, 4, 2)
    assert kmap.volume == 64
    assert kmap.bitmask.dtype == jnp.int32
    assert_kmap_matches_ref(kmap, np_build_kmap(stx, 4, 2))


# ---------------------------------------------------------------------------
# O(N) radix sort for bounded packed keys (vs the stable comparison argsort)
# ---------------------------------------------------------------------------

@property_test(
    "seed,extent,lo,batch,spec_kind",
    cases=[(0, 8, 0, 1, "one"), (1, 16, -8, 2, "one"), (2, 6, -5, 3, "one"),
           (3, 20, 0, 2, "two"), (4, 10, -12, 4, "two"), (5, 3, -2, 1, "two")],
    strategies=lambda st: dict(seed=st.integers(0, 10_000),
                               extent=st.integers(3, 20),
                               lo=st.integers(-12, 0),
                               batch=st.integers(1, 4),
                               spec_kind=st.sampled_from(["one", "two"])),
    max_examples=16)
def test_property_radix_argsort_is_stable_argsort(seed, extent, lo, batch,
                                                  spec_kind):
    """The O(N·bits) radix argsort (XLA twin and numpy twin) is
    *bit*-identical to the stable comparison argsort on bounded packed
    keys: same permutation including tie order, negative coordinates, and
    the PAD tail."""
    stx = random_tensor(seed, n=80, cap=96, extent=extent, lo=lo, batch=batch)
    spec = _spec_of_kind(spec_kind, batch, lo, extent)
    keys = hashing.pack_keys(stx.coords, spec, valid=stx.valid_mask)
    kn = np.array(keys)
    kn[70:80] = kn[0:10]     # duplicates: stability must be exercised
    if kn.ndim == 1:
        ref = np.argsort(kn, kind="stable").astype(np.int32)
    else:
        ref = hashing.lex_argsort_np(kn)
    np.testing.assert_array_equal(
        np.asarray(hashing.radix_argsort_keys(jnp.asarray(kn), spec)), ref)
    np.testing.assert_array_equal(hashing.np_radix_argsort_keys(kn, spec), ref)
    # the sort_keys dispatcher picks radix for bounded specs — identical
    # layout to the comparison path it replaces
    order, sk = hashing.sort_keys(jnp.asarray(kn), spec)
    np.testing.assert_array_equal(np.asarray(order), ref)
    np.testing.assert_array_equal(np.asarray(sk), kn[ref])


def test_radix_argsort_padded_matches_argsort_with_sentinels():
    """Bitmask sort keys carry MISS (-1) and PAD (int32 max) sentinels; the
    padded radix path must keep the signed-compare layout (MISS first, PAD
    last, ties stable)."""
    rng = np.random.default_rng(0)
    vals = rng.integers(0, 1 << 12, 300).astype(np.int32)
    vals[50:80] = np.iinfo(np.int32).max    # PAD
    vals[100:110] = vals[0:10]              # duplicates
    vals[200:205] = -1                      # MISS
    got = np.asarray(hashing.radix_argsort_padded(jnp.asarray(vals), 12))
    np.testing.assert_array_equal(got, np.argsort(vals, kind="stable"))
    # numpy twin of the same padded path
    np.testing.assert_array_equal(
        hashing.np_radix_argsort_bits(
            np.asarray(hashing._remap_radix_word(jnp.asarray(vals), 12)), 13),
        np.argsort(vals, kind="stable"))


def test_sort_keys_raw_spec_falls_back_to_comparison_sort():
    spec = hashing.key_spec_for(3)          # unknown bounds → raw columns
    assert hashing.radix_word_bits(spec) is None
    rng = np.random.default_rng(1)
    keys = jnp.asarray(rng.integers(-50, 50, (64, 4)).astype(np.int32))
    order, _ = hashing.sort_keys(keys, spec)
    np.testing.assert_array_equal(np.asarray(order),
                                  hashing.lex_argsort_np(np.asarray(keys)))
