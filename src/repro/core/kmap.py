"""Kernel-map construction: the "mapping operators" of the paper.

A kernel map relates output points to input points for every kernel offset
δ ∈ Δ^D(K).  Two representations exist (paper §4.2) and each dataflow needs
its own:

* **output-stationary** ``m_out[n, k]`` — index of the input neighbor of
  output ``n`` at offset ``k`` (or -1).  Required by implicit GEMM.
* **weight-stationary** ``(ws_in[k, i], ws_out[k, i])`` for ``i < ws_count[k]``
  — the per-offset gather/scatter lists.  Required by gather-GEMM-scatter and
  fetch-on-demand.

Packed-key mapping engine
-------------------------
The paper is explicit that mapping overhead (bitmask building, sorting,
reordering) can dominate end-to-end rankings (Tables 3 vs 4).  The mapping
path therefore minimizes sort work:

* the coordinate table is a ``hashing.CoordTable`` — coordinates packed into
  scalar int32 keys, **one** argsort;
* all K^D shifted queries are answered as one flattened ``(K^D·N,)`` batched
  lookup instead of K^D independent searches: a sort-merge join of the
  queries with the table (``hashing.join_lookup``), not a binary search,
  whose data-dependent gather per step is the costly op on a TPU;
* the weight-stationary pair lists are compacted by **one** sort of all
  K^D offset columns at once (a stable partition, hits first) instead of
  one argsort per offset;
* strided downsampling dedupes grid cells by masking the low stride bits of
  the *already-packed* sorted key array (power-of-two strides; one argsort),
  and the resulting unique key array doubles as the next level's
  ``CoordTable`` — adopted for free through the sidecar ``MapCache`` so
  submanifold layers at the same stride never rebuild the table.

(The seed's multi-word ``engine="legacy"`` A/B path was deleted after a
release cycle of bit-identical cross-checks — see ROADMAP PR-1; the tests
in tests/test_mapping_engine.py now verify against brute-force numpy
references instead.)

On top of the raw map we build the paper's redundancy-reduction machinery:
per-output neighbor **bitmasks**, bitmask **sorting** (Fig. 6), arbitrary
**mask splits** (Fig. 10) and per-(tile, δ) occupancy masks — the TPU analogue
of warp-level skipping (DESIGN.md §2).  ``make_split_plan`` slices per-split
bitmasks out of the stored per-row bitmask with shift/mask bit ops (no
re-scan of ``m_out``) and can emit the tile-occupancy tensor in the same
pass (``tile_m=...``).

Everything is static-shape: maps are built at the capacity of the output
tensor and padded with -1 rows, which is precisely the paper's §3.2 padding
trick (no bounds check in the kernel inner loop).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing
from repro.core.hashing import CoordTable, KeySpec
from repro.core.sparse_tensor import (INVALID_COORD, SparseTensor,
                                      axis_strides, stride_mul, stride_name)

_I32_MAX = int(jnp.iinfo(jnp.int32).max)


def kernel_offsets(kernel_size, ndim: int) -> np.ndarray:
    """Δ^D(K) as an (K^D, D) int array; ``kernel_size`` is an int (K on
    every axis) or one extent per axis, as the (1, 3, 3) of an asymmetric
    kernel.

    Odd K: centered window {-(K//2)..K//2} (submanifold convention).
    Even K: forward window {0..K-1} (downsampling convention, e.g. K=2,s=2).
    The *center-first* ordering puts δ=0 (or the lowest corner for even K)
    first: the center offset is always dense for submanifold convs, and
    leading with it makes split 0 the "dense" split.  It is product order
    stably sorted by L1 norm, so the offsets of a sub-kernel keep the
    relative order they have in the cubic kernel (``offset_columns``).
    """
    ranges = [range(-(k // 2), k // 2 + 1) if k % 2 else range(k)
              for k in axis_strides(kernel_size, ndim)]
    offs = np.array(list(itertools.product(*ranges)), dtype=np.int32)
    # center-first ordering
    norm = np.abs(offs).sum(axis=1)
    order = np.argsort(norm, kind="stable")
    return offs[order]


def offset_columns(kernel_size, full: int, ndim: int) -> np.ndarray:
    """Columns of ``kernel_size``'s offsets in the cubic kernel of extent
    ``full``, in the sub-kernel's own offset order."""
    index = {tuple(o): i for i, o in enumerate(kernel_offsets(full, ndim))}
    return np.array([index[tuple(o)]
                     for o in kernel_offsets(kernel_size, ndim)], np.int32)


def _bitmask(hit: jax.Array) -> jax.Array:
    """Neighbor bitmask (paper Fig. 6) in int32.  Kernel volumes ≤ 31 pack
    exactly; larger volumes use a (popcount << 24 | low-24-bits) composite — a
    rank-preserving proxy that keeps rows with similar occupancy adjacent
    after sorting (x64 stays disabled framework-wide)."""
    kd = hit.shape[-1]
    if kd <= 31:
        return jnp.sum(jnp.where(hit, jnp.int32(1) << jnp.arange(kd, dtype=jnp.int32), 0), axis=-1)
    pop = jnp.sum(hit, axis=-1).astype(jnp.int32)
    low = jnp.sum(jnp.where(hit[..., :24], jnp.int32(1) << jnp.arange(24, dtype=jnp.int32), 0), axis=-1)
    return (pop << 24) | low


def _np_bitmask(hit: np.ndarray) -> np.ndarray:
    """Numpy twin of ``_bitmask`` (identical exact/composite rules) for the
    host-side split-plan composition path."""
    kd = hit.shape[-1]
    h = hit.astype(np.int32)
    if kd <= 31:
        w = np.int32(1) << np.arange(kd, dtype=np.int32)
        return (h * w).sum(axis=-1).astype(np.int32)
    pop = h.sum(axis=-1).astype(np.int32)
    w24 = np.int32(1) << np.arange(24, dtype=np.int32)
    low = (h[..., :24] * w24).sum(axis=-1).astype(np.int32)
    return (pop << np.int32(24)) | low


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class KernelMap:
    """All map representations for one (layer-group) convolution."""

    m_out: jax.Array          # (N_out_cap, KD) int32, -1 = missing
    out_coords: jax.Array     # (N_out_cap, 1+D) int32
    n_out: jax.Array          # () int32
    ws_in: jax.Array          # (KD, cap) int32 gather indices (-1 pad)
    ws_out: jax.Array         # (KD, cap) int32 scatter indices (-1 pad)
    ws_count: jax.Array       # (KD,) int32
    bitmask: jax.Array        # (N_out_cap,) int32 neighbor bitmask (0 pad;
                              # composite popcount proxy when KD > 31)
    # static: an int, or one int per spatial axis
    out_stride: int | tuple = dataclasses.field(metadata=dict(static=True),
                                                default=1)
    kernel_size: int | tuple = dataclasses.field(metadata=dict(static=True),
                                                 default=3)

    @property
    def volume(self) -> int:
        return self.m_out.shape[1]

    @property
    def capacity(self) -> int:
        return self.m_out.shape[0]


class MapCache:
    """Sidecar cache of sorted ``CoordTable``s, keyed by coordinate-array
    identity (or a caller-supplied content key), sharing one ``KeySpec``
    across an entire model.

    Model map builders create one per input cloud; every ``build_kmap`` call
    at the same stride then reuses the sorted table (submanifold + strided
    convs over the same coordinates), and strided maps *adopt* their output
    table into the cache so the next pyramid level's table costs zero sorts.

    Serving hook: ``key=`` lets a caller that knows two coordinate arrays
    hold identical *content* (e.g. the serving engine, which digests packed
    request batches) share tables across distinct array objects — the
    cross-request analogue of the cross-layer reuse above.  ``hits``/
    ``misses`` counters and ``clear()`` expose cache behaviour to engine
    stats and tests.  A MapCache must not be reused across separate ``jit``
    traces (cached tables would leak tracers): create one per trace, or use
    it only eagerly.
    """

    def __init__(self, spec: KeySpec):
        self.spec = spec
        self._tables: dict = {}
        self._stride_tables: dict = {}
        self.hits = 0
        self.misses = 0

    @classmethod
    def for_tensor(cls, st: SparseTensor) -> "MapCache":
        return cls(hashing.key_spec_for(st.ndim_space, st.batch_bound,
                                        st.spatial_bound))

    def table(self, st: SparseTensor, key=None) -> CoordTable:
        key = id(st.coords) if key is None else key
        ent = self._tables.get(key)
        if ent is None:
            self.misses += 1
            t = CoordTable.build(st.coords, st.valid_mask, self.spec)
            # hold the coords array so its id stays unique for the cache's life
            self._tables[key] = (st.coords, t)
            return t
        self.hits += 1
        return ent[1]

    def adopt(self, coords: jax.Array, table: CoordTable, key=None) -> None:
        self._tables.setdefault(id(coords) if key is None else key,
                                (coords, table))

    def adopt_for_stride(self, out_stride: int, table: CoordTable,
                         n_out) -> None:
        """Pre-adopt a *composed* output table for the strided map at
        ``out_stride`` (before its output coordinates exist): ``build_kmap``
        then skips the floor-grid unique argsort entirely and derives the
        output coords from the table.  ``n_out`` may be a host int or a
        traced scalar (the composed valid-row count)."""
        self._stride_tables[out_stride] = (table, n_out)

    def table_for_stride(self, out_stride: int):
        return self._stride_tables.get(out_stride)

    def clear(self) -> None:
        self._tables.clear()
        self._stride_tables.clear()

    def __len__(self) -> int:
        return len(self._tables) + len(self._stride_tables)


def _unique_coords(coords: jax.Array, valid: jax.Array, capacity: int):
    """Sort-unique of coordinate rows; returns (coords[capacity], count).
    (Multi-word fallback for non-power-of-two strides — the happy path is
    ``_unique_from_keys``.)"""
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    words = jnp.where(valid[:, None], coords.astype(jnp.int32), big)
    order = hashing.lex_argsort(words)
    coords_s = coords[order]
    valid_s = valid[order]
    same_as_prev = hashing.rows_equal(coords_s[1:], coords_s[:-1])
    is_first = jnp.concatenate([jnp.ones((1,), bool), ~same_as_prev]) & valid_s
    dest = jnp.where(is_first, jnp.cumsum(is_first) - 1, capacity)
    out = jnp.full((capacity + 1, coords.shape[1]), INVALID_COORD, jnp.int32)
    out = out.at[dest].set(coords_s, mode="drop")
    return out[:capacity], jnp.minimum(jnp.sum(is_first), capacity).astype(jnp.int32)


def _grid_mask_ints(spec: KeySpec, out_stride):
    """Per-key-column AND masks (MSB-first, plain python ints) clearing the
    low ``log2`` of each axis' ``out_stride`` bits of that axis' field —
    turning a coordinate key into its floor-grid key in one bit op.  For
    ``raw`` specs the columns ARE the coordinates, and two's-complement
    masking floors negatives correctly.  Returns None when a stride is not a
    power of two, every axis has stride 1, or a packed field is too narrow
    (callers fall back to the multi-word grid dedup)."""
    strides = axis_strides(out_stride, spec.ndim_space)
    if any(s & (s - 1) for s in strides):
        return None
    log2s = [s.bit_length() - 1 for s in strides]
    if not any(log2s):
        return None
    if spec.raw:
        return (-1,) + tuple(~(s - 1) for s in strides)
    masks = [np.int64(2 ** 31 - 1), np.int64(2 ** 31 - 1)]
    for f, (word, shift, width) in enumerate(spec.layout()):
        if f == 0:
            continue  # batch never strides
        bits = log2s[f - 1]
        if bits > width - 1:
            return None  # bias 2^(width-1) must stay divisible by the stride
        masks[word] &= ~(((1 << bits) - 1) << shift) & (2 ** 32 - 1)
    cols = [int(np.int32(m)) for m in masks]
    # MSB-first column order: single word → (lo,), pair → (hi, lo)
    return (cols[0],) if spec.words == 1 else (cols[1], cols[0])


def _grid_key_mask(spec: KeySpec, out_stride: int):
    """jnp-scalar view of ``_grid_mask_ints`` for the traced unique pass."""
    ints = _grid_mask_ints(spec, out_stride)
    if ints is None:
        return None
    return tuple(jnp.int32(m) for m in ints)


def _unique_from_keys(table: CoordTable, out_stride: int, capacity: int):
    """Floor-grid unique pass that *reuses the already-packed sorted key
    array* of the input table.

    Masks the low stride bits of ``table.sorted_keys`` (exactly the packed
    key of each row's grid cell), argsorts the masked keys once, and
    compacts first occurrences.  Returns ``(out_coords, n_out, child_table)``
    where ``child_table`` is the output tensor's CoordTable for free (the
    unique keys come out sorted).  Returns None when masking doesn't apply.
    """
    spec = table.spec
    w = spec.words
    masks = _grid_key_mask(spec, out_stride)
    if masks is None:
        return None
    # PAD rows (invalid/out-of-range) are exactly the int32-max keys; keep
    # them PAD through the masking so they still sort last.  (A raw-spec
    # table row whose leading word legitimately equals int32 max is
    # indistinguishable from padding — the same ambiguity the seed's
    # multi-word table had.)
    if w == 1:
        row_valid = table.sorted_keys != _I32_MAX
        masked = jnp.where(row_valid, table.sorted_keys & masks[0], _I32_MAX)
    else:
        row_valid = table.sorted_keys[:, 0] != _I32_MAX
        masked = jnp.where(row_valid[:, None], table.sorted_keys &
                           jnp.stack(list(masks)), _I32_MAX)
    order, ks = hashing.sort_keys(masked, spec)
    return _unique_level(ks, row_valid[order], spec, capacity, clamp=True)


def _unique_level(ks, valid, spec: KeySpec, capacity: int, clamp: bool):
    """A level's table from sorted keys ``ks`` (rows with ``valid``): the
    first of each distinct key, compacted in order into ``capacity`` rows.
    Returns ``(out_coords, n, child_table)``; ``n`` counts the distinct
    keys, at most ``capacity`` with ``clamp``."""
    same = hashing.keys_equal(ks[1:], ks[:-1], spec.words)
    is_first = jnp.concatenate([jnp.ones((1,), bool), ~same]) & valid
    dest = jnp.where(is_first, jnp.cumsum(is_first) - 1, capacity)
    out_keys = jnp.full((capacity + 1,) + ks.shape[1:], _I32_MAX, jnp.int32)
    out_keys = out_keys.at[dest].set(ks, mode="drop")[:capacity]
    n = jnp.sum(is_first, dtype=jnp.int32)
    if clamp:
        n = jnp.minimum(n, capacity)
    key_valid = jnp.arange(capacity) < n
    out_coords = jnp.where(key_valid[:, None],
                           hashing.unpack_keys(out_keys, spec), INVALID_COORD)
    return out_coords, n, CoordTable.from_sorted_keys(spec, out_keys)


def _dilate(x: SparseTensor, kernel_size, stride, spec: KeySpec,
            capacity: int):
    """Output cells of a strided conv with an odd kernel, as spconv's
    ``SparseConv3d`` (padding K//2) makes them: every multiple of the output
    stride ``t·s`` (per axis) that reaches an input voxel ``i`` as
    ``o + δ·t`` for a kernel offset δ — a dilation of the input, not its
    floor grid.  Along an axis each input emits at most ceil(K/s) cells
    (2 for K=3, s=2; 3 for s=1), so an input emits ≤ 2×2×2 candidates at
    stride (2, 2, 2) and ≤ 2×2×3 at (2, 2, 1); one sort dedupes them.

    Returns ``(out_coords, n_need, child_table)`` at ``capacity`` rows.
    ``n_need`` counts every distinct cell, so it exceeds ``capacity`` when
    the level overflows; ``check_capacity`` raises on that on the host."""
    d = x.ndim_space
    t = np.asarray(axis_strides(x.stride, d), np.int32)
    s = np.asarray(axis_strides(stride, d), np.int32)
    r = np.asarray(axis_strides(kernel_size, d), np.int32) // 2
    ts = t * s
    c = x.coords[:, 1:]
    top = (c + r * t) // ts * ts          # the highest cell in reach
    axes = []
    for a in range(d):
        cells = [top[:, a] - j * ts[a]
                 for j in range(-(-(2 * r[a] + 1) // s[a]))]
        axes.append([(o, o >= c[:, a] - r[a] * t[a]) for o in cells])
    cand, ok = [], []
    for combo in itertools.product(*axes):
        cand.append(jnp.stack([x.coords[:, 0]] + [o for o, _ in combo], 1))
        reach = x.valid_mask
        for _, k in combo:
            reach = reach & k
        ok.append(reach)
    keys = hashing.pack_keys(jnp.concatenate(cand), spec,
                             valid=jnp.concatenate(ok))
    if spec.words == 1:
        ks = jnp.sort(keys)
        row_valid = ks != _I32_MAX
    else:
        ks = jnp.stack(jax.lax.sort(tuple(keys.T), num_keys=spec.words), 1)
        row_valid = ks[:, 0] != _I32_MAX
    return _unique_level(ks, row_valid, spec, capacity, clamp=False)


def check_capacity(maps: Dict[tuple, "KernelMap"]) -> None:
    """Raise when a dilated level needed more rows than its static capacity
    (its ``n_out`` then counts every cell it needed): a scene's rows are
    never dropped to fit.  Reads each map's ``n_out`` on the host."""
    for ref, km in maps.items():
        check_rows(ref, int(km.n_out), km.capacity)


def check_rows(ref, need: int, capacity: int) -> None:
    """``check_capacity`` of one map from host counts: raise when ``need``
    rows do not fit the level's ``capacity``."""
    if need > capacity:
        raise ValueError(f"map {ref} needs {need} rows, more than its "
                         f"level's capacity {capacity}")


def _compact_ws(m_out: jax.Array):
    """Weight-stationary pair lists: a stable partition of every offset
    column, hits first in row order and -1 after, in ONE sort.

    Each column of ``m_out`` (transposed to ``(KD, cap)``) is sorted on its
    row index, with every miss keyed ``cap`` (past all rows), carrying the
    input index along: the sorted keys are ``ws_out``, the carried indices
    ``ws_in``.  Every miss comes out as -1, so the order of the misses does
    not show, and the sort need not be stable.
    """
    cap, _ = m_out.shape
    cols = m_out.T
    hit = cols >= 0
    row = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    rows, ins = jax.lax.sort((jnp.where(hit, row, cap), cols), dimension=1,
                             num_keys=1)
    found = rows < cap
    return (jnp.where(found, ins, -1), jnp.where(found, rows, -1),
            jnp.sum(hit, axis=1, dtype=jnp.int32))


def _compact_groups(m_outs: Sequence[jax.Array]) -> list:
    """``_compact_ws`` of many maps: every offset column compacts alone, so
    the columns of all maps of one capacity share one sort.  Returns one
    (ws_in, ws_out, ws_count) per map, in order."""
    by_cap: Dict[int, list] = {}
    for i, m in enumerate(m_outs):
        by_cap.setdefault(m.shape[0], []).append(i)
    ws = [None] * len(m_outs)
    for idx in by_cap.values():
        ws_in, ws_out, ws_count = _compact_ws(
            jnp.concatenate([m_outs[i] for i in idx], axis=1))
        lo = 0
        for i in idx:
            hi = lo + m_outs[i].shape[1]
            ws[i] = (ws_in[lo:hi], ws_out[lo:hi], ws_count[lo:hi])
            lo = hi
    return ws


@dataclasses.dataclass(frozen=True)
class PendingKmap:
    """One kernel map between its table phase and its neighbour search:
    the coordinate table it searches, its output rows, and the packed
    shifted query keys (``(KD·cap,)``, offset-major)."""

    table: CoordTable
    out_coords: jax.Array
    n_out: jax.Array
    qkeys: jax.Array
    out_stride: int | tuple
    kernel_size: int | tuple


def prepare_kmap(x: SparseTensor, kernel_size, stride=1,
                 transposed: bool = False, out_coords: Optional[jax.Array] = None,
                 n_out: Optional[jax.Array] = None,
                 out_capacity: Optional[int] = None,
                 cache: Optional[MapCache] = None,
                 dilate: bool = False) -> PendingKmap:
    """The table phase of ``build_kmap`` (same arguments): the sorted
    coordinate table, the output rows (a strided map's unique floor grid, or
    with ``dilate`` its dilation, whose table is adopted into ``cache``
    here) and the query keys."""
    d = x.ndim_space
    t = x.stride
    offs = kernel_offsets(kernel_size, d)
    kd = offs.shape[0]
    cap_in = x.capacity
    spec = cache.spec if cache is not None else hashing.key_spec_for(
        d, x.batch_bound, x.spatial_bound)
    # the sorted coordinate tables (and a strided map's unique output
    # grid) apart from the neighbour search, by scope in a device trace
    with jax.named_scope("table"):
        if cache is not None:
            table = cache.table(x)
        else:
            table = CoordTable.build(x.coords, x.valid_mask, spec)

        child_table = None
        if transposed:
            assert out_coords is not None and n_out is not None
            out_stride = t // stride
            assert out_stride >= 1
            n_out_cap = out_capacity or out_coords.shape[0]
            out_coords = out_coords[:n_out_cap]
            # neighbor input coord = out + δ * out_stride mirrored
            # (q = p - δ·t_f)
            delta_scale = -out_stride
        elif stride == 1:
            out_coords, n_out = x.coords, x.num_valid
            out_stride = t
            n_out_cap = out_capacity or cap_in
            out_coords = out_coords[:n_out_cap]
            delta_scale = t
        else:
            out_stride = stride_mul(t, stride)
            n_out_cap = out_capacity or cap_in
            pre = (cache.table_for_stride(out_stride) if cache is not None
                   else None)
            use_pre = pre is not None and pre[0].n == n_out_cap
            dilated = dilate and not use_pre
            uniq = None if use_pre or dilated else \
                _unique_from_keys(table, out_stride, n_out_cap)
            if dilated:
                with jax.named_scope(f"kmap.dilate_s{stride_name(t)}"):
                    out_coords, n_out, child_table = _dilate(
                        x, kernel_size, stride, spec, n_out_cap)
            elif use_pre:
                # composed child table (scene-granular serving reuse): the
                # output coords ARE the unpacked table keys — no unique
                # argsort
                child_table, n_out = pre
                n_out = jnp.asarray(n_out, jnp.int32)
                key_valid = jnp.arange(n_out_cap) < n_out
                out_coords = jnp.where(
                    key_valid[:, None],
                    hashing.unpack_keys(child_table.sorted_keys, spec),
                    INVALID_COORD)
            elif uniq is not None:
                out_coords, n_out, child_table = uniq
            else:
                # non-power-of-two stride (or too-narrow fields): fall back to
                # the multi-word grid dedup — correctness over speed off the
                # happy path
                g = np.asarray(axis_strides(out_stride, d), np.int32)
                grid = jnp.concatenate(
                    [x.coords[:, :1], (x.coords[:, 1:] // g) * g], axis=1)
                grid = jnp.where(x.valid_mask[:, None], grid, INVALID_COORD)
                out_coords, n_out = _unique_coords(grid, x.valid_mask,
                                                   n_out_cap)
            delta_scale = t

    with jax.named_scope("search"), jax.named_scope("join"):
        # all K^D·N shifted queries, packed; padded/out-of-range rows pack
        # to the MISS key
        shifts = np.concatenate(
            [np.zeros((kd, 1), np.int32),
             offs * np.asarray(axis_strides(delta_scale, d), np.int32)],
            axis=1)
        q = out_coords[None, :, :] + jnp.asarray(shifts)[:, None, :]
        qkeys = hashing.pack_keys(q.reshape(kd * n_out_cap, d + 1), spec,
                                  query=True)
    if cache is not None and child_table is not None:
        cache.adopt(out_coords, child_table)
    return PendingKmap(table=table, out_coords=out_coords,
                       n_out=jnp.asarray(n_out, jnp.int32), qkeys=qkeys,
                       out_stride=out_stride, kernel_size=kernel_size)


def search_kmaps(pending: Sequence[PendingKmap]) -> list:
    """The neighbour search of many kernel maps at once: their lookups as
    joins batched by table and shape (``hashing.lookup_batched``), then
    their pair lists compacted in one sort per output capacity.  Batching
    changes no result; it keeps the count of sorts, and so the compiled
    program, small (a TPU sort compiles to megabytes of code)."""
    with jax.named_scope("search"), jax.named_scope("join"):
        found = hashing.lookup_batched([(p.table, p.qkeys) for p in pending])
    with jax.named_scope("search"), jax.named_scope("compact"):
        m_outs = []
        for p, f in zip(pending, found):
            cap = p.out_coords.shape[0]
            out_valid = jnp.arange(cap) < p.n_out
            m_outs.append(jnp.where(out_valid[:, None],
                                    f.reshape(-1, cap).T, -1))
        ws = _compact_groups(m_outs)
        maps = []
        for p, m_out, (ws_in, ws_out, ws_count) in zip(pending, m_outs, ws):
            out_valid = jnp.arange(m_out.shape[0]) < p.n_out
            maps.append(KernelMap(
                m_out=m_out, out_coords=p.out_coords, n_out=p.n_out,
                ws_in=ws_in, ws_out=ws_out, ws_count=ws_count,
                bitmask=jnp.where(out_valid, _bitmask(m_out >= 0), 0),
                out_stride=p.out_stride, kernel_size=p.kernel_size))
    return maps


def build_kmap(x: SparseTensor, kernel_size, stride=1,
               transposed: bool = False, out_coords: Optional[jax.Array] = None,
               n_out: Optional[jax.Array] = None, out_capacity: Optional[int] = None,
               cache: Optional[MapCache] = None,
               dilate: bool = False) -> KernelMap:
    """Build the kernel map for a sparse convolution over ``x``.

    stride == 1                 : submanifold conv, outputs = inputs.
    stride > 1, not transposed  : downsample; outputs = unique(floor-grid)
        (MinkowskiEngine's rule), or with ``dilate`` (an odd kernel) the
        dilation of the inputs (spconv's ``SparseConv3d``, ``_dilate``).
        ``kernel_size`` and ``stride`` may be per-axis tuples.
    transposed                  : upsample (inverse conv); ``out_coords`` (the
        cached finer coordinates) and ``n_out`` must be given.

    ``cache``: optional ``MapCache`` — reuses the sorted coordinate table
    across calls at the same stride and adopts strided outputs' tables.
    A map program builds its maps in two phases instead (``prepare_kmap``
    for each, then one ``search_kmaps``), so the searches batch.
    """
    return search_kmaps([prepare_kmap(x, kernel_size, stride, transposed,
                                      out_coords, n_out, out_capacity,
                                      cache, dilate)])[0]


def transpose_kmap(fwd: KernelMap, x_fine: SparseTensor) -> KernelMap:
    """Kernel map of the inverse (transposed) conv from a cached forward map.

    UNet decoders reuse the encoder's maps (paper: layers in the same *group*
    share maps).  We rebuild output-stationary structure for the fine outputs
    by swapping the weight-stationary pair lists.
    """
    kd = fwd.volume
    cap = x_fine.capacity
    # m_out for the fine side: column k of the transposed conv pairs
    # (in=coarse=fwd ws_out rows, out=fine=fwd ws_in rows).
    def col(k):
        m = jnp.full((cap,), -1, jnp.int32)
        src = fwd.ws_out[k]   # coarse index (input of transposed conv)
        dst = fwd.ws_in[k]    # fine index (output of transposed conv)
        ok = dst >= 0
        return m.at[jnp.where(ok, dst, cap)].set(jnp.where(ok, src, -1), mode="drop")

    m_out = jax.vmap(col, out_axes=1)(jnp.arange(kd))
    bm = _bitmask(m_out >= 0)
    return KernelMap(m_out=m_out, out_coords=x_fine.coords, n_out=x_fine.num_valid,
                     ws_in=fwd.ws_out, ws_out=fwd.ws_in, ws_count=fwd.ws_count,
                     bitmask=bm, out_stride=x_fine.stride, kernel_size=fwd.kernel_size)


def slice_kmap(km: KernelMap, kernel_size) -> KernelMap:
    """The map of a kernel whose offsets are a subset of ``km``'s cubic
    kernel — an asymmetric (1, 3, 3) out of a 3x3x3 submanifold map: the
    subset's columns, with no search of its own.  Works on traced and on
    composed (concrete) maps alike; padded rows stay all -1, so their
    bitmask stays 0."""
    cols = offset_columns(kernel_size, km.kernel_size,
                          km.out_coords.shape[1] - 1)
    m_out = km.m_out[:, cols]
    return KernelMap(m_out=m_out, out_coords=km.out_coords, n_out=km.n_out,
                     ws_in=km.ws_in[cols], ws_out=km.ws_out[cols],
                     ws_count=km.ws_count[cols],
                     bitmask=_bitmask(m_out >= 0), out_stride=km.out_stride,
                     kernel_size=kernel_size)


# ---------------------------------------------------------------------------
# Scene-granular composition (Minuet §4 proper: compose per-scene cached
# mapping work into batch-level structures instead of digesting whole batches)
# ---------------------------------------------------------------------------
#
# Batch bits are the most significant key field, so every sorted structure of
# a packed batch — the coordinate table at every pyramid level, and therefore
# every kernel map built on those tables — is the batch-major concatenation
# of the corresponding per-scene (batch-0) structure with index offsets added
# in.  The helpers below exploit that at two granularities:
#
# * ``scene_table_ladder`` + ``compose_batch_tables`` — per-scene sorted
#   table ladders merge-composed into batch tables (adopted into a MapCache
#   via ``build_maps_from_specs(..., tables=...)``, killing every argsort of
#   a batch map build);
# * ``compose_kmaps`` — per-scene *kernel map* stacks, kept on the device,
#   placed into the batch map stack on the device (``compose_place`` per
#   scene, then ``compose_finish``): warm scenes skip mapping entirely; only
#   cold scenes ever build maps, at their own size.  Bit-identical to a
#   fresh batch build (tests/test_streaming.py).


def level_key(stride) -> str:
    """A pyramid level's key in a scene entry's device arrays: its stride's
    name (``4``, ``8x8x4``), so int and per-axis strides sort together."""
    return stride_name(stride)


def map_strides(ms) -> tuple:
    """(input, output) tensor strides of a map spec: a "down" map outputs
    the next level, an "up" map reads it, the others stay on their level."""
    if ms.kind == "down":
        return ms.tensor_stride, stride_mul(ms.tensor_stride, ms.stride)
    if ms.kind == "up":
        return stride_mul(ms.tensor_stride, ms.stride), ms.tensor_stride
    return ms.tensor_stride, ms.tensor_stride


@dataclasses.dataclass
class SceneEntry:
    """Cached per-scene mapping work, keyed by the scene's content digest.

    n:          scene row count (level-1 size).
    sizes:      tensor-stride -> per-scene row count at that pyramid level
                (host ints).
    arrays:     the scene builder's device arrays, padded to the scene's
                rung: ``"m_out"`` (map ref -> output-stationary map of each
                searched "sub"/"down" map) and ``"coords"`` (``level_key``
                -> the level's coordinates).  Pair lists, bitmasks, "up"
                and "slice" maps are derived from the composed batch maps.
    root_keys/root_order: the scene's sorted batch-0 CoordTable (host) —
                the object ``CoordTable.delta_merge`` updates on streaming
                frames.
    splits:     lazily-filled (map ref, ranges) -> per-split (sorted bitmask
                values, local stable order) numpy pairs — the per-scene half
                of ``compose_split_plans``.
    ladder:     streaming down-ladder state: down out-stride -> (folded cell
                keys, root-row counts) — see ``cell_ladder``.
    """

    n: int
    sizes: Dict[int, int]
    arrays: dict
    root_keys: np.ndarray
    root_order: np.ndarray
    splits: Dict[tuple, list] = dataclasses.field(default_factory=dict)
    ladder: Dict[int, tuple] = dataclasses.field(default_factory=dict)

    @property
    def ndim(self) -> int:
        """Spatial dimensions of the scene's coordinates."""
        return next(iter(self.arrays["coords"].values())).shape[1] - 1

    @property
    def nbytes(self) -> int:
        """Device-memory footprint — the byte-aware scene-store LRU's unit:
        the bytes of the device arrays the entry pins.  O(#arrays), never
        touches array data."""
        return sum(a.nbytes for a in jax.tree.leaves(self.arrays))


def scene_table_ladder(coords: np.ndarray, spec: KeySpec,
                       down_strides: Sequence[int]) -> Dict[int, tuple]:
    """Per-scene sorted table ladder for batch composition.

    coords: (n, 1+D) batch-0 rows, all valid (exact size, no padding).
    down_strides: ascending out-strides of the plan's "down" maps.
    Returns {tensor_stride: (sorted_keys, order_or_None, n)} as numpy — the
    root level keeps its row order; deeper levels are identity-order unique
    key arrays (exactly what a strided map's adopted child table holds).
    Stops early when a stride's floor-grid masking doesn't apply (non-pow2
    stride / too-narrow fields) — composition then covers the upper levels.
    """
    n = coords.shape[0]
    table = CoordTable.build(jnp.asarray(coords), jnp.ones((n,), bool), spec)
    ladder = {1: (np.asarray(table.sorted_keys), np.asarray(table.order), n)}
    cur, cur_n = table, n
    for s in sorted(down_strides):
        res = _unique_from_keys(cur, s, cur_n)
        if res is None:
            break
        _, n_out, child = res
        m = int(n_out)
        keys = np.asarray(child.sorted_keys)[:m]
        ladder[s] = (keys, None, m)
        cur = CoordTable.from_sorted_keys(spec, jnp.asarray(keys))
        cur_n = m
    return ladder


def compose_batch_tables(spec: KeySpec, ladders: Sequence[Dict[int, tuple]],
                         capacity: int) -> Dict[int, tuple]:
    """Compose per-scene table ladders (batch order) into batch tables.

    Returns {tensor_stride: (keys, order_or_None, n)} as device arrays — the
    ``tables=`` argument of ``plan.build_maps_from_specs``, covering every
    level present in *all* ladders.  O(N) concatenation per level.
    """
    strides = set(ladders[0])
    for lad in ladders[1:]:
        strides &= set(lad)
    out: Dict[int, tuple] = {}
    for s in sorted(strides):
        off = 0
        parts = []
        for b, lad in enumerate(ladders):
            keys, order, n = lad[s]
            parts.append((keys, order, b, off))
            off += n
        keys, order = hashing.compose_tables(spec, parts, capacity)
        out[s] = (jnp.asarray(keys),
                  None if order is None else jnp.asarray(order),
                  jnp.asarray(off, jnp.int32))
    return out


def compose_blank(entry: SceneEntry, capacity: int) -> dict:
    """The empty batch map stack ``compose_place`` fills, as numpy: every
    map row a miss (-1), every level row ``INVALID_COORD``, at
    ``capacity`` rows and the widths of ``entry``'s arrays."""
    a = entry.arrays
    return {"m_out": {r: np.full((capacity, m.shape[1]), -1, np.int32)
                      for r, m in a["m_out"].items()},
            "coords": {k: np.full((capacity, c.shape[1]),
                                  int(INVALID_COORD), np.int32)
                       for k, c in a["coords"].items()}}


def _put_rows(dst: jax.Array, block: jax.Array, off) -> jax.Array:
    """``block``'s rows written into ``dst`` from row ``off`` on; rows past
    ``dst``'s end are dropped (a block may be taller than the batch).  The
    start never clamps: ``dst`` is extended by the block's height first."""
    cap = dst.shape[0]
    block = block[:cap]
    ext = jnp.concatenate([dst, jnp.zeros_like(block)])
    return jax.lax.dynamic_update_slice(ext, block, (off, 0))[:cap]


def compose_place(specs, acc: dict, scene: dict, b, offs: dict) -> dict:
    """Traceable: scene ``b``'s map blocks placed into the batch map stack
    ``acc`` (``compose_blank``'s structure, on the device).

    scene: the scene entry's ``arrays``.  offs: ``level_key`` -> (the
    scene's first row in the batch level, its rows there), int32.  Each
    level's rows land at the scene's offset with the batch column
    rewritten to ``b``; each map's rows at the offset of its output level,
    its neighbour indices shifted by the offset of its input level (misses
    stay -1).  The block's padding rows land past the scene's rows, where
    ``acc`` still holds padding: scenes must be placed in batch order."""
    with jax.named_scope("kmap.compose"):
        out = {"m_out": {}, "coords": {}}
        for k, dst in acc["coords"].items():
            off, n = offs[k]
            blk = scene["coords"][k]
            first = jnp.where(jnp.arange(blk.shape[0]) < n, b, blk[:, 0])
            out["coords"][k] = _put_rows(dst, blk.at[:, 0].set(first), off)
        for ms in specs:
            if ms.ref not in acc["m_out"]:
                continue
            in_s, out_s = map_strides(ms)
            blk = scene["m_out"][ms.ref]
            blk = jnp.where(blk >= 0, blk + offs[level_key(in_s)][0], -1)
            out["m_out"][ms.ref] = _put_rows(acc["m_out"][ms.ref], blk,
                                             offs[level_key(out_s)][0])
    return out


def compose_finish(specs, acc: dict, totals: dict) -> Dict[tuple, KernelMap]:
    """Traceable: the batch kernel maps from a filled stack (``totals``:
    ``level_key`` -> the batch's rows at that level, int32).  What a batch
    build derives from its searched maps is derived here the same way: the
    weight-stationary pair lists of every searched map (one compaction
    sort), the bitmasks, the transposed "up" maps (``transpose_kmap``) and
    the column-subset "slice" maps (``slice_kmap``) — so the result is
    bit-identical to a fresh batch build."""
    with jax.named_scope("kmap.compose"):
        coords = acc["coords"]
        searched = [ms for ms in specs if ms.ref in acc["m_out"]]
        ws = _compact_groups([acc["m_out"][ms.ref] for ms in searched])
        maps: Dict[tuple, KernelMap] = {}
        for ms, (ws_in, ws_out, ws_count) in zip(searched, ws):
            m_out = acc["m_out"][ms.ref]
            out_s = map_strides(ms)[1]
            maps[ms.ref] = KernelMap(
                m_out=m_out, out_coords=coords[level_key(out_s)],
                n_out=totals[level_key(out_s)], ws_in=ws_in, ws_out=ws_out,
                ws_count=ws_count, bitmask=_bitmask(m_out >= 0),
                out_stride=out_s, kernel_size=ms.kernel_size)
        for ms in specs:
            if ms.kind == "up":
                c = coords[level_key(ms.tensor_stride)]
                fine = SparseTensor(
                    coords=c, feats=jnp.zeros((c.shape[0], 1), jnp.float32),
                    num_valid=totals[level_key(ms.tensor_stride)],
                    stride=ms.tensor_stride)
                maps[ms.ref] = transpose_kmap(maps[ms.transpose_of], fine)
            elif ms.kind == "slice":
                maps[ms.ref] = slice_kmap(maps[ms.slice_of], ms.kernel_size)
    return {ms.ref: maps[ms.ref] for ms in specs}


def compose_offsets(entries: Sequence[SceneEntry],
                    capacity: int) -> Optional[Dict[int, np.ndarray]]:
    """Per level (tensor stride), each scene's first batch row and, last,
    the batch's rows there (cumulative sizes, ``len(entries) + 1`` long).
    None when composition does not apply: no scene, an empty scene, or a
    level with more rows than ``capacity`` (a batch build then runs)."""
    if not entries or any(e.n == 0 for e in entries):
        return None
    offs = {s: np.cumsum([0] + [e.sizes[s] for e in entries])
            for s in entries[0].sizes}
    if any(o[-1] > capacity for o in offs.values()):
        return None
    return offs


def compose_kmaps(specs, entries: Sequence[SceneEntry], capacity: int,
                  place: Optional[Callable] = None,
                  finish: Optional[Callable] = None,
                  blank: Optional[dict] = None
                  ) -> Optional[Dict[tuple, KernelMap]]:
    """Compose per-scene kernel-map stacks into the batch map stack, on the
    device: ``compose_place`` for each scene in batch (= packing) order,
    then ``compose_finish``.  Reads only the entries' device arrays and
    their host sizes.

    specs: the plan's map specs.  capacity: the batch bucket every composed
    map is padded to.  place/finish: the two stages as ``(acc, scene, b,
    offs)`` / ``(acc, totals)`` callables (the serving engine passes them
    jitted; the default runs them op by op).  blank: ``compose_blank`` of
    this capacity, already on the device, to reuse.  Returns None when
    composition does not apply (``compose_offsets``).
    """
    offs = compose_offsets(entries, capacity)
    if offs is None:
        return None
    place = place or (lambda *a: compose_place(specs, *a))
    finish = finish or (lambda *a: compose_finish(specs, *a))
    acc = compose_blank(entries[0], capacity) if blank is None else blank
    for b, e in enumerate(entries):
        acc = place(acc, e.arrays, np.int32(b),
                    {level_key(s): (np.int32(o[b]), np.int32(o[b + 1] - o[b]))
                     for s, o in offs.items()})
    return finish(acc, {level_key(s): np.int32(o[-1])
                        for s, o in offs.items()})


# ---------------------------------------------------------------------------
# Incremental down-ladder (cross-level delta maps): streaming deltas propagate
# through the pyramid as exact per-cell occupancy counts, so a delta-merged
# scene rebuilds its map stack from adopted tables at EVERY level — no
# per-level masked-key argsort on the merged root.  All host-side numpy.
# ---------------------------------------------------------------------------
#
# State per down level s: the sorted unique floor-grid cell keys (folded to
# int64 scalars for two-word specs) plus, per cell, the number of root rows
# inside it.  Counts make removal exact: a cell leaves the level exactly when
# its last root row leaves the scene.  Note masking a sorted key array does
# NOT keep it sorted (flooring two packed fields can swap neighbors), so the
# initial derivation argsorts per level — but chained level-from-previous-
# level (masks nest across pow2 strides), on strictly shrinking arrays, and
# the per-frame delta path (``cell_ladder_delta``) only ever sorts the delta.


def _fold_keys(keys: np.ndarray, words: int) -> np.ndarray:
    """Order-isomorphic int64 scalar fold of packed key rows
    (``hashing._np_cmp_keys``), always int64 so masks compose."""
    return np.asarray(hashing._np_cmp_keys(np.asarray(keys), words),
                      dtype=np.int64).reshape(-1)


def _fold_grid_mask(spec: KeySpec, out_stride: int) -> Optional[int]:
    """AND-mask on *folded* keys equivalent to per-word grid masking.  Valid
    packed low words are non-negative (fields live in bits 0..29), so the
    fold's ``lo - int32_min`` bias only sets bit 31 — kept in the mask."""
    if spec.raw:
        return None
    ints = _grid_mask_ints(spec, out_stride)
    if ints is None:
        return None
    if spec.words == 1:
        return ints[0]
    hi, lo = ints
    return (hi << 32) | (1 << 31) | lo


def _unique_counts(vals: np.ndarray, cnts: np.ndarray):
    """(unique sorted vals, summed counts) of an unsorted (vals, cnts) pair."""
    o = np.argsort(vals, kind="stable")
    v, c = vals[o], cnts[o]
    if not v.size:
        return v, c
    first = np.empty(v.shape, bool)
    first[0] = True
    np.not_equal(v[1:], v[:-1], out=first[1:])
    starts = np.flatnonzero(first)
    return v[starts], np.add.reduceat(c, starts)


def cell_ladder(spec: KeySpec, root_keys: np.ndarray,
                down_strides: Sequence[int]) -> Dict[int, tuple]:
    """Initial down-ladder occupancy state of a scene.

    root_keys: the scene's packed sorted keys, exact size (no PAD rows).
    Returns {down out-stride: (folded cell keys — sorted unique int64,
    int64 per-cell root-row counts)}.  Level s's cells are exactly
    ``unique(mask_s(root))``; since pow2 grid masks nest, each level is
    derived from the previous (smaller) level's cells with counts summed
    through.  Stops at the first stride whose masking doesn't apply; raw
    specs return {} (callers fall back to root-table-only adoption).
    """
    if spec.raw:
        return {}
    vals = _fold_keys(root_keys, spec.words)
    cnts = np.ones(vals.shape, np.int64)
    out: Dict[int, tuple] = {}
    for s in sorted(down_strides):
        fm = _fold_grid_mask(spec, s)
        if fm is None:
            break
        vals, cnts = _unique_counts(vals & fm, cnts)
        out[s] = (vals, cnts)
    return out


def cell_ladder_delta(spec: KeySpec, ladder: Dict[int, tuple],
                      removed_keys: np.ndarray,
                      added_keys: np.ndarray) -> Dict[int, tuple]:
    """Propagate a root delta through the cell ladder: per level an O(r+a)
    sort of the delta plus an O(cells) splice — never a sort of the full
    cloud.  ``removed_keys``/``added_keys`` are packed root key rows (exact
    sets: removed rows were present, added rows were absent).  Returns fresh
    {out-stride: (cells, counts)}; the input ladder is not mutated.
    """
    w = spec.words
    rem = _fold_keys(removed_keys, w)
    add = _fold_keys(added_keys, w)
    out: Dict[int, tuple] = {}
    for s, (cells, cnts) in ladder.items():
        fm = _fold_grid_mask(spec, s)
        dv = np.concatenate([rem & fm, add & fm])
        dc = np.concatenate([np.full(rem.shape, -1, np.int64),
                             np.ones(add.shape, np.int64)])
        dv, dc = _unique_counts(dv, dc)
        live = dc != 0
        dv, dc = dv[live], dc[live]
        pos = np.searchsorted(cells, dv)
        hit = np.zeros(dv.shape, bool)
        in_r = pos < cells.size
        hit[in_r] = cells[pos[in_r]] == dv[in_r]
        new_cnts = cnts.copy()
        new_cnts[pos[hit]] += dc[hit]
        keep = new_cnts > 0
        base_v, base_c = cells[keep], new_cnts[keep]
        ins_v, ins_c = dv[~hit], dc[~hit]  # unseen cells can only gain rows
        if ins_v.size:
            ip = np.searchsorted(base_v, ins_v)
            base_v = np.insert(base_v, ip, ins_v)
            base_c = np.insert(base_c, ip, ins_c)
        out[s] = (base_v, base_c)
    return out


def ladder_tables(spec: KeySpec, ladder: Dict[int, tuple],
                  capacity: int) -> Dict[int, tuple]:
    """Unfold ladder cells into the padded sorted-key arrays that
    ``build_maps_from_specs(tables=...)`` adopts: {down out-stride: (keys
    padded to ``capacity`` with PAD rows, None, n)} as numpy — every down
    level of a delta-merged scene build then takes the table-adoption path
    instead of re-argsorting masked keys."""
    out: Dict[int, tuple] = {}
    i32min = int(np.iinfo(np.int32).min)
    for s, (cells, _) in ladder.items():
        m = int(cells.shape[0])
        if m > capacity:
            return {}
        if spec.words == 1:
            keys = np.full((capacity,), _I32_MAX, np.int32)
            keys[:m] = cells.astype(np.int32)
        else:
            keys = np.full((capacity, 2), _I32_MAX, np.int32)
            keys[:m, 0] = (cells >> np.int64(32)).astype(np.int32)
            keys[:m, 1] = ((cells & np.int64(0xFFFFFFFF)) + i32min).astype(np.int32)
        out[s] = (keys, None, m)
    return out


# ---------------------------------------------------------------------------
# Sorting + mask splits (Sparse Autotuner design-space, paper §4.1)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SplitPlan:
    """Row orders and offset ranges for s-split (un)sorted implicit GEMM.

    order[s]   : (N_out_cap,) permutation of output rows for split s.
    inv_order[s]: inverse permutations (to undo the reordering on write-back).
    ranges     : static ((start, end), ...) partition of the KD offsets.
    sorted_    : False ⇒ identity order (paper's "unsorted", split=0 case).
    occupancy  : optional (S, n_tiles, KD) per-(split, tile, δ) occupancy,
                 fused into the plan pass when ``make_split_plan(tile_m=...)``.
    tile_m     : static tile height the occupancy was computed for (0 = none).
    """

    order: jax.Array       # (S, N_out_cap) int32
    inv_order: jax.Array   # (S, N_out_cap) int32
    ranges: Tuple[Tuple[int, int], ...] = dataclasses.field(metadata=dict(static=True))
    sorted_: bool = dataclasses.field(metadata=dict(static=True), default=True)
    occupancy: Optional[jax.Array] = None
    tile_m: int = dataclasses.field(metadata=dict(static=True), default=0)

    @property
    def num_splits(self) -> int:
        return len(self.ranges)


def split_ranges(volume: int, n_splits: int) -> Tuple[Tuple[int, int], ...]:
    """Partition KD offsets into ~equal contiguous ranges."""
    n_splits = max(1, min(n_splits, volume))
    bounds = np.linspace(0, volume, n_splits + 1).round().astype(int)
    return tuple((int(bounds[i]), int(bounds[i + 1])) for i in range(n_splits))


def make_split_plan(kmap: KernelMap, n_splits: int, sort: bool = True,
                    tile_m: Optional[int] = None) -> SplitPlan:
    """Paper Fig. 10: split the δ loop into s parts, argsort each split's
    bitmask independently and reorder rows per split.  ``n_splits=1, sort``
    reproduces SpConv v2 (Fig. 6); ``sort=False`` is the unsorted dataflow
    (Fig. 5) the paper re-adds to the design space.

    One pass over ``m_out``: per-split bitmasks are bit-sliced out of the
    stored ``kmap.bitmask`` (exact for KD ≤ 31), and passing ``tile_m``
    additionally emits the per-(split, tile, δ) occupancy on the already-
    permuted hit matrix instead of a separate ``tile_occupancy`` pass.
    """
    ranges = split_ranges(kmap.volume, n_splits)
    cap = kmap.capacity
    kd = kmap.volume
    hit = kmap.m_out >= 0
    valid = jnp.arange(cap) < kmap.n_out

    orders = []
    for (a, b) in ranges:
        if not sort:
            orders.append(jnp.arange(cap, dtype=jnp.int32))
            continue
        if kd <= 31:
            bm = (kmap.bitmask >> a) & jnp.int32((1 << (b - a)) - 1)
        else:
            bm = _bitmask(hit[:, a:b])
        # valid rows first (sorted by bitmask), padding last
        key = jnp.where(valid, bm, jnp.iinfo(jnp.int32).max)
        if kd <= 31 and (b - a) <= 29 and hashing.radix_enabled():
            orders.append(hashing.radix_argsort_padded(key, b - a))
        else:
            orders.append(jnp.argsort(key).astype(jnp.int32))
    order = jnp.stack(orders)
    inv = jax.vmap(lambda o: jnp.argsort(o).astype(jnp.int32))(order)

    occ = None
    if tile_m is not None:
        hit_i = hit.astype(jnp.int32)
        occ = jnp.stack([_split_occupancy(hit_i, order[s], r, tile_m)
                         for s, r in enumerate(ranges)])

    return SplitPlan(order=order, inv_order=inv, ranges=ranges, sorted_=sort,
                     occupancy=occ, tile_m=tile_m or 0)


def _scene_hits(specs: Dict[tuple, object], entry: SceneEntry,
                ref: tuple) -> np.ndarray:
    """One scene's (rows, KD) hit matrix of map ``ref`` (``specs``: map ref
    -> spec), fetched from its device arrays: a searched map's own rows, an
    "up" map's as the transpose of its forward map's pairs, a "slice"
    map's as its parent's columns."""
    ms = specs[ref]
    if ms.kind == "slice":
        return _scene_hits(specs, entry, ms.slice_of)[:, offset_columns(
            ms.kernel_size, specs[ms.slice_of].kernel_size, entry.ndim)]
    if ms.kind == "up":
        coarse, fine = map_strides(ms)
        fwd = np.asarray(entry.arrays["m_out"][ms.transpose_of])
        fwd = fwd[:entry.sizes[coarse]]
        hit = np.zeros((entry.sizes[fine], fwd.shape[1]), bool)
        c, k = np.nonzero(fwd >= 0)
        hit[fwd[c, k], k] = True
        return hit
    n_o = entry.sizes[map_strides(ms)[1]]
    return np.asarray(entry.arrays["m_out"][ref])[:n_o] >= 0


def _scene_split_keys(specs: Dict[tuple, object], entry: SceneEntry,
                      ref: tuple, ranges: Tuple[Tuple[int, int], ...]) -> list:
    """Per-split (sorted bitmask values, local stable order) of one scene's
    cached map — the per-scene half of a composed ``SplitPlan``.  Computed
    once per (ref, ranges) with numpy stable argsorts and cached on the
    entry; every subsequent batch containing the scene merge-composes the
    cached runs instead of re-sorting."""
    ck = (ref, ranges)
    cached = entry.splits.get(ck)
    if cached is not None:
        return cached
    hit = _scene_hits(specs, entry, ref)
    kd = hit.shape[1]
    runs = []
    for a, b in ranges:
        # the split's bits of the row bitmask (``make_split_plan``)
        bm = _np_bitmask(hit[:, a:b])
        if kd <= 31 and (b - a) <= 29 and hashing.radix_enabled():
            loc = hashing.np_radix_argsort_bits(bm, b - a)
        else:
            loc = np.argsort(bm, kind="stable").astype(np.int32)
        runs.append((bm[loc], loc))
    entry.splits[ck] = runs
    return runs


def _merge_sorted_runs(vals_a, ord_a, vals_b, ord_b):
    """Stable two-way merge of two sorted runs whose A row indices all
    precede B's — ties land A-first (the ``np_delta_merge`` searchsorted
    pattern), matching a stable sort of the concatenation."""
    pos_a = np.arange(vals_a.size) + np.searchsorted(vals_b, vals_a, side="left")
    pos_b = np.arange(vals_b.size) + np.searchsorted(vals_a, vals_b, side="right")
    vals = np.empty(vals_a.size + vals_b.size, vals_a.dtype)
    order = np.empty(vals.size, np.int32)
    vals[pos_a] = vals_a
    vals[pos_b] = vals_b
    order[pos_a] = ord_a
    order[pos_b] = ord_b
    return vals, order


def compose_split_plans(specs, entries: Sequence[SceneEntry], ref: tuple,
                        n_splits: int, sort: bool, capacity: int) -> SplitPlan:
    """Merge-compose per-scene sorted split orders into the batch
    ``SplitPlan`` — host-side numpy, no device argsort on the batch path.

    Bit-identical to ``make_split_plan(compose_kmaps(specs, entries,
    capacity)[ref], n_splits, sort)``: jnp's argsort is stable, so sorting
    the concatenated per-scene bitmask blocks (pad tail at int32 max) IS
    the stable k-way merge of the per-scene stable-sorted runs — ties break
    toward the lower global row, i.e. the earlier scene — followed by the
    pad rows in slot order.  Callers must pass the same entries/capacity
    that composed the kernel maps.
    """
    specs = {ms.ref: ms for ms in specs}
    ms = specs[ref]
    ranges = split_ranges(
        math.prod(axis_strides(ms.kernel_size, entries[0].ndim)), n_splits)
    cap = capacity
    if not sort:
        eye = np.ascontiguousarray(np.broadcast_to(
            np.arange(cap, dtype=np.int32), (len(ranges), cap)))
        order = jnp.asarray(eye)
        return SplitPlan(order=order, inv_order=order, ranges=ranges,
                         sorted_=False)
    out_s = map_strides(ms)[1]
    offs = np.cumsum([0] + [e.sizes[out_s] for e in entries])
    total = int(offs[-1])
    per_scene = [_scene_split_keys(specs, e, ref, ranges) for e in entries]
    order = np.empty((len(ranges), cap), np.int32)
    for s in range(len(ranges)):
        vals, merged = per_scene[0][s]
        for b in range(1, len(entries)):
            sv, so = per_scene[b][s]
            vals, merged = _merge_sorted_runs(vals, merged,
                                              sv, so + np.int32(offs[b]))
        order[s, :total] = merged
        order[s, total:] = np.arange(total, cap, dtype=np.int32)
    inv = np.empty_like(order)
    rows = np.arange(cap, dtype=np.int32)
    for s in range(len(ranges)):
        inv[s, order[s]] = rows
    # one batched transfer: two separate jnp.asarray dispatches would double
    # the per-batch host->device overhead that dominates at small capacities
    order_d, inv_d = jax.device_put((order, inv))
    return SplitPlan(order=order_d, inv_order=inv_d,
                     ranges=ranges, sorted_=True)


def _split_occupancy(hit: jax.Array, order: jax.Array, rng: Tuple[int, int],
                     tile_m: int) -> jax.Array:
    """(n_tiles, KD) occupancy of one split: 1 iff any row of the permuted
    tile has a neighbor at δ, zeroed outside the split's offset range."""
    cap, kd = hit.shape
    assert cap % tile_m == 0, "capacity must be padded to tile_m (paper §3.2)"
    a, b = rng
    h = hit[order].reshape(cap // tile_m, tile_m, kd)
    col = jnp.arange(kd)
    in_range = ((col >= a) & (col < b)).astype(jnp.int32)
    return jnp.max(h, axis=1) * in_range[None, :]


def tile_occupancy(kmap: KernelMap, plan: SplitPlan, tile_m: int) -> jax.Array:
    """Per-(split, tile, δ) occupancy: 1 iff any row of the tile has a
    neighbor at δ within the split's range (else the whole MXU tile matmul is
    skipped — the TPU analogue of warp-level zero skipping).

    Returns (S, n_tiles, KD) int32 (columns outside the split's range are 0).
    Reuses the plan's fused occupancy when it was built with the same
    ``tile_m``; otherwise recomputes.
    """
    if plan.occupancy is not None and plan.tile_m == tile_m:
        return plan.occupancy
    hit = (kmap.m_out >= 0).astype(jnp.int32)
    return jnp.stack([_split_occupancy(hit, plan.order[i], r, tile_m)
                      for i, r in enumerate(plan.ranges)])


def redundancy_stats(kmap: KernelMap, plan: SplitPlan, tile_m: int) -> dict:
    """Effective vs issued MACs (paper Fig. 11): issued = Σ occupied tiles ×
    tile_m; effective = Σ hits.  The autotuner's analytic cost model reads
    these."""
    occ = tile_occupancy(kmap, plan, tile_m)
    issued_rows = jnp.sum(occ) * tile_m
    effective = jnp.sum(kmap.m_out >= 0)
    return dict(issued_rows=issued_rows, effective_rows=effective,
                overhead=issued_rows / jnp.maximum(effective, 1))
