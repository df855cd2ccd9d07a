"""Coordinate hashing and sort-based lookup — int32-only, collision-free.

The paper builds kernel maps with a GPU hash table.  The TPU-idiomatic (and
JAX-native) equivalent is a *sort-merge join*: sort the coordinate table
once per map group and answer all K^D shifted queries by sorting them
together with it (``join_lookup``: two sorts and two scans, fully static
shapes, no data-dependent gather).  PointAcc (the ASIC the paper compares
against) and Minuet make the same observation — point-cloud mapping
operators reduce to sort/merge primitives.

Packed-key engine (the fast path)
---------------------------------
``CoordTable`` packs each ``(batch, x, y, z)`` row into a single int32 key
(or an ``(hi, lo)`` int32 key pair when the bit budget exceeds one word), so

* table construction is **one** ``argsort`` over scalar keys (two chained
  stable argsorts for the pair case), not one stable argsort per column;
* the lookup sorts **scalar** keys (the pair and raw cases keep a bisect
  with pair or row compares), not 4-word rows;
* all K^D shifted queries of a kernel map are answered as one flattened
  batched lookup of shape ``(K^D · N,)``.

Bit budgets are derived from the tensor's *declared* bounds by
``key_spec_for``: ``batch_bits = ceil(log2(batch_bound))`` and, per spatial
axis, ``ceil(log2(spatial_bound + 65)) + 1`` bits — one sign bit plus ≥64
voxels of headroom so strided floor-grids and shifted queries stay
representable.  Spatial fields are biased by ``2^(bits-1)`` (offset binary),
which keeps negative coordinates sort-correct.  Tensors that declare no
bounds (or whose bounds exceed the two-word budget) get the ``raw`` spec:
the key words are the coordinate columns themselves — no range limits, the
seed's multi-word contract — still driven through the batched-lookup,
pair-list compaction and MapCache machinery.  Packing is order-isomorphic
to the lexicographic order on rows, so packed tables sort and deduplicate
exactly like the multi-word path.

Out-of-range *queries* (e.g. a kernel shift off the edge of the declared
bounds, or the ``INVALID_COORD`` padding sentinel) pack to the ``MISS`` key
(-1), which can never equal a table key; out-of-range or padded *table* rows
pack to ``PAD`` (int32 max), which sorts last.  Everything is int32 (x64
stays disabled framework-wide).

Composable tables (scene-granular and streaming reuse)
------------------------------------------------------
Because the batch index is the *most significant* key field, the sorted key
array of a packed batch is exactly the batch-major concatenation of each
scene's own sorted (batch-0) table with the batch bits added in.  Two O(N)
merge primitives exploit that (Minuet's observation, lifted to first-class
table operations):

* ``compose_tables`` — build a batch table by merge-composing per-scene
  sorted tables (one key-delta add + concatenation per scene; no argsort),
  bit-identical to ``CoordTable.build`` on the packed batch;
* ``CoordTable.delta_merge`` — update a streaming scene's table by merging
  a small sorted insertion/eviction delta instead of re-sorting the full
  cloud, bit-identical to a fresh build of the updated scene.

(``SortedCoords``, the seed's multi-word reference table, and the
``engine="legacy"`` A/B flag in ``kmap.build_kmap`` were deleted after a
release cycle of bit-identical cross-checks; the property tests now verify
against brute-force numpy oracles.  The word-wise helpers below remain —
they serve multi-word packed keys, ``raw`` specs and ``voxelize``.)
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

_I32_MAX = int(jnp.iinfo(jnp.int32).max)

# Usable bits per key word.  Both words are capped at 30 bits so that no
# valid key word can ever equal the PAD sentinel (int32 max) — with 31
# usable bits a maximal in-field value would pack to exactly int32 max and
# be silently treated as padding.
_LO_BITS = 30
_HI_BITS = 30


@dataclasses.dataclass(frozen=True)
class KeySpec:
    """Static bit budget for packing (batch, *spatial) rows into int32 keys.

    Field layout is MSB→LSB ``batch | x | y | z`` so integer order on keys is
    lexicographic order on rows.  Fields never straddle the word boundary:
    the layout pads a field up to the next word instead (wasting a few bits
    but keeping pack/unpack to one shift+mask per field).

    ``raw=True`` is the no-range-limit fallback: the key "words" are simply
    the coordinate columns themselves (MSB-first: batch, x, y, z), valid for
    the full int32 range — exactly the seed's multi-word table, but still
    driven through the batched-lookup / pair-list compaction / MapCache
    machinery.  Used when no bounds are declared or the declared bounds
    exceed the two-word bit budget.
    """

    batch_bits: int
    spatial_bits: Tuple[int, ...]
    raw: bool = False

    @property
    def ndim_space(self) -> int:
        return len(self.spatial_bits)

    def _place_fields(self):
        """(placements LSB-first, in_budget) without raising — the budget
        check must hold even under ``python -O`` (no assert reliance)."""
        widths = list(self.spatial_bits)[::-1] + [self.batch_bits]  # LSB first
        placed = []
        cur = 0
        ok = True
        for w in widths:
            ok = ok and 0 < w <= _HI_BITS
            if cur < _LO_BITS and cur + w > _LO_BITS:
                cur = _LO_BITS  # don't straddle the word boundary
            word = 0 if cur < _LO_BITS else 1
            shift = cur if word == 0 else cur - _LO_BITS
            placed.append((word, shift, w))
            cur += w
            ok = ok and (word == 0 or shift + w <= _HI_BITS)
        return placed, ok

    def layout(self) -> Tuple[Tuple[int, int, int], ...]:
        """Per field (MSB-first: batch, x, y, …): (word, shift, width).

        word 0 is the low word (bit offsets 0..29), word 1 the high word
        (offsets 30..59).  Single-word specs place everything in word 0.
        """
        if self.raw:
            raise ValueError("raw specs have no packed layout")
        placed, ok = self._place_fields()
        if not ok:
            raise ValueError(f"KeySpec {self} exceeds the 60-bit two-word budget")
        # back to MSB-first (batch, x, y, z)
        return tuple(placed[::-1])

    def fits(self) -> bool:
        """True iff the budget packs into at most two 30-bit words."""
        return not self.raw and self._place_fields()[1]

    @property
    def words(self) -> int:
        if self.raw:
            return 1 + self.ndim_space
        return 1 + max(w for w, _, _ in self.layout())

    @property
    def total_bits(self) -> int:
        return self.batch_bits + sum(self.spatial_bits)


def key_spec_for(ndim_space: int, batch_bound: int = 0,
                 spatial_bound: int = 0) -> KeySpec:
    """Derive the bit budget from a tensor's declared bounds.

    ``batch_bound``: number of batches (coords in [0, batch_bound)); 0 = unknown.
    ``spatial_bound``: max |spatial coordinate|; 0 = unknown.  Unknown or
    too-large bounds fall back to the ``raw`` coordinate-column spec, which
    has no range limits (and a correspondingly wider sort/compare).
    """
    if batch_bound <= 0 or spatial_bound <= 0:
        return KeySpec(batch_bits=32, spatial_bits=(32,) * ndim_space, raw=True)
    bb = max(1, math.ceil(math.log2(max(batch_bound, 2))))
    sb = math.ceil(math.log2(spatial_bound + 65)) + 1
    spec = KeySpec(batch_bits=bb, spatial_bits=(sb,) * ndim_space)
    if not spec.fits():
        return KeySpec(batch_bits=32, spatial_bits=(32,) * ndim_space, raw=True)
    return spec


def pack_keys(coords: jax.Array, spec: KeySpec, valid=None,
              query: bool = False) -> jax.Array:
    """Pack coordinate rows ``(..., 1+D)`` into int32 keys.

    Returns ``(...,)`` for single-word specs, ``(..., W)`` MSB-first
    otherwise (``[hi, lo]`` for two-word packed specs; the coordinate
    columns themselves for ``raw`` specs).  Rows that are masked out by
    ``valid`` or fall outside the declared per-field range become ``PAD``
    (int32 max in every word, sorts last) — or ``MISS`` (-1 in every word,
    matches nothing) when ``query=True``.
    """
    c = coords.astype(jnp.int32)
    if spec.raw:
        if valid is None:
            return c
        sentinel = jnp.int32(-1 if query else _I32_MAX)
        return jnp.where(valid[..., None], c, sentinel)
    layout = spec.layout()
    words = spec.words
    lo = jnp.zeros(c.shape[:-1], jnp.int32)
    hi = jnp.zeros(c.shape[:-1], jnp.int32)
    b = c[..., 0]
    ok = (b >= 0) & (b < (1 << spec.batch_bits))
    for f, (word, shift, width) in enumerate(layout):
        if f == 0:
            val = b
        else:
            half = 1 << (width - 1)
            v = c[..., f]
            ok = ok & (v >= -half) & (v < half)
            val = v + half
        contrib = val << shift
        if word == 0:
            lo = lo + contrib
        else:
            hi = hi + contrib
    if valid is not None:
        ok = ok & valid
    sentinel = jnp.int32(-1 if query else _I32_MAX)
    lo = jnp.where(ok, lo, sentinel)
    if words == 1:
        return lo
    hi = jnp.where(ok, hi, sentinel)
    return jnp.stack([hi, lo], axis=-1)


def unpack_keys(keys: jax.Array, spec: KeySpec) -> jax.Array:
    """Inverse of ``pack_keys`` for in-range keys → ``(..., 1+D)`` int32.

    Sentinel keys produce garbage rows; callers mask them via validity.
    """
    if spec.raw:
        return keys
    if spec.words == 1:
        hi, lo = jnp.zeros_like(keys), keys
    else:
        hi, lo = keys[..., 0], keys[..., 1]
    cols = []
    for f, (word, shift, width) in enumerate(spec.layout()):
        src = lo if word == 0 else hi
        val = (src >> shift) & ((1 << width) - 1)
        cols.append(val if f == 0 else val - (1 << (width - 1)))
    return jnp.stack(cols, axis=-1)


def keys_less(a: jax.Array, b: jax.Array, words: int = 1) -> jax.Array:
    """a < b for packed keys (scalar when words==1, MSB-first rows else)."""
    if words == 1:
        return a < b
    return _lex_less(a, b)


def keys_equal(a: jax.Array, b: jax.Array, words: int = 1) -> jax.Array:
    if words == 1:
        return a == b
    return jnp.all(a == b, axis=-1)


def searchsorted_keys(sorted_keys: jax.Array, q: jax.Array, words: int = 1,
                      side: str = "left") -> jax.Array:
    """Insertion positions of ``q`` in packed sorted keys — the multi-word
    generalization of ``jnp.searchsorted``.  Returns int32 positions in
    ``[0, n]``."""
    if words == 1:
        return jnp.searchsorted(sorted_keys, q, side=side).astype(jnp.int32)
    n = sorted_keys.shape[0]
    m = q.shape[0]
    if n == 0:
        return jnp.zeros((m,), jnp.int32)
    lo = jnp.zeros((m,), jnp.int32)
    hi = jnp.full((m,), n, jnp.int32)
    for _ in range(max(1, math.ceil(math.log2(max(n, 2))) + 1)):
        active = lo < hi
        mid = (lo + hi) // 2
        row = sorted_keys[jnp.clip(mid, 0, n - 1)]
        adv = _lex_less(row, q) if side == "left" else ~_lex_less(q, row)
        lo = jnp.where(active & adv, mid + 1, lo)
        hi = jnp.where(active & ~adv, mid, hi)
    return lo


# ---------------------------------------------------------------------------
# O(N) radix sort for bounded packed keys (ROADMAP item 1; Minuet-style —
# the declared bit budget caps key entropy, so a bit-serial stable partition
# replaces XLA's O(N log N) comparison argsort on the table-build hot path)
# ---------------------------------------------------------------------------

def radix_enabled() -> bool:
    """Policy switch for the O(N·bits) radix sort tier.

    Default: on for compiled TPU execution (comparison sorts lower to the
    O(N·log²N) bitonic network there; the bit-partition passes beat it at
    table scale) and OFF for CPU/interpret containers, where XLA runs the
    ~30 sequential cumsum+scatter passes serially and a single comparison
    argsort wins outright (bench: ``kmap/speedup/key_sort``).  Both paths
    produce bit-identical permutations, so this flips cost, never layout.
    ``REPRO_RADIX_SORT=1/0`` overrides for A/B runs.
    """
    env = os.environ.get("REPRO_RADIX_SORT")
    if env is not None:
        return env not in ("0", "false", "")
    from repro.kernels.common import default_interpret
    return not default_interpret()


def radix_word_bits(spec: KeySpec) -> Optional[Tuple[int, ...]]:
    """Per-word used bit counts, indexed by word number (0 = low word), for
    a bounded packed spec — or ``None`` when the spec is raw / over budget
    (no bit bound ⇒ no radix; comparison sort stays)."""
    if spec.raw or not spec.fits():
        return None
    used = [0, 0]
    for word, shift, width in spec.layout():
        used[word] = max(used[word], shift + width)
    return tuple(used[:spec.words])


def _remap_radix_word(vals, nbits: int):
    """Map one key word onto the dense radix domain ``[0, 2**(nbits+1))``:
    ``MISS`` (-1) → 0, valid ``v ∈ [0, 2**nbits)`` → ``v+1``, ``PAD``
    (int32 max) → ``2**nbits + 1``.  Order-preserving (MISS first, PAD
    last — the signed-compare layout), so a radix sort of the remapped
    word is bit-identical to a stable argsort of the original."""
    return jnp.where(vals == _I32_MAX, jnp.int32((1 << nbits) + 1),
                     vals + jnp.int32(1))


def radix_argsort_bits(vals: jax.Array, nbits: int) -> jax.Array:
    """Stable argsort of non-negative int32 ``vals < 2**nbits`` in
    O(N·nbits): one stable binary partition (cumsum + scatter) per bit,
    LSB first.  Bit-identical to ``jnp.argsort(vals, stable=True)``."""
    n = vals.shape[0]
    order = jnp.arange(n, dtype=jnp.int32)
    if n == 0 or nbits <= 0:
        return order

    def body(b, carry):
        r, o = carry
        bit = (r >> b) & 1
        zeros = jnp.cumsum(1 - bit)
        pos = jnp.where(bit == 0, zeros - 1, zeros[-1] + jnp.cumsum(bit) - 1)
        return (jnp.zeros_like(r).at[pos].set(r),
                jnp.zeros_like(o).at[pos].set(o))

    _, order = jax.lax.fori_loop(0, nbits, body, (vals, order))
    return order


def radix_argsort_padded(vals: jax.Array, nbits: int) -> jax.Array:
    """Stable radix argsort of ``vals ∈ [0, 2**nbits) ∪ {MISS, PAD}`` —
    remaps the sentinels onto the dense domain then bit-partitions.
    Needs ``nbits ≤ 29`` so the remapped domain stays inside int32."""
    return radix_argsort_bits(_remap_radix_word(vals, nbits), nbits + 1)


def radix_argsort_keys(keys: jax.Array, spec: KeySpec) -> jax.Array:
    """O(N·bits) stable radix argsort of packed keys.  Requires a bounded
    spec; two-word keys chain lo-word then hi-word passes (stable LSD).
    The permutation is bit-identical to ``sort_keys``'s argsort, pads and
    MISS sentinels included."""
    wb = radix_word_bits(spec)
    if wb is None:
        raise ValueError(f"radix sort needs a bounded spec, got {spec}")
    if spec.words == 1:
        return radix_argsort_bits(_remap_radix_word(keys, wb[0]), wb[0] + 1)
    lo = _remap_radix_word(keys[:, 1], wb[0])
    hi = _remap_radix_word(keys[:, 0], wb[1])
    order = radix_argsort_bits(lo, wb[0] + 1)
    return order[radix_argsort_bits(hi[order], wb[1] + 1)]


def sort_keys(keys: jax.Array, spec: Optional[KeySpec] = None):
    """Argsort packed keys.  With a bounded ``spec``, an O(N·bits) stable
    radix sort keyed off the declared bit budget; otherwise one comparison
    argsort for scalar keys / one chained stable argsort per word
    (least-significant first) for multi-word keys.  The permutation is
    identical either way.  Returns (order, sorted_keys)."""
    if spec is not None and radix_word_bits(spec) is not None \
            and radix_enabled():
        order = radix_argsort_keys(keys, spec)
        return order, keys[order]
    if keys.ndim == 1:
        order = jnp.argsort(keys, stable=True).astype(jnp.int32)
    else:
        order = lex_argsort(keys)
    return order, keys[order]


def join_lookup(sorted_keys: jax.Array, order: jax.Array,
                q: jax.Array) -> jax.Array:
    """Sort-merge join of one-word query keys against a sorted table:
    ``order`` of the table row whose key equals each query, or -1.

    The table (``n ≥ 1`` rows) and the ``M`` queries are sorted together
    once on (key, tag), table rows first among equal keys.  A cumulative
    max carries the last table key forward (table keys are ascending), and
    a cumulative sum its row: each table row's tag holds the step from the
    previous table row's ``order`` to its own, so the running sum of those
    steps is the ``order`` of the last table row seen.  A second sort on
    the query index puts the answers back in query order; the table rows,
    keyed below every query, drop off the front.  No data-dependent loop,
    gather or scatter: two sorts and two scans of ``n + M`` elements.

    MISS queries (-1) and PAD table rows (int32 max) never meet: MISS sorts
    before every table key and no query packs to PAD, so duplicate PAD rows
    are harmless.  Any number of queries may share a key."""
    n = sorted_keys.shape[0]
    m = q.shape[0]
    step = order - jnp.concatenate([jnp.zeros((1,), order.dtype), order[:-1]])
    # table tags in [-2n+1, -1] (so below every query index) carry the step
    keys = jnp.concatenate([sorted_keys, q.astype(jnp.int32)])
    tags = jnp.concatenate([(step - n).astype(jnp.int32),
                            jnp.arange(m, dtype=jnp.int32)])
    keys, tags = jax.lax.sort((keys, tags), num_keys=2)
    is_table = tags < 0
    last_key = jax.lax.cummax(
        jnp.where(is_table, keys, jnp.iinfo(jnp.int32).min))
    last_row = jnp.cumsum(jnp.where(is_table, tags + n, 0), dtype=jnp.int32)
    found = jnp.where(~is_table & (last_key == keys), last_row, -1)
    # the same sort as the first (two keys; table rows tie at (-1, -1)), so
    # the compiled program holds one sort routine for both
    _, found = jax.lax.sort((jnp.where(is_table, -1, tags), found),
                            num_keys=2)
    return found[n:]


def lookup_batched(requests: Sequence[Tuple["CoordTable", jax.Array]]) -> list:
    """``table.lookup_keys(q)`` for each ``(table, q)`` pair, as few joins
    as the tables allow: the queries of one table are joined in one go, and
    the one-word joins of tables of one size run as one batched (vmapped)
    join, each query set padded with MISS keys to the longest."""
    by_table: dict = {}
    for i, (table, _) in enumerate(requests):
        by_table.setdefault(id(table), (table, []))[1].append(i)
    found: list = [None] * len(requests)
    joins: dict = {}
    for table, idx in by_table.values():
        if table.spec.words != 1 or table.n == 0:
            for i in idx:
                found[i] = table.lookup_keys(requests[i][1])
            continue
        q = jnp.concatenate([requests[i][1] for i in idx])
        joins.setdefault(table.n, []).append((table, q, idx))
    for group in joins.values():
        m = max(q.shape[0] for _, q, _ in group)
        res = jax.vmap(join_lookup)(
            jnp.stack([t.sorted_keys for t, _, _ in group]),
            jnp.stack([t.order for t, _, _ in group]),
            jnp.stack([jnp.pad(q, (0, m - q.shape[0]), constant_values=-1)
                       for _, q, _ in group]))
        for row, (_, _, idx) in zip(res, group):
            lo = 0
            for i in idx:
                hi = lo + requests[i][1].shape[0]
                found[i] = row[lo:hi]
                lo = hi
    return found


class CoordTable:
    """Sorted packed-key coordinate table answering batched exact-match
    queries.  Construction: pack (elementwise) + one argsort."""

    def __init__(self, spec: KeySpec, sorted_keys: jax.Array, order: jax.Array):
        self.spec = spec
        self.sorted_keys = sorted_keys
        self.order = order
        self.n = sorted_keys.shape[0]

    @classmethod
    def build(cls, coords: jax.Array, valid_mask: jax.Array,
              spec: KeySpec) -> "CoordTable":
        keys = pack_keys(coords, spec, valid=valid_mask)
        order, sorted_keys = sort_keys(keys, spec)
        return cls(spec, sorted_keys, order)

    @classmethod
    def from_sorted_keys(cls, spec: KeySpec, sorted_keys: jax.Array) -> "CoordTable":
        """Adopt an already-sorted key array (identity order) — used when a
        strided map's unique pass emits the next level's table for free."""
        n = sorted_keys.shape[0]
        return cls(spec, sorted_keys, jnp.arange(n, dtype=jnp.int32))

    def lookup_keys(self, q: jax.Array) -> jax.Array:
        """Original row index of each query key, or -1 if absent. q: (M,)
        int32 or (M, W) — any query count, e.g. the K^D·N flattened batch.

        One-word keys take the sort-merge join (``join_lookup``); two-word
        and raw keys a bisect unrolled over the table size, counted as
        ``kmap.search_bisect`` at trace time."""
        sk = self.sorted_keys
        w = self.spec.words
        if self.n == 0:
            return jnp.full(q.shape[:1], -1, jnp.int32)
        if w == 1:
            return join_lookup(sk, self.order, q)
        obs.count("kmap.search_bisect")
        m = q.shape[0]
        lo = jnp.zeros((m,), jnp.int32)
        hi = jnp.full((m,), self.n, jnp.int32)
        for _ in range(max(1, math.ceil(math.log2(max(self.n, 2))) + 1)):
            mid = (lo + hi) // 2
            less = keys_less(sk[jnp.clip(mid, 0, self.n - 1)], q, w)
            lo = jnp.where(less, mid + 1, lo)
            hi = jnp.where(less, hi, mid)
        pos = jnp.clip(lo, 0, self.n - 1)
        hit = keys_equal(sk[pos], q, w)
        return jnp.where(hit, self.order[pos], -1).astype(jnp.int32)

    def lookup(self, query_coords: jax.Array, valid=None) -> jax.Array:
        """Coordinate-row lookup: pack the query rows, search the table."""
        return self.lookup_keys(pack_keys(query_coords, self.spec,
                                          valid=valid, query=True))

    def delta_merge(self, removed_coords: jax.Array,
                    added_coords: jax.Array) -> "CoordTable":
        """Streaming-frame table update: merge a small sorted delta instead
        of re-sorting the full cloud.

        Requires an *exact-size* table (every row valid, all keys unique —
        the per-scene tables the serving engine caches).  ``removed_coords``
        must all be present (each exactly once) and ``added_coords`` absent.
        The result is bit-identical to ``CoordTable.build`` on the updated
        scene whose row layout is ``[kept rows in original order, then
        added rows]`` — exactly what ``serve.batcher.apply_delta`` produces.

        Cost: two O(r+a) binary-search passes plus O(N) compaction/scatter —
        no O(N log N) argsort of the full cloud.
        """
        spec = self.spec
        w = spec.words
        n = self.n
        r = int(removed_coords.shape[0])
        a = int(added_coords.shape[0])
        n_keep = n - r
        assert n_keep >= 0, (n, r)
        sk, order = self.sorted_keys, self.order
        if r:
            rk = pack_keys(jnp.asarray(removed_coords, jnp.int32), spec,
                           query=True)
            pos = jnp.clip(searchsorted_keys(sk, rk, w, side="left"), 0, n - 1)
            keep = jnp.ones((n,), bool).at[pos].set(False)
            # removal shifts every later row index down by the number of
            # removed rows before it (the fresh build's compacted layout)
            ind = jnp.zeros((n,), jnp.int32).at[order[pos]].set(1)
            shift = jnp.cumsum(ind)
            order = (order - shift[order]).astype(jnp.int32)
        else:
            keep = jnp.ones((n,), bool)
        dest = jnp.where(keep, jnp.cumsum(keep).astype(jnp.int32) - 1, n_keep)
        kept_keys = jnp.full((n_keep + 1,) + sk.shape[1:], _I32_MAX,
                             jnp.int32).at[dest].set(sk, mode="drop")[:n_keep]
        kept_order = jnp.zeros((n_keep + 1,), jnp.int32).at[dest].set(
            order, mode="drop")[:n_keep]
        if not a:
            return CoordTable(spec, kept_keys, kept_order)
        ak = pack_keys(jnp.asarray(added_coords, jnp.int32), spec)
        add_perm, add_sorted = sort_keys(ak, spec)
        add_order = (n_keep + add_perm).astype(jnp.int32)
        # stable two-way merge: scatter both sorted runs at their final ranks
        pos_k = jnp.arange(n_keep, dtype=jnp.int32) + \
            searchsorted_keys(add_sorted, kept_keys, w, side="left")
        pos_a = jnp.arange(a, dtype=jnp.int32) + \
            searchsorted_keys(kept_keys, add_sorted, w, side="right")
        out_keys = (jnp.zeros((n_keep + a,) + sk.shape[1:], jnp.int32)
                    .at[pos_k].set(kept_keys).at[pos_a].set(add_sorted))
        out_order = (jnp.zeros((n_keep + a,), jnp.int32)
                     .at[pos_k].set(kept_order).at[pos_a].set(add_order))
        return CoordTable(spec, out_keys, out_order)


def np_pack_keys(coords: np.ndarray, spec: KeySpec) -> np.ndarray:
    """Numpy twin of ``pack_keys`` for in-range, all-valid rows (the
    host-side streaming path packs delta rows; bounds are the caller's
    declared promise)."""
    c = np.asarray(coords, np.int32)
    if spec.raw:
        return c
    lo = np.zeros(c.shape[:-1], np.int64)
    hi = np.zeros(c.shape[:-1], np.int64)
    for f, (word, shift, width) in enumerate(spec.layout()):
        val = c[..., f].astype(np.int64)
        if f > 0:
            val = val + (1 << (width - 1))
        if word == 0:
            lo += val << shift
        else:
            hi += val << shift
    if spec.words == 1:
        return lo.astype(np.int32)
    return np.stack([hi, lo], axis=-1).astype(np.int32)


def np_radix_argsort_bits(vals: np.ndarray, nbits: int) -> np.ndarray:
    """Numpy twin of ``radix_argsort_bits`` — stable O(N·nbits) bit-serial
    partition, bit-identical to ``np.argsort(vals, kind="stable")`` for
    non-negative ``vals < 2**nbits``."""
    r = np.asarray(vals).astype(np.int64, copy=True)
    n = r.shape[0]
    order = np.arange(n, dtype=np.int32)
    if n == 0 or nbits <= 0:
        return order
    for b in range(nbits):
        bit = (r >> b) & 1
        zeros = np.cumsum(bit == 0)
        pos = np.where(bit == 0, zeros - 1, zeros[-1] + np.cumsum(bit) - 1)
        nr = np.empty_like(r)
        nr[pos] = r
        no = np.empty_like(order)
        no[pos] = order
        r, order = nr, no
    return order


def np_radix_argsort_keys(keys: np.ndarray, spec: KeySpec) -> np.ndarray:
    """Numpy twin of ``radix_argsort_keys`` (host-side scene tables)."""
    wb = radix_word_bits(spec)
    if wb is None:
        raise ValueError(f"radix sort needs a bounded spec, got {spec}")
    keys = np.asarray(keys)

    def remap(v, ub):
        v = v.astype(np.int64)
        return np.where(v == _I32_MAX, (1 << ub) + 1, v + 1)

    if spec.words == 1:
        return np_radix_argsort_bits(remap(keys, wb[0]), wb[0] + 1)
    order = np_radix_argsort_bits(remap(keys[:, 1], wb[0]), wb[0] + 1)
    hi = remap(keys[:, 0], wb[1])
    return order[np_radix_argsort_bits(hi[order], wb[1] + 1)]


def _np_cmp_keys(keys: np.ndarray, words: int) -> Optional[np.ndarray]:
    """Collapse packed keys into one order-isomorphic comparable numpy
    array: identity for scalar keys, a signed-int64 fold for [hi, lo]
    pairs, None for wider (raw) keys."""
    if words == 1:
        return keys
    if words == 2:
        return (keys[..., 0].astype(np.int64) * (1 << 32)
                + (keys[..., 1].astype(np.int64) - np.iinfo(np.int32).min))
    return None


def np_delta_merge(spec: KeySpec, keys: np.ndarray, order: np.ndarray,
                   removed_coords: np.ndarray, added_coords: np.ndarray):
    """Host-side twin of ``CoordTable.delta_merge`` on numpy arrays — the
    serving engine's streaming hot path (scene tables live on the host, and
    numpy has no per-shape compile cost).  Same contract: exact-size sorted
    table, removed rows present, added rows absent; returns ``(keys,
    order)`` bit-identical to a fresh build of ``[kept rows in original
    order, then added rows]``.  Raw (>2-word) specs fall back to one stable
    lexsort of the merged key set — still host-only, still exact."""
    keys = np.asarray(keys)
    order = np.asarray(order, np.int32)
    n = keys.shape[0]
    r = removed_coords.shape[0]
    a = added_coords.shape[0]
    cmp_keys = _np_cmp_keys(keys, spec.words)
    if r:
        rm = np_pack_keys(removed_coords, spec)
        if cmp_keys is None:
            keep = np.ones((n,), bool)
            view = {tuple(k): i for i, k in enumerate(keys)}
            pos = np.asarray([view[tuple(k)] for k in rm], np.int64)
        else:
            pos = np.searchsorted(cmp_keys, _np_cmp_keys(rm, spec.words))
            keep = np.ones((n,), bool)
        keep[pos] = False
        ind = np.zeros((n,), np.int32)
        ind[order[pos]] = 1
        shift = np.cumsum(ind).astype(np.int32)
        order = order - shift[order]
    else:
        keep = np.ones((n,), bool)
    kept_keys, kept_order = keys[keep], order[keep]
    n_keep = n - r
    if not a:
        return kept_keys, kept_order
    ak = np_pack_keys(added_coords, spec)
    ak_cmp = _np_cmp_keys(ak, spec.words)
    if ak_cmp is None:   # raw fallback: one stable host lexsort, no device
        merged = np.concatenate([kept_keys, ak])
        morder = np.concatenate([kept_order,
                                 n_keep + np.arange(a, dtype=np.int32)])
        perm = lex_argsort_np(merged)
        return merged[perm], morder[perm]
    if radix_word_bits(spec) is not None and radix_enabled():
        perm = np_radix_argsort_keys(ak, spec)   # bounded keys: O(N) radix
    else:
        perm = np.argsort(ak_cmp, kind="stable").astype(np.int32)
    ak, ak_cmp = ak[perm], ak_cmp[perm]
    add_order = (n_keep + perm).astype(np.int32)
    kept_cmp = _np_cmp_keys(kept_keys, spec.words)
    pos_k = np.arange(n_keep) + np.searchsorted(ak_cmp, kept_cmp, side="left")
    pos_a = np.arange(a) + np.searchsorted(kept_cmp, ak_cmp, side="right")
    out_keys = np.empty((n_keep + a,) + keys.shape[1:], np.int32)
    out_order = np.empty((n_keep + a,), np.int32)
    out_keys[pos_k], out_keys[pos_a] = kept_keys, ak
    out_order[pos_k], out_order[pos_a] = kept_order, add_order
    return out_keys, out_order


def lex_argsort_np(words: np.ndarray) -> np.ndarray:
    """Stable lexicographic argsort of (N, W) int32 rows, MSB-first — the
    numpy twin of ``lex_argsort``."""
    return np.lexsort(words.T[::-1]).astype(np.int32)


def batch_key_delta(spec: KeySpec, batch_id: int) -> np.ndarray:
    """Additive key delta rebasing a batch-0 key row to ``batch_id``.

    Returns an ``(spec.words,)`` int32 vector in the same MSB-first column
    order as the packed keys (scalar layouts use the single entry).  Valid
    because the batch field of a batch-0 key is all zeros, so adding the
    shifted batch value equals packing with ``batch_id`` directly.
    """
    b = int(batch_id)
    d = np.zeros((spec.words,), np.int32)
    if spec.raw:
        d[0] = b          # raw keys ARE the coordinate columns, batch first
        return d
    word, shift, width = spec.layout()[0]
    assert 0 <= b < (1 << width), (b, width)
    # MSB-first column order: the batch field always lands in the highest
    # word (it is placed last / most significant), i.e. column 0.
    assert word == spec.words - 1, (word, spec.words)
    d[0] = np.int32(b << shift)
    return d


def rebase_batch_keys(keys, spec: KeySpec, batch_id: int):
    """Rebase batch-0 keys (numpy or jax, ``(n,)`` or ``(n, W)``) to
    ``batch_id`` by adding the batch-field delta."""
    d = batch_key_delta(spec, batch_id)
    if keys.ndim == 1:
        return keys + d[0]
    return keys + d[None, :]


def compose_tables(spec: KeySpec,
                   parts: Sequence[Tuple[np.ndarray, Optional[np.ndarray],
                                         int, int]],
                   capacity: int) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Merge-compose per-scene sorted batch-0 tables into one batch table.

    ``parts``: per scene, in batch order: ``(sorted_keys, order_or_None,
    batch_id, row_offset)`` where the arrays are the scene's *exact-size*
    sorted table (no padding) and ``row_offset`` is the scene's first row in
    the packed batch.  Because the batch index is the most significant key
    field and scenes are packed batch-major, the k-way merge degenerates to
    a concatenation: O(N) total, no argsort.  Padding rows (``PAD`` keys;
    order ``arange(total, capacity)``) reproduce a fresh build's stable-sort
    layout exactly, so the result is bit-identical to ``CoordTable.build``
    on the packed batch.  Host-side numpy (the serving engine composes on
    the host); wrap in ``CoordTable`` after ``jnp.asarray``.
    """
    key_parts, order_parts = [], []
    with_order = bool(parts) and parts[0][1] is not None
    total = 0
    for keys, order, batch_id, row_offset in parts:
        keys = np.asarray(keys)
        key_parts.append(rebase_batch_keys(keys, spec, batch_id)
                         .astype(np.int32, copy=False))
        if with_order:
            order_parts.append(np.asarray(order, np.int32) + np.int32(row_offset))
        total += keys.shape[0]
    assert total <= capacity, (total, capacity)
    tail_shape = (capacity - total,) + key_parts[0].shape[1:] if key_parts \
        else (capacity,) + ((spec.words,) if spec.words > 1 else ())
    key_parts.append(np.full(tail_shape, _I32_MAX, np.int32))
    keys = np.concatenate(key_parts)
    if not with_order:
        return keys, None
    order_parts.append(np.arange(total, capacity, dtype=np.int32))
    return keys, np.concatenate(order_parts)


# ---------------------------------------------------------------------------
# Multi-word helpers (raw/two-word specs, voxelize, non-pow2-stride dedup)
# ---------------------------------------------------------------------------

def lex_argsort(words: jax.Array) -> jax.Array:
    """Stable lexicographic argsort of rows. words: (N, W) int32 → (N,) int32."""
    n, w = words.shape
    order = jnp.arange(n, dtype=jnp.int32)
    # least-significant word first; stable sorts compose lexicographically
    for col in range(w - 1, -1, -1):
        order = order[jnp.argsort(words[order, col], stable=True)]
    return order


def rows_equal(a: jax.Array, b: jax.Array) -> jax.Array:
    """Elementwise row equality for (N, W) word matrices → (N,) bool."""
    return jnp.all(a == b, axis=-1)


def _lex_less(row_a, row_b):
    """row_a < row_b lexicographically; rows are (..., W)."""
    w = row_a.shape[-1]
    lt = row_a[..., 0] < row_b[..., 0]
    eq = row_a[..., 0] == row_b[..., 0]
    for c in range(1, w):
        lt = lt | (eq & (row_a[..., c] < row_b[..., c]))
        eq = eq & (row_a[..., c] == row_b[..., c])
    return lt
