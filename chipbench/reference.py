"""The plain reference's building blocks: neighbour search, convolution and
number formats, written from the published description alone.

A sparse convolution over voxels at tensor stride ``s`` pairs each output
voxel ``o`` with the input voxel at ``o + δ·s`` for every kernel offset
``δ``; the output is ``Σ_δ x[in(o, δ)] @ W[δ]`` over the pairs that exist.

* submanifold (K=3): outputs are the inputs, ``δ ∈ {-1, 0, 1}^3``;
* downsampling (K=2, stride 2): outputs are the distinct cells
  ``floor(c / 2s)·2s``, ``δ ∈ {0, 1}^3``;
* transposed (K=2, stride 2): the inverse of a downsampling map, each fine
  voxel reading its one coarse parent through ``W[δ]`` with
  ``δ = (c - parent) / s``.

``W`` is stored as ``(K^3, Cin, Cout)`` with the offsets in "centre first"
order: ``itertools.product`` order, stably sorted by L1 norm.  That is
the layout of the parameters the program is given, and the only thing the
reference shares with it.

Neighbour search runs in numpy on sorted int64 keys (``scenes.pack``),
outside any timed window.  The forward runs in ``jax.numpy`` on rows
padded to a fixed capacity, at float32 ``highest`` precision, or in a
lower number format for the control (``MODES``).
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.scenes import pack, unpack

#: number formats the reference computes in, every product exact and
#: accumulated in float32 (``highest`` matmul precision).  "f32": float32
#: operands and storage (the reference).  "bf16": operands and stored
#: activations rounded to bfloat16.  "fp8":
#: matmul operands rounded to float8 e4m3 (3 mantissa bits, 4 exponent
#: bits) with one scale per tensor (amax / 240), float32 accumulation,
#: activations stored as bfloat16.  Rounding is ``lax.reduce_precision``,
#: which the compiler keeps: a round trip through a narrower dtype may be
#: folded away on the TPU (an fp8 round trip read no larger error than
#: bf16 there).
MODES = ("f32", "bf16", "fp8")

#: largest finite e4m3 value under reduce_precision's IEEE-style rules
_E4M3_MAX = 240.0


def _round(a, exponent_bits: int, mantissa_bits: int):
    return jax.lax.reduce_precision(a, exponent_bits=exponent_bits,
                                    mantissa_bits=mantissa_bits)


def offsets(kernel_size: int) -> np.ndarray:
    """Kernel offsets in the parameters' (centre first) order."""
    if kernel_size % 2:
        r = range(-(kernel_size // 2), kernel_size // 2 + 1)
    else:
        r = range(kernel_size)
    offs = np.array(list(itertools.product(r, repeat=3)), np.int32)
    return offs[np.argsort(np.abs(offs).sum(1), kind="stable")]


def lookup(keys_sorted: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """Row index of each query key in a sorted key array, -1 if absent."""
    pos = np.searchsorted(keys_sorted, queries)
    pos = np.minimum(pos, len(keys_sorted) - 1)
    return np.where(keys_sorted[pos] == queries, pos, -1).astype(np.int32)


def neighbours(out_coords: np.ndarray, in_coords: np.ndarray, kernel_size: int,
               scale: int) -> np.ndarray:
    """(n_out, K^3) input row of output ``o`` at offset ``δ``: the input
    voxel at ``o + δ·scale``, or -1."""
    keys = pack(in_coords)
    order = np.argsort(keys, kind="stable")
    ks = keys[order]
    offs = offsets(kernel_size) * scale
    q = pack((out_coords[:, None, :] + offs[None]).reshape(-1, 3))
    pos = lookup(ks, q).reshape(len(out_coords), len(offs))
    return np.where(pos >= 0, order[np.maximum(pos, 0)], -1).astype(np.int32)


def downsample(coords: np.ndarray, out_stride: int) -> np.ndarray:
    """The distinct grid cells of ``coords`` at ``out_stride``, in
    lexicographic order."""
    cells = (coords // out_stride) * out_stride
    return unpack(np.unique(pack(cells)))


def parents(fine: np.ndarray, coarse: np.ndarray, stride: int):
    """For a transposed K=2 conv from ``coarse`` (stride 2s) to ``fine``
    (stride s): each fine row's coarse parent row and its offset index."""
    par = (fine // (2 * stride)) * (2 * stride)
    idx = lookup(pack(coarse), pack(par))   # coarse is lexicographic
    delta = (fine - par) // stride
    offs = offsets(2)
    k = np.full(len(fine), -1, np.int32)
    for j, d in enumerate(offs):
        k[(delta == d).all(1)] = j
    return idx, k


class Pyramid:
    """One scene's coordinates at every stride and every map the two
    networks run on, built by the reference's own search."""

    def __init__(self, coords: np.ndarray, levels: int, with_up: bool):
        self.coords = {1: coords}
        self.maps: dict = {("sub", 1): neighbours(coords, coords, 3, 1)}
        s = 1
        for _ in range(levels):
            out = downsample(self.coords[s], 2 * s)
            self.maps[("down", s)] = neighbours(out, self.coords[s], 2, s)
            self.coords[2 * s] = out
            s *= 2
            self.maps[("sub", s)] = neighbours(out, out, 3, s)
        if with_up:
            for lvl in range(levels):
                s = 2 ** lvl
                self.maps[("up", s)] = parents(self.coords[s],
                                               self.coords[2 * s], s)

    def pairs(self, ref) -> int:
        """Valid (input, output) pairs of one map."""
        m = self.maps[ref]
        return int((m[0] >= 0).sum()) if ref[0] == "up" else int((m >= 0).sum())

    def padded(self, cap: int) -> dict:
        """Device arrays of every map (``maps``), rows padded to ``cap``,
        and each stride's voxel count (``n``); a missing neighbour points
        at row ``cap`` (a zero row)."""
        out: dict = {}
        for ref, m in self.maps.items():
            if ref[0] == "up":
                idx, k = m
                a = np.full((cap,), cap, np.int32)
                a[:len(idx)] = np.where(idx >= 0, idx, cap)
                b = np.full((cap,), -1, np.int32)
                b[:len(k)] = k
                out[ref] = (jnp.asarray(a), jnp.asarray(b))
            else:
                a = np.full((cap, m.shape[1]), cap, np.int32)
                a[:len(m)] = np.where(m >= 0, m, cap)
                out[ref] = jnp.asarray(a)
        return {"maps": out, "n": {s: jnp.asarray(len(c), jnp.int32)
                                   for s, c in self.coords.items()}}


# ----------------------------------------------------------------- numerics

def operand(a, mode: str):
    """A matmul operand as the format ``mode`` holds it (returned in
    float32, so the dot itself is exact up to accumulation)."""
    a = a.astype(jnp.float32)
    if mode == "bf16":
        return _round(a, 8, 7)
    if mode == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _E4M3_MAX
        return _round(a / scale, 4, 3) * scale
    return a


def store(a, mode: str):
    """An activation as the format ``mode`` stores it between layers."""
    if mode in ("bf16", "fp8"):
        return _round(a, 8, 7)
    return a


def _dot(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def dense(x, w, mode: str):
    """``x @ w`` with both operands in the format ``mode`` holds them."""
    return _dot(operand(x, mode), operand(w, mode))


def valid(n, cap: int):
    return (jnp.arange(cap) < n)[:, None]


def _with_zero_row(x, mode: str):
    x = operand(x, mode)
    return jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])


def conv(x, w, nbr, mode: str):
    """Σ_δ x[nbr[:, δ]] @ W[δ]; row ``cap`` of the gathered input is zero.
    Input and weights take the format of ``mode`` once per layer."""
    xz, wq = _with_zero_row(x, mode), operand(w, mode)
    acc = jnp.zeros((nbr.shape[0], w.shape[-1]), jnp.float32)
    for k in range(w.shape[0]):
        acc = acc + _dot(xz[nbr[:, k]], wq[k])
    return acc


def conv_up(x, w, parent, k_of, mode: str):
    """Transposed K=2 conv: each fine row reads its parent through
    ``W[k_of]``."""
    rows = _with_zero_row(x, mode)[parent]
    wq = operand(w, mode)
    acc = jnp.zeros((parent.shape[0], w.shape[-1]), jnp.float32)
    for k in range(w.shape[0]):
        acc = acc + jnp.where((k_of == k)[:, None], _dot(rows, wq[k]), 0)
    return acc


def bn_relu(x, p, mask, relu: bool = True):
    """Inference-mode batch norm (a per-channel affine) and ReLU, on
    valid rows."""
    y = x * p["scale"] + p["bias"]
    if relu:
        y = jax.nn.relu(y)
    return jnp.where(mask, y, 0)


def init_params(layers: list, key, conv_dtype=jnp.float32) -> dict:
    """Parameters for the layer list of a reference module (``layers``),
    in the pytree layout the program takes: ``{name: {"w"}}`` per conv
    (``(K^3, Cin, Cout)``, He-style scale) with ``{name}_bn: {"scale",
    "bias"}``, and the head ``{"w": (Cin, classes)}``.  Batch-norm affines
    are drawn too, so a lost affine shows in the outputs."""
    out = {}
    for i, (name, _, cin, cout, vol) in enumerate(layers):
        kw, ks, kb = jax.random.split(jax.random.fold_in(key, i), 3)
        if vol == 1:
            out[name] = {"w": jax.random.normal(kw, (cin, cout)) * cin ** -0.5}
            continue
        w = jax.random.normal(kw, (vol, cin, cout)) * (vol * cin) ** -0.5
        out[name] = {"w": w.astype(conv_dtype)}
        out[f"{name}_bn"] = {
            "scale": jax.random.uniform(ks, (cout,), minval=0.5, maxval=1.5),
            "bias": 0.1 * jax.random.normal(kb, (cout,))}
    return out
