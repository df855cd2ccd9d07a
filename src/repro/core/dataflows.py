"""The three sparse-convolution dataflows behind one config (paper Fig. 9).

Every dataflow computes the same math (Equation 1); they differ in *where*
redundant work and memory traffic land:

* ``gather_scatter``   — weight-stationary, vendor-library (here: XLA) GEMMs,
                         gather/scatter buffers in DRAM, no overlap. Cheap to
                         maintain, fundamentally latency-bound (paper §2.2.1).
* ``fetch_on_demand``  — fused weight-stationary Pallas kernel, zero redundant
                         compute, Σ|M_δ| write-back amplification (§2.2.2).
* ``implicit_gemm``    — output-stationary Pallas kernel, minimal write-back,
                         tile-granular redundant compute, tunable mask
                         splits/sorting (§2.2.3, §4.1).

``backend='xla'`` runs mathematically-identical jnp paths (used on CPU and in
the distributed dry-run, where the roofline is derived from HLO);
``backend='pallas'`` dispatches the hand-tiled kernels (validated in
interpret mode on CPU, native on TPU).

Every dataflow additionally honours a ``PrecisionPolicy``
(``core/precision.py``): GEMM operands are cast to ``policy.compute``
(bf16 under the mixed-precision policy), partial sums accumulate in
``policy.accum`` (fp32 — the Pallas kernels already keep an fp32 VMEM
accumulator, so operand-level casting composes), and results come out in
``policy.output`` (or the input features' dtype when unset).  The default
FP32 policy is bit-identical to the pre-policy behaviour.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.kmap import KernelMap, SplitPlan, make_split_plan
from repro.core.precision import FP32, PrecisionPolicy, gemm_operand
from repro.kernels.fetch_on_demand.ops import fetch_on_demand as fod_pallas_op
from repro.kernels.fetch_on_demand.ref import fetch_on_demand_ref
from repro.kernels.implicit_gemm.ops import implicit_gemm as igemm_pallas_op
from repro.kernels.implicit_gemm.ref import implicit_gemm_ref

DATAFLOWS = ("gather_scatter", "fetch_on_demand", "implicit_gemm")


@dataclasses.dataclass(frozen=True)
class DataflowConfig:
    """One point in the Sparse Autotuner design space (paper Fig. 9)."""

    dataflow: str = "implicit_gemm"
    n_splits: int = 1          # 0 = unsorted (paper Fig. 5); ≥1 = sorted splits
    tile_m: int = 128
    tile_n: int = 128
    backend: str = "xla"       # 'xla' | 'pallas'
    worklist: bool = False     # pallas implicit GEMM: launch over the
    #                            compacted occupied-(tile, δ) worklist
    #                            instead of the dense grid (tile skipping)

    def __post_init__(self):
        assert self.dataflow in DATAFLOWS, self.dataflow

    @property
    def sorted(self) -> bool:
        return self.n_splits >= 1

    @property
    def effective_splits(self) -> int:
        return max(1, self.n_splits)

    def effective_backend(self, kernel: str = "fwd") -> str:
        """The backend that *actually executes* this config for ``kernel``
        (fwd/dgrad/wgrad) — not the one requested.  A ``backend='pallas'``
        request silently runs the XLA path for dataflows that have no
        Pallas kernel (gather_scatter fwd, every dgrad), and the tuner /
        PlanRegistry must record what ran, not what was asked for."""
        if self.backend != "pallas":
            return "xla"
        if kernel == "fwd":
            return "pallas" if self.dataflow in ("implicit_gemm",
                                                 "fetch_on_demand") else "xla"
        if kernel == "dgrad":
            return "xla"    # dgrad is always the XLA scan (see sparse_conv_dgrad)
        if kernel == "wgrad":
            return "pallas"
        raise ValueError(f"unknown kernel {kernel!r}")

    def to_dict(self) -> dict:
        """JSON-safe dict (all fields are ints/strs).  Round-trips through
        ``from_dict`` — the serving engine's PlanRegistry persists tuned
        assignments with this.  Carries a derived ``effective_backend``
        stamp (what actually executes the forward) so persisted plans can't
        claim pallas where xla ran; ``from_dict`` drops it."""
        d = dataclasses.asdict(self)
        d["effective_backend"] = self.effective_backend("fwd")
        return d

    @staticmethod
    def from_dict(d: dict) -> "DataflowConfig":
        d = dict(d)
        d.pop("effective_backend", None)   # derived stamp, not a field
        unknown = set(d) - {f.name for f in dataclasses.fields(DataflowConfig)}
        if unknown:
            raise ValueError(f"unknown DataflowConfig fields: {sorted(unknown)}")
        return DataflowConfig(**d)


DEFAULT_CONFIG = DataflowConfig()


def default_serving_space(include_pallas: bool = True) -> Tuple[DataflowConfig, ...]:
    """The serving tuner's default search space: all three dataflows on the
    XLA backend plus (``include_pallas``) the same three on the Pallas
    backend — compiled on TPU, interpret mode elsewhere.  The worklist
    variant is not searched: the served executor is jitted, and the
    worklist kernel runs eagerly only."""
    space = [DataflowConfig("gather_scatter"),
             DataflowConfig("fetch_on_demand"),
             DataflowConfig("implicit_gemm", n_splits=1)]
    if include_pallas:
        from repro.kernels.common import default_interpret
        # Interpret mode unrolls the per-row DMA bodies at trace time, so
        # CPU containers search small tiles (the math — and therefore the
        # tuner's dataflow ranking — is tile-independent); real TPUs keep
        # the MXU-shaped defaults.
        tm, tn = (16, 128) if default_interpret() else (128, 128)
        space += [dataclasses.replace(cfg, backend="pallas", tile_m=tm,
                                      tile_n=tn) for cfg in space]
    return tuple(space)


def plan_for(kmap: KernelMap, cfg: DataflowConfig) -> SplitPlan:
    tile_m = None
    if cfg.backend == "pallas" and cfg.dataflow == "implicit_gemm" \
            and cfg.worklist:
        # fuse the per-(split, tile, δ) occupancy into the plan pass — the
        # worklist kernel compacts its launch grid from it
        tile_m = math.gcd(cfg.tile_m, kmap.capacity)
    return make_split_plan(kmap, cfg.effective_splits, sort=cfg.sorted,
                           tile_m=tile_m)


def _gather_scatter_xla(x, w, kmap: KernelMap,
                        precision: PrecisionPolicy = FP32) -> jax.Array:
    """Vanilla gather-GEMM-scatter via lax.scan over stacked per-δ maps.

    TorchSparse v1's "adaptive grouping" batches offsets with similar |M_δ|;
    with static capacities every offset already has an identical shape, so the
    scan *is* the grouped batched GEMM (DESIGN.md §2, sequential host loop →
    scan)."""
    cap_out = kmap.capacity
    ct, at = precision.compute_dtype, precision.accum_dtype
    # round/cast the loop-invariant operands ONCE, not per δ iteration
    xq, wq = gemm_operand(x, ct, at), gemm_operand(w, ct, at)

    def body(acc, inputs):
        wk, i_in, i_out = inputs
        rows = jnp.where((i_in >= 0)[:, None], xq[jnp.clip(i_in, 0)], 0)
        y = jnp.dot(rows, wk, preferred_element_type=at)
        return acc.at[i_out].add(y, mode="drop"), None

    acc0 = jnp.zeros((cap_out, w.shape[-1]), at)
    acc, _ = jax.lax.scan(body, acc0, (wq, kmap.ws_in, kmap.ws_out))
    return acc.astype(precision.output_dtype(x.dtype))


def _implicit_gemm_xla(x, w, kmap: KernelMap,
                       precision: PrecisionPolicy = FP32) -> jax.Array:
    """Output-stationary jnp path (splits/sorting are a no-op for the math)."""
    return implicit_gemm_ref(x, w, kmap.m_out,
                             acc_dtype=precision.accum_dtype,
                             compute_dtype=precision.compute_dtype,
                             out_dtype=precision.output_dtype(x.dtype))


def _pallas_operands(x, w, precision: PrecisionPolicy):
    """Operand-level mixed precision for the Pallas kernels: they already
    keep an fp32 VMEM accumulator (preferred_element_type=f32) and emit
    ``x.dtype``, so casting the operands is the whole policy."""
    return x.astype(precision.compute_dtype), w.astype(precision.compute_dtype)


def sparse_conv_forward(x: jax.Array, w: jax.Array, kmap: KernelMap,
                        cfg: DataflowConfig = DEFAULT_CONFIG,
                        plan: Optional[SplitPlan] = None,
                        precision: PrecisionPolicy = FP32) -> jax.Array:
    """Dispatch one sparse convolution. x: (N_in_cap, Cin), w: (KD, Cin, Cout).

    Returns (N_out_cap, Cout) in ``precision.output`` (input dtype by
    default)."""
    if cfg.backend == "pallas":
        out = precision.output_dtype(x.dtype)
        if cfg.dataflow == "implicit_gemm":
            if plan is None:
                plan = plan_for(kmap, cfg)
            xc, wc = _pallas_operands(x, w, precision)
            return igemm_pallas_op(xc, wc, kmap, plan, tile_m=cfg.tile_m,
                                   tile_n=cfg.tile_n,
                                   worklist=cfg.worklist).astype(out)
        if cfg.dataflow == "fetch_on_demand":
            xc, wc = _pallas_operands(x, w, precision)
            return fod_pallas_op(xc, wc, kmap, tile_r=cfg.tile_m).astype(out)
        return _gather_scatter_xla(x, w, kmap, precision)  # g-g-s *is* the vendor path
    # XLA backend
    if cfg.dataflow == "implicit_gemm":
        return _implicit_gemm_xla(x, w, kmap, precision)
    if cfg.dataflow == "fetch_on_demand":
        return fetch_on_demand_ref(x, w, kmap.ws_in, kmap.ws_out, kmap.capacity,
                                   acc_dtype=precision.accum_dtype,
                                   compute_dtype=precision.compute_dtype,
                                   out_dtype=precision.output_dtype(x.dtype))
    return _gather_scatter_xla(x, w, kmap, precision)


def sparse_conv_dgrad(dy: jax.Array, w: jax.Array, kmap: KernelMap,
                      cfg: DataflowConfig = DEFAULT_CONFIG,
                      in_capacity: Optional[int] = None,
                      precision: PrecisionPolicy = FP32) -> jax.Array:
    """Input-feature gradient: a sparse conv over the *transposed* map with
    W^T per offset — expressed weight-stationarily by swapping the pair lists
    (so any dataflow config applies; the autotuner tunes it separately).

    ``in_capacity`` is the *input* tensor's row capacity.  The pair lists are
    sized at the output capacity, which differs from the input capacity for
    strided/transposed maps — callers that know the input shape (e.g. the
    custom_vjp in sparse_conv.py) must pass it so gradients scatter into a
    correctly-sized accumulator instead of being silently dropped."""
    if in_capacity is not None:
        cap_in = in_capacity
    else:
        cap_in = int(jnp.shape(kmap.ws_in)[1])  # submanifold: == out capacity
    ct, at = precision.compute_dtype, precision.accum_dtype
    dyq, wq = gemm_operand(dy, ct, at), gemm_operand(w, ct, at)

    def body(acc, inputs):
        wk, i_in, i_out = inputs
        rows = jnp.where((i_out >= 0)[:, None], dyq[jnp.clip(i_out, 0)], 0)
        g = jnp.dot(rows, wk.T, preferred_element_type=at)
        return acc.at[i_in].add(g, mode="drop"), None

    acc0 = jnp.zeros((cap_in, w.shape[1]), at)
    acc, _ = jax.lax.scan(body, acc0, (wq, kmap.ws_in, kmap.ws_out))
    return acc.astype(precision.output_dtype(dy.dtype))


def sparse_conv_wgrad(x: jax.Array, dy: jax.Array, kmap: KernelMap,
                      cfg: DataflowConfig = DEFAULT_CONFIG,
                      precision: PrecisionPolicy = FP32) -> jax.Array:
    """Weight gradient: per-δ  gather(X)ᵀ @ gather(dY) — a GEMM with *two*
    sparse iterators (the reason the paper tunes wgrad separately: its K loop
    runs over N_out, so reordering/pair layout dominates).

    Partial sums accumulate in ``precision.accum`` (fp32) and round at most
    once at the end; the custom_vjp caller re-casts to the weight dtype so
    the cotangent always matches the parameter leaf."""
    if cfg.backend == "pallas":
        from repro.kernels.wgrad.ops import wgrad as wgrad_kernel

        xc, yc = (x.astype(precision.compute_dtype),
                  dy.astype(precision.compute_dtype))
        return wgrad_kernel(xc, yc, kmap,
                            tile_r=cfg.tile_m).astype(precision.output_dtype(x.dtype))
    ct, at = precision.compute_dtype, precision.accum_dtype
    xq, dyq = gemm_operand(x, ct, at), gemm_operand(dy, ct, at)

    def body(_, inputs):
        i_in, i_out = inputs
        xs = jnp.where((i_in >= 0)[:, None], xq[jnp.clip(i_in, 0)], 0)
        ys = jnp.where((i_out >= 0)[:, None], dyq[jnp.clip(i_out, 0)], 0)
        return None, jnp.dot(xs.T, ys, preferred_element_type=at)

    _, dw = jax.lax.scan(body, None, (kmap.ws_in, kmap.ws_out))
    return dw.astype(precision.output_dtype(x.dtype))
