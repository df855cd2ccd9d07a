"""SparseConv layer: the paper's operator as a composable JAX module.

Training support (paper §4.2/Fig. 13): forward, dgrad and wgrad are *three
different kernels* with independently tunable dataflow parameters.  We express
that with a ``custom_vjp`` whose backward pass dispatches on the layer's
``TrainDataflowConfig`` — the exact mechanism the Sparse Autotuner's binding
schemes tune.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import dataflows as df
from repro.core.kmap import KernelMap, MapCache, build_kmap, transpose_kmap
from repro.core.precision import FP32, PrecisionPolicy
from repro.core.sparse_tensor import SparseTensor, axis_strides


@dataclasses.dataclass(frozen=True)
class TrainDataflowConfig:
    """Per-layer(-group) dataflow parameters for fwd / dgrad / wgrad."""

    fwd: df.DataflowConfig = df.DEFAULT_CONFIG
    dgrad: df.DataflowConfig = df.DEFAULT_CONFIG
    wgrad: df.DataflowConfig = df.DEFAULT_CONFIG

    # Binding schemes (paper Fig. 13): construct coupled configs.
    @staticmethod
    def bind_all(cfg: df.DataflowConfig) -> "TrainDataflowConfig":
        return TrainDataflowConfig(cfg, cfg, cfg)

    @staticmethod
    def bind_fwd_dgrad(cfg: df.DataflowConfig, wgrad: df.DataflowConfig) -> "TrainDataflowConfig":
        """Workload-pattern oriented (low-parallelism devices)."""
        return TrainDataflowConfig(cfg, cfg, wgrad)

    @staticmethod
    def bind_dgrad_wgrad(fwd: df.DataflowConfig, cfg: df.DataflowConfig) -> "TrainDataflowConfig":
        """Sparse-mapping oriented (high-parallelism devices)."""
        return TrainDataflowConfig(fwd, cfg, cfg)

    def to_dict(self) -> dict:
        return {"fwd": self.fwd.to_dict(), "dgrad": self.dgrad.to_dict(),
                "wgrad": self.wgrad.to_dict()}

    @staticmethod
    def from_dict(d: dict) -> "TrainDataflowConfig":
        unknown = set(d) - {"fwd", "dgrad", "wgrad"}
        if unknown:
            raise ValueError(
                f"unknown TrainDataflowConfig fields: {sorted(unknown)}")
        return TrainDataflowConfig(fwd=df.DataflowConfig.from_dict(d["fwd"]),
                                   dgrad=df.DataflowConfig.from_dict(d["dgrad"]),
                                   wgrad=df.DataflowConfig.from_dict(d["wgrad"]))


DEFAULT_TRAIN_CONFIG = TrainDataflowConfig()


def sparse_conv_apply(feats: jax.Array, w: jax.Array, kmap: KernelMap,
                      cfg: TrainDataflowConfig = DEFAULT_TRAIN_CONFIG,
                      precision: PrecisionPolicy = FP32,
                      plan=None) -> jax.Array:
    """Differentiable sparse conv with decoupled fwd/dgrad/wgrad dataflows.

    ``precision`` applies to all three kernels: bf16 compute / fp32
    accumulate under the mixed policy.  Cotangents are re-cast to the primal
    dtypes as the last step (custom_vjp contract), so the weight gradient
    rounds at most once — after full-precision accumulation — on its way to
    the optimizer's fp32 master copy.

    ``plan``: optional pre-built ``SplitPlan`` for the forward dataflow
    (serving composes these per batch); None keeps the build-in-trace path.
    """

    @jax.custom_vjp
    def f(feats, w):
        return df.sparse_conv_forward(feats, w, kmap, cfg.fwd,
                                      precision=precision, plan=plan)

    def f_fwd(feats, w):
        return f(feats, w), (feats, w)

    def f_bwd(res, dy):
        feats_, w_ = res
        dx = df.sparse_conv_dgrad(dy, w_, kmap, cfg.dgrad,
                                  in_capacity=feats_.shape[0],
                                  precision=precision)
        dw = df.sparse_conv_wgrad(feats_, dy, kmap, cfg.wgrad,
                                  precision=precision)
        return dx.astype(feats_.dtype), dw.astype(w_.dtype)

    f.defvjp(f_fwd, f_bwd)
    return f(feats, w)


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """One conv layer's shape.  ``kernel_size`` and ``stride`` are an int
    (the same on every axis) or a tuple of one int per spatial axis, as
    Cylinder3D's (1, 3, 3) kernels and (2, 2, 1) pools have."""

    in_channels: int
    out_channels: int
    kernel_size: int | tuple = 3
    stride: int | tuple = 1
    transposed: bool = False
    bias: bool = False

    @property
    def volume(self) -> int:
        """Kernel offsets: the product of the kernel's extent per axis."""
        return math.prod(axis_strides(self.kernel_size))


def init_conv(key: jax.Array, spec: ConvSpec, ndim: int = 3, dtype=jnp.float32) -> dict:
    kd = math.prod(axis_strides(spec.kernel_size, ndim))
    fan_in = spec.in_channels * kd
    w = jax.random.normal(key, (kd, spec.in_channels, spec.out_channels), dtype) * (fan_in ** -0.5)
    params = {"w": w}
    if spec.bias:
        params["b"] = jnp.zeros((spec.out_channels,), dtype)
    return params


def apply_conv(params: dict, x: SparseTensor, kmap: KernelMap,
               cfg: TrainDataflowConfig = DEFAULT_TRAIN_CONFIG,
               precision: PrecisionPolicy = FP32,
               plan=None) -> SparseTensor:
    """Apply a sparse conv given a prebuilt kernel map; returns the output
    SparseTensor on the map's coordinates."""
    y = sparse_conv_apply(x.feats, params["w"], kmap, cfg, precision=precision,
                          plan=plan)
    if "b" in params:
        y = y + params["b"][None, :].astype(y.dtype)
    valid = jnp.arange(kmap.capacity) < kmap.n_out
    y = jnp.where(valid[:, None], y, 0)
    # Output coordinates live in the same declared (batch, spatial) region as
    # the input's: propagate the bounds so downstream build_kmap calls stay on
    # the single-word packed-key path instead of falling back to raw keys.
    return SparseTensor(coords=kmap.out_coords, feats=y, num_valid=kmap.n_out,
                        stride=kmap.out_stride, batch_bound=x.batch_bound,
                        spatial_bound=x.spatial_bound)


def conv_kmap(x: SparseTensor, spec: ConvSpec,
              cached_fine: Optional[SparseTensor] = None,
              cached_fwd: Optional[KernelMap] = None,
              cache: Optional[MapCache] = None) -> KernelMap:
    """Build (or derive) the kernel map for ``spec`` applied to ``x``.

    Decoder (transposed) convs reuse the encoder's map (paper: same group).
    ``cache`` (a ``kmap.MapCache``) lets layers at the same stride share the
    sorted coordinate table instead of rebuilding it per layer group."""
    if spec.transposed:
        assert cached_fwd is not None and cached_fine is not None
        return transpose_kmap(cached_fwd, cached_fine)
    return build_kmap(x, spec.kernel_size, spec.stride, cache=cache)
