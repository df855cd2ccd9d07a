"""Production mesh definitions and serving-device helpers.

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before any jax init).
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

from repro.models.lm_common import ShardCtx


def _auto(n_axes: int) -> tuple:
    return (AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, elastic restore)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=_auto(len(axes)))


def make_ctx(mesh, fsdp: bool = False) -> ShardCtx:
    axes = mesh.axis_names
    batch = tuple(a for a in axes if a in ("pod", "data"))
    return ShardCtx(mesh=mesh, batch=batch, model="model",
                    model_size=mesh.shape["model"], fsdp=fsdp)


# --------------------------------------------------------------- serving tier

def host_device_flag(n: int) -> str:
    """The XLA flag that splits the host platform into ``n`` virtual devices
    (how the multi-device serving tier runs in CPU CI):
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    assert n >= 1
    return f"--xla_force_host_platform_device_count={n}"


def serving_devices(n: Optional[int] = None) -> Sequence[jax.Device]:
    """The first ``n`` jax devices for the sharded serving tier.

    ``n=None`` takes every visible device.  Raises with an actionable hint
    (the ``XLA_FLAGS`` virtual-device split) when fewer than ``n`` devices
    are attached — serving must never silently run N workers on one device
    and report it as sharded throughput.
    """
    devs = jax.devices()
    if n is None:
        return list(devs)
    if n < 1:
        raise ValueError(f"need at least one serving device, got n={n}")
    if len(devs) < n:
        raise RuntimeError(
            f"{n} serving devices requested but only {len(devs)} attached; "
            f"for host-platform virtual devices set "
            f"XLA_FLAGS={host_device_flag(n)!r} before the first jax import "
            f"(current XLA_FLAGS={os.environ.get('XLA_FLAGS', '')!r})")
    return list(devs[:n])


def make_serving_mesh(n: Optional[int] = None):
    """1-D ``("serve",)`` mesh over the serving devices — the device roster
    the ``serve.DeviceRouter`` shards its bucket-ladder workers across."""
    devs = serving_devices(n)
    return jax.make_mesh((len(devs),), ("serve",), devices=devs,
                         axis_types=_auto(1))


# TPU v5e hardware constants for the roofline (per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link
