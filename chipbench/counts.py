"""The work a scene asks of a network, and the chip's peaks.

Counts belong to the harness and count the same work whatever implements
it.  Per conv layer: FLOPs = 2 x valid neighbour pairs of the layer's map
x Cin x Cout (the transposed map for ``up*``); the head adds 2 x voxels x
Cin x classes.  Minimum bytes = input features read once + weights +
output features written once, at the configuration's storage types.
Pairs come from the reference's own neighbour search of the scene.
"""
from __future__ import annotations

import dataclasses

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
#: Google Cloud documentation, "TPU v5e" (system architecture): 197
#: TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops": {"bf16": 197e12, "fp32": 197e12,
                              "int8": 393e12},
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}

#: bytes per stored element: (activations, conv weights) by precision
STORAGE = {"bf16": (2, 2), "fp32": (4, 4)}


def peaks(device_kind: str) -> dict:
    """The peaks of a chip; a chip not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


@dataclasses.dataclass
class Work:
    flops: float = 0.0
    min_s: float = 0.0       # Σ per layer of max(FLOPs/peak, bytes/bandwidth)
    compute_bound_s: float = 0.0   # the part of min_s where FLOPs bind

    def __iadd__(self, o: "Work") -> "Work":
        self.flops += o.flops
        self.min_s += o.min_s
        self.compute_bound_s += o.compute_bound_s
        return self


def scene_work(ref, model: dict, coords, precision: str, peak: dict) -> Work:
    """The work of one scene through the network whose plain reference is
    the module ``ref`` (``layers``, ``pyramid``)."""
    pyr = ref.pyramid(coords, model)
    act, wb = STORAGE[precision]
    flops_peak = peak["flops"][precision]
    bw = peak["hbm_bytes_per_s"]
    out = Work()
    for name, mref, cin, cout, vol in ref.layers(model):
        kind, s = mref
        if vol == 1:   # the head: a dense map of every voxel at stride s
            pairs = n_in = n_out = len(pyr.coords[s])
        else:
            pairs = pyr.pairs(mref)
            n_in = len(pyr.coords[2 * s if kind == "up" else s])
            n_out = len(pyr.coords[2 * s if kind == "down" else s])
        f = 2.0 * pairs * cin * cout
        b = n_in * cin * act + vol * cin * cout * wb + n_out * cout * act
        tf, tb = f / flops_peak, b / bw
        out += Work(flops=f, min_s=max(tf, tb),
                    compute_bound_s=tf if tf >= tb else 0.0)
    return out
