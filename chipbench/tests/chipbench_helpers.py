"""Shared set-up of the harness's CPU tests: import paths, tiny
configurations, and a stand-in for the chip check."""
from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import system  # noqa: E402

#: width-reduced stand-ins of the two configurations, a few hundred voxels
TINY = {
    "minkunet-kitti-1x": {"model": {"width": 0.25, "blocks_per_stage": 1},
                          "voxels_per_scene": [150, 400]},
    "centerpoint-waymo-1x": {"model": {"width": 0.5},
                             "voxels_per_scene": [300, 800]},
}


def tiny_config(name: str, precision: str = "fp32") -> dict:
    cfg = system.load_json("configs", name)
    cfg["model"].update(TINY[name]["model"])
    cfg["voxels_per_scene"] = TINY[name]["voxels_per_scene"]
    cfg["serving"] = {**cfg["serving"], "buckets": [1024]}
    cfg["precision"] = precision
    # float32 on the CPU: the served path and the reference agree to
    # rounding, so a tenth of a percent leaves room only for rounding
    cfg["limits"] = {"rel_err": 1e-3}
    return cfg


class FakeDevice:
    """What the harness reads of a chip, for runs on the CPU."""

    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def bench_tree(tmp, cells: list, configs: dict, mixes: dict,
               overrides: dict = None, metrics: dict = None,
               per_layer: list = None) -> str:
    """A checkout-like directory holding ``BENCHMARK.json`` and the
    benchmark's data files (the configurations' references copied in)."""
    base = os.path.join(tmp, "chipbench")
    for d in ("configs", "traffic", "workloads", "metrics"):
        os.makedirs(os.path.join(base, d), exist_ok=True)
    for name, cfg in configs.items():
        with open(os.path.join(base, "configs", f"{name}.json"), "w") as f:
            json.dump(cfg, f)
        shutil.copy(os.path.join(system.HERE, "configs",
                                 f"{cfg['reference']}.py"),
                    os.path.join(base, "configs"))
    for name, mix in mixes.items():
        with open(os.path.join(base, "traffic", f"{name}.json"), "w") as f:
            json.dump(mix, f)
    for name, over in (overrides or {}).items():
        with open(os.path.join(base, "workloads", f"{name}.json"), "w") as f:
            json.dump(over, f)
    e2e = []
    for name, src in (metrics or {}).items():
        with open(os.path.join(base, "metrics", f"{name}.py"), "w") as f:
            f.write(src)
    for name in ("scenes_per_s", "setup_s", "p95_latency_ms"):
        shutil.copy(os.path.join(system.HERE, "metrics", f"{name}.py"),
                    os.path.join(base, "metrics"))
        e2e.append({"name": name, "unit": "x", "better": "lower",
                    "bound": 0.1, "source": "host_clock"})
    bench = {"command": ["python3", "chipbench/run.py"],
             "paths": ["chipbench"], "run_seconds": 1, "configs": [],
             "workloads": cells, "end_to_end": e2e,
             "per_layer": per_layer or []}
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
