"""Readings that set a configuration's ``rel_err`` limit: the program's
own error on many seeds, and the lower-precision control's on the same
answers.  Not part of a benchmark run.

    python chipbench/control.py --workload mink1x.fresh.backlog \
        --seeds 11,12,13 --seconds 8

For each seed, in one process: serve the cell as ``run.py`` does, for a
short window at the cell's own load, and on the answers ``run.py`` would
compare, read (a) ``rel_err`` of the served rows against the float32
reference, and (b) ``rel_err`` of the control, the reference computed in
the next format below the configuration's precision (``CONTROL``),
against the same float32 reference.  One JSON line per seed, then the
largest program reading and the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the format a later change might be tempted to serve in, per stated
#: precision: fp8 (per-tensor scaled e4m3) below bf16, bf16 below float32
CONTROL = {"bf16": "fp8", "fp32": "bf16"}


def readings(bench: dict, cell: str, seed: int, seconds: float, base: str,
             dev) -> dict:
    from chipbench import check, run
    served = run.serve(bench, cell, seed, seconds, False, base, dev)
    w, params = served.window, served.params
    reference = check.Reference(served.ref, served.config)
    control = CONTROL[served.config["precision"]]
    prog, ctrl = [], []
    for t in check.sample(w, seed):
        scene = w.requests[t].scene
        vox, want = reference(params, scene)
        got_vox, got = check.served(w.results[t])
        same = got_vox.shape == vox.shape and (got_vox == vox).all()
        prog.append(check.rel_err(got, want) if same else float("nan"))
        ctrl.append(check.rel_err(reference(params, scene, control)[1], want))
    return {"seed": seed, "answered": w.completed, "program": max(prog),
            "control": min(ctrl), "control_format": control,
            "program_per_scene": prog, "control_per_scene": ctrl}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import run
    base = os.path.join(ROOT, "chipbench")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    run.compile_cache()
    dev = run.device_or_exit(1)
    rows = []
    for s in args.seeds.split(","):
        rows.append(readings(bench, args.workload, int(s), args.seconds,
                             base, dev))
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"workload": args.workload,
                      "program_max": max(r["program"] for r in rows),
                      "control_min": min(r["control"] for r in rows),
                      "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
