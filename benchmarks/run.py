# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness entrypoint: ``PYTHONPATH=src python -m benchmarks.run``.

One module per paper table/figure (see DESIGN.md §6):
  Fig. 14 inference, Fig. 15/22 training, Tab. 3/4 + Fig. 17 sorted-vs-
  unsorted, Tab. 5 mask splits, Fig. 18 hybrid dataflow, Fig. 16 R-GCN,
  Fig. 8 generator-vs-dense-GEMM.

``--tiny`` runs every suite at CI smoke scale (suites without a tiny knob
run at their only scale) and ``--out BENCH_CI.json`` consolidates the
emitted rows into one machine-readable artifact — per-suite rows +
medians + environment metadata — which CI uploads every run, so the perf
trajectory of the repo accumulates instead of scrolling away in job logs.

CPU-container caveat: wall-clock numbers here validate *ranking logic*
(mapping overhead vs kernel time trade-offs) at reduced scale; the TPU
performance story lives in the dry-run roofline (EXPERIMENTS.md §Roofline).
"""
from __future__ import annotations

import argparse
import inspect
import json
import platform
import statistics
import subprocess
import sys
import time
import traceback


def _git_sha() -> str:
    try:
        return subprocess.run(["git", "rev-parse", "HEAD"],
                              capture_output=True, text=True,
                              timeout=10).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _metadata(tiny: bool) -> dict:
    import jax
    return {
        "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_sha": _git_sha(),
        "tiny": tiny,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "python": platform.python_version(),
        "platform": platform.platform(),
    }


def _row_dict(record: tuple) -> dict:
    name, us, derived = record
    return {"name": name, "us_per_call": float(us), "derived": derived}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke scale for every suite that supports it")
    ap.add_argument("--out", default=None, metavar="BENCH_CI.json",
                    help="write the consolidated perf artifact here")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names to run (default all)")
    ap.add_argument("--hosts", type=int, default=2,
                    help="localhost worker count for the fleet_serving suite")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_generator, bench_graph, bench_hybrid,
                            bench_inference, bench_kmap, bench_serving,
                            bench_sorted, bench_splits, bench_streaming,
                            bench_training, common)

    suites = [
        ("kmap_engine", bench_kmap.run),
        ("serving_engine", bench_serving.run),
        ("streaming_serving", bench_streaming.run),
        ("fig14_inference", bench_inference.run),
        ("fig15_training", bench_training.run),
        ("tab34_sorted", bench_sorted.run),
        ("tab5_splits", bench_splits.run),
        ("fig18_hybrid", bench_hybrid.run),
        ("fig16_graph", bench_graph.run),
        ("fig8_generator", bench_generator.run),
    ]
    # Opt-in suites: spawn subprocesses (localhost fleet workers), so they
    # run only when explicitly named in --only, never by default.
    opt_in = [
        ("fleet_serving", bench_serving.run_fleet),
    ]
    if args.only:
        keep = {s.strip() for s in args.only.split(",")}
        unknown = keep - {name for name, _ in suites + opt_in}
        if unknown:
            raise SystemExit(f"unknown suites: {sorted(unknown)}")
        suites = [(n, f) for n, f in suites + opt_in if n in keep]

    print("name,us_per_call,derived")
    failures = []
    report = {"meta": _metadata(args.tiny), "suites": {}}
    for name, fn in suites:
        start = len(common.RECORDS)
        t0 = time.perf_counter()
        try:
            params = inspect.signature(fn).parameters
            kw = {}
            if args.tiny and "tiny" in params:
                kw["tiny"] = True
            if "hosts" in params:
                kw["hosts"] = args.hosts
            fn(**kw)
            ok = True
        except Exception:
            failures.append(name)
            ok = False
            traceback.print_exc()
        rows = [_row_dict(r) for r in common.RECORDS[start:]]
        timed = [r["us_per_call"] for r in rows if r["us_per_call"] > 0]
        report["suites"][name] = {
            "ok": ok,
            "wall_s": round(time.perf_counter() - t0, 3),
            "median_us": statistics.median(timed) if timed else None,
            "rows": rows,
        }
    report["failures"] = failures

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.out} ({len(report['suites'])} suites)",
              file=sys.stderr)

    if failures:
        print(f"FAILED suites: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == '__main__':
    main()
