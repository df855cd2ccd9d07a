"""Run one cell of the chip benchmark and print its result line.

    python chipbench/run.py --workload mink1x.fresh.backlog --seed 7 \
        --seconds 51 --trace 0

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``chipbench/configs/<name>.json``, with its plain reference
``configs/<reference>.py``) and a traffic mix (``chipbench/traffic/
<name>.json``), and may carry overrides in ``chipbench/workloads/
<cell>.json``.  Each metric is read by ``chipbench/metrics/<name>.py``.
The harness finds all of them by name, so a cell, a mix, a configuration
or a metric is added as files and ``BENCHMARK.json`` entries.

The run: check for the chips the cell asks for (exit 2 without them),
make the parameters and the traffic from the seed, warm every rung the
traffic uses (``setup_s`` ends here), serve for ``--seconds``, then
compare a sample of the answers with the reference (check.py).  The last
stdout line is one JSON object; with ``--trace 1`` it carries the
per-layer metrics of a profiled stretch of the window instead of the
end-to-end ones.  The compared numbers and their limits end stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def metrics_for(bench: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that apply to a cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(bench: dict, name: str, base: str):
    """The cell's entry, configuration and merged traffic mix, from the
    files under ``base`` (a ``chipbench`` directory)."""
    from chipbench import system, traffic
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    config = system.load_json("configs", cell["config"], base)
    path = os.path.join(base, "workloads", f"{name}.json")
    over = (system.load_json("workloads", name, base)
            if os.path.exists(path) else None)
    mix = traffic.merged(system.load_json("traffic", cell["traffic"], base),
                         over)
    return cell, config, mix


def compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program,
    however quick to compile, so a second run of a cell compiles nothing."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR", CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_or_exit(chips: int):
    """The first device, or exit 2 when jax finds no TPU or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"chipbench: needs {chips} TPU chip(s); jax found "
            f"{len(devs)} {devs[0].platform} device(s)")
        sys.exit(2)
    return devs[0]


@dataclasses.dataclass
class Served:
    """One cell served for one window, the engine already freed."""

    config: dict
    ref: object            # the configuration's reference module
    params: dict
    window: object         # drive.Window
    setup_s: float
    compiles: int          # compiles inside the window (should be 0)
    peak_bytes: int        # device memory peak, read before the reference


def serve(bench: dict, name: str, seed: int, seconds: float, trace: bool,
          base: str, dev) -> Served:
    """Set up the cell from the seed, serve one window, free the engine."""
    from chipbench import drive, system, traffic
    _, config, mix = load_cell(bench, name, base)
    ref = system.load_module("configs", config["reference"], base)
    params = system.init_params(config, ref, seed)
    serving = {**config["serving"], **mix.get("serving", {})}
    eng = system.engine(config, serving, params, seed)
    req = traffic.build(mix, config, seed, seconds)
    drive.warm_up(eng, req)
    setup_s = time.perf_counter() - T_START
    if mix["loop"] == "open":
        window = drive.open_loop(eng, req, seconds, trace)
    else:
        window = drive.closed_loop(eng, req, mix, seconds, trace)
    s0, s1 = window.stats0, window.stats1
    compiles = sum(sum(s1[k].values()) - sum(s0[k].values())
                   for k in ("recompiles", "map_compiles", "plan_compiles"))
    compiles += (sum(s1["scene_tables"]["compiles"].values())
                 - sum(s0["scene_tables"]["compiles"].values()))
    peak_bytes = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    del eng
    gc.collect()
    return Served(config, ref, params, window, setup_s, compiles, peak_bytes)


def main(argv=None, root: str = ROOT) -> int:
    """``root``: the checkout whose ``BENCHMARK.json`` and ``chipbench/``
    files define the cells (the harness's own code is always this one)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    base = os.path.join(root, "chipbench")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = load_cell(bench, args.workload, base)[0]
    compile_cache()
    dev = device_or_exit(cell["chips"])

    from chipbench import check, context, counts, drive, system, xplane
    peak = counts.peaks(dev.device_kind)
    served = serve(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), base, dev)
    config, ref, params, window = (served.config, served.ref, served.params,
                                   served.window)
    setup_s, compiles = served.setup_s, served.compiles

    reduced = None
    if window.traced is not None:
        reduced = xplane.reduce(xplane.events(drive.trace_file(window)))
        drive.drop_trace(window)
    run = context.Run(config=config, ref=ref, peak=peak, window=window,
                      setup_s=setup_s, trace=reduced)
    kind = "per_layer" if args.trace else "end_to_end"
    values = {}
    for m in metrics_for(bench, args.workload, kind):
        v = system.load_module("metrics", m["name"], base).read(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}

    checks = check.compare(window, check.Reference(ref, config), params,
                           args.seed, config["limits"])
    ok = check.correct(checks)
    attempted = len(window.requests) + window.submit_errors
    failed = (window.submit_errors + checks["missing"]["value"]
              + checks["wrong_voxels"]["value"])
    log(f"window: {window.completed} scenes answered in "
        f"{window.seconds:.3f} s; {attempted} attempted, {failed} failed; "
        f"compiles inside the window: {compiles}; setup {setup_s:.3f} s")
    late = sorted(window.lateness_ms)
    if late:
        log(f"generator lateness: median {late[len(late) // 2]:.3f} ms, "
            f"max {late[-1]:.3f} ms over {len(late)} arrivals")
    line = {"correct": ok, "attempted": attempted, "failed": failed,
            "metrics": values,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": cell["chips"],
                       "memory_peak_bytes": served.peak_bytes}}
    if reduced is not None:
        work = run.work(window.traced.tickets)
        log(f"trace: window {reduced.window_s:.6f} s, device busy "
            f"{reduced.busy_s:.6f} s, {len(window.traced.tickets)} scenes "
            f"answered inside it; least time {work.min_s:.6f} s of which "
            f"compute-bound {work.compute_bound_s:.6f} s")
        line["device"].update(busy_s=reduced.busy_s,
                              window_s=reduced.window_s)
        line["breakdown"] = {"device_ops": reduced.device_ops,
                             "idle_gaps": reduced.idle_gaps}
    line["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
