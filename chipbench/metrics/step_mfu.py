"""Model FLOPs of the scenes answered inside the profiled stretch, over
its seconds, over the chip's peak at the configuration's precision,
percent.  FLOPs are the harness's own count (counts.py)."""


def read(run):
    if run.trace is None or not run.window.traced.tickets:
        return None
    flops = run.work(run.window.traced.tickets).flops
    peak = run.peak["flops"][run.precision]
    return 100.0 * flops / run.trace.window_s / peak
