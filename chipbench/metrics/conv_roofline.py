"""The least time the chip could take for the conv layers and head of the
scenes answered inside the profiled stretch (per layer, the larger of
FLOPs over peak FLOP/s and minimum bytes over HBM bandwidth), over the
device's busy time in that stretch, percent."""


def read(run):
    if run.trace is None or not run.window.traced.tickets:
        return None
    return 100.0 * run.work(run.window.traced.tickets).min_s / run.trace.busy_s
