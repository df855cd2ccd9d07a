"""Share of the profiled stretch in which no operation ran on the chip,
percent (1 - union of device op intervals / stretch)."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
