"""Padding rows over capacity rows of the levels below the root of the
scene builds in the window, percent, from the engine's ``level_tables``
counters ``rows`` (the rows each cold scene build emitted at every level
below its root) and ``cap_rows`` (the capacity those levels were padded
to): the convs of every level run over all of its capacity.  Nothing to
read where the program has no such counters."""


def read(run):
    if "level_tables" not in run.window.stats1:
        return None
    cap = run.counter("level_tables", "cap_rows")
    if not cap:
        return None
    return 100.0 * (1.0 - run.counter("level_tables", "rows") / cap)
