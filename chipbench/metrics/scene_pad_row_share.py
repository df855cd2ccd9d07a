"""Padding rows over scene-rung rows of the scene builds (cold or delta)
in the window, percent, from the engine's ``scene_tables`` counters
``rows`` and ``rung_rows``: the map build searches the whole rung."""


def read(run):
    if "rung_rows" not in run.window.stats1.get("scene_tables", {}):
        return None
    rung = run.counter("scene_tables", "rung_rows")
    if not rung:
        return None
    return 100.0 * (1.0 - run.counter("scene_tables", "rows") / rung)
