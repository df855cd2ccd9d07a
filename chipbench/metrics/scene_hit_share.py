"""Batch slots served from the engine's scene store over all slots looked
up in the window, percent (the engine's scene hit/miss counters)."""


def read(run):
    hits = run.counter("scene_tables", "hits")
    total = hits + run.counter("scene_tables", "misses")
    return 100.0 * hits / total if total else None
