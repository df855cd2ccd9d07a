"""The host's own mapping work per scene answered, ms: the engine's
``map`` phase (flush time) plus its ``delta_merge`` phase (submit time,
never inside ``map``), less the ``scene_wait`` inside them, summed over
the window.  Nothing to read where no ``scene_wait`` was recorded."""


def read(run):
    wait, n = run.phase("scene_wait"), run.window.completed
    if not wait or not n:
        return None
    return (sum(run.phase("map")) + sum(run.phase("delta_merge"))
            - sum(wait)) / n
