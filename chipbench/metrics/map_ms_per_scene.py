"""The engine's ``map`` phase (scene builds, map composition or a batch
map build) summed over the window, per scene answered, ms.  The host
waits for each scene build, so device mapping time is inside it."""


def read(run):
    v, n = run.phase("map"), run.window.completed
    return sum(v) / n if v and n else None
