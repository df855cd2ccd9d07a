"""The engine's ``scene_wait`` phase (the host blocked on a scene
builder's outputs: the device's map build and any device work queued
ahead of it) summed over the window, per scene answered, ms."""


def read(run):
    v, n = run.phase("scene_wait"), run.window.completed
    return sum(v) / n if v and n else None
