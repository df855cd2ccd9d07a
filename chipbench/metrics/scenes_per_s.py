"""Scenes answered in the window over the window's seconds: all the work
and all the time, the window holding whole flushes (host clock)."""


def read(run):
    w = run.window
    return w.completed / w.seconds if w.seconds > 0 else None
