"""Process start to the first timed request: imports, compile-cache
loads, parameters, the traffic, the warm-up flushes."""


def read(run):
    return run.setup_s
