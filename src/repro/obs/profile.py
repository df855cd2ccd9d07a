"""XLA-level profiling behind the same ``--trace`` flag.

``jax_profile(logdir)`` brackets a region with
``jax.profiler.start_trace``/``stop_trace``, writing a TensorBoard/XProf
trace next to the repo's own Chrome trace — on TPU that is the device-side
view of the same run.

jax is imported lazily so ``repro.obs`` itself stays dependency-free.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def jax_profile(logdir: str):
    """Capture a jax profiler trace of the enclosed region into ``logdir``."""
    import jax.profiler
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
