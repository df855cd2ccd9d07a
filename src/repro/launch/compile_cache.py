"""One persistent XLA compilation cache for every entry point.

A cold full-width MinkUNet compiles for minutes on a TPU; the persistent
cache lets a second process (the next serving start, the next training
run) load those executables instead.  Every launcher calls
:func:`enable_compile_cache` once, before its first compile.
"""
from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` — src/repro/launch/ is three levels below the
#: checkout root.  A fixed path: the directory is where later runs look, so
#: one built from a temp name, a pid or the time would never be hit again.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it from
    the environment and this sets nothing.  Otherwise the cache lives in
    :data:`DEFAULT_DIR` (git-ignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
