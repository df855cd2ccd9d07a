"""The system under test, reached only through its public entry points:
``repro.serve.engine.Engine`` and ``ARCHS``, ``repro.serve.service.
ServiceConfig``, ``repro.serve.batcher.Scene``/``SceneDelta`` and each
model's ``init_params``.  Also the loaders of the benchmark's own files
(configurations, traffic mixes, cells), which the harness finds by name.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")


def load_json(kind: str, name: str, base: str = HERE) -> dict:
    """``<base>/<kind>/<name>.json``."""
    with open(os.path.join(base, kind, f"{name}.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str, base: str = HERE):
    """``<base>/<kind>/<name>.py``, imported by path: the harness finds a
    configuration's reference and each metric's reader by name."""
    path = os.path.join(base, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def prng_key(seed: int):
    """A jax key that keeps every bit of a seed wider than 32 bits."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def model_config(config: dict):
    """The program's model configuration object for ``config``."""
    from repro.serve.engine import ARCHS
    model = {k: tuple(v) if isinstance(v, list) else v
             for k, v in config["model"].items()}
    return dataclasses.replace(ARCHS[config["arch"]].default_config, **model)


def init_params(config: dict, ref, seed: int):
    """Parameters from the seed, made by the harness on the device in one
    jitted call, conv weights in the type they are served in."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference as R
    layers = ref.layers(config["model"])
    dtype = jnp.bfloat16 if config["precision"] == "bf16" else jnp.float32
    return jax.block_until_ready(jax.jit(
        lambda k: R.init_params(layers, k, dtype))(prng_key(seed)))


def engine(config: dict, serving: dict, params, seed: int):
    """An ``Engine`` for ``config`` with the cell's serving knobs."""
    from repro.serve.engine import Engine
    from repro.serve.service import ServiceConfig
    from chipbench.scenes import Geometry
    knobs = dict(serving)
    knobs.setdefault("spatial_bound", Geometry.of(config).spatial_bound)
    return Engine(config["arch"], config=ServiceConfig(seed=seed, **knobs),
                  model_config=model_config(config), params=params,
                  precision=config["precision"])


def to_scene(s):
    from repro.serve.batcher import Scene
    return Scene(coords=s.coords, feats=s.feats)


def to_delta(d):
    from repro.serve.batcher import SceneDelta
    return SceneDelta(removed=d.removed, added_coords=d.added_coords,
                      added_feats=d.added_feats)
