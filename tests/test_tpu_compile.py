"""Compile the sparse-path Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see Mosaic's tiling and
memory rules, so each kernel is also lowered and compiled here by the
installed TPU compiler for a v5e chip that is described, not attached —
at MinkUNet 1x channel pairs and a 32768-row rung.  Nothing runs: these
tests prove the chip's compiler accepts the kernels, not their results.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fetch_on_demand.fetch_on_demand import fetch_on_demand_pallas
from repro.kernels.implicit_gemm.implicit_gemm import (
    implicit_gemm_pallas, implicit_gemm_worklist_pallas)
from repro.kernels.wgrad.wgrad import wgrad_pallas

ROWS = 32768          # the largest serving rung
KD = 27               # 3x3x3 submanifold kernel
TILE = 128
#: MinkUNet 1x (cin, cout): the stem, a decoder block, an encoder widening
CHANNELS = [(4, 32), (96, 96), (128, 256)]


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (a compile for a described chip cannot be read back here)."""
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _lanes(cout: int) -> int:
    return TILE if cout % TILE == 0 else cout


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cin,cout", CHANNELS)
def test_implicit_gemm_compiles_for_v5e(one_chip, cin, cout, dtype):
    _compile(lambda m, o, x, w: implicit_gemm_pallas(
                 m, o, x, w, tile_m=TILE, tile_n=_lanes(cout), interpret=False),
             one_chip, ((ROWS, KD), jnp.int32),
             ((ROWS // TILE, KD), jnp.int32), ((ROWS, cin), dtype),
             ((KD, cin, cout), dtype))


@pytest.mark.parametrize("cin,cout", CHANNELS)
def test_implicit_gemm_worklist_compiles_for_v5e(one_chip, cin, cout):
    wn = (ROWS // TILE) * KD      # every (tile, δ) pair occupied
    _compile(lambda t, d, f, m, x, w: implicit_gemm_worklist_pallas(
                 t, d, f, m, x, w, n_tiles_m=ROWS // TILE, tile_m=TILE,
                 tile_n=_lanes(cout), interpret=False),
             one_chip, ((wn,), jnp.int32), ((wn,), jnp.int32),
             ((wn,), jnp.int32), ((wn, TILE), jnp.int32),
             ((ROWS, cin), jnp.float32), ((KD, cin, cout), jnp.float32))


@pytest.mark.parametrize("cin,cout", CHANNELS)
def test_fetch_on_demand_compiles_for_v5e(one_chip, cin, cout):
    _compile(lambda a, b, x, w: fetch_on_demand_pallas(
                 a, b, x, w, n_out=ROWS, tile_r=TILE, interpret=False),
             one_chip, ((KD, ROWS), jnp.int32), ((KD, ROWS), jnp.int32),
             ((ROWS, cin), jnp.float32), ((KD, cin, cout), jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cin,cout", CHANNELS)
def test_wgrad_compiles_for_v5e(one_chip, cin, cout, dtype):
    _compile(lambda a, b, x, dy: wgrad_pallas(
                 a, b, x, dy, tile_r=TILE, interpret=False),
             one_chip, ((KD, ROWS), jnp.int32), ((KD, ROWS), jnp.int32),
             ((ROWS, cin), dtype), ((ROWS, cout), dtype))
