"""Per-kernel shape/dtype sweeps against the ref.py pure-jnp oracles
(interpret mode on CPU; these kernels target TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dataflows as df
from repro.core import kmap as km
from repro.kernels.fetch_on_demand.ops import fetch_on_demand
from repro.kernels.fetch_on_demand.ref import fetch_on_demand_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.implicit_gemm.ops import implicit_gemm
from repro.kernels.implicit_gemm.ref import implicit_gemm_ref
from tests.test_kmap import random_tensor


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tile_m,tile_n", [(8, 8), (16, 16)])
@pytest.mark.parametrize("splits,sort", [(1, True), (2, True), (3, True), (1, False)])
def test_implicit_gemm_sweep(dtype, tile_m, tile_n, splits, sort):
    stx = random_tensor(11, n=90, cap=128, channels=8, extent=7)
    kmap = km.build_kmap(stx, 3, 1)
    w = (jax.random.normal(jax.random.PRNGKey(1), (27, 8, 16)) * 0.3).astype(dtype)
    x = stx.feats.astype(dtype)
    plan = km.make_split_plan(kmap, splits, sort=sort)
    got = implicit_gemm(x, w, kmap, plan, tile_m=tile_m, tile_n=tile_n, interpret=True)
    ref = implicit_gemm_ref(x, w, kmap.m_out)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref, np.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cin,cout", [(8, 16), (16, 8)])
def test_implicit_gemm_channel_shapes(dtype, cin, cout):
    stx = random_tensor(12, n=60, cap=64, channels=cin, extent=6)
    kmap = km.build_kmap(stx, 3, 1)
    w = (jax.random.normal(jax.random.PRNGKey(2), (27, cin, cout)) * 0.3).astype(dtype)
    x = stx.feats.astype(dtype)
    plan = km.make_split_plan(kmap, 2)
    got = implicit_gemm(x, w, kmap, plan, tile_m=16, tile_n=8, interpret=True)
    ref = implicit_gemm_ref(x, w, kmap.m_out)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref, np.float32), **tol(dtype))


def test_implicit_gemm_strided():
    stx = random_tensor(13, n=80, cap=128, channels=8, extent=10)
    kmap = km.build_kmap(stx, 2, 2)
    w = jax.random.normal(jax.random.PRNGKey(3), (8, 8, 16)) * 0.3
    plan = km.make_split_plan(kmap, 1)
    got = implicit_gemm(stx.feats, w, kmap, plan, tile_m=16, tile_n=16, interpret=True)
    ref = implicit_gemm_ref(stx.feats, w, kmap.m_out)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tile_r", [8, 32])
def test_fetch_on_demand_sweep(dtype, tile_r):
    stx = random_tensor(14, n=70, cap=96, channels=8, extent=7)
    kmap = km.build_kmap(stx, 3, 1)
    w = (jax.random.normal(jax.random.PRNGKey(4), (27, 8, 16)) * 0.3).astype(dtype)
    x = stx.feats.astype(dtype)
    got = fetch_on_demand(x, w, kmap, tile_r=tile_r, interpret=True)
    ref = fetch_on_demand_ref(x, w, kmap.ws_in, kmap.ws_out, kmap.capacity)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref, np.float32), **tol(dtype))


def test_pallas_kernels_agree_with_each_other():
    stx = random_tensor(15, n=90, cap=128, channels=8, extent=8)
    kmap = km.build_kmap(stx, 3, 1)
    w = jax.random.normal(jax.random.PRNGKey(5), (27, 8, 8)) * 0.3
    plan = km.make_split_plan(kmap, 2)
    a = implicit_gemm(stx.feats, w, kmap, plan, tile_m=16, tile_n=8, interpret=True)
    b = fetch_on_demand(stx.feats, w, kmap, tile_r=16, interpret=True)
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("splits,sort", [(1, True), (2, True), (3, True),
                                         (1, False)])
def test_implicit_gemm_worklist_bit_identical_to_dense(splits, sort):
    """Tile skipping changes the launch geometry, not the math: the
    worklist kernel visits the occupied (tile, δ) pairs in the same order
    the dense grid's gated steps run them, so the two are *bit*-identical
    (same float add sequence) — with ad-hoc occupancy and with the
    occupancy fused into the split plan.  Under jit it refuses."""
    stx = random_tensor(11, n=90, cap=128, channels=8, extent=7)
    kmap = km.build_kmap(stx, 3, 1)
    w = jax.random.normal(jax.random.PRNGKey(1), (27, 8, 16)) * 0.3
    plan = km.make_split_plan(kmap, splits, sort=sort)
    fused = km.make_split_plan(kmap, splits, sort=sort, tile_m=16)
    dense = implicit_gemm(stx.feats, w, kmap, plan, tile_m=16, tile_n=8,
                          interpret=True)
    for p in (plan, fused):
        wl = implicit_gemm(stx.feats, w, kmap, p, tile_m=16, tile_n=8,
                           worklist=True, interpret=True)
        assert jnp.array_equal(dense, wl)
    # under jit the occupancy is a tracer: there is no concrete worklist to
    # compact, and the wrapper refuses rather than run the dense grid
    jitted = jax.jit(lambda x, w_: implicit_gemm(
        x, w_, kmap, plan, tile_m=16, tile_n=8, worklist=True,
        interpret=True))
    with pytest.raises(ValueError, match="worklist"):
        jitted(stx.feats, w)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,hkv,g", [(128, 2, 1), (256, 2, 2)])
def test_flash_attention_sweep(dtype, causal, s, hkv, g):
    b, d = 2, 16
    h = hkv * g
    key = jax.random.PRNGKey(0)
    q = (jax.random.normal(key, (b, h, s, d)) * 0.5).astype(dtype)
    k = (jax.random.normal(jax.random.fold_in(key, 1), (b, hkv, s, d)) * 0.5).astype(dtype)
    v = (jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, s, d)) * 0.5).astype(dtype)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
    ref = mha_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref, np.float32),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_flash_attention_rectangular_blocks():
    b, h, s, d = 1, 2, 128, 32
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (b, h, s, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, h, s, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, h, s, d))
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=64, interpret=True)
    ref = mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
