"""Per-kernel allclose sweeps: shapes x dtypes vs the ref.py oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _tol(dt):
    return 1e-4 if dt == "float32" else 6e-2


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("M,bk,bn,nkb,nnb,nd", [
    (32, 16, 16, 2, 2, 2),
    (64, 32, 64, 3, 2, 4),
    (100, 16, 128, 2, 3, 3),        # ragged M (pad path)
    (16, 64, 32, 1, 4, 1),          # single distinct block (full dedup)
])
def test_dedup_matmul_sweep(dtype, M, bk, bn, nkb, nnb, nd):
    x = RNG.standard_normal((M, nkb * bk)).astype(dtype)
    pool = RNG.standard_normal((nd, bk, bn)).astype(dtype)
    bmap = RNG.integers(0, nd, (nkb, nnb)).astype(np.int32)
    y = ops.dedup_matmul(jnp.asarray(x), jnp.asarray(pool),
                         jnp.asarray(bmap), bm=16)
    yr = ref.dedup_matmul(jnp.asarray(x), jnp.asarray(pool),
                          jnp.asarray(bmap))
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=_tol(dtype), atol=_tol(dtype))


def test_dedup_matmul_batched_lead_dims():
    x = RNG.standard_normal((2, 5, 32)).astype(np.float32)
    pool = RNG.standard_normal((3, 16, 16)).astype(np.float32)
    bmap = RNG.integers(0, 3, (2, 2)).astype(np.int32)
    y = ops.dedup_matmul(jnp.asarray(x), jnp.asarray(pool),
                         jnp.asarray(bmap), bm=8)
    assert y.shape == (2, 5, 32)


@pytest.mark.parametrize("V,bv,D,B", [
    (64, 8, 32, 7), (128, 16, 64, 33),
    (256, 64, 64, 8),               # one full row tile, 2 rows per lane row
    (256, 64, 64, 13),              # ragged last row tile
    (64, 8, 256, 9),                # a row spans two lane rows
])
def test_dedup_embedding_sweep(V, bv, D, B):
    pool = RNG.standard_normal((5, bv, D)).astype(np.float32)
    rbmap = RNG.integers(0, 5, (V // bv,)).astype(np.int32)
    ids = RNG.integers(0, V, (B,)).astype(np.int32)
    e = ops.dedup_embedding(jnp.asarray(ids), jnp.asarray(pool),
                            jnp.asarray(rbmap))
    expect = ref.dedup_embedding(jnp.asarray(ids), jnp.asarray(pool),
                                 jnp.asarray(rbmap), D)
    np.testing.assert_array_equal(np.asarray(e), np.asarray(expect))


@pytest.mark.parametrize("bh,bw,gh,gw,B", [
    (64, 64, 3, 4, 16),             # the store's blocks, 2 row tiles
    (64, 64, 2, 3, 11),             # ragged last row tile
    (16, 16, 4, 2, 5),              # 8 block rows per lane row
    (16, 128, 2, 3, 9),             # one block row per lane row
])
@pytest.mark.parametrize("lane_rows", [False, True])
def test_dedup_embedding_striped_sweep(bh, bw, gh, gw, B, lane_rows):
    """Column-striped gather == rows of the reference's materialized
    virtual tensor, from a block pool or its lane-row (slab) layout."""
    pool = RNG.standard_normal((6, bh, bw)).astype(np.float32)
    bmap = RNG.integers(0, 6, (gh, gw)).astype(np.int32)
    ids = RNG.integers(0, gh * bh, (B,)).astype(np.int32)
    src = pool.reshape(-1, 128) if lane_rows else pool
    width = gw * bw - 5
    e = ops.dedup_embedding_striped(jnp.asarray(ids), jnp.asarray(src),
                                    jnp.asarray(bmap), (bh, bw),
                                    width=width)
    W = ref.materialize_virtual(jnp.asarray(pool), jnp.asarray(bmap),
                                gh * bh, gw * bw)
    np.testing.assert_array_equal(np.asarray(e),
                                  np.asarray(W)[ids][:, :width])


def test_dedup_embedding_refuses_blocks_off_the_lane_grid():
    pool = jnp.zeros((2, 4, 8), jnp.float32)          # 32 floats per block
    with pytest.raises(ValueError, match="lane"):
        ops.dedup_embedding_striped(jnp.zeros((8,), jnp.int32), pool,
                                    jnp.zeros((1, 1), jnp.int32), (4, 8))


@pytest.mark.parametrize("n,dim,nh,r", [
    (16, 64, 16, 2.0), (33, 100, 24, 4.0), (128, 512, 128, 1.0)])
def test_lsh_signature_sweep(n, dim, nh, r):
    blocks = RNG.standard_normal((n, dim)).astype(np.float32)
    proj = RNG.standard_normal((dim, nh)).astype(np.float32)
    bias = (RNG.random(nh) * r).astype(np.float32)
    s = ops.lsh_signature(jnp.asarray(blocks), jnp.asarray(proj),
                          jnp.asarray(bias), r=r)
    sr = ref.lsh_signature(jnp.asarray(blocks), jnp.asarray(proj),
                           jnp.asarray(bias), r)
    assert (np.asarray(s) == np.asarray(sr)).all()


@pytest.mark.parametrize("B,Sq,Skv,H,K,hd,causal,window,cap", [
    (2, 64, 64, 4, 2, 16, True, 0, 0.0),
    (1, 32, 48, 4, 4, 8, True, 16, 30.0),     # window + softcap
    (2, 16, 64, 2, 1, 16, False, 0, 0.0),     # cross attention
    (1, 48, 48, 8, 2, 32, True, 0, 50.0),     # GQA + softcap
])
def test_flash_attention_sweep(B, Sq, Skv, H, K, hd, causal, window, cap):
    q = RNG.standard_normal((B, Sq, H, hd)).astype(np.float32)
    k = RNG.standard_normal((B, Skv, K, hd)).astype(np.float32)
    v = RNG.standard_normal((B, Skv, K, hd)).astype(np.float32)
    o = ops.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            causal=causal, window=window, softcap=cap,
                            bq=16, bkv=16)
    orf = ref.flash_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=causal, window=window,
                              softcap=cap)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=1e-4, atol=1e-5)


def test_flash_matches_model_attention():
    """Pallas kernel vs the model-zoo chunked attention implementation."""
    from repro.models.attention import attend
    q = jnp.asarray(RNG.standard_normal((2, 32, 4, 16)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 32, 2, 16)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 32, 2, 16)), jnp.float32)
    o1 = ops.flash_attention(q, k, v, causal=True, bq=8, bkv=8)
    o2 = attend(q, k, v, causal=True, chunk=8)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-4, atol=1e-5)


def test_dedup_matmul_matches_store_virtual_tensor():
    """End-to-end: ModelStore virtual tensor -> kernel == dense matmul."""
    from repro.core import DedupConfig, LSHConfig, ModelStore, StoreConfig
    store = ModelStore(StoreConfig(
        dedup=DedupConfig(block_shape=(16, 16),
                          lsh=LSHConfig(num_bands=8, rows_per_band=2,
                                        r=8.0, collision_threshold=6),
                          validate=False),
        blocks_per_page=4))
    base = RNG.standard_normal((64, 32)).astype(np.float32)
    store.register("m0", {"w": base})
    store.register("m1", {"w": base + 1e-5})
    vt = store.virtual_tensor("m1", "w")
    pool = store.page_pool().reshape(-1, 16, 16)
    bmap = vt.block_map.reshape(vt.grid.grid)
    x = RNG.standard_normal((8, 64)).astype(np.float32)
    y = ops.dedup_matmul(jnp.asarray(x), jnp.asarray(pool),
                         jnp.asarray(bmap), bm=8)
    dense = store.materialize("m1", "w")
    np.testing.assert_allclose(np.asarray(y), x @ dense, rtol=1e-4,
                               atol=1e-4)
