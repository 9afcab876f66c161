"""Ahead-of-time compiles for a TPU v5e chip that is described, not
attached: the served path's kernels and slab programs at the shapes
``chip_smoke.py`` runs, so a tiling, VMEM or memory refusal shows up
here instead of on the chip.  Nothing runs; results and times are
untested.

The topology is described inside a module fixture only: describing it
loads the TPU compiler library, which one process at a time may hold.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.blocks import make_grid
from repro.kernels import ops
from repro.kernels.dedup_embedding import LANES
from repro.serving import device_pool
from repro.serving.transfer import TransferEngine

HBM_BYTES = 16 * 10**9          # one v5e chip
D = 4096                        # chip_smoke.D_MODEL
VOCAB = 8192                    # chip_smoke.VOCAB
BLOCK = (64, 64)                # chip_smoke.BLOCK
BLOCKS_PER_PAGE = 8
SLAB_PAGES = 1100               # chip_smoke's slab holds ~1,100 pages
PAGE_ROWS = BLOCKS_PER_PAGE * BLOCK[0] * BLOCK[1] // LANES
IDS_BUCKET = 256                # 16 docs x 16 tokens


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # repro: allow-silent-except
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_compile(one_chip, monkeypatch):
    """Compile ``fn`` for one v5e chip from shapes; the persistent
    compile cache is off (a described chip's entries cannot be read
    back) and the Pallas wrappers are steered off interpret mode."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        mem = compiled.memory_analysis()
        used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        assert used < HBM_BYTES, used
        return compiled, mem

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


SLAB = ((SLAB_PAGES, PAGE_ROWS, LANES), jnp.float32)
BMAP = ((VOCAB // BLOCK[0], D // BLOCK[1]), jnp.int32)


def test_dedup_embedding_compiles_at_smoke_shapes(tpu_compile):
    compiled, mem = tpu_compile(
        lambda ids, slab, bmap: ops.dedup_embedding_striped(
            ids, slab, bmap, BLOCK, width=D),
        ((IDS_BUCKET,), jnp.int32), SLAB, BMAP)
    assert "tpu_custom_call" in compiled.as_text()
    # the lane-row slab is dense: no 128-lane padding of 64-wide blocks
    slab_bytes = int(np.prod(SLAB[0])) * 4
    assert mem.argument_size_in_bytes < slab_bytes + (1 << 20)
    assert mem.output_size_in_bytes == IDS_BUCKET * D * 4


@pytest.mark.parametrize("block", [(128, 128), (256, 128)])
def test_dedup_matmul_compiles_at_accepted_blocks(tpu_compile, block):
    bh, bw = block
    assert device_pool.pallas_matmul_accepts(block)
    page_rows = BLOCKS_PER_PAGE * bh * bw // LANES
    gh, gw = 4096 // bh, 1024 // bw
    compiled, _ = tpu_compile(
        lambda x, slab, bmap: ops.dedup_matmul(
            x, slab.reshape(-1, bh, bw), bmap, bm=128),
        ((256, gh * bh), jnp.float32),
        ((256, page_rows, LANES), jnp.float32),
        ((gh, gw), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_matmul_refuses_store_blocks():
    assert not device_pool.pallas_matmul_accepts(BLOCK)
    assert not device_pool.pallas_matmul_accepts((128, 256))


@pytest.mark.parametrize("group", [64, 512])
def test_transfer_scatter_compiles(tpu_compile, group):
    engine = TransferEngine(None)
    slots = np.arange(group, dtype=np.int64)
    tpu_compile(lambda slab, staged: engine._scatter(slab, slots, staged),
                SLAB, ((group, PAGE_ROWS, LANES), jnp.float32))


def test_gather_rows_xla_compiles(tpu_compile):
    tpu_compile(
        lambda slab, bmap, rows: device_pool._gather_rows_xla(
            slab, bmap, rows, block_shape=BLOCK, width=D),
        SLAB, BMAP, ((IDS_BUCKET,), jnp.int32))


def test_unblock_xla_compiles(tpu_compile):
    grid = make_grid((VOCAB, D), BLOCK)
    _, mem = tpu_compile(
        lambda slab, dev_map: device_pool._unblock_xla(slab, dev_map,
                                                       grid=grid),
        SLAB, ((grid.num_blocks,), jnp.int32))
    assert mem.output_size_in_bytes == VOCAB * D * 4
