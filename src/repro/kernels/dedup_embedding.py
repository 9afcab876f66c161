"""Pallas TPU kernel: embedding lookup from a deduplicated block pool.

The paper's word2vec scenario (Sec. 7.1.1/7.2.1): the embedding matrix is
stored as ``(bh, bw)`` blocks, deduplicated across model variants, and a
per-variant block map ``[gh, gw]`` names the distinct block behind each
tile of the virtual ``[V, D]`` matrix.  Row ``t`` of the virtual matrix
is the concatenation, over the ``gw`` column stripes, of row ``t % bh``
of block ``block_map[t // bh, j]``.

Layout.  The pool is read as *lane rows*: a flat ``[N, 128]`` view of
the distinct blocks laid end to end (``N = n_blocks * bh * bw / 128``).
This is the layout the device page slab keeps (DESIGN.md §3): a
``[..., 64, 64]`` float32 array is padded to 128 lanes on the TPU, while
``[..., 128]`` rows are dense and can be copied by DMA one row at a time.
A block row of width ``bw`` therefore starts at lane row
``blk * bh * bw / 128 + off * bw / 128`` — one lane row holds ``128/bw``
block rows when ``bw < 128``, and a block row spans ``bw/128`` lane rows
when ``bw >= 128``.

Kernel.  The wrapper turns token ids into lane-row starts (``[B, gw]``
int32, plain XLA arithmetic on the block map).  The kernel walks
``ROW_TILE`` tokens per grid step: the tile's starts sit in SMEM, the pool
stays in HBM (``memory_space=pl.ANY``), and one DMA per (token, stripe)
copies the lane rows straight into the ``(ROW_TILE, gw * span, 128)``
VMEM output block.  All of a tile's copies are started before the first
wait, so they overlap.  When ``bw < 128`` each copied lane row also holds
its ``128/bw - 1`` neighbours; the wrapper keeps the requested lane slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128       # TPU vector lane width: the pool's row length
ROW_TILE = 8      # tokens per grid step (one float32 sublane tile)


def lane_geometry(block_shape) -> tuple:
    """``(rows_per_block, span, per)`` of a ``(bh, bw)`` block in the
    lane-row view: lane rows per block, lane rows per block row, and
    block rows per lane row.  Raises for blocks that do not tile into
    whole lane rows."""
    bh, bw = block_shape
    if (bh * bw) % LANES or (bw % LANES and LANES % bw):
        raise ValueError(
            f"block shape {tuple(block_shape)} does not tile into "
            f"{LANES}-lane rows: bh*bw must be a multiple of {LANES} and "
            f"bw a divisor or a multiple of {LANES}")
    return bh * bw // LANES, max(1, bw // LANES), max(1, LANES // bw)


def _kernel(src_ref, pool_ref, o_ref, sem, *, span: int):
    tile, g = src_ref.shape

    def copy(i):
        r, j = i // g, i % g
        return pltpu.make_async_copy(
            pool_ref.at[pl.ds(src_ref[r, j], span)],
            o_ref.at[r, pl.ds(j * span, span)], sem)

    def start(i, carry):
        copy(i).start()
        return carry

    def wait(i, carry):
        copy(i).wait()
        return carry

    jax.lax.fori_loop(0, tile * g, start, 0)
    jax.lax.fori_loop(0, tile * g, wait, 0)


def gather_lane_rows(src, pool, *, span: int, interpret: bool = False):
    """``out[b, j*span + s] = pool[src[b, j] + s]`` for ``s < span``.

    src [B, g] int32 (B a multiple of ``ROW_TILE``); pool [N, 128].
    Returns [B, g * span, 128] in the pool's dtype."""
    B, g = src.shape
    return pl.pallas_call(
        functools.partial(_kernel, span=span),
        grid=(B // ROW_TILE,),
        in_specs=[
            pl.BlockSpec((ROW_TILE, g), lambda t: (t, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((ROW_TILE, g * span, LANES),
                               lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, g * span, LANES), pool.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(src, pool)


@functools.partial(jax.jit,
                   static_argnames=("block_shape", "interpret"))
def dedup_embedding(ids, pool, block_map, *, block_shape,
                    interpret: bool = False):
    """ids [B] -> [B, gw * bw] rows of the virtual embedding.

    pool: any array holding the distinct ``(bh, bw)`` blocks end to end
    (``[n, bh, bw]``, or the slab's lane-row layout); block_map [gh, gw]
    int32 distinct-block ids.  B must be a multiple of ``ROW_TILE``
    (``ops.py`` pads)."""
    bh, bw = block_shape
    rows_per_block, span, per = lane_geometry(block_shape)
    gw = block_map.shape[1]
    ids = ids.astype(jnp.int32)
    off = ids % bh
    blk = block_map.astype(jnp.int32)[ids // bh]              # [B, gw]
    src = blk * rows_per_block + (off * span // per)[:, None]
    out = gather_lane_rows(src, pool.reshape(-1, LANES), span=span,
                           interpret=interpret)
    if per > 1:                          # keep this row's slice of the lane row
        out = out.reshape(out.shape[0], gw, per, bw)
        out = jnp.take_along_axis(out, (off % per)[:, None, None, None],
                                  axis=2)
    return out.reshape(out.shape[0], gw * bw)
