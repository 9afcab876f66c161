"""Pallas TPU kernel: matmul against a *virtual* (deduplicated) weight.

The paper stores a weight tensor as pages of distinct blocks plus a
per-tensor indirection (Sec. 3/5).  On TPU we keep the distinct-block
pool in HBM and let the **scalar-prefetched block map drive the
``BlockSpec`` index_map**: for output tile (i, j) at contraction step k,
the kernel DMAs physical block ``block_map[k, j]`` from the pool into
VMEM instead of a dense W tile.  Dedup therefore happens *inside the
HBM->VMEM stream*: shared blocks are fetched once per (k, j) visit, and
Pallas's pipeline skips the re-fetch entirely when consecutive grid
steps map to the same physical block — the VMEM-level analogue of the
paper's shared-page buffer-pool hit.

Tiling: block shape (bk, bn) is the storage block shape — hardware
aligned (multiples of 8x128; default 256x256 = MXU-native).  x is tiled
(bm, bk); the k-loop is the innermost ("arbitrary") grid dim and
accumulates into the output tile in fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


F32 = jnp.float32


def _kernel(bmap_ref, x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                            preferred_element_type=F32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "interpret", "out_dtype"))
def dedup_matmul(x, pool, block_map, *, bm: int = 128,
                 interpret: bool = False, out_dtype=None):
    """x [M, K] @ W_virtual -> [M, N].

    pool [n_distinct, bk, bn]; block_map [K/bk, N/bn] int32.
    M must be a multiple of ``bm`` (ops.py pads).
    """
    M, K = x.shape
    nkb, nnb = block_map.shape
    bk, bn = pool.shape[1], pool.shape[2]
    assert K == nkb * bk, (K, nkb, bk)
    N = nnb * bn
    out_dtype = out_dtype or x.dtype

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M // bm, nnb, nkb),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, bmap: (i, k)),
            pl.BlockSpec((1, bk, bn),
                         lambda i, j, k, bmap: (bmap[k, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, bmap: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), F32)],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, nk=nkb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    return fn(block_map, x, pool)
