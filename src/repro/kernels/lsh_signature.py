"""Pallas TPU kernel: fused L2-LSH signature computation (index build).

Signature = floor((blocks @ proj + bias) / r) — a matmul with a fused
quantize epilogue.  This is the hot loop of the paper's duplicate
detection (Alg. 1 computes a signature per block per model); fusing the
floor/divide avoids materializing the fp32 projection in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


F32 = jnp.float32


def _kernel(x_ref, p_ref, b_ref, o_ref, acc_ref, *, nk: int, r: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], p_ref[...],
                            preferred_element_type=F32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = jnp.floor((acc_ref[...] + b_ref[...]) / r
                               ).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("r", "bm", "bk", "bh", "interpret"))
def lsh_signature(blocks, proj, bias, *, r: float, bm: int = 128,
                  bk: int = 512, bh: int = 128, interpret: bool = False):
    """blocks [n, dim] fp32; proj [dim, num_hashes]; bias [num_hashes]
    -> int32 [n, num_hashes].  ops.py pads n/dim/num_hashes to tiles."""
    n, dim = blocks.shape
    num_hashes = proj.shape[1]
    bm, bk, bh = min(bm, n), min(bk, dim), min(bh, num_hashes)
    assert n % bm == 0 and dim % bk == 0 and num_hashes % bh == 0
    nk = dim // bk

    fn = pl.pallas_call(
        functools.partial(_kernel, nk=nk, r=r),
        grid=(n // bm, num_hashes // bh, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bh), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bh), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bh), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, num_hashes), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bh), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    return fn(blocks, proj, bias.reshape(1, -1))
