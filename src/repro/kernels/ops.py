"""Public jit'd wrappers for the Pallas kernels.

On TPU the Pallas path compiles natively; on CPU the kernels execute
through ``interpret=True`` — same kernel body, lowered for the CPU, used
by the allclose test sweeps against ``ref.py``.  Any other backend
raises: a GPU must not silently interpret TPU kernels.  Wrappers handle
padding to tile multiples and unpadding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .dedup_embedding import ROW_TILE
from .dedup_embedding import dedup_embedding as _dedup_embedding
from .dedup_matmul import dedup_matmul as _dedup_matmul
from .flash_attention import flash_attention as _flash_attention
from .lsh_signature import lsh_signature as _lsh_signature


def _interpret() -> bool:
    """True on the CPU (interpret mode), False on the TPU; raises on any
    other backend, where the TPU kernels neither compile nor should be
    interpreted in silence."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas TPU kernels cannot run on the "
                           f"{backend!r} backend")
    return backend == "cpu"


def _pad_to(x, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def dedup_matmul(x, pool, block_map, bm: int = 128, out_dtype=None):
    """x [M, K] (or [..., K]) @ virtual W -> [..., N]."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    x2, padm = _pad_to(x2, 0, bm)
    y = _dedup_matmul(x2, pool, block_map, bm=bm,
                      interpret=_interpret(), out_dtype=out_dtype)
    if padm:
        y = y[: y.shape[0] - padm]
    return y.reshape(lead + (y.shape[-1],))


def dedup_embedding(ids, pool, row_block_map):
    """Row-block embedding: pool [n_distinct, bv, D] with row blocks
    spanning the full model dimension; row_block_map [V/bv].  ids of any
    shape -> [..., D]."""
    return dedup_embedding_striped(ids, pool, row_block_map[:, None],
                                   block_shape=pool.shape[1:])


def dedup_embedding_striped(ids, pool, block_map, block_shape, width=None):
    """Row gather from a 2-D virtual tensor stored as ``(bh, bw)`` blocks.

    Storage blocks are square tiles, so a row of the virtual tensor
    crosses ``gw`` column stripes; the kernel gathers every stripe of a
    row in one call (``block_map[:, j]`` is stripe ``j``'s row-block
    map) and the ragged last stripe is trimmed to ``width``.

    ids: any shape; pool: the distinct blocks end to end (``[n, bh, bw]``
    or the device slab's lane-row layout); block_map [gh, gw] int32.
    Returns [..., width or gw*bw].
    """
    lead = ids.shape
    flat = ids.reshape(-1)
    # pad with a requested id, not id 0: under partial residency row 0's
    # block may be a -1 hole, and a DMA from it would read out of bounds
    pad = (-flat.shape[0]) % ROW_TILE
    flat = jnp.concatenate([flat, jnp.broadcast_to(flat[:1], (pad,))])
    out = _dedup_embedding(flat, pool, block_map,
                           block_shape=tuple(int(b) for b in block_shape),
                           interpret=_interpret())
    out = out[:out.shape[0] - pad]
    if width is not None:
        out = out[:, :width]
    return out.reshape(lead + (out.shape[-1],))


def lsh_signature(blocks, proj, bias, r: float):
    n, dim = blocks.shape
    blocks = blocks.reshape(n, dim).astype(jnp.float32)
    blocks, padn = _pad_to(blocks, 0, 128)
    blocks, padk = _pad_to(blocks, 1, 512 if dim >= 512 else 8)
    proj = jnp.pad(proj.astype(jnp.float32), ((0, padk), (0, 0)))
    nh = proj.shape[1]
    proj, padh = _pad_to(proj, 1, 128 if nh >= 128 else 8)
    bias = jnp.pad(bias.astype(jnp.float32), (0, padh))
    bk = 512 if blocks.shape[1] % 512 == 0 else 8
    bh = 128 if proj.shape[1] % 128 == 0 else 8
    sig = _lsh_signature(blocks, proj, bias, r=float(r), bk=bk, bh=bh,
                         interpret=_interpret())
    return sig[:n, :nh]


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    scale=None, bq=512, bkv=512):
    Sq, Skv = q.shape[1], k.shape[1]
    bq = min(bq, Sq)
    bkv = min(bkv, Skv)
    q, padq = _pad_to(q, 1, bq)
    k, padk = _pad_to(k, 1, bkv)
    v, _ = _pad_to(v, 1, bkv)
    if padk and not causal:
        raise ValueError("non-causal padding needs an explicit kv mask; "
                         "pad Skv to a bkv multiple upstream")
    out = _flash_attention(q, k, v, causal=causal, window=window,
                           softcap=softcap, scale=scale, bq=bq, bkv=bkv,
                           interpret=_interpret())
    return out[:, :Sq]


__all__ = ["dedup_matmul", "dedup_embedding", "dedup_embedding_striped",
           "lsh_signature", "flash_attention", "ref"]
