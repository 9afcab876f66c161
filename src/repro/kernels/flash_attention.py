"""Pallas TPU kernel: flash attention (prefill hot-spot).

Online-softmax blocked attention: grid (B*K_heads*G, Sq/bq, Skv/bkv) with
the KV dim innermost; m/l/acc accumulators live in VMEM scratch across KV
steps.  Supports causal masking, sliding window, and gemma2 logit
softcap.  Causal/window-skipped KV blocks are masked (the index map still
visits them; the §Perf log covers the block-skip upgrade).

This kernel is the TPU hot path behind ``models.attention.attend`` (the
pure-JAX chunked implementation doubles as its oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


F32 = jnp.float32
NEG_INF = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            nkv: int, bq: int, bkv: int, causal: bool, window: int,
            softcap: float, scale: float):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(1)
    q = q_ref[0].astype(F32) * scale                     # [bq, hd]
    k = k_ref[0].astype(F32)                             # [bkv, hd]
    s = jnp.dot(q, k.T, preferred_element_type=F32)      # [bq, bkv]
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qp = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kp = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = jnp.ones((bq, bkv), bool)
    if causal:
        mask &= qp >= kp
    if window:
        mask &= (qp - kp) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))           # [bq]
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    acc_ref[...] = (acc_ref[...] * corr[:, None]
                    + jnp.dot(p, v_ref[0].astype(F32),
                              preferred_element_type=F32))
    m_ref[...] = m_new

    @pl.when(j == nkv - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "bq", "bkv", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale=None, bq: int = 512,
                    bkv: int = 512, interpret: bool = False):
    """q [B, Sq, H, hd]; k, v [B, Skv, K, hd] (GQA) -> [B, Sq, H, hd].

    Sq % bq == 0 and Skv % bkv == 0 (ops.py pads).
    """
    B, Sq, H, hd = q.shape
    Skv, Kh = k.shape[1], k.shape[2]
    G = H // Kh
    scale = scale if scale is not None else hd ** -0.5
    bq, bkv = min(bq, Sq), min(bkv, Skv)
    assert Sq % bq == 0 and Skv % bkv == 0

    # Layout: fold heads into the batch grid dim; q by (kv-head, group).
    qf = (q.reshape(B, Sq, Kh, G, hd)
           .transpose(0, 2, 3, 1, 4)
           .reshape(B * Kh * G, Sq, hd))
    kf = k.transpose(0, 2, 1, 3).reshape(B * Kh, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Kh, Skv, hd)
    nkv = Skv // bkv

    fn = pl.pallas_call(
        functools.partial(_kernel, nkv=nkv, bq=bq, bkv=bkv, causal=causal,
                          window=window, softcap=softcap, scale=scale),
        grid=(B * Kh * G, Sq // bq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j: (b // G, j, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j: (b // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Kh * G, Sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), F32),
                        pltpu.VMEM((bq,), F32),
                        pltpu.VMEM((bq, hd), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    of = fn(qf, kf, vf)
    return (of.reshape(B, Kh, G, Sq, hd)
              .transpose(0, 3, 1, 2, 4)
              .reshape(B, Sq, H, hd))
