"""Pallas TPU causal GQA flash attention (streaming softmax, O(S) memory).

The transformer hotspot: the baseline jnp attention materializes the
(S × S) score tensor in HBM (fp32) — at prefill_32k that is the dominant
B_M term and busts the 16 GiB budget.  This kernel streams K/V blocks
through VMEM with the online max/sum rescaling of FlashAttention
[arXiv:2205.14135], adapted to the TPU memory hierarchy: block shapes are
MXU-aligned (q 256 × kv 512 × dh), the running (m, l, acc) state lives in
VMEM scratch across the innermost kv-grid dimension, and masking (causal /
sliding-window / length padding) is applied with block-position iota instead
of a materialized mask.

Layout contract (ops.py handles transposes): q (B, H, S, dh),
k/v (B, K, S, dh) with H = G·K query groups per kv head.
Checked against ``ref.ref_flash_attention`` in interpret mode on CPU and
compiled on a TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            kv_steps: int, block_q: int, block_k: int, sm_scale: float,
            causal: bool, window: int, seq_len: int):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                               # (bq, dh)
    k = k_ref[0, 0]                               # (bk, dh)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale   # (bq, bk)

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < seq_len                          # padded keys
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                            # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be exp(0)=1
    p = jnp.exp(jnp.where(m_new <= NEG_INF, NEG_INF, s - m_new))
    alpha = jnp.exp(jnp.where(m_new <= NEG_INF, 0.0, m_prev - m_new))
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == kv_steps - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         causal: bool = True, window: int = 0,
                         seq_len: Optional[int] = None,
                         block_q: int = 256, block_k: int = 512,
                         interpret: bool = True) -> jnp.ndarray:
    """q (B,H,Sp,dh), k/v (B,K,Sp,dh), Sp padded to block multiples.

    ``seq_len`` = true (unpadded) length for key masking.
    ``interpret=True`` runs the kernel body on CPU; on a TPU pass
    interpret=False.
    """
    B, H, Sp, dh = q.shape
    K = k.shape[1]
    G = H // K
    seq_len = Sp if seq_len is None else seq_len
    bq, bk = min(block_q, Sp), min(block_k, Sp)
    assert Sp % bq == 0 and Sp % bk == 0
    grid = (B * H, Sp // bq, Sp // bk)
    sm_scale = 1.0 / (dh ** 0.5)

    kernel = functools.partial(
        _kernel, kv_steps=grid[2], block_q=bq, block_k=bk,
        sm_scale=sm_scale, causal=causal, window=window, seq_len=seq_len)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh),
                         lambda bh, iq, ik: (bh // H, bh % H, iq, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda bh, iq, ik: (bh // H, (bh % H) // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda bh, iq, ik: (bh // H, (bh % H) // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda bh, iq, ik: (bh // H, bh % H, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum l
            pltpu.VMEM((bq, dh), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
