"""Jit'd dispatch wrappers around the Pallas kernels.

Handles layout/padding glue so callers keep model-native shapes:
  * ``matmul``: collapses leading dims, pads (M, K, N) to block multiples,
    slices back.
  * ``flash_attention``: (B, S, H, dh) model layout -> (B, H, S, dh) kernel
    layout, pads S, restores.

The wrappers fall back to the jnp reference for shapes where a kernel launch
is not worth it (tiny matrices in smoke tests).  Otherwise the kernels are
compiled on a TPU backend and interpreted on any other, decided when the
wrapper is traced.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.blocked_matmul import blocked_matmul
from repro.kernels.flash_attention import flash_attention_bhsd

_MIN_DIM = 256  # below this, kernel launch overhead > any win: use jnp


def _interpret() -> bool:
    """Compile the kernels on a TPU, interpret them anywhere else.  Asked
    when a wrapper is traced, never at import."""
    return jax.default_backend() != "tpu"


def _pad_to(x: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("act", "block"))
def matmul(a: jnp.ndarray, b: jnp.ndarray,
           bias: Optional[jnp.ndarray] = None,
           act: Optional[str] = None, block: int = 512) -> jnp.ndarray:
    """(…, K) @ (K, N) with fused bias+activation via the Pallas kernel."""
    *lead, K = a.shape
    N = b.shape[1]
    M = 1
    for d in lead:
        M *= d
    if min(M, N, K) < _MIN_DIM:
        y = ref.ref_matmul(a.reshape(M, K), b, bias=bias, act=act)
        return y.reshape(*lead, N)
    a2 = _pad_to(_pad_to(a.reshape(M, K), block, 0), block, 1)
    b2 = _pad_to(_pad_to(b, block, 0), block, 1)
    bias2 = _pad_to(bias, block, 0) if bias is not None else None
    y = blocked_matmul(a2, b2, bias=bias2, act=act,
                       block_m=block, block_n=block, block_k=block,
                       interpret=_interpret())
    return y[:M, :N].reshape(*lead, N)


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: int = 0) -> jnp.ndarray:
    """Model layout q (B,S,H,dh), k/v (B,S,K,dh) -> (B,S,H,dh)."""
    B, S, H, dh = q.shape
    if S < _MIN_DIM:
        return ref.ref_flash_attention(q, k, v, causal=causal, window=window)
    # pad S to a multiple of 512 = lcm(block_q, block_k)
    qt = _pad_to(jnp.swapaxes(q, 1, 2), 512, 2)         # (B,H,Sp,dh)
    kt = _pad_to(jnp.swapaxes(k, 1, 2), 512, 2)
    vt = _pad_to(jnp.swapaxes(v, 1, 2), 512, 2)
    bq, bk = 256, 512
    o = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                             seq_len=S, block_q=bq, block_k=bk,
                             interpret=_interpret())
    return jnp.swapaxes(o[:, :, :S], 1, 2)
