"""Pallas TPU blocked matmul with fused bias + activation epilogue.

The paper's case-study hotspot: the MLP layer GEMM ``O = f(W·I + b)``.
Fusing the bias-add and activation into the GEMM epilogue removes the
elementwise HBM round-trip the paper's B_M accounting would otherwise pay
(2 extra R/W of the (batch, features) activation per layer).

TPU mapping: grid (M/bm, N/bn, K/bk) with the K dimension innermost so the
fp32 VMEM accumulator carries across K steps; blocks default to 512×512×512
(MXU-aligned multiples of 128; ~1.5 MiB of VMEM for bf16 operands + fp32
accumulator, well inside the 16 MiB/core budget).  Checked against
``ref.ref_matmul`` in interpret mode on CPU and compiled on a TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ACTS = ("relu", "relu2", "silu", "gelu")


def _epilogue(y: jnp.ndarray, act: Optional[str]) -> jnp.ndarray:
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "relu2":
        r = jnp.maximum(y, 0.0)
        return r * r
    if act == "silu":
        return y * jax.nn.sigmoid(y)
    if act == "gelu":
        return jax.nn.gelu(y)
    return y


def _kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *, k_steps: int,
            act: Optional[str]):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        y = acc_ref[...]
        if bias_ref is not None:
            y = y + bias_ref[...].astype(jnp.float32)
        o_ref[...] = _epilogue(y, act).astype(o_ref.dtype)


def blocked_matmul(a: jnp.ndarray, b: jnp.ndarray,
                   bias: Optional[jnp.ndarray] = None,
                   act: Optional[str] = None,
                   block_m: int = 512, block_n: int = 512, block_k: int = 512,
                   interpret: bool = True) -> jnp.ndarray:
    """a (M, K) @ b (K, N) [+ bias (N,)] with fused activation.

    Requires M % block_m == K % block_k == N % block_n == 0 (the ops.py
    wrapper pads).  ``interpret=True`` runs the kernel body on CPU; on real
    TPU pass interpret=False.
    """
    if act is not None and act not in _ACTS:
        raise ValueError(f"unsupported activation {act}")
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
        f"shape ({M},{K})x({K},{N}) not divisible by blocks ({bm},{bn},{bk})"
    grid = (M // bm, N // bn, K // bk)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    args = [a, b]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(bias.reshape(1, N))
        kernel = functools.partial(_kernel, k_steps=grid[2], act=act)
    else:
        kernel = functools.partial(
            lambda a_ref, b_ref, o_ref, acc_ref, **kw:
            _kernel(a_ref, b_ref, None, o_ref, acc_ref, **kw),
            k_steps=grid[2], act=act)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(*args)
