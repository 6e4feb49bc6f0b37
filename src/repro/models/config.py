"""ModelConfig — the jax-free architecture description.

Split out of ``models/common.py`` so that purely analytic consumers (the
Ridgeline sweep engine, the parallelism planner CLI, the collective cost
models) can load every config in ``repro.configs`` without importing jax.
``ml_dtypes.bfloat16`` *is* ``jnp.bfloat16`` as far as ``np.dtype`` equality
(and hence every ``astype``/array constructor) is concerned, so the dtype
defaults behave identically on the jax paths.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import ml_dtypes
import numpy as np

Params = Any  # nested dict pytree of jnp arrays
Specs = Any   # matching pytree of tuples of logical axis names (or None)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config covers the whole LM family (dense/MoE/SSM/hybrid/enc-dec)."""

    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm | mlp
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    # attention
    qkv_bias: bool = False
    qk_norm: bool = False
    pos_emb: str = "rope"            # rope | learned | none
    rope_theta: float = 10000.0
    sliding_window: int = 0          # 0 = full attention
    global_attn_layers: Tuple[int, ...] = ()   # full-attn layers when windowed
    causal: bool = True
    # ffn
    ffn_activation: str = "swiglu"   # swiglu | gelu
    ffn_bias: bool = False
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # moe
    n_experts: int = 0
    n_shared_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0                # per-expert hidden
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    pad_experts_to: int = 0          # pad expert count for EP divisibility
                                     # (dead experts are never routed to)
    moe_group_tokens: int = 2048     # GShard dispatch-group size: dispatch
                                     # HBM traffic scales ~T·Tg·k·cf
    # ssm / hybrid
    ssm_state: int = 0               # per-head SSM state size
    ssm_conv: int = 4                # short conv width
    slstm_layers: Tuple[int, ...] = ()   # xLSTM: which blocks are sLSTM
    ssm_chunk: int = 256             # chunked-scan block length
    # enc-dec
    encoder_layers: int = 0
    encoder_seq: int = 0             # fixed encoder context (audio frames)
    # vlm
    visual_tokens: int = 0
    visual_width: int = 0            # ViT stub embedding width
    # mlp (DLRM case study)
    mlp_widths: Tuple[int, ...] = ()
    # numerics / lowering
    compute_dtype: Any = ml_dtypes.bfloat16
    param_dtype: Any = np.float32
    scan_layers: bool = True
    remat: str = "none"              # none | dots | full
    use_flash: bool = False          # Pallas flash-attention path
    use_pallas_matmul: bool = False  # Pallas blocked-matmul path (MLP)
    attn_impl: str = "dense"         # dense | chunked (O(S·bq) XLA blockwise)
    attn_block_q: int = 1024         # q-block for chunked attention
    sp_outputs: bool = False         # Megatron-SP: constrain row-parallel
                                     # block outputs to seq-sharded, turning
                                     # their all-reduce into reduce-scatter
    max_seq_len: int = 8192          # learned-pos table size; rope is unbounded
    # multi-head latent attention (DeepSeek-V2/V3); 0 = grouped-query
    kv_lora_rank: int = 0            # width of the compressed K/V latent
    qk_rope_head_dim: int = 0        # rope'd key width, shared by the heads
    qk_nope_head_dim: int = 0        # per-head query/key width without rope
    v_head_dim: int = 0              # per-head value width
    first_dense_layers: int = 0      # leading layers with a dense FFN (moe)
    # the expert layer: capacity (GShard, softmax then top-k, tokens over
    # capacity dropped) | dropless (DeepSeek-V3: sigmoid scores plus a
    # correction bias choose the top-k, pairs sorted by expert, none dropped)
    moe_dispatch: str = "capacity"
    routed_scale: float = 1.0        # dropless gates' scale after renormalising
    experts_held: int = 0            # experts this chip holds; 0 = all
    first_expert: int = 0            # the first of them

    @property
    def dh(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.dh

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.dh

    @property
    def n_held(self) -> int:
        return self.experts_held or self.n_experts

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)
