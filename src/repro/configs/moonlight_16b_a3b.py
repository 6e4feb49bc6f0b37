"""moonlight-16b-a3b [hf:moonshotai/Moonlight-16B-A3B; hf] (deepseek_v3).

27L, d_model=2048, 16 heads of multi-head latent attention (no query
LoRA; K/V latent 512 with its own RMSNorm, shared rope key 64, per-head
nope 128 and value 128), vocab=163840, untied head, rope theta 50000,
rms_norm_eps 1e-5.  Layer 0 is dense (d_ff 11264); layers 1-26 are MoE:
64 routed SwiGLU experts of 1408, top-6 by sigmoid score plus a
correction bias (noaux_tc; one group, so group limiting does nothing),
gates renormalised and scaled by 2.446, plus 2 shared experts; no token
dropped.
"""
from repro.models.config import ModelConfig

ARCH = "moonlight-16b-a3b"


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH, family="moe", n_layers=27, d_model=2048, n_heads=16,
        n_kv_heads=16, head_dim=128, d_ff=11264, vocab_size=163840,
        rope_theta=50000.0, norm_eps=1e-5, kv_lora_rank=512,
        qk_rope_head_dim=64, qk_nope_head_dim=128, v_head_dim=128,
        first_dense_layers=1, n_experts=64, n_shared_experts=2, moe_top_k=6,
        moe_d_ff=1408, routed_scale=2.446,
        moe_dispatch="dropless", moe_group_tokens=8192)


def reduced() -> ModelConfig:
    return config().replace(n_layers=3, d_model=64, n_heads=4, head_dim=16,
                            n_kv_heads=4, d_ff=128, vocab_size=512,
                            kv_lora_rank=32, qk_rope_head_dim=8,
                            qk_nope_head_dim=16, v_head_dim=16, n_experts=16,
                            moe_top_k=4, moe_d_ff=24, experts_held=8)
