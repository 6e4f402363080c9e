"""moonlight-16b-a3b (Moonlight-16B-A3B) [hf:moonshotai/Moonlight-16B-A3B,
config.json, model_type deepseek_v3].

27 layers at d2048: layer 0 a dense SwiGLU of width 11264
(first_k_dense_replace 1), layers 1-26 DeepSeek-V3 expert layers (64 routed
experts of width 1408, top-6 by sigmoid score plus a correction bias,
weights normalised and scaled by 2.446, 2 shared experts).  Attention is
MLA with 16 heads, no query low-rank projection (q_lora_rank null),
kv_lora_rank 512, 128 + 64 query/key lanes a head, 128 value lanes, rope
theta 50000 without scaling.  Vocabulary 163840, untied head, RMSNorm eps
1e-5."""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="mla_moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=1408, vocab_size=163840, head_dim=128,
    moe=MoEConfig(num_experts=64, top_k=6, shared_experts=2,
                  routed_scaling=2.446, dense_layers=1, dense_d_ff=11264),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    rope_theta=50000.0, norm_eps=1e-5,
)


def reduced() -> ModelConfig:
    """Every mechanism at CPU size: MLA with small ranks, a dense first
    layer, shared experts, 8 routed experts at top-2."""
    return ModelConfig(
        name="moonlight-smoke", family="mla_moe",
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
        d_ff=32, vocab_size=256, head_dim=16,
        moe=MoEConfig(num_experts=8, top_k=2, shared_experts=2,
                      routed_scaling=2.446, dense_layers=1, dense_d_ff=96),
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=16, v_head_dim=16),
        rope_theta=50000.0, norm_eps=1e-5, remat=False,
    )
