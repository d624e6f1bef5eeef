"""DeepSeek-V2 (236B) [arXiv:2405.04434; hf] — MLA + fine-grained MoE.

60L, d_model=5120, 128 heads, MLA kv_lora_rank=512 (q_lora 1536,
qk_nope 128 + qk_rope 64, v_head 128, YaRN rope factor 40), the first layer
a dense MLP of 12288, then MoE: 2 shared + 160 routed top-6 of width 1536,
group-limited greedy routing (8 groups, top 3), unnormalised gates x16,
vocab=102400.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-236b", family="moe",
    num_layers=60, d_model=5120, num_heads=128, num_kv_heads=128,
    d_ff=12288, vocab_size=102400, rope_theta=10000.0, norm_eps=1e-6,
    use_mla=True, kv_lora_rank=512, q_lora_rank=1536,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    moe_num_experts=160, moe_top_k=6, moe_d_ff=1536, moe_shared_experts=2,
    first_k_dense_replace=1, moe_n_group=8, moe_topk_group=3,
    moe_norm_topk_prob=False, moe_routed_scaling=16.0,
    rope_scaling="yarn", yarn_factor=40.0, yarn_original_max_position=4096,
    yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
)
