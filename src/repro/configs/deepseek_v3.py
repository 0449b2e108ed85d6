"""DeepSeek-V3 — 671B-A37B MoE: 256 routed experts, top-8, node-limited.

[hf:deepseek-ai/DeepSeek-V3 config.json; arXiv:2412.19437] 61L
d_model=7168 128H MLA (q_lora 1536, kv_lora 512, qk_nope 128, qk_rope
64, v 128), dense d_ff=18432 in the first 3 layers, then MoE: 256
routed experts of width 2048, 8 a token, sigmoid scores (noaux_tc) over
8 groups of which a token reaches 4, plus 1 shared expert; vocab 129280.
The scheduling plans (``appdag.plans.ep_stage_dag``) read it; it is not
one of the ten ``ARCH_NAMES`` that the step-plan sweeps cover.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v3", family="moe",
    n_layers=61, d_model=7168, n_heads=128, n_kv_heads=128, head_dim=128,
    d_ff=18432, vocab_size=129280, rope_theta=10000.0, norm_eps=1e-6,
    n_experts=256, experts_per_token=8, moe_layer_period=1,
    d_expert=2048, n_expert_groups=8, groups_per_token=4,
    n_shared_experts=1, first_dense_layers=3, router_scoring="sigmoid",
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128,
)
