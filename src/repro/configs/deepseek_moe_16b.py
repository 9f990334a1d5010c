"""deepseek-moe-16b [moe] — fine-grained MoE, 2 shared + 64 routed top-6
(arXiv:2401.06066; hf:deepseek-ai/deepseek-moe-16b-base). 28L, d_model=2048,
16 heads (kv=16, MHA), vocab=102400. Layer 0 is a dense SwiGLU of
intermediate_size 10944 (first_k_dense_replace 1); layers 1-27 hold 64
routed experts of moe_intermediate_size 1408, top-6 by a softmax router
whose top-6 weights are used as they are (norm_topk_prob false), plus 2
shared experts.
"""
from .base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-moe-16b",
    block="moe",
    n_layers=28,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=10944,
    vocab=102400,
    moe=MoEConfig(n_experts=64, top_k=6, expert_ff=1408, n_shared=2,
                  first_dense=1, norm_topk=False),
    act="swiglu",
    norm="rms",
)
