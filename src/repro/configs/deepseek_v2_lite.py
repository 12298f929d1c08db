"""deepseek-v2-lite [mla] — 27L d_model=2048 16H, MLA kv_lora_rank=512
(qk 128 nope + 64 rope, v 128, no query LoRA), YaRN x40 over 4096;
layer 0 a dense SwiGLU of 10944, layers 1-26 64 routed experts of 1408
(softmax, greedy top-6, weights not renormalized) + 2 shared; vocab=102400
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite]."""
from .base import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="mla",
    source="arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=10944,
    vocab_size=102400, rope_theta=10000.0, norm_eps=1e-6,
    moe_experts=64, moe_top_k=6, moe_d_ff=1408, moe_shared_d_ff=2 * 1408,
    moe_norm_topk=False, dense_layers=1,
    kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    yarn=Yarn(factor=40.0, original_max_pos=4096, beta_fast=32.0,
              beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
)
