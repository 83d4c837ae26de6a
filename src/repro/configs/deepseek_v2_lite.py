"""deepseek-v2-lite — latent attention (MLA) and fine-grained experts.

[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite, config.json]
27L d_model=2048 16H; one leading dense layer (SwiGLU 10944), then 26 MoE
layers of 64 routed experts of width 1408 (top-6, softmax, gates not
renormalised) and 2 shared; MLA with a 512-wide k/v latent, no q latent,
qk heads of 128 + 64 rotated, v heads of 128; YaRN rope (factor 40 over
4096 positions); vocab 102400, untied.
"""

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab=102_400,
    head_dim=192,
    mlp="swiglu",
    norm="rms",
    rope_theta=10_000.0,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    norm_topk_prob=False,
    first_dense_layers=1,
    dense_d_ff=10944,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    max_seq=163_840,
    notes="MLA trains only: decode and paged serving of its latent cache "
          "raise NotImplementedError; full attention -> long_500k skipped",
)
