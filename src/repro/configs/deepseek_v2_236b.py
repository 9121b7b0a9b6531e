"""deepseek-v2-236b [moe] — MLA (kv_lora=512) + 160-expert top-6 MoE with
2 shared experts.  [arXiv:2405.04434]

Assignment line: 60L d_model=5120 128H (GQA kv=128) d_ff=1536 vocab=102400,
MoE 160e top-6.  The assigned d_ff=1536 is the *per-expert* hidden size
(DeepSeek-V2 moe_intermediate_size); the single leading dense layer uses
the model-card intermediate_size of 12288.

The router's weights are not renormalised and are scaled by 16, with
the sequence-wise balance loss (alpha 0.003), as published.  The
published router is group-limited (the top-6 experts are drawn from the
3 best of 8 expert groups); that is not implemented, and this config
routes by plain greedy top-6 over all 160 experts.
"""
from .base import AttentionSpec, ModelConfig, MoESpec, YarnSpec

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    arch_type="moe",
    n_layers=60,
    d_model=5120,
    d_ff=12288,                 # dense FFN width (layer 0)
    vocab=102_400,
    attention=AttentionSpec(
        kind="mla",
        n_heads=128,
        n_kv_heads=128,
        head_dim=192,           # qk_nope + qk_rope
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_dim=128,
        qk_rope_dim=64,
        v_head_dim=128,
        rope_theta=10_000.0,
        rope_scaling=YarnSpec(factor=40.0, original_max_position=4096,
                              beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                              mscale_all_dim=0.707),
        rope_interleaved=True,
    ),
    activation="silu",
    moe=MoESpec(n_experts=160, top_k=6, n_shared=2, d_ff_expert=1536,
                norm_topk_prob=False, routed_scaling_factor=16.0,
                balance="seq", aux_loss=0.003),
    n_dense_layers=1,
    rms_eps=1e-6,
    source="arXiv:2405.04434",
)
