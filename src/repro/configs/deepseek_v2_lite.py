"""deepseek-v2-lite [moe] — DeepSeek-V2-Lite, 15.7B parameters of which
2.4B are active per token.  [hf:deepseek-ai/DeepSeek-V2-Lite;
arXiv:2405.04434]

27 layers at hidden size 2,048: the first has a dense FFN of width
10,944, the other 26 an MoE FFN of 64 routed experts of width 1,408
(greedy softmax top-6, weights not renormalised, routed scaling 1) and
2 shared experts.  Attention is MLA with a 512-wide latent KV, no
low-rank query, 16 heads of 128 + 64 (rope) query/key and 128 value
dimensions, and YaRN rotary scaling (factor 40, mscale = mscale_all_dim
= 0.707, beta 32/1, original length 4,096) in DeepSeek's pair layout.
The balance loss is the sequence-wise one; its alpha of 0.001 is
assumed, as the published config's catalog row leaves it out.
"""
from .base import AttentionSpec, ModelConfig, MoESpec, YarnSpec

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    arch_type="moe",
    n_layers=27,
    d_model=2048,
    d_ff=10_944,                # dense FFN width (layer 0)
    vocab=102_400,
    attention=AttentionSpec(
        kind="mla",
        n_heads=16,
        n_kv_heads=16,
        head_dim=192,           # qk_nope + qk_rope
        q_lora_rank=0,
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
    moe=MoESpec(n_experts=64, top_k=6, n_shared=2, d_ff_expert=1408,
                norm_topk_prob=False, routed_scaling_factor=1.0,
                balance="seq", aux_loss=0.001),
    n_dense_layers=1,
    rms_eps=1e-6,
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
)
