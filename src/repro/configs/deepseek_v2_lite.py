"""deepseek-v2-lite  [MLA + moe]  [arXiv:2405.04434;
hf:deepseek-ai/DeepSeek-V2-Lite]

27L d_model=2048 16H vocab=102400, untied head.  Multi-head latent
attention without query LoRA (a direct wq), kv_lora_rank=512, 128 nope +
64 rope dims per head, v 128; YaRN rope (factor 40 over 4096 positions,
mscale = mscale_all_dim = 0.707) on interleaved rotary pairs.  Layer 0 is
a dense SwiGLU (10944); layers 1-26 route top-6 of 64 experts (1408) by
softmax, weights not renormalised, plus 2 shared experts.

This replica is rank 0 of a four-way expert-parallel deployment (one v5e
2x2 host): it holds experts 0-15 of every MoE layer and its own slots;
the router keeps all 64 outputs, and pairs routed to experts 16-63 add
nothing here.
"""
from repro.common.config import MLAConfig, ModelConfig, MoEConfig, YaRNConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab_size=102400,
    head_dim=192,              # qk_nope(128) + qk_rope(64)
    attention="mla",
    mla=MLAConfig(q_lora_rank=0, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128,
                  rope_interleaved=True),
    rope_theta=10000.0,
    yarn=YaRNConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    dense_first_layer=True,
    dense_first_d_ff=10944,
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  impl="ragged", norm_topk=False, routed_scaling=1.0,
                  n_held=16, held_start=0),
    moe_pattern=(True,),
    activation="silu",
    gated_mlp=True,
    norm_eps=1e-6,
    max_seq_len=163840,
)
