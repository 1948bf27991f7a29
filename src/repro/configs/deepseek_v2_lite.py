"""DeepSeek-V2-Lite (15.7B, 2.4B active) — MLA (kv_lora 512, no q_lora)
with YaRN rope scaling, then fine-grained MoE: 64 routed experts top-6
and 2 shared, after one dense layer. [arXiv:2405.04434;
huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,       # MLA: kv heads notional; latent cache is shared
    d_ff=10944,            # dense layer-0 FFN
    vocab_size=102_400,
    max_seq_len=163_840,
    rope_theta=10_000.0,
    rope_scaling=YarnConfig(factor=40.0, original_max_position=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    norm_eps=1e-6,
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, qk_rope_head_dim=64,
                  qk_nope_head_dim=128, v_head_dim=128),
    moe=MoEConfig(num_experts=64, num_shared_experts=2, top_k=6,
                  expert_d_ff=1408, first_dense_layers=1),
    peer_axes=("pod", "data"),
).validate()
