"""DeepSeek-V2 236B — MLA (kv_lora=512) + fine-grained MoE 160e top-6, 2 shared.
[arXiv:2405.04434]"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    family="moe",
    source="arXiv:2405.04434",
    num_layers=60,
    d_model=5120,
    num_heads=128,
    num_kv_heads=128,      # MLA: kv heads notional; latent cache is shared
    d_ff=12288,            # dense layer-0 FFN
    vocab_size=102_400,
    max_seq_len=131_072,
    rope_scaling=YarnConfig(factor=40.0, original_max_position=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    param_dtype="bfloat16",
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=1536,
                  qk_rope_head_dim=64, qk_nope_head_dim=128, v_head_dim=128),
    moe=MoEConfig(num_experts=160, num_shared_experts=2, top_k=6,
                  expert_d_ff=1536, first_dense_layers=1),
    # 236B cannot replicate per 16-chip peer: peers live on the pod axis;
    # experts shard over data x model (256-way within a pod).  See DESIGN §4.
    peer_axes=("pod",),
).validate()
