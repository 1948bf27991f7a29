"""Architecture registry: ``get_config(arch_id)`` + reduced smoke variants.

Reduced variants keep the *family-defining structure* (GQA ratio, MoE
routing, shared experts and leading dense layers, latent attention with
or without a query low rank, YaRN, SSM heads, stub frontends,
cross-attention) at ≤2 layers, d_model ≤ 512, ≤4 experts so they run a
real step on one CPU device.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict

from repro.configs.base import (FrontendConfig, InputShape, MLAConfig,
                                ModelConfig, MoEConfig, SSMConfig)
from repro.configs.shapes import SHAPES

_ARCH_MODULES = {
    "qwen2-1.5b": "repro.configs.qwen2_1_5b",
    "rwkv6-3b": "repro.configs.rwkv6_3b",
    "yi-34b": "repro.configs.yi_34b",
    "hymba-1.5b": "repro.configs.hymba_1_5b",
    "internvl2-2b": "repro.configs.internvl2_2b",
    "whisper-base": "repro.configs.whisper_base",
    "deepseek-v2-236b": "repro.configs.deepseek_v2_236b",
    "deepseek-v2-lite": "repro.configs.deepseek_v2_lite",
    "yi-6b": "repro.configs.yi_6b",
    "deepseek-moe-16b": "repro.configs.deepseek_moe_16b",
    "h2o-danube-3-4b": "repro.configs.h2o_danube_3_4b",
    "templar-1b": "repro.configs.templar_1b",
}

ASSIGNED_ARCHS = tuple(a for a in _ARCH_MODULES if a != "templar-1b")


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(_ARCH_MODULES[arch]).CONFIG


def get_shape(name: str) -> InputShape:
    return SHAPES[name]


def reduced_config(arch: str) -> ModelConfig:
    """Smoke-test variant: same family/topology, tiny dims."""
    cfg = get_config(arch)
    kw: Dict = dict(
        name=cfg.name + "-smoke",
        num_layers=2,
        d_model=256,
        d_ff=512,
        vocab_size=512,
        max_seq_len=512,
        param_dtype="float32",
        dtype="float32",
        peer_axes=("data",),
    )
    if not cfg.attention_free:
        # preserve the GQA ratio with 8 query heads of dim 32
        ratio = cfg.num_heads // cfg.num_kv_heads
        heads = 8
        kw.update(num_heads=heads, num_kv_heads=max(1, heads // min(ratio, heads)),
                  head_dim=32)
    else:
        kw.update(num_heads=4, num_kv_heads=4, head_dim=64)  # rwkv: 4x64=256
    if cfg.moe is not None:
        kw["moe"] = MoEConfig(num_experts=4, num_shared_experts=1, top_k=2,
                              expert_d_ff=128,
                              first_dense_layers=cfg.moe.first_dense_layers)
    if cfg.mla is not None:
        kw["mla"] = MLAConfig(kv_lora_rank=64,
                              q_lora_rank=48 if cfg.mla.q_lora_rank else 0,
                              qk_rope_head_dim=16, qk_nope_head_dim=32,
                              v_head_dim=32)
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(cfg.ssm, head_dim=64, chunk_len=32)
    if cfg.frontend is not None:
        kw["frontend"] = FrontendConfig(kind=cfg.frontend.kind,
                                        num_prefix_tokens=16, embed_dim=64)
    if cfg.attn_window:
        kw["attn_window"] = 64
    return cfg.with_overrides(**kw).validate()


def tiny_config(**overrides) -> ModelConfig:
    """Minimal dense config for unit tests / convergence benches."""
    base = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=128,
                       num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256,
                       vocab_size=512, max_seq_len=512, dtype="float32",
                       param_dtype="float32")
    return base.with_overrides(**overrides).validate()
