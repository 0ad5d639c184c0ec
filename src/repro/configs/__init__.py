"""Architecture registry: one module per assigned architecture."""
from __future__ import annotations

import dataclasses

from .base import (ALL_SHAPES, DECODE_32K, LONG_500K, PREFILL_32K, SHAPES_BY_NAME,
                   TRAIN_4K, MLAConfig, ModelConfig, MoEConfig, ShapeConfig,
                   TrainConfig, YarnScaling, shapes_for)

from .recurrentgemma_2b import CONFIG as recurrentgemma_2b
from .deepseek_7b import CONFIG as deepseek_7b
from .qwen2_7b import CONFIG as qwen2_7b
from .mistral_large_123b import CONFIG as mistral_large_123b
from .gemma3_12b import CONFIG as gemma3_12b
from .chameleon_34b import CONFIG as chameleon_34b
from .qwen3_moe_30b_a3b import CONFIG as qwen3_moe_30b_a3b
from .dbrx_132b import CONFIG as dbrx_132b
from .musicgen_large import CONFIG as musicgen_large
from .xlstm_1_3b import CONFIG as xlstm_1_3b
from .deepseek_v2_lite import CONFIG as deepseek_v2_lite

ARCHS = {
    c.name: c
    for c in (
        recurrentgemma_2b,
        deepseek_7b,
        qwen2_7b,
        mistral_large_123b,
        gemma3_12b,
        chameleon_34b,
        qwen3_moe_30b_a3b,
        dbrx_132b,
        musicgen_large,
        xlstm_1_3b,
        deepseek_v2_lite,
    )
}


def get_config(name: str) -> ModelConfig:
    key = name.replace("_", "-")
    if key not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[key]


def reduced_config(cfg: ModelConfig) -> ModelConfig:
    """Tiny same-family config for CPU smoke tests (one fwd/train step)."""
    kw = dict(
        num_layers=max(len(cfg.block_pattern), 2),
        d_model=64,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_kv_heads < cfg.num_heads else 4,
        head_dim=16,
        d_ff=128 if cfg.d_ff else 0,
        vocab_size=256,
        window_size=min(cfg.window_size, 16) if cfg.window_size else 0,
        rnn_width=64 if cfg.rnn_width else 0,
        param_dtype="float32",
        compute_dtype="float32",
    )
    if cfg.moe is not None:
        # capacity_factor 8 => effectively dropless at smoke-test scale, so
        # train-vs-decode consistency checks are exact (dropping is a
        # legitimate train/serve divergence in capacity-bounded MoE). A
        # dropless model stays dropless, with its shared experts and gates.
        kw["moe"] = MoEConfig(
            num_experts=8, top_k=2, d_ff_expert=32,
            capacity_factor=8.0 if cfg.moe.capacity_factor else None,
            num_shared_experts=cfg.moe.num_shared_experts,
            norm_topk_prob=cfg.moe.norm_topk_prob)
    if cfg.mla is not None:
        kw["mla"] = dataclasses.replace(cfg.mla, kv_lora_rank=32,
                                        qk_nope_head_dim=16,
                                        qk_rope_head_dim=8, v_head_dim=16)
        kw["head_dim"] = 24
    return cfg.with_(**kw)
