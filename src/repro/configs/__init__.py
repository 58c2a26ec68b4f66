"""Architecture / shape registry.

Public ids use dashes (``--arch qwen2-7b``); modules use underscores.
"""
from __future__ import annotations

import dataclasses
import importlib

from repro.configs.base import (
    DECODE_32K,
    LONG_500K,
    PREFILL_32K,
    SHAPES,
    TRAIN_4K,
    ModelConfig,
    ShapeConfig,
    SpecDecodeConfig,
    SpecInFConfig,
    TrainConfig,
    draft_config,
    mesh_axes,
    shape_applicable,
)

_ARCH_MODULES = {
    "zamba2-2.7b": "zamba2_2p7b",
    "moonshot-v1-16b-a3b": "moonshot_v1_16b_a3b",
    "dbrx-132b": "dbrx_132b",
    "deepseek-coder-33b": "deepseek_coder_33b",
    "qwen2-7b": "qwen2_7b",
    "qwen3-1.7b": "qwen3_1p7b",
    "olmo-1b": "olmo_1b",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "musicgen-large": "musicgen_large",
    "pixtral-12b": "pixtral_12b",
}

ARCH_IDS = tuple(_ARCH_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch]}")
    return mod.CONFIG


def get_shape(shape: str) -> ShapeConfig:
    if shape not in SHAPES:
        raise KeyError(f"unknown shape {shape!r}; known: {sorted(SHAPES)}")
    return SHAPES[shape]


# ---------------------------------------------------------------------------
# Reduced smoke configs: same family/block layout, tiny dims, CPU-runnable.
# ---------------------------------------------------------------------------


def smoke_config(arch: str) -> ModelConfig:
    full = get_config(arch)
    reduced = dict(
        name=full.name + "-smoke",
        num_layers=2 if full.family != "hybrid" else 4,
        d_model=64,
        d_ff=128 if full.d_ff else 0,
        vocab_size=256,
        head_dim=16 if full.num_heads else 0,
        rope_theta=full.rope_theta,
    )
    if full.num_heads:
        reduced["num_heads"] = 4
        reduced["num_kv_heads"] = 4 if full.num_kv_heads == full.num_heads else 2
    if full.family == "moe":
        reduced["num_experts"] = 4
        reduced["experts_per_token"] = 2
    if full.ssm_version:
        reduced["ssm_state"] = 8
        reduced["ssm_head_dim"] = 16
        reduced["dt_rank"] = 8
    if full.shared_attn_every:
        reduced["shared_attn_every"] = 2
    return dataclasses.replace(full, **reduced)


SMOKE_SHAPE = ShapeConfig("smoke", seq_len=32, global_batch=2, kind="train")
SMOKE_DECODE = ShapeConfig("smoke_decode", seq_len=64, global_batch=2, kind="decode")

# ---------------------------------------------------------------------------
# Paper-native workload presets (§5.1): the paper trains BERT/RoBERTa (DP) and
# LLaMA2-7B / ChatGLM-6B (MP, PP), and serves medium models.  We model each by
# an LM-family stand-in of matching scale; CV inference workloads (ResNet152,
# VGG19) enter the *simulator* as cost profiles (see core/simulator.py).
# ---------------------------------------------------------------------------

ROBERTA_LARGE = ModelConfig(
    name="roberta-large", family="dense", num_layers=24, d_model=1024,
    num_heads=16, num_kv_heads=16, d_ff=4096, vocab_size=50265,
    norm_type="layernorm",
)
BERT_BASE = ModelConfig(
    name="bert-base", family="dense", num_layers=12, d_model=768,
    num_heads=12, num_kv_heads=12, d_ff=3072, vocab_size=30522,
    head_dim=64, norm_type="layernorm",
)
GPT2_LARGE = ModelConfig(
    name="gpt2-large", family="dense", num_layers=36, d_model=1280,
    num_heads=20, num_kv_heads=20, d_ff=5120, vocab_size=50257,
    head_dim=64, norm_type="layernorm", tie_embeddings=True,
)
LLAMA2_7B = ModelConfig(
    name="llama2-7b", family="dense", num_layers=32, d_model=4096,
    num_heads=32, num_kv_heads=32, d_ff=11008, vocab_size=32000,
)
CHATGLM_6B = ModelConfig(
    name="chatglm-6b", family="dense", num_layers=28, d_model=4096,
    num_heads=32, num_kv_heads=2, d_ff=13696, vocab_size=65024,
)

PAPER_MODELS = {
    m.name: m
    for m in (ROBERTA_LARGE, BERT_BASE, GPT2_LARGE, LLAMA2_7B, CHATGLM_6B)
}

__all__ = [
    "ARCH_IDS",
    "SHAPES",
    "TRAIN_4K",
    "PREFILL_32K",
    "DECODE_32K",
    "LONG_500K",
    "SMOKE_SHAPE",
    "SMOKE_DECODE",
    "PAPER_MODELS",
    "ModelConfig",
    "ShapeConfig",
    "TrainConfig",
    "SpecInFConfig",
    "get_config",
    "get_shape",
    "smoke_config",
    "shape_applicable",
    "mesh_axes",
]
