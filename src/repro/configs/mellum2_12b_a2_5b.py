"""Mellum2-12B-A2.5B: 28 layers of 3 sliding-window (1,024) + 1 full
attention, GQA 32/4 of 128, YaRN (x16 over 8,192) on the full layers and
plain RoPE (theta 5e5) on the sliding ones, and in every layer 64 routed
experts of width 896, top 8 renormalised, no shared expert; untied
vocabulary of 98,304. [hf:JetBrains/Mellum2-12B-A2.5B-Instruct]

``full()`` holds all 64 experts. ``ep_share(chips, index)`` is one chip's
share of the expert-parallel deployment: each layer's experts divided over
``chips`` chips, attention, routers, norms, embedding and head replicated;
the router keeps its 64 outputs and top 8, and the chip computes its own
experts' part of the layer (the dropless layer, ``models/moe``). The
benchmark serves ``ep_share(8, 0)``. q/k RMSNorm follows the Qwen3-MoE
block whose keys the config carries (an assumption: config.json states
none).
"""
import dataclasses

from repro.configs.base import (ATTN, AttnKind, ModelConfig, MoEConfig,
                                RopeConfig, register)

SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
          "blob/main/config.json")

SLIDING = AttnKind(window=1024, rope=RopeConfig(theta=500000.0))
FULL = AttnKind(rope=RopeConfig(
    theta=500000.0, yarn_factor=16.0, original_max_position=8192,
    beta_fast=32.0, beta_slow=1.0, attention_factor=1.2772588722239782))


def full() -> ModelConfig:
    return ModelConfig(
        name="mellum2-12b-a2.5b", family="moe",
        num_layers=28, d_model=2304, num_heads=32, num_kv_heads=4,
        d_ff=0, vocab_size=98304, head_dim=128, qk_norm=True,
        rope_theta=500000.0, norm_eps=1e-6, tie_embeddings=False,
        block_pattern=(ATTN,) * 4,
        attn_kinds=(SLIDING, SLIDING, SLIDING, FULL),
        moe=MoEConfig(num_experts=64, num_experts_per_token=8, d_ff=896,
                      dropless=True, norm_topk_prob=True),
        source=SOURCE,
    )


def ep_share(chips: int, index: int, cfg: ModelConfig = None) -> ModelConfig:
    """Chip ``index``'s share of ``cfg`` (default ``full()``) when each
    layer's experts are divided over ``chips`` chips."""
    cfg = cfg or full()
    held = cfg.moe.num_experts // chips
    assert held * chips == cfg.moe.num_experts, (chips, cfg.moe.num_experts)
    return dataclasses.replace(
        cfg, name=f"{cfg.name}-ep{chips}",
        moe=dataclasses.replace(cfg.moe, experts_held=held,
                                held_offset=index * held))


def smoke() -> ModelConfig:
    """One period of the same shape at a CPU-test size: window 8, 16
    experts top 4, YaRN over an original 32 positions."""
    return ModelConfig(
        name="mellum2-12b-a2.5b-smoke", family="moe",
        num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
        d_ff=0, vocab_size=512, head_dim=32, qk_norm=True,
        rope_theta=10000.0, block_pattern=(ATTN,) * 4,
        attn_kinds=(AttnKind(window=8),) * 3 + (AttnKind(rope=RopeConfig(
            theta=10000.0, yarn_factor=4.0, original_max_position=32,
            attention_factor=1.1386)),),
        moe=MoEConfig(num_experts=16, num_experts_per_token=4, d_ff=32,
                      dropless=True),
    )


register("mellum2-12b-a2.5b", full, smoke)
