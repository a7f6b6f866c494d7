"""Qwen3-MoE-235B-A22B [hf:Qwen/Qwen3-30B-A3B family; hf].

MoE decoder: 94L, d_model=4096, 64 heads (kv=4, head_dim=128),
expert d_ff=1536, 128 experts top-8, vocab=151936, qk-norm.
The paper's branch-divergence showcase: lookahead (proactive) routing.
"""
from dataclasses import replace

from repro.configs.base import ModelConfig, register, shrink

FULL = ModelConfig(
    name="qwen3-moe-235b-a22b",
    family="moe",
    num_layers=94,
    d_model=4096,
    num_heads=64,
    num_kv_heads=4,
    head_dim=128,
    d_ff=1536,          # dense fallback width (unused: all layers MoE)
    d_ff_expert=1536,
    vocab_size=151_936,
    qk_norm=True,
    block_pattern=("moe",),
    num_experts=128,
    top_k=8,
    route_mode="lookahead",
    optimizer="adafactor",  # memory roofline: 235B params on 256 chips
)

register(FULL, shrink(FULL, num_experts=8))

# One TPU v5e chip's share of FULL (16 GB of HBM), every width as published:
# d_model, 64 query / 4 KV heads of 128, expert FFN 1536, top-8, qk-norm.
# Weights are random (seeded); only scale is cut.
ONE_CHIP_REDUCED = {
    "num_layers": "94 -> 4 (depth; each layer is identical in shape)",
    "num_experts": "128 -> 16 held: the share of one chip when 128 experts "
                   "spread over 8 expert-parallel chips",
    "vocab_size": "151936 -> 18992 (1/8, a vocabulary-parallel share)",
    "param_dtype": "float32 -> bfloat16 (the published weights are bf16)",
}
# Departure, not a cut: the router here spans the 16 held experts, where the
# published layer routes over all 128 and a chip computes its own 16.
ONE_CHIP = replace(
    FULL,
    name="qwen3-moe-235b-a22b-1chip",
    num_layers=4,
    num_experts=16,
    vocab_size=18_992,
    param_dtype="bfloat16",
)
# The same preset with all 128 published experts, for expert parallelism over
# a (data=1, model=4) mesh: 32 experts per chip.
FOUR_CHIP_REDUCED = {k: v for k, v in ONE_CHIP_REDUCED.items() if k != "num_experts"}
FOUR_CHIP = replace(ONE_CHIP, name="qwen3-moe-235b-a22b-4chip", num_experts=128)

register(ONE_CHIP, shrink(ONE_CHIP, num_experts=8), preset=True)
register(FOUR_CHIP, shrink(FOUR_CHIP, num_experts=8), preset=True)
