"""qwen3-next-80b-a3b — Qwen3-Next-80B-A3B's own block, one chip's share.

[hf Qwen/Qwen3-Next-80B-A3B-Instruct config.json]  48 decoder layers,
hidden 2048, every fourth layer gated attention (16 q / 2 kv heads of 256,
partial rotary 0.25, theta 1e7), the others Gated DeltaNet (16 k / 32 v
heads of 128, short conv 4); every layer a sparse MoE of 512 experts of
width 512, 10 per token, renormalized, plus one shared expert of 512;
vocabulary 151936, untied.

Each decoder layer is two residual sublayers here — the mixer, then the
MoE as the stateless ``qnext_moe`` mixer kind — so ``n_layers`` counts
sublayers and ``ffn`` is "none".  The cut (one chip of a 32-chip
deployment: 2 pipeline stages of 24 decoder layers, the MoE of each
stage divided 16 ways by expert): 24 decoder layers (six whole periods,
18 GDN and 6 attention), and experts 0-31 of each layer's 512 held; the
router keeps its 512 outputs and 10 per token.  Every width is as
published.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="qwen3-next-80b-a3b",
    family="hybrid",
    vocab=151936,
    d_model=2048,
    n_layers=48,                      # 24 decoder layers x 2 sublayers
    pattern=("qnext_gdn", "qnext_moe", "qnext_gdn", "qnext_moe",
             "qnext_gdn", "qnext_moe", "qnext_attn", "qnext_moe"),
    ffn="none",
    n_heads=16,
    n_kv_heads=2,
    head_dim=256,
    rotary_dim=64,
    rope_theta=1e7,
    d_ff=512,
    moe_experts=512,
    moe_experts_held=32,
    moe_top_k=10,
    moe_shared_d_ff=512,
    gdn_k_heads=16,
    gdn_v_heads=32,
    gdn_head_dim=128,
    gdn_conv_width=4,
    subquadratic=True,
    notes="One chip of a 16-way expert-parallel pipeline stage: 3.91e9 "
          "parameters (7.28 GiB bf16); per slot 36 MiB of GDN state, "
          "0.84 MiB of conv carry and 12 KiB of KV per position.",
)
