"""deepseek-v2-lite-16b [moe]: 27L d_model=2048 16H (MLA kv_lora=512, no
q_lora, qk head 128 nope + 64 rope, v head 128, YaRN factor 40) vocab=102400,
MoE 64 routed experts top-6 (softmax, greedy, not renormalised) + 2 shared,
per-expert d_ff=1408, sequence-level balance loss.  First layer uses a dense
FFN (d_ff=10944), per the HF config.
[arXiv:2405.04434; https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite]

The balance-loss weight alpha = 0.001 is DeepSeek's published value for
V2-Lite (the config.json row carries no ``aux_loss_alpha``).  ``FULL`` holds
all 64 routed experts (``moe_held``); a chip of an expert-parallel
deployment holds its share.
"""
from repro.nn.rope import YaRN

from .base import ArchConfig, LayerSpec

FULL = ArchConfig(
    name="deepseek-v2-lite-16b", family="moe",
    d_model=2048, n_layers=27, n_heads=16, n_kv_heads=16, head_dim=128,
    d_ff=10944, vocab=102400,
    prefix=(LayerSpec("mla", "dense"),),
    pattern=(LayerSpec("mla", "moe"),),
    rope_theta=10000.0,
    rope_scaling=YaRN(factor=40.0, original_max_position=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
    mla_kv_lora=512, mla_rope_dim=64, mla_nope_dim=128, mla_v_dim=128,
    moe_experts=64, moe_top_k=6, moe_d_ff=1408,
    moe_shared=2, moe_shared_d_ff=2816,
    moe_norm_topk_prob=False, moe_seq_aux=True,
    moe_aux_alpha=0.001, moe_held=(0, 64),
)

SMOKE = ArchConfig(
    name="deepseek-v2-lite-16b-smoke", family="moe",
    d_model=64, n_layers=3, n_heads=4, n_kv_heads=4, head_dim=16,
    d_ff=128, vocab=256,
    prefix=(LayerSpec("mla", "dense"),),
    pattern=(LayerSpec("mla", "moe"),),
    rope_scaling=YaRN(factor=40.0, original_max_position=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
    mla_kv_lora=32, mla_rope_dim=8, mla_nope_dim=16, mla_v_dim=16,
    moe_experts=8, moe_top_k=2, moe_d_ff=32, moe_shared=2,
    moe_shared_d_ff=64,
    moe_norm_topk_prob=False, moe_seq_aux=True, moe_aux_alpha=0.001,
    moe_held=(0, 8),
)
