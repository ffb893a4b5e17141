"""qwen3-moe-30b-a3b [moe] — 128 fine-grained experts (d_ff=768), top-8
with the weights renormalised over the 8, no shared expert; GQA 32/4 with
head_dim 128 (q_proj 2048 → 4096) and RMSNorm over head_dim on q and k
before RoPE (QK-norm). The expert layer is dropless (models/moe.py).
[hf:Qwen/Qwen3-30B-A3B config.json; arXiv:2505.09388]"""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-moe-30b-a3b",
    family="moe",
    n_layers=48,
    d_model=2048,
    n_heads=32,
    n_kv_heads=4,
    d_head=128,
    d_ff=768,
    vocab=151936,
    n_experts=128,
    top_k=8,
    qk_norm=True,
    rope_theta=1e6,
    act="swiglu",
    # served at batch 24: 512 x 1024 float32 score blocks of 32 heads would
    # take 1.6e9 bytes each in a long prefill
    attn_q_chunk=256,
    attn_k_chunk=512,
    microbatches=8,   # fits 16 GB/device on the 16x16 mesh (EXPERIMENTS §Dry-run)
)
