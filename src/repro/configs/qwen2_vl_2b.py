"""qwen2-vl-2b [vlm] — M-RoPE, dynamic resolution [arXiv:2409.12191; hf].

Backbone only; the vision frontend is a STUB — the model takes token
ids plus the (3, B, S) M-RoPE position streams that precomputed patch
embeddings would induce.
"""
from ..models.config import LayerSlot, ModelConfig

CONFIG = ModelConfig(
    name="qwen2-vl-2b",
    family="vlm",
    n_layers=28,
    d_model=1536,
    n_heads=12,
    n_kv_heads=2,
    head_dim=128,
    d_ff=8960,
    vocab_size=151936,
    pattern=(LayerSlot("attn_global", "dense"),),
    qkv_bias=True,
    rope_theta=1_000_000.0,
    mrope_sections=(16, 24, 24),   # t/h/w frequency lanes (sums to half-dim)
    frontend="patch",
    tie_embeddings=True,
    loss_chunk=512,
)
