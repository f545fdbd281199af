"""whisper-small [audio] — enc-dec, conv frontend (stub)
[arXiv:2212.04356; unverified].

The model takes precomputed frame embeddings (B, S, d), the batch's
``enc_frames``, in place of the log-mel conv frontend.
"""
from ..models.config import LayerSlot, ModelConfig

CONFIG = ModelConfig(
    name="whisper-small",
    family="audio",
    n_layers=12,                # decoder layers
    d_model=768,
    n_heads=12,
    n_kv_heads=12,
    head_dim=64,
    d_ff=3072,
    vocab_size=51865,
    pattern=(LayerSlot("attn_global", "dense"),),
    is_encoder_decoder=True,
    encoder_layers=12,
    encoder_pattern=(LayerSlot("attn_global", "dense"),),
    max_target_len=448,
    frontend="audio_frames",
    tie_embeddings=True,
    loss_chunk=0,
)
