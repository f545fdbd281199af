"""deepseek-v2-lite-16b [moe] — MLA (kv_lora 512, no q-LoRA, YaRN rope),
one dense layer, then 26 layers of 64 routed experts (top-6, softmax,
unnormalised weights) beside 2 shared experts [arXiv:2405.04434;
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json].
"""
from ..models.config import LayerSlot, ModelConfig, Yarn

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,                 # dense first layer FFN
    vocab_size=102400,
    pattern=(LayerSlot("mla", "moe"),),
    first_dense_layers=1,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    d_ff_expert=1408,
    norm_topk_prob=False,
    mla=True,
    kv_lora_rank=512,
    q_lora_rank=0,              # v2-lite: full-rank q
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    yarn=Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
              beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    tie_embeddings=False,
    loss_chunk=512,
)
