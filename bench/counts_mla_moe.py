"""Operations and bytes of a DeepSeek-V2 decode step, computed from the
configuration file's shapes: latent attention decoded in absorbed form,
a dense first layer, then layers of routed and shared experts, of which
this chip holds ``n_routed_experts`` of ``deployment.routed_experts``.

The roofline and utilisation metrics of the latent-attention,
sparse-expert cell divide these by measured time, so they are kept with
the benchmark.
"""
from __future__ import annotations

__all__ = ["held_param_count", "expert_bytes", "kv_bytes_per_token",
           "token_flops", "step_bytes"]


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["num_hidden_layers"], cfg["first_k_dense_replace"])


def _mla_params(cfg: dict) -> int:
    """A layer's attention weights: query, latent, rotary key, the two
    up-projections and the output, and the latent's norm."""
    d, h, r, dn, dr, dv, _, _ = _dims(cfg)
    return d * h * (dn + dr) + d * r + d * dr + r * h * (dn + dv) \
        + h * dv * d + r


def _expert(cfg: dict, width: int) -> int:
    return 3 * cfg["hidden_size"] * width


def _moe_fixed_params(cfg: dict) -> int:
    """An expert layer's weights outside the routed experts: the shared
    experts and the router (its outputs are the deployment's)."""
    return (_expert(cfg, cfg["moe_intermediate_size"]
                    * cfg["n_shared_experts"])
            + cfg["hidden_size"] * cfg["deployment"]["routed_experts"])


def held_param_count(cfg: dict) -> int:
    """Every parameter this chip holds: embedding, head, each layer's
    attention, norms and feed-forward (the held routed experts only)."""
    d, _, _, _, _, _, layers, dense = _dims(cfg)
    moe = layers - dense
    vocab = 2 * cfg["vocab_size"] * d
    per_layer = _mla_params(cfg) + 2 * d
    return (vocab + d + layers * per_layer
            + dense * _expert(cfg, cfg["intermediate_size"])
            + moe * (_moe_fixed_params(cfg) + cfg["n_routed_experts"]
                     * _expert(cfg, cfg["moe_intermediate_size"])))


def expert_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of one routed expert's weights."""
    return _expert(cfg, cfg["moe_intermediate_size"]) * itemsize


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """Bytes a cached token holds over all layers: its latent and rotary
    key, and the int32 position the program keeps beside them."""
    _, _, r, _, dr, _, layers, _ = _dims(cfg)
    return layers * ((r + dr) * itemsize + 4)


def token_flops(cfg: dict, keys: int) -> int:
    """FLOPs of one decoded token attending to ``keys`` cached keys.
    Attention in absorbed form: query, latent and rotary key
    projections, the query's no-position part through ``W_uk`` into the
    latent, the context through ``W_uv`` and the output (2 per weight),
    and per key a head's score over latent and rotary key and its
    weighted latent, ``2 h (r + dr + r)``.  The dense layers' SwiGLU;
    in each expert layer the shared experts, the router, and the routed
    experts at the deployment's share of a token: top-k times the held
    fraction of the router's outputs.  The output head."""
    d, h, r, dn, dr, dv, layers, dense = _dims(cfg)
    moe = layers - dense
    attn = d * h * (dn + dr) + d * r + d * dr + h * dn * r + h * r * dv \
        + h * dv * d
    routed = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
              / cfg["deployment"]["routed_experts"])
    per_token = (2 * layers * attn
                 + 2 * dense * _expert(cfg, cfg["intermediate_size"])
                 + 2 * moe * (_moe_fixed_params(cfg) + routed * _expert(
                     cfg, cfg["moe_intermediate_size"]))
                 + 2 * d * cfg["vocab_size"])
    return int(per_token + 2 * h * (2 * r + dr) * layers * int(keys))


def step_bytes(cfg: dict, keys_per_seq, experts_hit: int,
               itemsize: int = 2) -> int:
    """HBM bytes one decode step needs: every held parameter but the
    embedding table and the routed experts once, the ``experts_hit``
    held experts that received a token, and each live sequence's cached
    tokens up to its position."""
    d = cfg["hidden_size"]
    moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    fixed = (held_param_count(cfg) - cfg["vocab_size"] * d
             - moe * cfg["n_routed_experts"]
             * _expert(cfg, cfg["moe_intermediate_size"]))
    return (fixed * itemsize + int(experts_hit) * expert_bytes(cfg, itemsize)
            + sum(kv_bytes_per_token(cfg, itemsize) * int(k)
                  for k in keys_per_seq))
