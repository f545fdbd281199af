"""Operations and bytes the algorithms need, computed from shapes.

The roofline and utilisation metrics divide these by measured time, so
they are kept with the benchmark: a change to the program cannot change
what counts as the work.
"""
from __future__ import annotations

__all__ = ["codec_bytes", "qwen2_matmul_params", "qwen2_param_count",
           "qwen2_token_flops", "qwen2_kv_bytes", "qwen2_step_bytes"]


def codec_bytes(rows: int, record_bytes: int) -> int:
    """HBM bytes the relocation codec needs for ``rows`` records: each
    record is read and written once by encode+pack and once by decode."""
    return 4 * int(rows) * int(record_bytes)


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    return (d, hd, cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["num_hidden_layers"],
            cfg["vocab_size"])


def qwen2_matmul_params(cfg: dict) -> int:
    """Weights a decoded token multiplies: every layer's projections and
    MLP, and the (tied) output head."""
    d, hd, h, hkv, ff, layers, vocab = _dims(cfg)
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    return layers * (attn + 3 * d * ff) + vocab * d


def qwen2_param_count(cfg: dict) -> int:
    """All parameters: matmul weights, q/k/v biases and the norms."""
    d, hd, h, hkv, _, layers, _ = _dims(cfg)
    return (qwen2_matmul_params(cfg)
            + layers * (h * hd + 2 * hkv * hd + 2 * d) + d)


def qwen2_token_flops(cfg: dict, keys: int) -> int:
    """FLOPs of one decoded token attending to ``keys`` cached keys:
    2 per matmul weight, and 4 per head, head dimension, layer and key
    (scores and the weighted sum of values)."""
    d, hd, h, _, _, layers, _ = _dims(cfg)
    return 2 * qwen2_matmul_params(cfg) + 4 * h * hd * layers * int(keys)


def qwen2_kv_bytes(cfg: dict, keys: int, itemsize: int = 2) -> int:
    """Bytes of ``keys`` cached keys and values over all layers."""
    _, hd, _, hkv, _, layers, _ = _dims(cfg)
    return 2 * hkv * hd * itemsize * layers * int(keys)


def qwen2_step_bytes(cfg: dict, keys_per_seq, itemsize: int = 2) -> int:
    """HBM bytes one decode step needs: every parameter once, and each
    live sequence's cached keys and values up to its position."""
    return (qwen2_param_count(cfg) * itemsize
            + sum(qwen2_kv_bytes(cfg, k, itemsize) for k in keys_per_seq))
