"""Random Qwen2 weights made from the seed on the device, in one jitted
call, in the type they are served in.

The tree has the layout the program's decoder reads (``embed.table``,
``final_norm.scale``, and the layers stacked under ``scan``), so the
benchmark can hand it to the system under test; the plain reference
reads the same names.  RMSNorm weights are stored as ``scale`` with the
weight ``1 + scale``.  The table keeps the program's padded vocabulary;
rows past ``vocab_size`` are zero, as a loader padding the checkpoint
would leave them, so they never win an argmax.
"""
from __future__ import annotations

import functools
import math

__all__ = ["qwen2_params", "prng_key", "vocab_padded"]


def vocab_padded(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def prng_key(seed: int):
    """A PRNG key for any non-negative seed, 64 bits and more included."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _shapes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    ff, L = cfg["intermediate_size"], cfg["num_hidden_layers"]
    # (shape, standard deviation) of each leaf; layers stack on axis 0
    return {
        "embed": {"table": ((vocab_padded(cfg), d), 0.02)},
        "final_norm": {"scale": ((d,), 0.1)},
        "scan": {
            "norm1": {"scale": ((L, d), 0.1)},
            "mixer": {
                "wq": {"w": ((L, d, h * hd), 1 / math.sqrt(d)),
                       "b": ((L, h * hd), 0.1)},
                "wk": {"w": ((L, d, hkv * hd), 1 / math.sqrt(d)),
                       "b": ((L, hkv * hd), 0.1)},
                "wv": {"w": ((L, d, hkv * hd), 1 / math.sqrt(d)),
                       "b": ((L, hkv * hd), 0.1)},
                "wo": {"w": ((L, h * hd, d), 1 / math.sqrt(h * hd))},
            },
            "norm2": {"scale": ((L, d), 0.1)},
            "ffn": {
                "wg": {"w": ((L, d, ff), 1 / math.sqrt(d))},
                "wi": {"w": ((L, d, ff), 1 / math.sqrt(d))},
                "wo": {"w": ((L, ff, d), 1 / math.sqrt(ff))},
            },
        },
    }


def _flat(tree, prefix=""):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _flat(v, f"{prefix}{k}.")
        else:
            yield f"{prefix}{k}", v


def _set(tree, path, value):
    *head, last = path.split(".")
    for k in head:
        tree = tree.setdefault(k, {})
    tree[last] = value


@functools.lru_cache(maxsize=None)
def _maker(cfg_key: tuple, dtype: str):
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_key)
    leaves = list(_flat(_shapes(cfg)))
    vocab = cfg["vocab_size"]

    def make(key):
        out: dict = {}
        for i, (path, (shape, scale)) in enumerate(leaves):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale
            if path == "embed.table":
                x = jnp.where(jnp.arange(shape[0])[:, None] < vocab, x, 0.0)
            _set(out, path, x.astype(dtype))
        out["prefix"] = ()
        out["suffix"] = ()
        out["scan"] = (out["scan"],)
        return out

    return jax.jit(make)


def qwen2_params(cfg: dict, seed: int, dtype: str = "bfloat16"):
    """The weight tree for ``cfg`` (a configuration file's dict)."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "num_hidden_layers",
            "vocab_size")
    cfg_key = tuple((k, cfg.get(k)) for k in keys)
    return _maker(cfg_key, dtype)(prng_key(seed))
