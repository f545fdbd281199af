"""Shared building blocks: norms, RoPE (+M-RoPE), MLPs, embeddings.

Parameters are plain nested dicts of jnp arrays; ``init_*`` functions
return the dict, ``apply`` logic lives alongside.  Everything is
init-by-closure so shapes can be obtained with ``jax.eval_shape``
without allocating (``zoo.abstract_params``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = [
    "dense_init", "dense", "rmsnorm_init", "rmsnorm", "embed_init",
    "rope", "mrope", "yarn_inv_freq", "yarn_mscale", "swiglu_init",
    "swiglu", "geglu_init", "geglu",
]


def _normal(key, shape, dtype, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def dense_init(key, d_in: int, d_out: int, dtype, *, bias: bool = False,
               scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": _normal(key, (d_in, d_out), dtype, scale)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def rmsnorm_init(d: int, dtype):
    return {"scale": jnp.zeros((d,), dtype)}  # gemma-style (1 + scale)


def rmsnorm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + p["scale"].astype(jnp.float32))).astype(x.dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return {"table": _normal(key, (vocab, d), dtype, 0.02)}


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction ``0.1 * mscale * ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, yarn) -> jnp.ndarray:
    """YaRN rope frequencies (``DeepseekV2YarnRotaryEmbedding``): each
    lane blends the base frequency with it divided by ``factor``, by a
    linear ramp between the correction dims of ``beta_fast`` and
    ``beta_slow`` rotations over ``original_max_position``."""
    def corr(rotations):
        return (dim * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    extra = theta ** (-2.0 * i / dim)
    mask = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return extra / yarn.factor * (1.0 - mask) + extra * mask


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float, yarn=None):
    """Rotary embedding. x: (B, S, H, D_head) — rotates over last dim.
    positions: (B, S) int32.  ``yarn`` (a ``config.Yarn``) scales the
    frequencies and the rotated magnitudes as YaRN does."""
    d = x.shape[-1]
    half = d // 2
    if yarn is None:
        freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        mag = 1.0
    else:
        freq = yarn_inv_freq(d, theta, yarn)
        mag = (yarn_mscale(yarn.factor, yarn.mscale)
               / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    ang = positions[..., None].astype(jnp.float32) * freq  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if mag != 1.0:
        cos, sin = cos * mag, sin * mag
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def mrope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
          sections: tuple[int, ...]):
    """Qwen2-VL multimodal RoPE: the half-dim frequency lanes are split
    into sections, each rotated by its own position stream (t, h, w).

    x: (B, S, H, D); positions: (3, B, S)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # build per-frequency position selection by section
    sec = []
    for i, s in enumerate(sections):
        sec.append(jnp.full((s,), i, jnp.int32))
    sec = jnp.concatenate(sec)  # (half,) section id per freq lane
    pos = positions.astype(jnp.float32)  # (3, B, S)
    # gather the right position stream per lane: (B, S, half)
    pos_sel = jnp.take(pos, sec, axis=0)         # (half, B, S) -> transpose
    pos_sel = jnp.moveaxis(pos_sel, 0, -1)       # (B, S, half)
    ang = pos_sel * freq
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu_init(key, d: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": dense_init(k1, d, d_ff, dtype),
        "wg": dense_init(k2, d, d_ff, dtype),
        "wo": dense_init(k3, d_ff, d, dtype, scale=1.0 / math.sqrt(d_ff)),
    }


def swiglu(p, x):
    return dense(p["wo"], jax.nn.silu(dense(p["wg"], x)) * dense(p["wi"], x))


def geglu_init(key, d: int, d_ff: int, dtype):
    return swiglu_init(key, d, d_ff, dtype)


def geglu(p, x):
    return dense(p["wo"],
                 jax.nn.gelu(dense(p["wg"], x), approximate=True)
                 * dense(p["wi"], x))
