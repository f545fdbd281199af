"""Plain float32 Qwen2 decoder in ``jax.numpy``: the reference the
serving cells' tokens are compared against.

It follows the published architecture (arXiv:2407.10671; the Hugging
Face ``Qwen2ForCausalLM``): token embedding, then per layer RMSNorm,
grouped-query attention with q/k/v biases and rotary position embedding
(rotate-half, base ``rope_theta``), a residual add, RMSNorm, a SwiGLU
MLP and a residual add; a final RMSNorm and the tied output head.  It
reads the benchmark's weight tree (``bench/weights.py``; a norm's weight
is ``1 + scale``) and imports nothing of the program.

One departure, shared with the program's serving path: there is no
prefill.  A request that starts at position ``start`` holds no prompt
in its cache, so its first token attends to itself alone and is rotated
as position ``start``; token ``i`` sits at position ``start + i`` and
attends to tokens ``0..i``.

Matrix products run at ``highest`` precision, so float32 on a TPU is
float32.  ``weight_dtype`` rounds every matrix weight (the output head
included) to a lower type before use: the low-precision control.
"""
from __future__ import annotations

import functools

import numpy as np

__all__ = ["logits", "quantize"]


def _rms(x, scale, eps):
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale)


def _rope(x, pos, theta):
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv          # (n, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def quantize(w, dtype: str):
    """``w`` rounded to ``dtype`` with one scale per output column
    (the last axis), and widened back to float32."""
    import jax.numpy as jnp

    w = jnp.asarray(w, jnp.float32)
    top = float(jnp.finfo(jnp.dtype(dtype)).max)
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / top
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(dtype).astype(jnp.float32) * s


@functools.lru_cache(maxsize=None)
def _layer_fn(h, hkv, hd, eps, theta, weight_dtype):
    import jax
    import jax.numpy as jnp

    def w(a):
        return quantize(a, weight_dtype) if weight_dtype \
            else a.astype(jnp.float32)

    def layer(x, p, pos):
        f32 = lambda a: a.astype(jnp.float32)
        n = x.shape[0]
        a = _rms(x, f32(p["norm1"]["scale"]), eps)
        m = p["mixer"]
        q = (a @ w(m["wq"]["w"]) + f32(m["wq"]["b"])).reshape(n, h, hd)
        k = (a @ w(m["wk"]["w"]) + f32(m["wk"]["b"])).reshape(n, hkv, hd)
        v = (a @ w(m["wv"]["w"]) + f32(m["wv"]["b"])).reshape(n, hkv, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        g = h // hkv
        qg = q.reshape(n, hkv, g, hd)
        s = jnp.einsum("ikgd,jkd->kgij", qg, k) / np.sqrt(hd)
        causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
        s = jnp.where(causal, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgij,jkd->ikgd", pr, v).reshape(n, h * hd)
        x = x + o @ w(m["wo"]["w"])
        b = _rms(x, f32(p["norm2"]["scale"]), eps)
        f = p["ffn"]
        y = jax.nn.silu(b @ w(f["wg"]["w"])) * (b @ w(f["wi"]["w"]))
        return x + y @ w(f["wo"]["w"])

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(vocab, eps, weight_dtype):
    import jax
    import jax.numpy as jnp

    def head(x, scale, table):
        t = table[:vocab]
        t = quantize(t.T, weight_dtype).T if weight_dtype \
            else t.astype(jnp.float32)
        return _rms(x, scale.astype(jnp.float32), eps) @ t.T

    return jax.jit(head)


def logits(params, cfg: dict, tokens, start: int, *, weight_dtype=None):
    """float32 logits at each of ``tokens`` (the request's first token,
    then its served tokens but the last), the first at position
    ``start``: ``(m, vocab)`` with ``m`` the token count rounded up to a
    multiple of 64, so requests of many lengths share a few compiled
    programs.  Rows past the token count are padding; causal attention
    leaves the rows before them as they are."""
    import jax
    import jax.numpy as jnp

    d = cfg["hidden_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    n = len(tokens)
    padded = np.zeros(-(-n // 64) * 64, np.int32)
    padded[:n] = tokens
    tokens = jnp.asarray(padded)
    pos = start + jnp.arange(tokens.shape[0], dtype=jnp.int32)
    layer = _layer_fn(h, hkv, hd, eps, theta, weight_dtype)
    stacked = params["scan"][0]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][tokens].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            p = jax.tree_util.tree_map(lambda a: a[i], stacked)
            x = layer(x, p, pos)
        return _head_fn(cfg["vocab_size"], eps, weight_dtype)(
            x, params["final_norm"]["scale"], params["embed"]["table"])
