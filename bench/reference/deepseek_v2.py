"""Plain float32 DeepSeek-V2 decoder in ``jax.numpy``: the reference the
latent-attention, sparse-expert serving cell's tokens are compared
against.

It follows the published model (arXiv:2405.04434; the Hugging Face
``modeling_deepseek.py`` of DeepSeek-V2-Lite): token embedding, then per
layer RMSNorm, multi-head latent attention, a residual add, RMSNorm, a
feed-forward block and a residual add; a final RMSNorm and an untied
output head.

* Attention, in its published (non-absorbed) form: queries from a
  full-rank projection, split into a no-position part and a rotary
  part; a latent ``c = RMSNorm(x W_dkv)`` from which each head's keys
  ``c W_uk`` and values ``c W_uv`` are built; one rotary key ``x W_krope``
  shared by every head.  Rotary embedding is YaRN's
  (``DeepseekV2YarnRotaryEmbedding``): base frequencies blended with
  them over ``factor`` by a linear ramp between the correction dims of
  ``beta_fast`` and ``beta_slow`` rotations, magnitudes scaled by
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, and the
  softmax scale ``(dn + dr)^-1/2 * mscale(factor, mscale_all_dim)^2``,
  with ``mscale(s, m) = 0.1 m ln s + 1``.
* Feed-forward: SwiGLU in the first ``first_k_dense_replace`` layers;
  after them a router (softmax over all the deployment's experts,
  greedy top-k, weights left unnormalised when ``norm_topk_prob`` is
  false, times ``routed_scaling_factor``), the routed experts' SwiGLUs
  weighted by the router, and the shared experts' SwiGLU.  The
  configuration holds a share of the routed experts (``n_routed_experts``
  of ``deployment.routed_experts``, from ``deployment.held_first`` on):
  only those contribute, as on the chip that holds them; the rest would
  be added on the chips that hold them.

It reads the benchmark's weight tree (``bench/weights_mla_moe.py``; a
norm's weight is ``1 + scale``) and imports nothing of the program.

Departures from the published code, shared with the program: rotary
embedding rotates halves (the first half of a head's rotary lanes with
the second) where the published code rotates interleaved pairs, which
is a fixed permutation of the rotary columns of ``W_q`` and ``W_krope``;
and there is no prefill, as in ``reference/qwen2.py``: a request that
starts at position ``start`` holds no prompt in its cache, so token
``i`` sits at position ``start + i`` and attends to tokens ``0..i``.

Matrix products run at ``highest`` precision, so float32 on a TPU is
float32.  ``weight_dtype`` rounds every matrix weight (router and output
head included) to a lower type before use: the low-precision control.
The model is computed layer by layer from the served weights, one
layer's weights widened to float32 at a time.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from .qwen2 import quantize

__all__ = ["logits", "yarn_inv_freq", "yarn_mscale", "softmax_scale"]


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, base: float, rs: dict) -> np.ndarray:
    """float32 ``(dim / 2,)`` rotary frequencies under YaRN ``rs``
    (a config.json ``rope_scaling``)."""
    def corr(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2, dtype=np.float64)
    extra = base ** (-2.0 * i / dim)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return (extra / rs["factor"] * (1.0 - mask) + extra * mask) \
        .astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _rms(x, scale, eps):
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale)


def _rope(x, pos, inv, mag):
    """Rotate-half rotary embedding of ``x`` (n, heads, dr)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    ang = pos[:, None].astype(jnp.float32) * inv          # (n, half)
    cos = jnp.cos(ang)[:, None, :] * mag
    sin = jnp.sin(ang)[:, None, :] * mag
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _dims(cfg: dict) -> tuple:
    rs = cfg.get("rope_scaling") or {}
    dr, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    if rs.get("type") == "yarn":
        inv = yarn_inv_freq(dr, base, rs)
        mag = (yarn_mscale(rs["factor"], rs["mscale"])
               / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    else:
        inv = (base ** (-2.0 * np.arange(dr // 2) / dr)).astype(np.float32)
        mag = 1.0
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            float(cfg["rms_norm_eps"]), tuple(inv.tolist()), float(mag),
            softmax_scale(cfg), int(cfg["num_experts_per_tok"]),
            bool(cfg["norm_topk_prob"]), float(cfg["routed_scaling_factor"]),
            int(cfg["deployment"]["held_first"]))


@functools.lru_cache(maxsize=None)
def _layer_fn(dims: tuple, moe: bool, weight_dtype):
    import jax
    import jax.numpy as jnp

    (h, dn, dr, dv, eps, inv, mag, scale, top_k, norm_topk, routed_scale,
     first) = dims
    inv = jnp.asarray(inv, jnp.float32)

    def w(a):
        return quantize(a, weight_dtype) if weight_dtype \
            else a.astype(jnp.float32)

    def swiglu(f, b):
        return (jax.nn.silu(b @ w(f["wg"]["w"])) * (b @ w(f["wi"]["w"]))) \
            @ w(f["wo"]["w"])

    def layer(x, p, pos):
        f32 = lambda a: a.astype(jnp.float32)
        n = x.shape[0]
        a = _rms(x, f32(p["norm1"]["scale"]), eps)
        m = p["mixer"]
        q = (a @ w(m["w_q"]["w"])).reshape(n, h, dn + dr)
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, inv, mag)
        c = _rms(a @ w(m["w_dkv"]["w"]), f32(m["kv_norm"]["scale"]), eps)
        k_rope = _rope((a @ w(m["w_krope"]["w"]))[:, None, :], pos, inv,
                       mag)[:, 0]
        k_nope = (c @ w(m["w_uk"]["w"])).reshape(n, h, dn)
        v = (c @ w(m["w_uv"]["w"])).reshape(n, h, dv)
        s = (jnp.einsum("ihd,jhd->hij", q_nope, k_nope)
             + jnp.einsum("ihd,jd->hij", q_rope, k_rope)) * scale
        causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hij,jhd->ihd", pr, v).reshape(n, h * dv)
        x = x + o @ w(m["wo"]["w"])
        b = _rms(x, f32(p["norm2"]["scale"]), eps)
        f = p["ffn"]
        if not moe:
            return x + swiglu(f, b)
        probs = jax.nn.softmax(b @ w(f["router"]["w"]["w"]), axis=-1)
        top_p, top_i = jax.lax.top_k(probs, top_k)
        if norm_topk:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        top_p = top_p * routed_scale
        e = f["experts"]
        held = jnp.arange(e["wi"].shape[0]) + first     # the experts here
        gate = jnp.sum(jnp.where(top_i[..., None] == held,
                                 top_p[..., None], 0.0), axis=1)  # (n, held)
        y = swiglu(f["shared"], b)
        for j in range(e["wi"].shape[0]):
            ej = {k: {"w": e[k][j]} for k in ("wg", "wi", "wo")}
            y = y + gate[:, j:j + 1] * swiglu(ej, b)
        return x + y

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(vocab, eps, weight_dtype):
    import jax
    import jax.numpy as jnp

    def head(x, scale, w):
        w = w[:, :vocab]
        w = quantize(w, weight_dtype) if weight_dtype \
            else w.astype(jnp.float32)
        return _rms(x, scale.astype(jnp.float32), eps) @ w

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

    n = len(tokens)
    padded = np.zeros(-(-n // 64) * 64, np.int32)
    padded[:n] = tokens
    tokens = jnp.asarray(padded)
    pos = start + jnp.arange(tokens.shape[0], dtype=jnp.int32)
    dims = _dims(cfg)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][tokens].astype(jnp.float32)
        for p in params["prefix"]:
            x = _layer_fn(dims, False, weight_dtype)(x, p, pos)
        stacked = params["scan"][0]
        layer = _layer_fn(dims, True, weight_dtype)
        for i in range(cfg["num_hidden_layers"]
                       - cfg["first_k_dense_replace"]):
            p = jax.tree_util.tree_map(lambda a: a[i], stacked)
            x = layer(x, p, pos)
        return _head_fn(cfg["vocab_size"], float(cfg["rms_norm_eps"]),
                        weight_dtype)(x, params["final_norm"]["scale"],
                                      params["head"]["w"])
