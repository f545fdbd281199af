"""Mixture-of-Experts with relocation-engine dispatch, and DeepSeek MLA.

The token→expert dispatch is a *collective relocation* (paper §3.4/§5.3)
specialized to a fixed schema: the router is the ``move_by_rule``
key→destination function, capacity buffers play the Alltoallv byte
buffers, and the weighted combine is the accumulator 'accept'.  It
reuses ``core/relocation._pack_by_dest`` — the same packing code path
the host CollectiveMoveManager models — executed as a dense
``lax.all_to_all`` over the expert-parallel mesh axis.

Execution modes:
* ``expert_all_to_all`` — inside shard_map, explicit EP (paper-faithful
  flat all_to_all; hierarchical pod-local variant as a perf option).
* ``held_experts_forward`` — the part of the result a chip's held
  experts give: under shard_map with a psum (``expert_replicated``,
  decode), or alone on one chip that holds a share of the experts.
* dense fallback for single-device smoke tests (no mesh axis).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..compat import axis_size

from ..core.relocation import _pack_by_dest
from .config import ModelConfig
from .layers import (dense, dense_init, rmsnorm, rmsnorm_init, rope, swiglu,
                     swiglu_init, yarn_mscale)

__all__ = ["router_init", "route", "moe_init", "moe_forward_dense",
           "expert_all_to_all", "held_experts_forward", "routing_counts",
           "expert_replicated", "mla_init", "mla_softmax_scale",
           "mla_forward", "mla_decode", "mla_decode_project",
           "mla_attend_cache"]


# ---------------------------------------------------------------------------
# Router
# ---------------------------------------------------------------------------
def router_init(key, d: int, n_experts: int, dtype):
    return {"w": dense_init(key, d, n_experts, jnp.float32)}


def route(p, x, top_k: int, *, n_experts: int, normalize: bool = True):
    """Top-k softmax router: softmax over all experts, greedy top-k; with
    ``normalize`` the k weights are renormalised to sum to one.

    x: (T, d) → (weights (T, k) f32, idx (T, k) i32, aux_metrics)."""
    logits = x.astype(jnp.float32) @ p["w"]["w"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    if normalize:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # aux load-balance loss (Switch/GShard form) + router z-loss
    me = jnp.mean(probs, axis=0)                                  # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_i, n_experts, dtype=jnp.float32), axis=1),
        axis=0)
    aux = n_experts * jnp.sum(me * ce) / top_k
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return top_p, top_i.astype(jnp.int32), {"aux": aux, "z": z}


# ---------------------------------------------------------------------------
# Experts
# ---------------------------------------------------------------------------
def moe_init(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 4)
    d, dff = cfg.d_model, cfg.d_ff_expert
    E = len(cfg.expert_range)           # the bank holds the held experts
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(dff)

    def ebank(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "wi": (jax.random.normal(k1, (E, d, dff), jnp.float32) * scale_in).astype(dtype),
            "wg": (jax.random.normal(k2, (E, d, dff), jnp.float32) * scale_in).astype(dtype),
            "wo": (jax.random.normal(k3, (E, dff, d), jnp.float32) * scale_out).astype(dtype),
        }

    p = {"router": router_init(ks[0], d, cfg.n_experts, dtype),
         "experts": ebank(ks[1])}
    if cfg.n_shared_experts:
        p["shared"] = swiglu_init(ks[2], d,
                                  dff * cfg.n_shared_experts, dtype)
    return p


def _expert_ffn(bank, x):
    """Batched expert SwiGLU: x (E, C, d) → (E, C, d)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, bank["wg"])) \
        * jnp.einsum("ecd,edf->ecf", x, bank["wi"])
    return jnp.einsum("ecf,efd->ecd", h, bank["wo"])


def moe_forward_dense(p, cfg: ModelConfig, x):
    """Single-device MoE (smoke tests): capacity dispatch without a mesh."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    T = xt.shape[0]
    E, K = cfg.n_experts, cfg.top_k
    w, idx, aux = route(p["router"], xt, K, n_experts=E,
                        normalize=cfg.norm_topk_prob)
    # capacity floor min(T, 64) makes small batches (decode) drop-free:
    # an expert can receive at most T rows (top-k indices are distinct)
    cap = max(int(cfg.capacity_factor * T * K / E), min(T, 64))
    flat_dest = idx.reshape(-1)
    rows = jnp.repeat(xt, K, axis=0)
    buf, valid, slot = _pack_by_dest(rows, flat_dest, E, cap)
    y = _expert_ffn(p["experts"], buf.astype(x.dtype))              # (E, cap, d)
    yf = y.reshape(E * cap, d)
    safe = jnp.where(slot >= 0, slot, 0)
    back = jnp.where((slot >= 0)[:, None], yf[safe], 0.0)           # (T*K, d)
    back = back.reshape(T, K, d)
    out = jnp.einsum("tk,tkd->td", w.astype(jnp.float32),
                     back.astype(jnp.float32)).astype(x.dtype)
    if "shared" in p:
        out = out + swiglu(p["shared"], xt)
    return out.reshape(B, S, d), aux


def expert_all_to_all(router_p, local_bank, shared_p, cfg: ModelConfig, x, *,
                      axis_name: str):
    """EP MoE inside shard_map: tokens x (T_local, d) on each shard.

    The relocation round (paper §5.3 two-phase exchange):
      1. route (move_by_rule) → per-expert capacity pack (_pack_by_dest)
      2. all_to_all over the EP axis (Alltoallv)
      3. expert compute (batched SwiGLU over local experts)
      4. inverse all_to_all + slot unpack + weighted combine (accept)

    ``local_bank`` is this shard's expert slice (shard_map in_spec
    P(model) on the expert dim); router/shared params are replicated.
    """
    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    n_shards = axis_size(axis_name)
    eps = E // n_shards                     # experts per shard
    cap = max(1, int(cfg.capacity_factor * T * K / E))

    w, idx, aux = route(router_p, x, K, n_experts=E,
                        normalize=cfg.norm_topk_prob)
    rows = jnp.repeat(x, K, axis=0)                      # (T*K, d)
    flat_dest = idx.reshape(-1)                          # global expert id
    # pack per global expert: (E, cap, d) == (n_shards, eps, cap, d)
    buf, valid, slot = _pack_by_dest(rows, flat_dest, E, cap)
    buf = buf.reshape(n_shards, eps * cap, d)
    valid = valid.reshape(n_shards, eps * cap)
    recv = jax.lax.all_to_all(buf, axis_name, 0, 0, tiled=False)
    recv_valid = jax.lax.all_to_all(valid.astype(jnp.int8), axis_name, 0, 0,
                                    tiled=False).astype(bool)
    # recv: (n_shards, eps*cap, d) → (eps, n_shards*cap, d) per local expert
    recv = recv.reshape(n_shards, eps, cap, d).transpose(1, 0, 2, 3) \
               .reshape(eps, n_shards * cap, d)
    rv = recv_valid.reshape(n_shards, eps, cap).transpose(1, 0, 2) \
                   .reshape(eps, n_shards * cap)
    recv = jnp.where(rv[..., None], recv, 0.0)

    y = _expert_ffn(local_bank, recv.astype(x.dtype))    # (eps, S*cap, d)

    # route back: reshape to the send layout and inverse all_to_all
    y = y.reshape(eps, n_shards, cap, d).transpose(1, 0, 2, 3) \
         .reshape(n_shards, eps * cap, d)
    back = jax.lax.all_to_all(y, axis_name, 0, 0, tiled=False)
    back = back.reshape(E * cap, d)
    safe = jnp.where(slot >= 0, slot, 0)
    got = jnp.where((slot >= 0)[:, None], back[safe], 0.0).reshape(T, K, d)
    out = jnp.einsum("tk,tkd->td", w.astype(jnp.float32),
                     got.astype(jnp.float32)).astype(x.dtype)
    if shared_p is not None:
        out = out + swiglu(shared_p, x)
    return out, aux


def held_experts_forward(router_p, bank, cfg: ModelConfig, x, first):
    """The part of the routed experts' result that experts ``[first,
    first + held)`` give, ``held`` being the bank's length: tokens x
    (T, d) are routed over all ``cfg.n_experts``, assignments to other
    experts go to a drop bin, and the held bank runs over the rest.

    The capacity never drops an assignment to a held expert while
    T <= 64 (an expert receives at most T rows: top-k indices are
    distinct).  Returns (part (T, d) f32, aux, idx (T, k))."""
    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    held = bank["wi"].shape[0]
    cap = max(int(2 * cfg.capacity_factor * T * K * held / E), min(T, 64))

    w, idx, aux = route(router_p, x, K, n_experts=E,
                        normalize=cfg.norm_topk_prob)
    owned = (idx >= first) & (idx < first + held)        # (T, K)
    local_e = jnp.where(owned, idx - first, held)        # held = drop bin
    rows = jnp.repeat(x, K, axis=0)
    buf, valid, slot = _pack_by_dest(rows, local_e.reshape(-1), held + 1, cap)
    y = _expert_ffn(bank, buf[:held].astype(x.dtype))    # (held, cap, d)
    yf = jnp.concatenate([y, jnp.zeros((1,) + y.shape[1:], y.dtype)], 0) \
            .reshape((held + 1) * cap, d)
    safe = jnp.where(slot >= 0, slot, 0)
    got = jnp.where((slot >= 0)[:, None], yf[safe], 0.0).reshape(T, K, d)
    wmask = jnp.where(owned, w, 0.0)
    out = jnp.einsum("tk,tkd->td", wmask.astype(jnp.float32),
                     got.astype(jnp.float32))
    return out, aux, idx


def routing_counts(idx, first: int, held: int, rows):
    """int32 (3,): assignments of the ``rows`` (T,) bool tokens, those
    to held experts ``[first, first + held)``, and held experts that
    received at least one."""
    real = jnp.broadcast_to(rows[:, None], idx.shape)
    owned = real & (idx >= first) & (idx < first + held)
    hit = jnp.any(jax.nn.one_hot(jnp.where(owned, idx - first, held), held,
                                 dtype=jnp.bool_), axis=(0, 1))
    return jnp.stack([jnp.sum(real), jnp.sum(owned),
                      jnp.sum(hit)]).astype(jnp.int32)


def expert_replicated(router_p, local_bank, shared_p, cfg: ModelConfig, x, *,
                      axis_name: str):
    """Decode-mode EP: tokens replicated over the expert axis; each shard
    computes its local experts' part (:func:`held_experts_forward`), and
    the combine is a psum over the expert axis (no all_to_all — the right
    trade when T_local is tiny, e.g. one decode token per sequence)."""
    eps = cfg.n_experts // axis_size(axis_name)
    first = jax.lax.axis_index(axis_name) * eps
    out, aux, _ = held_experts_forward(router_p, local_bank, cfg, x, first)
    out = jax.lax.psum(out, axis_name).astype(x.dtype)
    if shared_p is not None:
        out = out + swiglu(shared_p, x)
    return out, aux


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (DeepSeek V2/V3)
# ---------------------------------------------------------------------------
def mla_init(key, cfg: ModelConfig, dtype):
    d = cfg.d_model
    H = cfg.n_heads
    r = cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    p = {
        "w_dkv": dense_init(ks[0], d, r, dtype),            # down: latent kv
        "w_krope": dense_init(ks[1], d, dr, dtype),         # shared rope key
        "kv_norm": rmsnorm_init(r, dtype),
        "w_uk": dense_init(ks[2], r, H * dn, dtype),        # up: keys
        "w_uv": dense_init(ks[3], r, H * dv, dtype),        # up: values
        "wo": dense_init(ks[4], H * dv, d, dtype),
    }
    if cfg.q_lora_rank:
        p["w_dq"] = dense_init(ks[5], d, cfg.q_lora_rank, dtype)
        p["q_norm"] = rmsnorm_init(cfg.q_lora_rank, dtype)
        p["w_uq"] = dense_init(ks[6], cfg.q_lora_rank, H * (dn + dr), dtype)
    else:
        p["w_q"] = dense_init(ks[7], d, H * (dn + dr), dtype)
    return p


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``(dn + dr)^-1/2``, times YaRN's ``mscale(factor, mscale_all_dim)``
    squared under YaRN rope (``DeepseekV2Attention.softmax_scale``)."""
    scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    if cfg.yarn is not None:
        scale *= yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim) ** 2
    return scale


def _mla_q(p, cfg: ModelConfig, x, positions):
    B, S, _ = x.shape
    H, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank:
        cq = rmsnorm(p["q_norm"], dense(p["w_dq"], x), cfg.norm_eps)
        q = dense(p["w_uq"], cq)
    else:
        q = dense(p["w_q"], x)
    q = q.reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    pos = positions if positions.ndim == 2 else positions[0]
    q_rope = rope(q_rope, pos, cfg.rope_theta, cfg.yarn)
    return q_nope, q_rope


def mla_forward(p, cfg: ModelConfig, x, positions, *, impl=None):
    """MLA training/prefill: materializes per-head K/V from the latent.
    Returns (out, (c_kv, k_rope)) — the compressed cache entries."""
    from ..kernels import ops
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(p, cfg, x, positions)

    c_kv = rmsnorm(p["kv_norm"], dense(p["w_dkv"], x), cfg.norm_eps)  # (B,S,r)
    pos = positions if positions.ndim == 2 else positions[0]
    k_rope = rope(dense(p["w_krope"], x).reshape(B, S, 1, dr), pos,
                  cfg.rope_theta, cfg.yarn)                           # (B,S,1,dr)
    k_nope = dense(p["w_uk"], c_kv).reshape(B, S, H, dn)
    v = dense(p["w_uv"], c_kv).reshape(B, S, H, dv)

    q = jnp.concatenate([q_nope, q_rope], axis=-1)                    # (B,S,H,dn+dr)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, dr))],
                        axis=-1)
    sm_scale = mla_softmax_scale(cfg)
    # pad v to qk dim for the shared attention kernel, slice after
    if dv < dn + dr:
        v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv)))
    else:
        v_p = v
    out = ops.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v_p.transpose(0, 2, 1, 3), causal=True,
                        sm_scale=sm_scale, impl=impl)
    out = out.transpose(0, 2, 1, 3)[..., :dv].reshape(B, S, H * dv)
    return dense(p["wo"], out), (c_kv, k_rope[:, :, 0, :])


def mla_decode_project(p, cfg: ModelConfig, x, positions):
    """MLA decode projections: latent cache rows + absorbed queries."""
    B = x.shape[0]
    dr = cfg.qk_rope_dim
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c_new = rmsnorm(p["kv_norm"], dense(p["w_dkv"], x), cfg.norm_eps)
    pos = positions if positions.ndim == 2 else positions[0]
    kr_new = rope(dense(p["w_krope"], x).reshape(B, 1, 1, dr), pos,
                  cfg.rope_theta, cfg.yarn)[:, 0, 0]
    return (q_nope, q_rope), c_new[:, 0], kr_new


def mla_attend_cache(p, cfg: ModelConfig, q_pair, cache_ckv, cache_krope,
                     cache_pos, cur):
    """Absorbed-form MLA attention against the (updated) latent cache —
    the cache holds only (c_kv: r) + (k_rope: dr) per token (the MLA
    memory win, from the DeepSeek paper)."""
    q_nope, q_rope = q_pair
    B = q_nope.shape[0]
    H = cfg.n_heads
    r = cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_dim, cfg.v_head_dim
    # absorb W_uk into q: q_abs (B,1,H,r)
    w_uk = p["w_uk"]["w"].astype(jnp.float32).reshape(r, H, dn)
    q_abs = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32), w_uk)
    valid = (cache_pos >= 0) & (cache_pos <= cur)
    ckv = cache_ckv.astype(jnp.float32)
    krp = cache_krope.astype(jnp.float32)
    sm_scale = mla_softmax_scale(cfg)
    s = (jnp.einsum("bshr,btr->bhst", q_abs, ckv)[:, :, 0]
         + jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32),
                      krp)[:, :, 0]) * sm_scale
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    mx = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    pr = jnp.exp(s - jnp.where(jnp.isfinite(mx), mx, 0.0))
    pr = jnp.where(valid[:, None, :], pr, 0.0)
    pr = pr / jnp.maximum(jnp.sum(pr, axis=-1, keepdims=True), 1e-20)
    ctx = jnp.einsum("bht,btr->bhr", pr, ckv)
    w_uv = p["w_uv"]["w"].astype(jnp.float32).reshape(r, H, dv)
    out = jnp.einsum("bhr,rhv->bhv", ctx, w_uv)
    out = out.reshape(B, 1, H * dv).astype(cache_ckv.dtype)
    return dense(p["wo"], out)


def mla_decode(p, cfg: ModelConfig, x, positions, cache_ckv, cache_krope,
               cache_pos):
    """Legacy single-call MLA decode (reference for tests)."""
    B = x.shape[0]
    q_pair, c_new, kr_new = mla_decode_project(p, cfg, x, positions)
    cur = positions.reshape(B, 1)
    size = cache_ckv.shape[1]
    slot = (cur[:, 0] % size).astype(jnp.int32)
    bidx = jnp.arange(B)
    ckv = cache_ckv.at[bidx, slot].set(c_new.astype(cache_ckv.dtype))
    krp = cache_krope.at[bidx, slot].set(kr_new.astype(cache_krope.dtype))
    cp = cache_pos.at[bidx, slot].set(cur[:, 0])
    out = mla_attend_cache(p, cfg, q_pair, ckv, krp, cp, cur)
    return out, c_new, kr_new
