"""Pure-jnp oracles for every Pallas kernel (allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["attention_ref", "gather_rows_ref", "moe_combine_ref",
           "rg_lru_ref", "mlstm_ref", "reloc_encode_pack_ref",
           "reloc_pack_rows_ref", "reloc_decode_rows_ref"]


def attention_ref(q, k, v, *, causal=True, window=None, softcap=0.0,
                  sm_scale=None):
    """Dense softmax attention with GQA/causal/window/softcap."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    group = Hq // Hkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    rows = jnp.arange(Sq)[:, None]
    cols = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows → 0
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def flash_ref(q, k, v, *, causal=True, window=None, softcap=0.0,
              sm_scale=None, block_q=128):
    """Blocked flash-style attention in pure jnp — the XLA execution path
    of ops.attention.

    Never materializes the full S×S score matrix: a checkpointed scan
    over q-blocks computes (block_q × k_span) scores, where k_span is the
    whole kv length for global attention but only a static
    ``window + block_q`` slice for sliding-window layers (so local-layer
    FLOPs stay honest in cost_analysis). Numerics match attention_ref.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    group = Hq // Hkv
    block_q = min(block_q, Sq)
    pad = (-Sq) % block_q
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nq = (Sq + pad) // block_q
    qb = q.reshape(B, Hq, nq, block_q, D).transpose(2, 0, 1, 3, 4)

    use_window = window is not None and window + block_q < Skv
    k_span = (window + block_q) if use_window else Skv

    def body(_, args):
        qi, qblk = args                          # (), (B, Hq, bq, D)
        q_start = qi * block_q
        if use_window:
            start = jnp.clip(q_start + block_q - k_span, 0, Skv - k_span)
            kk = jax.lax.dynamic_slice_in_dim(k, start, k_span, axis=2)
            vv = jax.lax.dynamic_slice_in_dim(v, start, k_span, axis=2)
            col0 = start
        else:
            kk, vv = k, v
            col0 = 0
        kk = jnp.repeat(kk, group, axis=1)
        vv = jnp.repeat(vv, group, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qblk.astype(jnp.float32),
                       kk.astype(jnp.float32)) * sm_scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        rows = q_start + jnp.arange(block_q)[:, None]
        cols = col0 + jnp.arange(k_span)[None, :]
        mask = jnp.ones((block_q, k_span), bool)
        mask &= cols < Skv
        mask &= rows < Sq
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= cols > rows - window
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(s - m)
        p = jnp.where(mask[None, None], p, 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
        o = o / jnp.maximum(l, 1e-20)
        return (), o.astype(q.dtype)

    _, ob = jax.lax.scan(jax.checkpoint(body),
                         (), (jnp.arange(nq), qb))
    out = ob.transpose(1, 2, 0, 3, 4).reshape(B, Hq, Sq + pad, D)
    return out[:, :, :Sq, :]


def gather_rows_ref(x, idx):
    """out[i] = x[idx[i]] (MoE dispatch oracle)."""
    return jnp.take(x, idx, axis=0)


def moe_combine_ref(y, slots, weights):
    """out[t] = sum_k weights[t,k] * y[slots[t,k]]; slot<0 contributes 0."""
    safe = jnp.where(slots >= 0, slots, 0)
    gathered = y[safe]                                  # (T, K, D)
    w = jnp.where(slots >= 0, weights, 0.0)
    return jnp.einsum("tk,tkd->td", w.astype(jnp.float32),
                      gathered.astype(jnp.float32)).astype(y.dtype)


def rg_lru_ref(x, a, h0=None):
    """RG-LRU recurrence: h_t = a_t * h_{t-1} + sqrt(1-a_t^2) * x_t.

    x, a: (B, S, D); a in (0, 1). Returns (h_seq, h_last)."""
    B, S, D = x.shape
    if h0 is None:
        h0 = jnp.zeros((B, D), jnp.float32)
    gx = jnp.sqrt(jnp.clip(1.0 - a.astype(jnp.float32) ** 2, 0.0, 1.0))
    gx = gx * x.astype(jnp.float32)

    def step(h, t):
        at, bt = t
        h = at * h + bt
        return h, h

    h_last, hs = jax.lax.scan(
        step, h0, (a.astype(jnp.float32).transpose(1, 0, 2),
                   gx.transpose(1, 0, 2)))
    return hs.transpose(1, 0, 2).astype(x.dtype), h_last


def mlstm_ref(q, k, v, i_gate, f_gate, c0=None, n0=None, m0=None):
    """Stabilized mLSTM recurrence (xLSTM eqs.), exact sequential oracle.

    q,k,v: (B, S, d); i_gate, f_gate: (B, S) pre-activations.
      m_t = max(f~_t + m_{t-1}, i~_t)
      C_t = exp(f~_t + m_{t-1} - m_t) C_{t-1} + exp(i~_t - m_t) k_t v_t^T
      n_t = exp(f~ + m_{t-1} - m_t) n_{t-1} + exp(i~_t - m_t) k_t
      h_t = (C_t^T q_t) / max(|n_t . q_t|, 1)
    Returns (h (B,S,d), (C_last, n_last, m_last))."""
    B, S, d = q.shape
    qf = q.astype(jnp.float32) / math.sqrt(d)
    kf = k.astype(jnp.float32) / math.sqrt(d)
    vf = v.astype(jnp.float32)
    ig = i_gate.astype(jnp.float32)
    fg = f_gate.astype(jnp.float32)
    if c0 is None:
        c0 = jnp.zeros((B, d, d), jnp.float32)
    if n0 is None:
        n0 = jnp.zeros((B, d), jnp.float32)
    if m0 is None:
        m0 = jnp.full((B,), -jnp.inf, jnp.float32)

    def step(carry, t):
        C, n, m = carry
        qt, kt, vt, it, ft = t
        logf = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(logf + m, it)
        fdec = jnp.exp(logf + m - m_new)
        iamp = jnp.exp(it - m_new)
        C = fdec[:, None, None] * C + iamp[:, None, None] * (
            kt[:, :, None] * vt[:, None, :])
        n = fdec[:, None] * n + iamp[:, None] * kt
        denom = jnp.maximum(jnp.abs(jnp.sum(n * qt, axis=-1)), 1.0)
        h = jnp.einsum("bkv,bk->bv", C, qt) / denom[:, None]
        return (C, n, m_new), h

    (C, n, m), hs = jax.lax.scan(
        step, (c0, n0, m0),
        (qf.transpose(1, 0, 2), kf.transpose(1, 0, 2), vf.transpose(1, 0, 2),
         ig.transpose(1, 0), fg.transpose(1, 0)))
    return hs.transpose(1, 0, 2).astype(q.dtype), (C, n, m)


# ---------------------------------------------------------------------------
# relocation codec oracles (reloc_codec.py)
# ---------------------------------------------------------------------------
def _wire(rows, pairs, slots, width):
    """(pairs*slots, W/4) word rows → the ``(pairs, chunks, 1, 128)``
    send-buffer layout."""
    per_pair = rows.reshape(pairs, slots * (width // 4))
    n = max(slots * width // 512, 1) * 128
    per_pair = jnp.pad(per_pair, ((0, 0), (0, n - per_pair.shape[1])))
    return per_pair.reshape(pairs, -1, 1, 128)


def _keep(widths, width):
    live = (jnp.asarray(widths, jnp.int32)[:, None] + 3) // 4
    return jnp.arange(width // 4, dtype=jnp.int32)[None, :] < live


def reloc_encode_pack_ref(mat, idx, widths, *, pairs, slots, width):
    """Oracle for :func:`repro.kernels.reloc_codec.encode_pack`."""
    from .reloc_codec import to_words

    words = to_words(jnp.asarray(mat))
    words = jnp.pad(words, ((0, 0), (0, width // 4 - words.shape[1])))
    idx = jnp.clip(jnp.asarray(idx, jnp.int32), 0, words.shape[0] - 1)
    rows = jnp.where(_keep(widths, width), words[idx], jnp.uint32(0))
    return _wire(rows, pairs, slots, width)


def reloc_pack_rows_ref(words, offsets, widths, *, pairs, slots, width):
    """Oracle for :func:`repro.kernels.reloc_codec.pack_rows`."""
    words = jnp.asarray(words, jnp.uint32)
    span = jnp.arange(width // 4, dtype=jnp.int32)
    pos = jnp.asarray(offsets, jnp.int32)[:, None] + span[None, :]
    rows = words[jnp.clip(pos, 0, words.shape[0] - 1)]
    rows = jnp.where(_keep(widths, width), rows, jnp.uint32(0))
    return _wire(rows, pairs, slots, width)


def reloc_decode_rows_ref(rows, *, nbytes, dtype):
    """Oracle for :func:`repro.kernels.reloc_codec.decode_rows`."""
    import numpy as np

    from .reloc_codec import from_words

    dt = np.dtype(dtype)
    return from_words(jnp.asarray(rows, jnp.uint32), dt,
                      nbytes // dt.itemsize)
