"""Composable decoder LM: every assigned architecture assembles from the
same block machinery (mixer × ffn slots, scanned over pattern periods).

Layer stacking = prefix (first-dense / remainder-breaking layers,
unstacked) + ``lax.scan`` over full pattern periods (stacked params →
small HLO) + suffix remainder.

Teamed-operation islands (shard_map): MoE expert dispatch
(= collective relocation), vocab-parallel cross-entropy (= teamed
reduction over the model axis), sequence-parallel decode attention
(= teamed LSE reduction).  Everything else is GSPMD via constraints.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..compat import pcast_varying, shard_map

from .attention import (attn_attend_cache, attn_decode_project, attn_forward,
                        attn_init)
from .config import LayerSlot, ModelConfig
from .layers import dense, dense_init, embed_init, rmsnorm, rmsnorm_init, swiglu, swiglu_init
from .moe import (expert_all_to_all, expert_replicated, held_experts_forward,
                  mla_attend_cache, mla_decode_project, mla_forward, mla_init,
                  moe_forward_dense, moe_init, routing_counts)
from .parallel import Parallel, constrain
from .rglru import rglru_block, rglru_block_init, rglru_block_step, rglru_empty_state
from .ssm import (mlstm_block, mlstm_block_init, mlstm_block_step,
                  mlstm_empty_state, slstm_block, slstm_block_init,
                  slstm_block_step, slstm_empty_state)

__all__ = ["init_params", "train_loss", "decode_step", "prefill",
           "init_decode_state", "param_partition_specs"]

MAX_SOURCE_LEN = 32768  # whisper learned-pos table bound


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def cast_params(params, cfg: ModelConfig):
    """f32 master params → compute dtype at use (mixed precision)."""
    cd = jnp.dtype(cfg.dtype)

    def cast(a):
        if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(cd)
        return a

    return jax.tree_util.tree_map(cast, params)


# ---------------------------------------------------------------------------
# Block init / forward
# ---------------------------------------------------------------------------
def _block_init(key, cfg: ModelConfig, slot: LayerSlot, dtype, *,
                cross: bool = False):
    ks = jax.random.split(key, 6)
    p: dict[str, Any] = {}
    if slot.mixer in ("attn_global", "attn_local"):
        p["norm1"] = rmsnorm_init(cfg.d_model, dtype)
        p["mixer"] = attn_init(ks[0], cfg, dtype)
    elif slot.mixer == "mla":
        p["norm1"] = rmsnorm_init(cfg.d_model, dtype)
        p["mixer"] = mla_init(ks[0], cfg, dtype)
    elif slot.mixer == "rec":
        p["norm1"] = rmsnorm_init(cfg.d_model, dtype)
        p["mixer"] = rglru_block_init(ks[0], cfg, dtype)
    elif slot.mixer == "mlstm":
        p["norm1"] = rmsnorm_init(cfg.d_model, dtype)
        p["mixer"] = mlstm_block_init(ks[0], cfg, dtype)
    elif slot.mixer == "slstm":
        p["mixer"] = slstm_block_init(ks[0], cfg, dtype)  # self-contained
    else:
        raise ValueError(f"unknown mixer {slot.mixer}")
    if cross:
        p["cross_norm"] = rmsnorm_init(cfg.d_model, dtype)
        p["cross"] = attn_init(ks[1], cfg, dtype)
    if slot.ffn == "dense":
        p["norm2"] = rmsnorm_init(cfg.d_model, dtype)
        p["ffn"] = swiglu_init(ks[2], cfg.d_model, cfg.d_ff, dtype)
    elif slot.ffn == "moe":
        p["norm2"] = rmsnorm_init(cfg.d_model, dtype)
        p["ffn"] = moe_init(ks[2], cfg, dtype)
        if cfg.n_shared_experts:
            p["shared_norm_alias"] = ()  # marker only; shared lives in ffn
    elif slot.ffn != "none":
        raise ValueError(f"unknown ffn {slot.ffn}")
    return p


def _moe_apply(p_moe, cfg: ModelConfig, par: Parallel, x, *, decode: bool,
               rows=None):
    """MoE island: collective relocation over the model axis.  Without a
    model axis the chip computes its held experts' part of the result
    (all of it when it holds every expert; training on every expert
    takes the dense path), and with ``rows`` (T,) bool that path counts
    those tokens' routing into ``aux["routed"]``
    (:func:`moe.routing_counts`)."""
    B, S, d = x.shape
    if par.mesh is None or par.n_model_shards == 1 or cfg.n_experts < par.n_model_shards:
        if not decode and cfg.held_experts is None:
            return moe_forward_dense(p_moe, cfg, x)
        held = cfg.expert_range
        xt = x.reshape(-1, d)
        out, aux, idx = held_experts_forward(p_moe["router"], p_moe["experts"],
                                             cfg, xt, held.start)
        if rows is not None:
            aux = dict(aux, routed=routing_counts(idx, held.start, len(held),
                                                  rows))
        out = out.astype(x.dtype)
        if "shared" in p_moe:
            out = out + swiglu(p_moe["shared"], xt)
        return out.reshape(B, S, d), aux
    router, bank = p_moe["router"], p_moe["experts"]
    axis = par.model_axis

    if not decode:
        xt = x.reshape(-1, d)
        spec_tok = par.token_flat_spec()

        def body(r, b, t):
            out, aux = expert_all_to_all(r, b, None, cfg, t, axis_name=axis)
            aux = jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, par.all_axes), aux)
            return out, aux

        out, aux = shard_map(
            body, mesh=par.mesh,
            in_specs=(P(), P(axis), spec_tok),
            out_specs=(spec_tok, P()))(router, bank, xt)
        out = out.reshape(B, S, d)
    else:
        xt = x.reshape(B * S, d)
        spec_tok = P(par.batch_axes, None)

        def body(r, b, t):
            out, aux = expert_replicated(r, b, None, cfg, t, axis_name=axis)
            # tokens are replicated over the model axis here, so aux is
            # already invariant over it — average over batch axes only
            aux = jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, par.batch_axes), aux)
            return out, aux

        out, aux = shard_map(
            body, mesh=par.mesh,
            in_specs=(P(), P(axis), spec_tok),
            out_specs=(spec_tok, P()))(router, bank, xt)
        out = out.reshape(B, S, d)
    if "shared" in p_moe:  # shared experts are dense compute (GSPMD)
        out = out + swiglu(p_moe["shared"], x.reshape(-1, d)).reshape(B, S, d)
    return out, aux


def _block_forward(p, cfg: ModelConfig, slot: LayerSlot, par: Parallel, x,
                   positions, *, impl=None, causal=True, cross_kv=None,
                   decode_moe=False):
    """Full-sequence block application. Returns (x, aux, cache_entry)."""
    aux = {"aux": jnp.zeros((), jnp.float32), "z": jnp.zeros((), jnp.float32)}
    cache = None
    if slot.mixer == "slstm":
        x, cache = slstm_block(p["mixer"], cfg, x, return_state=True)
    else:
        h = rmsnorm(p["norm1"], x, cfg.norm_eps)
        if slot.mixer == "attn_global":
            y, kv = attn_forward(p["mixer"], cfg, h, positions,
                                 causal=causal, window=None, impl=impl,
                                 par=par)
            cache = kv
        elif slot.mixer == "attn_local":
            y, kv = attn_forward(p["mixer"], cfg, h, positions,
                                 causal=causal, window=cfg.window, impl=impl,
                                 par=par)
            cache = kv
        elif slot.mixer == "mla":
            y, kv = mla_forward(p["mixer"], cfg, h, positions, impl=impl)
            cache = kv
        elif slot.mixer == "rec":
            y, cache = rglru_block(p["mixer"], cfg, h, impl=impl,
                                   return_state=True)
        elif slot.mixer == "mlstm":
            y, cache = mlstm_block(p["mixer"], cfg, h, impl=impl,
                                   return_state=True)
        else:
            raise ValueError(slot.mixer)
        x = x + y
    if cross_kv is not None and "cross" in p:
        h = rmsnorm(p["cross_norm"], x, cfg.norm_eps)
        y, _ = attn_forward(p["cross"], cfg, h, positions,
                            kv_override=_project_cross(p["cross"], cfg, cross_kv),
                            impl=impl)
        x = x + y
    if slot.ffn == "dense":
        x = x + swiglu(p["ffn"], rmsnorm(p["norm2"], x, cfg.norm_eps))
    elif slot.ffn == "moe":
        y, aux = _moe_apply(p["ffn"], cfg, par,
                            rmsnorm(p["norm2"], x, cfg.norm_eps),
                            decode=decode_moe)
        x = x + y
    return x, aux, cache


def _project_cross(p_attn, cfg: ModelConfig, enc_out):
    """Project encoder hidden states to this block's cross k/v heads."""
    B, S_enc, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = dense(p_attn["wk"], enc_out).reshape(B, S_enc, cfg.n_kv_heads, hd)
    v = dense(p_attn["wv"], enc_out).reshape(B, S_enc, cfg.n_kv_heads, hd)
    return k, v


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------
def _layer_plan(cfg: ModelConfig):
    """(prefix_slots, n_periods, suffix_slots) honoring first_dense."""
    slots = cfg.layer_slots()
    period = len(cfg.pattern)
    n_prefix = cfg.first_dense_layers
    rest = len(slots) - n_prefix
    n_periods = rest // period
    n_suffix = rest - n_periods * period
    return (slots[:n_prefix], n_periods,
            slots[n_prefix + n_periods * period:])


def init_params(key, cfg: ModelConfig):
    dtype = _dtype(cfg)
    ks = jax.random.split(key, 12)
    prefix_slots, n_periods, suffix_slots = _layer_plan(cfg)
    p: dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.vocab_padded, cfg.d_model, dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], cfg.d_model, cfg.vocab_padded, dtype)

    cross = cfg.is_encoder_decoder
    kp = jax.random.split(ks[2], max(len(prefix_slots), 1))
    p["prefix"] = tuple(
        _block_init(kp[i], cfg, s, dtype, cross=cross)
        for i, s in enumerate(prefix_slots))

    def stack_init(k, slot):
        kk = jax.random.split(k, max(n_periods, 1))
        layers = [_block_init(kk[i], cfg, slot, dtype, cross=cross)
                  for i in range(n_periods)]
        return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers)

    kscan = jax.random.split(ks[3], len(cfg.pattern))
    p["scan"] = tuple(stack_init(kscan[j], slot)
                      for j, slot in enumerate(cfg.pattern)) if n_periods else ()
    ksuf = jax.random.split(ks[4], max(len(suffix_slots), 1))
    p["suffix"] = tuple(
        _block_init(ksuf[i], cfg, s, dtype, cross=cross)
        for i, s in enumerate(suffix_slots))

    if cfg.is_encoder_decoder:
        enc_pattern = cfg.encoder_pattern or (LayerSlot("attn_global", "dense"),)
        n_enc_periods = cfg.encoder_layers // len(enc_pattern)
        kk = jax.random.split(ks[5], len(enc_pattern))

        def enc_stack(k, slot):
            kk2 = jax.random.split(k, max(n_enc_periods, 1))
            layers = [_block_init(kk2[i], cfg, slot, dtype)
                      for i in range(n_enc_periods)]
            return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers)

        p["encoder"] = {
            "scan": tuple(enc_stack(kk[j], s) for j, s in enumerate(enc_pattern)),
            "final_norm": rmsnorm_init(cfg.d_model, dtype),
            "pos": (jax.random.normal(ks[6], (MAX_SOURCE_LEN, cfg.d_model),
                                      jnp.float32) * 0.02).astype(dtype),
            "dec_pos": (jax.random.normal(ks[7], (cfg.max_target_len, cfg.d_model),
                                          jnp.float32) * 0.02).astype(dtype),
        }
    if cfg.mtp_depth:
        kk = jax.random.split(ks[8], 3)
        p["mtp"] = {
            "proj": dense_init(kk[0], 2 * cfg.d_model, cfg.d_model, dtype),
            "norm": rmsnorm_init(cfg.d_model, dtype),
            "block": _block_init(kk[1], cfg, cfg.pattern[-1], dtype),
        }
    return p


# ---------------------------------------------------------------------------
# Sharding rules (path-based)
# ---------------------------------------------------------------------------
def param_partition_specs(cfg: ModelConfig, par: Parallel, params_shape):
    """PartitionSpec pytree matching the param tree, by leaf path."""
    m = par.model_axis
    f = par.batch_axes[-1] if par.fsdp else None

    COL = {"wq", "wk", "wv", "wi", "wg", "w_up", "w_uq", "w_uk", "w_uv",
           "w_q", "w_gate", "w_x", "w_dkv", "w_dq", "w_rg", "w_ig"}
    ROW = {"wo", "w_down", "w_out"}

    def spec_for(path: str, ndim: int, shape) -> P:
        parts = path.strip("/").split("/")

        def pad(spec_list):
            spec = list(spec_list) + [None] * (ndim - len(spec_list))
            return P(*spec)

        lead = ndim - 2  # stacked scan layers add a leading period dim
        pre = [None] * max(lead, 0)
        if "embed" in parts or "head" in parts:
            return pad([m, f])
        if "experts" in parts:  # (E, d, ff) possibly stacked
            if ndim == 3:
                return P(m, f, None)
            if ndim == 4:
                return P(None, m, f, None)
        mods = set(parts)
        if parts[-1] == "b":
            # column-parallel biases shard their (single) out dim
            if mods & COL:
                return P(*([None] * (ndim - 1) + [m]))
            return P()
        if mods & COL:
            if ndim >= 2:
                return pad(pre + [f, m])
        if mods & ROW:
            if ndim >= 2:
                return pad(pre + [m, f])
        return P()  # norms, small gates/tables: replicated

    def walk(tree, path=""):
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in tree.items()}
        if isinstance(tree, (tuple, list)):
            return type(tree)(walk(v, f"{path}/{i}") for i, v in enumerate(tree))
        return spec_for(path, getattr(tree, "ndim", 0), getattr(tree, "shape", ()))

    return walk(params_shape)


# ---------------------------------------------------------------------------
# Forward (training) + loss
# ---------------------------------------------------------------------------
def _positions_for(cfg: ModelConfig, batch) -> jnp.ndarray:
    if cfg.mrope_sections and "mrope_positions" in batch:
        return batch["mrope_positions"]
    tokens = batch["tokens"]
    B, S = tokens.shape
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))


def _embed(params, cfg: ModelConfig, tokens):
    h = jnp.take(params["embed"]["table"], tokens, axis=0)
    if cfg.embed_scale:
        h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
    return h.astype(jnp.dtype(cfg.dtype))


def _run_encoder(params, cfg: ModelConfig, par: Parallel, frames, impl):
    """Whisper encoder over stub frame embeddings (B, S_enc, d)."""
    enc = params["encoder"]
    B, S, _ = frames.shape
    h = frames + enc["pos"][None, :S].astype(frames.dtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    enc_pattern = cfg.encoder_pattern or (LayerSlot("attn_global", "dense"),)

    def period_fn(x, stacked):
        for j, slot in enumerate(enc_pattern):
            pj = stacked[j]
            x, _, _ = _block_forward(pj, cfg, slot, par, x, positions,
                                     impl=impl, causal=False)
        return x, None

    if enc["scan"]:
        if cfg.scan_layers:
            h, _ = jax.lax.scan(period_fn, h, enc["scan"])
        else:
            n_enc = jax.tree_util.tree_leaves(enc["scan"])[0].shape[0]
            for i in range(n_enc):
                sl = jax.tree_util.tree_map(lambda a: a[i], enc["scan"])
                h, _ = period_fn(h, sl)
    return rmsnorm(enc["final_norm"], h, cfg.norm_eps)


def _trunk(params, cfg: ModelConfig, par: Parallel, h, positions, *,
           impl=None, cross_kv=None, collect_caches=False):
    """prefix → scanned periods → suffix.

    Returns (h, aux_sum, z_sum[, caches]) — caches mirror the decode
    state layout when collect_caches=True (prefill)."""
    prefix_slots, n_periods, suffix_slots = _layer_plan(cfg)
    aux_sum = jnp.zeros((), jnp.float32)
    z_sum = jnp.zeros((), jnp.float32)
    caches = {"prefix": [], "scan": (), "suffix": []}

    for p_blk, slot in zip(params["prefix"], prefix_slots):
        h, aux, c = _block_forward(p_blk, cfg, slot, par, h, positions,
                                   impl=impl, cross_kv=cross_kv)
        aux_sum += aux["aux"]
        z_sum += aux["z"]
        caches["prefix"].append(c)

    if n_periods:
        def period_fn(carry, stacked):
            x, a_s, z_s = carry
            cs = []
            for j, slot in enumerate(cfg.pattern):
                pj = stacked[j]
                x, aux, c = _block_forward(pj, cfg, slot, par, x, positions,
                                           impl=impl, cross_kv=cross_kv)
                a_s = a_s + aux["aux"]
                z_s = z_s + aux["z"]
                cs.append(c)
            x = constrain(par, x, par.batch_spec(None, None))
            return (x, a_s, z_s), (tuple(cs) if collect_caches else None)

        if cfg.remat != "none":
            policy = (jax.checkpoint_policies.nothing_saveable
                      if cfg.remat in ("full", "full_cse")
                      else jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
            period_fn = jax.checkpoint(period_fn, policy=policy,
                                       prevent_cse=(cfg.remat == "full_cse"))
        if cfg.scan_layers:
            (h, aux_sum, z_sum), scan_caches = jax.lax.scan(
                period_fn, (h, aux_sum, z_sum), params["scan"])
        else:
            # unrolled (exact cost_analysis: while bodies are counted once
            # by XLA, so the roofline lowering unrolls)
            carry = (h, aux_sum, z_sum)
            percall = []
            for i in range(n_periods):
                sl = jax.tree_util.tree_map(lambda a: a[i], params["scan"])
                carry, cs = period_fn(carry, sl)
                percall.append(cs)
            (h, aux_sum, z_sum) = carry
            scan_caches = None
            if collect_caches:
                scan_caches = jax.tree_util.tree_map(
                    lambda *a: jnp.stack(a), *percall)
        if collect_caches:
            caches["scan"] = scan_caches

    for p_blk, slot in zip(params["suffix"], suffix_slots):
        h, aux, c = _block_forward(p_blk, cfg, slot, par, h, positions,
                                   impl=impl, cross_kv=cross_kv)
        aux_sum += aux["aux"]
        z_sum += aux["z"]
        caches["suffix"].append(c)

    if collect_caches:
        return h, aux_sum, z_sum, caches
    return h, aux_sum, z_sum


def _head_table(params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return params["embed"]["table"]              # (V, d)
    return params["head"]["w"].T                      # (V, d)


def lm_loss(params, cfg: ModelConfig, par: Parallel, h, labels, mask=None):
    """Vocab-parallel chunked cross-entropy (teamed reduction island).

    h: (B, S, d); labels: (B, S) int32; mask: (B, S) or None.
    """
    table = _head_table(params, cfg)
    B, S, d = h.shape
    V = table.shape[0]
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    chunk = cfg.loss_chunk if cfg.loss_chunk else S
    n_chunks = max(S // chunk, 1)
    chunk = S // n_chunks

    if par.mesh is None or par.n_model_shards == 1:
        def chunk_loss(carry, xs):
            hc, lc, mc = xs
            logits = hc.astype(jnp.float32) @ table.astype(jnp.float32).T
            if cfg.final_softcap:
                logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
            return carry + jnp.sum((lse - ll) * mc), None

        h_c = h.reshape(B, n_chunks, chunk, d).transpose(1, 0, 2, 3)
        l_c = labels.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
        m_c = mask.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
        total, _ = jax.lax.scan(jax.checkpoint(chunk_loss),
                                jnp.zeros((), jnp.float32), (h_c, l_c, m_c))
        return total / jnp.maximum(jnp.sum(mask), 1.0)

    axis = par.model_axis
    n_shards = par.n_model_shards
    v_local = V // n_shards

    def body(tbl, hh, ll, mm):
        shard = jax.lax.axis_index(axis)
        v0 = shard * v_local

        def chunk_loss(carry, xs):
            hc, lc, mc = xs                      # (B_loc, chunk, d) ...
            logits = hc.astype(jnp.float32) @ tbl.astype(jnp.float32).T
            if cfg.final_softcap:
                logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
            m_loc = jnp.max(logits, axis=-1)
            # stop_gradient: the stabilizer shift cancels in CE's gradient
            m_glob = jax.lax.pmax(jax.lax.stop_gradient(m_loc), axis)
            se = jnp.sum(jnp.exp(logits - m_glob[..., None]), axis=-1)
            lse = m_glob + jnp.log(jax.lax.psum(se, axis))
            li = lc - v0
            in_range = (li >= 0) & (li < v_local)
            ll_loc = jnp.take_along_axis(
                logits, jnp.clip(li, 0, v_local - 1)[..., None], axis=-1)[..., 0]
            ll_glob = jax.lax.psum(jnp.where(in_range, ll_loc, 0.0), axis)
            return carry + jnp.sum((lse - ll_glob) * mc), None

        Bl = hh.shape[0]
        h_c = hh.reshape(Bl, n_chunks, chunk, d).transpose(1, 0, 2, 3)
        l_c = ll.reshape(Bl, n_chunks, chunk).transpose(1, 0, 2)
        m_c = mm.reshape(Bl, n_chunks, chunk).transpose(1, 0, 2)
        zero = pcast_varying(jnp.zeros((), jnp.float32),
                             tuple(par.batch_axes), to="varying")
        tot, _ = jax.lax.scan(jax.checkpoint(chunk_loss), zero,
                              (h_c, l_c, m_c))
        tot = jax.lax.psum(tot, par.batch_axes)
        cnt = jax.lax.psum(jnp.sum(mm), par.batch_axes)
        return tot / jnp.maximum(cnt, 1.0)

    return shard_map(
        body, mesh=par.mesh,
        in_specs=(P(axis, None), par.batch_spec(None, None),
                  par.batch_spec(None), par.batch_spec(None)),
        out_specs=P())(table, h, labels, mask)


def train_loss(params, cfg: ModelConfig, par: Parallel, batch, *, impl=None):
    """Next-token LM loss (+ MoE aux, + MTP). Returns (loss, metrics)."""
    params = cast_params(params, cfg)
    tokens = batch["tokens"]
    labels = batch.get("labels")
    if labels is None:
        labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    positions = _positions_for(cfg, batch)

    cross_kv = None
    if cfg.is_encoder_decoder:
        frames = batch["enc_frames"].astype(jnp.dtype(cfg.dtype))
        enc_out = _run_encoder(params, cfg, par, frames, impl)
        # decoder cross-attention keys/values from a shared projection:
        # computed per block inside attn_forward via kv_override — here we
        # precompute the encoder hidden (keys projected per-block).
        cross_kv = enc_out

    h = _embed(params, cfg, tokens)
    h = constrain(par, h, par.batch_spec(None, None))
    if cfg.is_encoder_decoder:
        S = tokens.shape[1]
        h = h + params["encoder"]["dec_pos"][None, :S].astype(h.dtype)

    h, aux_sum, z_sum = _trunk(params, cfg, par, h, positions, impl=impl,
                               cross_kv=cross_kv)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)

    mask = batch.get("mask")
    loss = lm_loss(params, cfg, par, h, labels, mask)
    metrics = {"lm_loss": loss, "moe_aux": aux_sum, "router_z": z_sum}

    if cfg.mtp_depth and not cfg.is_encoder_decoder:
        mtp = params["mtp"]
        emb_next = _embed(params, cfg, jnp.roll(tokens, -1, axis=1))
        h_in = dense(mtp["proj"],
                     jnp.concatenate([rmsnorm(mtp["norm"], h, cfg.norm_eps),
                                      emb_next], axis=-1))
        h_mtp, _, _ = _block_forward(mtp["block"], cfg, cfg.pattern[-1], par,
                                     h_in, positions, impl=impl)
        labels2 = jnp.pad(labels[:, 1:], ((0, 0), (0, 1)))
        mtp_loss = lm_loss(params, cfg, par, h_mtp, labels2, mask)
        metrics["mtp_loss"] = mtp_loss
        loss = loss + cfg.mtp_loss_weight * mtp_loss

    loss = loss + cfg.router_aux_weight * aux_sum + cfg.router_z_weight * z_sum
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Serving: decode with caches
# ---------------------------------------------------------------------------
def _slot_cache_shape(cfg: ModelConfig, slot: LayerSlot, batch: int,
                      s_cache: int):
    hd = cfg.resolved_head_dim
    if slot.mixer == "attn_global" or (slot.mixer == "attn_local"):
        size = s_cache if slot.mixer == "attn_global" else min(
            s_cache, cfg.window or s_cache)
        return {
            "k": jnp.zeros((batch, size, cfg.n_kv_heads, hd), jnp.dtype(cfg.dtype)),
            "v": jnp.zeros((batch, size, cfg.n_kv_heads, hd), jnp.dtype(cfg.dtype)),
            "pos": jnp.full((batch, size), -1, jnp.int32),
        }
    if slot.mixer == "mla":
        return {
            "ckv": jnp.zeros((batch, s_cache, cfg.kv_lora_rank), jnp.dtype(cfg.dtype)),
            "krope": jnp.zeros((batch, s_cache, cfg.qk_rope_dim), jnp.dtype(cfg.dtype)),
            "pos": jnp.full((batch, s_cache), -1, jnp.int32),
        }
    if slot.mixer == "rec":
        return rglru_empty_state(cfg, batch)
    if slot.mixer == "mlstm":
        return mlstm_empty_state(cfg, batch)
    if slot.mixer == "slstm":
        return slstm_empty_state(cfg, batch)
    raise ValueError(slot.mixer)


def init_decode_state(cfg: ModelConfig, batch: int, s_cache: int):
    """Abstract-friendly decode state (zeros; shapes only under eval_shape)."""
    prefix_slots, n_periods, suffix_slots = _layer_plan(cfg)
    state = {
        "pos": jnp.zeros((batch,), jnp.int32),
        "prefix": tuple(_slot_cache_shape(cfg, s, batch, s_cache)
                        for s in prefix_slots),
        "suffix": tuple(_slot_cache_shape(cfg, s, batch, s_cache)
                        for s in suffix_slots),
    }
    if n_periods:
        state["scan"] = tuple(
            jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (n_periods,) + a.shape),
                _slot_cache_shape(cfg, slot, batch, s_cache))
            for slot in cfg.pattern)
    else:
        state["scan"] = ()
    if cfg.is_encoder_decoder:
        state["cross_kv"] = None  # filled by prefill
    return state


def _block_decode(p, cfg: ModelConfig, slot: LayerSlot, par: Parallel, x,
                  positions, cache, *, cross_kv=None, rows=None):
    """One-token decode through a block. Returns (x, new_cache, routed):
    ``routed`` counts an MoE block's routing of ``rows`` (None else)."""
    routed = None
    if slot.mixer == "slstm":
        x, new = slstm_block_step(p["mixer"], cfg, x, cache)
    else:
        h = rmsnorm(p["norm1"], x, cfg.norm_eps)
        if slot.mixer in ("attn_global", "attn_local"):
            window = cfg.window if slot.mixer == "attn_local" else None
            # write-then-attend: the new row joins the cache first so the
            # attention runs entirely in the cache's static layout
            q, k_new, v_new = attn_decode_project(p["mixer"], cfg, h,
                                                  positions)
            size = cache["k"].shape[1]
            wslot = (positions[:, 0] % size).astype(jnp.int32)
            bidx = jnp.arange(x.shape[0])
            new = {
                "k": cache["k"].at[bidx, wslot].set(k_new.astype(cache["k"].dtype)),
                "v": cache["v"].at[bidx, wslot].set(v_new.astype(cache["v"].dtype)),
                "pos": cache["pos"].at[bidx, wslot].set(positions[:, 0]),
            }
            y = attn_attend_cache(p["mixer"], cfg, q, new["k"], new["v"],
                                  new["pos"], positions, window=window)
            x = x + y
        elif slot.mixer == "mla":
            q_pair, ckv_new, kr_new = mla_decode_project(p["mixer"], cfg, h,
                                                         positions)
            size = cache["ckv"].shape[1]
            wslot = (positions[:, 0] % size).astype(jnp.int32)
            bidx = jnp.arange(x.shape[0])
            new = {
                "ckv": cache["ckv"].at[bidx, wslot].set(
                    ckv_new.astype(cache["ckv"].dtype)),
                "krope": cache["krope"].at[bidx, wslot].set(
                    kr_new.astype(cache["krope"].dtype)),
                "pos": cache["pos"].at[bidx, wslot].set(positions[:, 0]),
            }
            y = mla_attend_cache(p["mixer"], cfg, q_pair, new["ckv"],
                                 new["krope"], new["pos"], positions)
            x = x + y
        elif slot.mixer == "rec":
            y, new = rglru_block_step(p["mixer"], cfg, h, cache)
            x = x + y
        elif slot.mixer == "mlstm":
            y, new = mlstm_block_step(p["mixer"], cfg, h, cache)
            x = x + y
        else:
            raise ValueError(slot.mixer)
    if cross_kv is not None and "cross" in p:
        h = rmsnorm(p["cross_norm"], x, cfg.norm_eps)
        y, _ = attn_forward(p["cross"], cfg, h, positions,
                            kv_override=_project_cross(p["cross"], cfg, cross_kv))
        x = x + y
    if slot.ffn == "dense":
        x = x + swiglu(p["ffn"], rmsnorm(p["norm2"], x, cfg.norm_eps))
    elif slot.ffn == "moe":
        y, aux = _moe_apply(p["ffn"], cfg, par,
                            rmsnorm(p["norm2"], x, cfg.norm_eps), decode=True,
                            rows=rows)
        routed = aux.get("routed")
        x = x + y
    return x, new, routed


def decode_step(params, cfg: ModelConfig, par: Parallel, state, token_ids, *,
                impl=None, count_rows=None):
    """serve_step: one new token per sequence against the cache.

    token_ids: (B, 1) int32. Returns (new_state, logits (B, V)).  With
    ``count_rows`` (an MoE config's int32 count of leading real rows) it
    returns (new_state, logits, routed): int32 (3,) sums over MoE layers
    of those rows' assignments, the assignments to held experts, and the
    held experts they hit (:func:`moe.routing_counts`)."""
    params = cast_params(params, cfg)
    prefix_slots, n_periods, suffix_slots = _layer_plan(cfg)
    B = token_ids.shape[0]
    positions = state["pos"].reshape(B, 1)
    rows = None if count_rows is None else jnp.arange(B) < count_rows
    routed = None if rows is None else jnp.zeros((3,), jnp.int32)

    def count(total, r):
        return total if r is None else total + r

    h = _embed(params, cfg, token_ids)
    if cfg.is_encoder_decoder:
        # decoder learned positions (clipped to table)
        pidx = jnp.clip(positions[:, 0], 0, cfg.max_target_len - 1)
        h = h + jnp.take(params["encoder"]["dec_pos"], pidx, axis=0)[:, None, :]
    cross_kv = state.get("cross_kv")

    new_state = {"pos": state["pos"] + 1, "cross_kv": cross_kv} \
        if cfg.is_encoder_decoder else {"pos": state["pos"] + 1}

    new_prefix = []
    for p_blk, slot, cache in zip(params["prefix"], prefix_slots,
                                  state["prefix"]):
        h, new, r = _block_decode(p_blk, cfg, slot, par, h, positions, cache,
                                  cross_kv=cross_kv, rows=rows)
        routed = count(routed, r)
        new_prefix.append(new)
    new_state["prefix"] = tuple(new_prefix)

    if n_periods:
        def period_fn(carry, xs):
            x, total = carry
            stacked_p, stacked_c = xs
            new_caches = []
            for j, slot in enumerate(cfg.pattern):
                x, nc, r = _block_decode(stacked_p[j], cfg, slot, par, x,
                                         positions, stacked_c[j],
                                         cross_kv=cross_kv, rows=rows)
                total = count(total, r)
                new_caches.append(nc)
            return (x, total), tuple(new_caches)

        if cfg.scan_layers:
            (h, routed), new_scan = jax.lax.scan(
                period_fn, (h, routed), (params["scan"], state["scan"]))
        else:
            percall = []
            for i in range(n_periods):
                xs_i = jax.tree_util.tree_map(
                    lambda a: a[i], (params["scan"], state["scan"]))
                (h, routed), nc = period_fn((h, routed), xs_i)
                percall.append(nc)
            new_scan = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *percall)
        new_state["scan"] = new_scan
    else:
        new_state["scan"] = ()

    new_suffix = []
    for p_blk, slot, cache in zip(params["suffix"], suffix_slots,
                                  state["suffix"]):
        h, new, r = _block_decode(p_blk, cfg, slot, par, h, positions, cache,
                                  cross_kv=cross_kv, rows=rows)
        routed = count(routed, r)
        new_suffix.append(new)
    new_state["suffix"] = tuple(new_suffix)

    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    table = _head_table(params, cfg)
    logits = h[:, 0].astype(jnp.float32) @ table.astype(jnp.float32).T
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    if par.mesh is not None:
        logits = constrain(par, logits, P(par.batch_axes, par.model_axis))
    if routed is not None:
        return new_state, logits, routed
    return new_state, logits


# ---------------------------------------------------------------------------
# Parallel prefill
# ---------------------------------------------------------------------------
def _fill_attn_cache(cfg: ModelConfig, slot: LayerSlot, kv, positions,
                     s_cache: int):
    """Turn prefill (k, v) of shape (B, S, Hkv, hd) into a decode cache
    ({k, v, pos} sized s_cache — or ring of `window` for local layers)."""
    k, v = kv
    B, S = k.shape[0], k.shape[1]
    size = s_cache if slot.mixer != "attn_local" else min(
        s_cache, cfg.window or s_cache)
    pos = positions if positions.ndim == 2 else positions[0]
    if S <= size:
        pad = size - S
        ck = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cp = jnp.pad(pos.astype(jnp.int32), ((0, 0), (0, pad)),
                     constant_values=-1)
        return {"k": ck, "v": cv, "pos": cp}
    # ring scatter of the last `size` rows
    tail_k = k[:, -size:]
    tail_v = v[:, -size:]
    tail_p = pos[:, -size:].astype(jnp.int32)
    slots = (tail_p % size).astype(jnp.int32)              # (B, size)
    bidx = jnp.arange(B)[:, None]
    ck = jnp.zeros((B, size) + k.shape[2:], k.dtype).at[bidx, slots].set(tail_k)
    cv = jnp.zeros((B, size) + v.shape[2:], v.dtype).at[bidx, slots].set(tail_v)
    cp = jnp.full((B, size), -1, jnp.int32).at[bidx, slots].set(tail_p)
    return {"k": ck, "v": cv, "pos": cp}


def _fill_mla_cache(cfg: ModelConfig, kv, positions, s_cache: int):
    ckv, krope = kv                                       # (B,S,r), (B,S,dr)
    B, S = ckv.shape[0], ckv.shape[1]
    pos = positions if positions.ndim == 2 else positions[0]
    if S > s_cache:
        ckv, krope, pos = ckv[:, -s_cache:], krope[:, -s_cache:], pos[:, -s_cache:]
        S = s_cache
    pad = s_cache - S
    return {
        "ckv": jnp.pad(ckv, ((0, 0), (0, pad), (0, 0))),
        "krope": jnp.pad(krope, ((0, 0), (0, pad), (0, 0))),
        "pos": jnp.pad(pos.astype(jnp.int32), ((0, 0), (0, pad)),
                       constant_values=-1),
    }


def _cache_to_state(cfg: ModelConfig, slot: LayerSlot, c, positions,
                    s_cache: int, stacked: bool):
    if slot.mixer in ("attn_global", "attn_local"):
        fn = lambda kv: _fill_attn_cache(cfg, slot, kv, positions, s_cache)
        return jax.vmap(fn)(c) if stacked else fn(c)
    if slot.mixer == "mla":
        fn = lambda kv: _fill_mla_cache(cfg, kv, positions, s_cache)
        return jax.vmap(fn)(c) if stacked else fn(c)
    return c  # recurrent states pass through (already final)


def prefill_forward(params, cfg: ModelConfig, par: Parallel, batch,
                    s_cache: int, *, impl=None):
    """Parallel prefill: full forward, returns (decode_state, last_logits).

    One pass through the parallel kernels, caches/recurrent states
    assembled for decode.
    """
    params = cast_params(params, cfg)
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = _positions_for(cfg, batch)
    cross_kv = None
    if cfg.is_encoder_decoder:
        enc_out = _run_encoder(params, cfg, par,
                               batch["enc_frames"].astype(jnp.dtype(cfg.dtype)),
                               impl)
        cross_kv = enc_out
    h = _embed(params, cfg, tokens)
    h = constrain(par, h, par.batch_spec(None, None))
    if cfg.is_encoder_decoder:
        h = h + params["encoder"]["dec_pos"][None, :S].astype(h.dtype)
    h, _, _, caches = _trunk(params, cfg, par, h, positions, impl=impl,
                             cross_kv=cross_kv, collect_caches=True)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    table = _head_table(params, cfg)
    last = h[:, -1].astype(jnp.float32) @ table.astype(jnp.float32).T
    if cfg.final_softcap:
        last = cfg.final_softcap * jnp.tanh(last / cfg.final_softcap)

    prefix_slots, n_periods, suffix_slots = _layer_plan(cfg)
    pos2 = positions if positions.ndim == 2 else positions[0]
    state = {
        "pos": pos2[:, -1].astype(jnp.int32) + 1,
        "prefix": tuple(
            _cache_to_state(cfg, slot, c, positions, s_cache, False)
            for slot, c in zip(prefix_slots, caches["prefix"])),
        "suffix": tuple(
            _cache_to_state(cfg, slot, c, positions, s_cache, False)
            for slot, c in zip(suffix_slots, caches["suffix"])),
        "scan": tuple(
            _cache_to_state(cfg, slot, c, positions, s_cache, True)
            for slot, c in zip(cfg.pattern, caches["scan"]))
        if n_periods else (),
    }
    if cfg.is_encoder_decoder:
        state["cross_kv"] = cross_kv
    return state, last


# ---------------------------------------------------------------------------
# Sequential prefill (oracle for tests; exercises decode_step exactly)
# ---------------------------------------------------------------------------
def prefill(params, cfg: ModelConfig, par: Parallel, tokens, s_cache: int, *,
            impl=None, enc_frames=None):
    """Sequential prefill via decode_step scan (correct for every mixer;
    attention archs could use the parallel path — this is the simple
    reference used by tests and the serving example)."""
    B, S = tokens.shape
    state = init_decode_state(cfg, B, s_cache)
    if cfg.is_encoder_decoder:
        enc_out = _run_encoder(params, cfg, par,
                               enc_frames.astype(jnp.dtype(cfg.dtype)), impl)
        state["cross_kv"] = enc_out

    def step(st, tok):
        st, logits = decode_step(params, cfg, par, st, tok[:, None],
                                 impl=impl)
        return st, logits

    state, all_logits = jax.lax.scan(step, state, tokens.T)
    return state, jnp.transpose(all_logits, (1, 0, 2))
