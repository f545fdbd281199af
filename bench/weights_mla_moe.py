"""Random DeepSeek-V2 weights (latent attention, a dense first layer, then
layers of routed and shared experts) made from the seed on the device,
leaf by leaf, in the type they are served in.

The tree has the layout the program's decoder reads: ``embed.table``,
``head.w``, ``final_norm.scale``, the dense layers under ``prefix`` and
the expert layers stacked under ``scan``.  The routed expert banks hold
the experts this chip holds (``n_routed_experts`` of the configuration
file); the router keeps all of the deployment's outputs.  The plain
reference reads the same names.  RMSNorm weights are stored as ``scale``
with the weight ``1 + scale``.  Each leaf is made by a compiled program
of its own, so set-up holds at most one leaf in float32 beside the
finished ones.  The router is kept in float32, as the program keeps it,
at values the served type holds exactly: the served router is the one
the program computes with.
"""
from __future__ import annotations

import functools
import math

from .weights import prng_key, vocab_padded

__all__ = ["deepseek_v2_params", "leaf_shapes", "routed_experts"]


def routed_experts(cfg: dict) -> int:
    """The experts the router chooses among: the deployment's count."""
    return int(cfg["deployment"]["routed_experts"])


def leaf_shapes(cfg: dict) -> dict:
    """``{path: (shape, standard deviation)}`` of every leaf; expert
    layers stack on axis 0."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    ff, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    held = cfg["n_routed_experts"]
    dense = cfg["first_k_dense_replace"]
    moe = cfg["num_hidden_layers"] - dense

    def mla(*lead):
        return {
            "norm1.scale": ((*lead, d), 0.1),
            "mixer.w_q.w": ((*lead, d, h * (dn + dr)), 1 / math.sqrt(d)),
            "mixer.w_dkv.w": ((*lead, d, r), 1 / math.sqrt(d)),
            "mixer.w_krope.w": ((*lead, d, dr), 1 / math.sqrt(d)),
            "mixer.kv_norm.scale": ((*lead, r), 0.1),
            "mixer.w_uk.w": ((*lead, r, h * dn), 1 / math.sqrt(r)),
            "mixer.w_uv.w": ((*lead, r, h * dv), 1 / math.sqrt(r)),
            "mixer.wo.w": ((*lead, h * dv, d), 1 / math.sqrt(h * dv)),
            "norm2.scale": ((*lead, d), 0.1),
        }

    def swiglu(prefix, *lead, width, suffix=".w"):
        return {
            f"{prefix}.wg{suffix}": ((*lead, d, width), 1 / math.sqrt(d)),
            f"{prefix}.wi{suffix}": ((*lead, d, width), 1 / math.sqrt(d)),
            f"{prefix}.wo{suffix}": ((*lead, width, d), 1 / math.sqrt(width)),
        }

    out = {"embed.table": ((vocab_padded(cfg), d), 0.02),
           "head.w": ((d, vocab_padded(cfg)), 1 / math.sqrt(d)),
           "final_norm.scale": ((d,), 0.1)}
    for i in range(dense):
        for k, v in {**mla(), **swiglu("ffn", width=ff)}.items():
            out[f"prefix.{i}.{k}"] = v
    for k, v in {**mla(moe),
                 "ffn.router.w.w": ((moe, d, routed_experts(cfg)),
                                    1 / math.sqrt(d)),
                 **swiglu("ffn.experts", moe, held, width=fe, suffix=""),
                 **swiglu("ffn.shared", moe, width=fs)}.items():
        out[f"scan.0.{k}"] = v
    return out


@functools.lru_cache(maxsize=None)
def _leaf_maker(shape: tuple, scale: float, dtype: str, vocab: int,
                axis: int):
    import jax
    import jax.numpy as jnp

    def make(key):
        x = jax.random.normal(key, shape, jnp.float32) * scale
        if vocab:     # the padded vocabulary's rows are zero
            keep = jnp.arange(shape[axis]) < vocab
            x = jnp.where(jnp.expand_dims(keep, 1 - axis), x, 0.0)
        return x.astype(dtype)

    return jax.jit(make)


def deepseek_v2_params(cfg: dict, seed: int, dtype: str = "bfloat16"):
    """The weight tree for ``cfg`` (a configuration file's dict)."""
    import jax
    import jax.numpy as jnp

    key = prng_key(seed)
    vocab_axis = {"embed.table": 0, "head.w": 1}
    tree: dict = {}
    for i, (path, (shape, scale)) in enumerate(
            sorted(leaf_shapes(cfg).items())):
        x = _leaf_maker(tuple(shape), float(scale), dtype,
                        cfg["vocab_size"] if path in vocab_axis else 0,
                        vocab_axis.get(path, 0))(jax.random.fold_in(key, i))
        if path.endswith("router.w.w"):
            x = x.astype(jnp.float32)
        node = tree
        *head, last = path.split(".")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = x
    prefix = tree.pop("prefix", {})
    tree["prefix"] = tuple(prefix[str(i)] for i in range(len(prefix)))
    tree["scan"] = (dict(tree["scan"]["0"], shared_norm_alias=()),)
    tree["suffix"] = ()
    return tree
