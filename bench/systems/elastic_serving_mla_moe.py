"""Elastic serving of a DeepSeek-V2 model (latent attention, a dense first
layer, then routed and shared experts) at the chip's share of an
expert-parallel deployment: the same ``ElasticServingDriver`` over a
``DecodeEngine``, rounds, traffic and KV migration as
``elastic_serving.py``, which this driver reuses.

The benchmark makes the weights (``bench/weights_mla_moe.py``) and hands
them to the engine.  The program's ``ModelConfig`` holds the routed
experts ``[held_first, held_first + n_routed_experts)`` of the
deployment's ``routed_experts``; its router keeps all their outputs.
A window also reports the engine's routing counts over its steps.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .. import weights_mla_moe as weights
from ..reference import deepseek_v2 as ref
from . import elastic_serving as base

KERNELS = ()
_FAR = base._FAR

# what the program implements of a DeepSeek-V2 config.json
_REQUIRED = {"hidden_act": "silu", "attention_bias": False,
             "scoring_func": "softmax", "topk_method": "greedy",
             "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
             "tie_word_embeddings": False}


def model_config(config: dict):
    """The program's ``ModelConfig`` at the configuration file's sizes."""
    from repro.configs import get_config
    from repro.models.config import Yarn

    for k, v in _REQUIRED.items():
        if config[k] != v:
            raise ValueError(f"the program serves {k}={v!r}, the "
                             f"configuration has {config[k]!r}")
    if config["routed_scaling_factor"] != 1:
        raise ValueError("the program leaves routed weights unscaled")
    rs = config["rope_scaling"]
    if rs.get("type") != "yarn":
        raise ValueError("the program's latent attention takes YaRN rope")
    first = int(config["deployment"]["held_first"])
    cfg = dataclasses.replace(
        get_config(config["program_config"]),
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        first_dense_layers=config["first_k_dense_replace"],
        n_experts=weights.routed_experts(config),
        held_experts=range(first, first + config["n_routed_experts"]),
        n_shared_experts=config["n_shared_experts"],
        top_k=config["num_experts_per_tok"],
        d_ff_expert=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        kv_lora_rank=config["kv_lora_rank"],
        q_lora_rank=config["q_lora_rank"] or 0,
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], rope_theta=config["rope_theta"],
        yarn=Yarn(factor=rs["factor"],
                  original_max_position=rs["original_max_position_embeddings"],
                  beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
                  mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]),
        norm_eps=config["rms_norm_eps"], tie_embeddings=False,
        dtype=config["torch_dtype"], param_dtype=config["torch_dtype"])
    if cfg.vocab_padded != weights.vocab_padded(config):
        raise ValueError("the program pads the vocabulary differently")
    return cfg


class System(base.System):
    def __init__(self, config: dict, mix: dict, seed: int):
        from repro.core import GLBConfig
        from repro.serving import DecodeEngine
        from repro.serving.elastic import ElasticServingDriver

        self.config, self.mix, self.seed = config, mix, seed
        s = config["serving"]
        self.cfg = model_config(config)
        with base._weights_from_benchmark(weights.deepseek_v2_params(
                config, seed, config["torch_dtype"])):
            self.engine = DecodeEngine(self.cfg, s_cache=s["s_cache"],
                                       max_batch=s["max_batch"], seed=seed)
        self.driver = ElasticServingDriver(
            s["replicas"], slots_per_replica=s["slots_per_replica"],
            glb=GLBConfig(period=s["glb_period"], policy=s["glb_policy"],
                          ema=s["glb_ema"], asynchronous=True,
                          pipeline_depth=s["pipeline_depth"]),
            engine=self.engine, transport=s["transport"])
        self.traffic = base.generators.load(mix, config, seed)
        self.requests = {}
        self.rounds = 0
        self.seen_done = 0
        self.window_done = []
        self.failed_admissions = 0
        self.failed_rounds = 0
        self.place = {}
        self.migrated = set()
        self.calls = []
        self.token_keys = []

    def run_window(self, seconds: float) -> dict:
        before = self.engine.moe_counts()
        out = super().run_window(seconds)
        after = self.engine.moe_counts()
        out["counters"].update({k: after[k] - before[k] for k in after})
        return out


def check(config: dict, mix: dict, seed: int, outcome: dict, *,
          control: bool = False) -> dict:
    """``{name: (value, limit)}`` for the program's run, or with
    ``control`` for the reference put in its place one precision down:
    at each served position, the gap of the token that the reference
    with float8 weights puts first."""
    import jax.numpy as jnp

    limit = config["correct_limits"]["max_logit_gap"]
    vocab = config["vocab_size"]
    params = weights.deepseek_v2_params(config, seed, config["torch_dtype"])
    widest, count_errors, tokens = 0.0, 0, 0
    for s in outcome["samples"]:
        served = s["served"]
        count_errors += abs(len(served) - s["max_new"])
        if not served:
            continue
        tokens += len(served)
        inputs = [s["first"]] + served[:-1]
        lg = ref.logits(params, config, inputs, s["start"])
        rows = jnp.arange(lg.shape[0])
        best = jnp.max(lg, axis=-1)
        if control:
            low = ref.logits(params, config, inputs, s["start"],
                             weight_dtype="float8_e4m3fn")
            gaps = np.asarray(best - lg[rows, jnp.argmax(low, axis=-1)])
        else:
            y = np.zeros(lg.shape[0], np.int64)
            y[:len(served)] = served
            inside = y < vocab
            got = lg[rows, jnp.asarray(np.where(inside, y, 0))]
            gaps = np.where(inside, np.asarray(best - got), _FAR)
        widest = max(widest, float(gaps[:len(served)].max()))
    return {
        "max_logit_gap": (widest, limit),
        "served_count_errors": (count_errors, 0),
        "lost_requests": (outcome["lost"], 0),
        "failed_rounds": (outcome["failed"], 0),
        "unchecked": (0 if tokens else 1, 0),
    }
