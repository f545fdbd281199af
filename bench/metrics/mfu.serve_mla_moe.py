"""The latent-attention, sparse-expert serving loop's share of the chip's
bf16 peak, in percent: the FLOPs of the tokens decoded in the window,
each at the keys it attended (``counts_mla_moe.token_flops``: absorbed
attention, the dense layer, shared experts and router, routed experts at
the deployment's share of a token, the head), over the window's length
and the peak.  It reads the whole round (decode, migration, driver), so
no layer's share can pass it."""

from bench.counts_mla_moe import token_flops


def read(obs):
    keys = obs.counters.get("token_keys")
    if not keys or obs.window_s <= 0:
        return None
    base = token_flops(obs.config, 0)
    per_key = token_flops(obs.config, 1) - base
    flops = base * len(keys) + per_key * sum(keys)
    return 100.0 * flops / obs.window_s / obs.peaks["bf16_flops_per_s"]
