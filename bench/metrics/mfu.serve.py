"""The serving loop's share of the chip's bf16 peak, in percent: the
FLOPs of the tokens decoded in the window, each at the keys it attended
(``counts.qwen2_token_flops``), over the window's length and the peak.
It reads the whole round (decode, migration, driver), so a gain in any
layer of the service shows here, and no kernel's roofline can pass it."""

from bench.counts import qwen2_token_flops


def read(obs):
    keys = obs.counters.get("token_keys")
    if not keys or obs.window_s <= 0:
        return None
    base = qwen2_token_flops(obs.config, 0)
    per_key = qwen2_token_flops(obs.config, 1) - base
    flops = base * len(keys) + per_key * sum(keys)
    return 100.0 * flops / obs.window_s / obs.peaks["bf16_flops_per_s"]
