"""A closed population of serving requests with published length
statistics.

``population`` requests are resident at once: each completion is
replaced by one admission before the next round.  A request is (prompt
length, tokens to generate).  Each length is lognormal, with the median
and the log-space standard deviation the mix gives (``prompt``,
``output``), and whole from 1 up; a prompt is held to what the context
leaves after its output (``context``, the configuration's cache length).
The sizes are a fixed pool of ``pool`` requests at evenly spaced
quantiles of the two distributions, paired by a fixed shuffle; the seed
only orders the pool, so every seed draws the same set of sizes.
"""
from __future__ import annotations

import numpy as np
from statistics import NormalDist


def lognormal_quantiles(median: float, sigma: float, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a lognormal, whole and >= 1."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.maximum(np.rint(median * np.exp(sigma * z)), 1).astype(np.int64)


class Traffic:
    def __init__(self, mix: dict, config: dict, seed: int):
        n = int(mix["pool"])
        context = int(config["serving"]["s_cache"])
        prompts = lognormal_quantiles(mix["prompt"]["median"],
                                      mix["prompt"]["sigma"], n)
        outputs = lognormal_quantiles(mix["output"]["median"],
                                      mix["output"]["sigma"], n)
        outputs = np.minimum(outputs, context // 2)
        # a fixed pairing of the two, the same for every seed
        outputs = outputs[np.random.default_rng(0).permutation(n)]
        prompts = np.minimum(prompts, context - outputs)
        order = np.random.default_rng(int(seed)).permutation(n)
        self.pool = list(zip(prompts[order].tolist(),
                             outputs[order].tolist()))
        self.population = int(mix["population"])
        self.drawn = 0

    def next_request(self) -> tuple[int, int]:
        """(prompt length, tokens to generate) of the next admission."""
        req = self.pool[self.drawn % len(self.pool)]
        self.drawn += 1
        return req
