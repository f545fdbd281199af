"""Public model API: config → params and the training loss."""
from __future__ import annotations

import jax

from .config import ModelConfig
from .parallel import Parallel
from . import transformer as T

__all__ = ["abstract_params", "init_params", "train_loss_fn"]


def init_params(cfg: ModelConfig, seed: int = 0):
    return T.init_params(jax.random.PRNGKey(seed), cfg)


def abstract_params(cfg: ModelConfig):
    """Shape-only param tree (no allocation)."""
    return jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))


def train_loss_fn(cfg: ModelConfig, par: Parallel, *, impl=None):
    def fn(params, batch):
        return T.train_loss(params, cfg, par, batch, impl=impl)
    return fn
