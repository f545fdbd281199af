"""Traffic generators, one module per kind of mix.

A mix lives in ``bench/traffic/<name>.json`` and names its ``kind``;
``bench/generators/<kind>.py`` turns it into work with a ``Traffic``
class.  A mix of an existing kind is data alone; a new kind is a new
module here.
"""
from __future__ import annotations

import importlib


def load(mix: dict, config: dict, seed: int):
    """The ``Traffic`` object of ``mix`` for ``config`` and ``seed``."""
    mod = importlib.import_module(f"{__name__}.{mix['kind']}")
    return mod.Traffic(mix, config, seed)
